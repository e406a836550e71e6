"""Versioned on-disk deploy artifacts: the unit a served model loads.

A ``DeployArtifact`` is the packed, self-describing deployment state of
one CIM layer or a whole model tree: int digit planes, learned scales,
the ``CIMConfig`` that produced them (pinned to a packed backend) and a
layout-version tag. ``save``/``load`` are built on ``repro.checkpoint``
(atomic rename, raw-byte leaves) so the round trip is **bit-exact** —
including int4 planes and variation-baked (float) planes — and a pack
benched today is byte-identical to the pack a server loads tomorrow.

On-disk layout::

    <path>/
      artifact.json        kind, layout_version, config, meta
      step_00000000/       repro.checkpoint leaf store for ``params``

``pack_model`` generalizes the per-layer pack to arbitrary param trees:
any dict node carrying the CIM-layer quartet {w, s_w, s_p, s_a} is
packed (linear for 2-D weights, conv for 4-D HWIO; stacked
scan-over-layers variants vmap over the leading layer axis), and MoE
expert banks — flat ``nm``/``nm_s_w``/``nm_s_p``/``nm_s_a`` keys with a
leading expert axis — pack per expert into ``nm_digits`` planes with
per-expert column scales. Every other node — embeddings, norms, biases,
full-precision stems — passes through untouched.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt as _ckpt
from repro.core.cim_linear import CIMConfig
from repro.core.variation import path_fold_key

# Layout 2 adds the optional per-node ``deq_scale`` leaf (in-service
# recalibration, eval/recalibrate.py); layout 3 stamps the packing
# backend (``head["backend"]`` == config.mode, DESIGN.md §13) so tools
# can see which hardware style an artifact targets from artifact.json
# alone. Layout 4 (DESIGN.md §14) stores int4 digit planes nibble-packed
# (two 4-bit digits per uint8 byte along the row/channel-slice axis) and
# adds a per-(split, array tile, column) ``w_occ`` occupancy map next to
# every plane. Readers of 4 still read 1-3: ``load`` migrates older
# standard-pack artifacts in memory (``_migrate_pre_v4``) bit-exactly.
ARTIFACT_LAYOUT_VERSION = 4

# Version of the ScaleDelta side-artifact format (eval/recalibrate.py).
# Stamped into a delta at fit time and into ``artifact.meta`` at apply
# time; load() refuses artifacts recalibrated by a newer delta format.
SCALE_DELTA_VERSION = 1

# Which PR introduced each on-disk format version — named in version-
# mismatch errors so "which side is stale" is answerable from the message.
_LAYOUT_WRITERS = {1: "PR 3 (lifecycle API)", 2: "PR 6 (self-healing serving)",
                   3: "PR 9 (hardware-style backends)",
                   4: "PR 10 (nibble planes + occupancy)"}
_DELTA_WRITERS = {1: "PR 6 (self-healing serving)"}

_KINDS = ("linear", "conv", "model")


class ArtifactVersionError(ValueError):
    """A DeployArtifact or ScaleDelta carries a format version this build
    cannot honor — too new to read, or (for a ScaleDelta) fitted against
    a different artifact layout than the one it is being applied to.
    Subclasses ValueError for compatibility with callers that caught the
    old untyped load error. Carries ``field``/``found``/``supported`` so
    tooling can triage without parsing the message."""

    def __init__(self, what: str, field: str, found, supported: int, *,
                 writers: Optional[Dict[int, str]] = None, relation: str = "<=",
                 detail: str = ""):
        self.field, self.found, self.supported = field, found, supported
        writers = writers or {}
        by = writers.get(found) if isinstance(found, int) else None
        ours = writers.get(supported)
        msg = (f"{what} has {field} {found!r}"
               + (f" (written by {by})" if by else "")
               + f"; this build expects {field} {relation} {supported}"
               + (f" (writer: {ours})" if ours else "") + ".")
        if detail:
            msg += " " + detail
        super().__init__(msg)


def _migrate_pre_v4(params, cfg: CIMConfig):
    """In-memory migration of a layout 1-3 params tree to layout 4.

    For every digit-plane leaf (``*_digits``) of a standard-pack backend:

      * add the sibling ``*_occ`` occupancy map (computed from the planes
        as stored — for variation-baked float planes this is still
        exact: multiplicative noise keeps zero cells zero);
      * nibble-pack dense int4 planes two-per-byte when the packed axis
        is even (``repro.core.nibble``). int8 / float planes and odd
        axes keep their dense storage.

    The decode path is unchanged arithmetic, so a migrated artifact
    serves bit-exact with the bytes it was written with
    (tests/test_artifact_migration.py). Backends with their own pack
    format (``pack_linear``/``pack_conv`` set, e.g. ``binary``) are
    passed through untouched — their planes are not the standard digit
    layout and their forwards do not consume occupancy maps.
    """
    from repro.core.nibble import (can_pack_nibbles, occupancy_map,
                                   pack_nibbles)
    from .backends import get_backend
    b = get_backend(cfg.mode)
    if b.pack_linear is not None or b.pack_conv is not None:
        return params

    def walk(node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if isinstance(v, (dict, list, tuple)):
                    out[k] = walk(v)
                    continue
                out[k] = v
                if not k.endswith("_digits"):
                    continue
                d = jnp.asarray(v)
                # conv planes are always the quartet key "w_digits" with
                # the 6-D (or stacked 7-D) geometry shape; every other
                # rank — incl. rank-5/6 expert banks — is linear
                conv = k == "w_digits" and d.ndim >= 6
                occ_key = k[: -len("_digits")] + "_occ"
                if occ_key not in node:
                    out[occ_key] = occupancy_map(d, conv=conv)
                if (jnp.dtype(d.dtype) == jnp.dtype(jnp.int4)
                        and can_pack_nibbles(d.shape[-2], d.dtype)):
                    out[k] = pack_nibbles(d)
            return out
        if isinstance(node, (list, tuple)):
            return [walk(v) for v in node]
        return node
    return walk(params)


def _packed_config(cfg: CIMConfig) -> CIMConfig:
    """Pin the artifact's config to a packed backend (deploy by default)."""
    from .backends import get_backend
    if get_backend(cfg.mode).packed:
        return cfg
    return cfg.replace(mode="deploy")


@dataclasses.dataclass(frozen=True)
class DeployArtifact:
    """Packed deployment state: digit planes + scales + config + version.

    ``params`` is the packed tree the deploy/ref backends consume
    directly (``w_digits`` digit planes, ``s_w``/``s_p``/``s_a`` scales;
    for ``kind="model"`` the whole packed model tree). ``config`` always
    names a packed backend, so ``forward(x, artifact.params,
    artifact.config)`` is the served fast path with no further mode
    surgery. ``meta`` carries layer geometry (k/n, conv stride/padding)
    and free-form provenance.
    """

    kind: str                              # linear | conv | model
    config: CIMConfig
    params: Dict[str, Any]
    layout_version: int = ARTIFACT_LAYOUT_VERSION
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown artifact kind {self.kind!r}; "
                             f"valid: {_KINDS}")
        from .backends import get_backend
        if not get_backend(self.config.mode).packed:
            raise ValueError(
                f"DeployArtifact.config must name a packed backend, got "
                f"mode={self.config.mode!r}; use config.replace("
                "mode='deploy') (packing helpers do this for you)")

    # -- persistence ---------------------------------------------------------

    def save(self, path: str) -> str:
        """Write the artifact; ``artifact.json`` lands last (fsynced +
        renamed), so its presence marks a complete artifact. When
        overwriting an existing artifact the stale header is removed
        *before* the new params land — a crash mid-overwrite leaves an
        incomplete (loudly unloadable) artifact, never new params paired
        with an old header."""
        os.makedirs(path, exist_ok=True)
        stale = os.path.join(path, "artifact.json")
        if os.path.exists(stale):
            os.remove(stale)
        _ckpt.save(path, 0, self.params)
        head = {
            "format": "repro.api.DeployArtifact",
            "layout_version": self.layout_version,
            "kind": self.kind,
            # which hardware-style backend the pack targets (== config
            # mode; layout >= 3) — surfaced in the header so placement/
            # fleet tools can route without opening the leaf store
            "backend": self.config.mode,
            "config": dataclasses.asdict(self.config),
            "meta": self.meta,
        }
        jpath = os.path.join(path, "artifact.json")
        tmp = jpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(head, f, indent=2)
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, jpath)   # atomic: never a headers/params mismatch
        return path

    @classmethod
    def load(cls, path: str, *, mesh=None,
             mesh_axis: str = "model") -> "DeployArtifact":
        """Read an artifact back, bit-exactly. With ``mesh``, each CIM
        node's digit planes (and full-column scales) are placed
        column-sharded over ``mesh_axis`` as they come off disk — every
        device receives only its own column shard of the host buffer, so
        no device ever materializes a full plane (DESIGN.md §10)."""
        jpath = os.path.join(path, "artifact.json")
        if not os.path.exists(jpath):
            raise FileNotFoundError(
                f"{path} is not a DeployArtifact (no artifact.json)")
        with open(jpath) as f:
            head = json.load(f)
        version = head.get("layout_version")
        if version is None or version > ARTIFACT_LAYOUT_VERSION:
            raise ArtifactVersionError(
                f"artifact at {path}", "layout_version", version,
                ARTIFACT_LAYOUT_VERSION, writers=_LAYOUT_WRITERS,
                detail="Upgrade the repro library or re-pack the artifact.")
        meta = dict(head.get("meta", {}))
        dv = meta.get("delta_version")
        if dv is not None and dv > SCALE_DELTA_VERSION:
            raise ArtifactVersionError(
                f"artifact at {path} (recalibrated)", "delta_version", dv,
                SCALE_DELTA_VERSION, writers=_DELTA_WRITERS,
                detail="Upgrade the repro library or re-fit the ScaleDelta "
                       "with eval/recalibrate.py.")
        try:
            cfg = CIMConfig(**head["config"])
        except ValueError as e:
            if "unknown CIM mode" not in str(e):
                raise
            from .backends import registered_backends
            backend = head.get("backend", head["config"].get("mode"))
            raise ValueError(
                f"artifact at {path} was packed for backend {backend!r}, "
                f"which is not registered in this session (registered: "
                f"{registered_backends()}). Import or register_backend() "
                f"the backend that owns this hardware style before "
                f"loading.") from None
        params = _ckpt.restore_tree(path, step=0)
        if version < 4:
            # older standard-pack artifacts load into the v4 in-memory
            # layout (nibble planes + occupancy), bit-exact on serve
            params = _migrate_pre_v4(params, cfg)
            version = ARTIFACT_LAYOUT_VERSION
        if mesh is None:
            params = jax.tree.map(jnp.asarray, params)
        art = cls(kind=head["kind"], config=cfg, params=params,
                  layout_version=version, meta=meta)
        if mesh is not None:
            # shard() device_puts straight from the restored host (numpy)
            # buffers: each device receives only its own column slice; the
            # full plane is never committed to any single device
            art = art.shard(mesh, mesh_axis=mesh_axis)
        return art

    def shard(self, mesh, *, mesh_axis: str = "model") -> "DeployArtifact":
        """Place the packed params on ``mesh``: digit planes and their
        full-column scales sharded along the output-column axis (the
        layout the column-parallel deploy path consumes in place — no
        per-call resharding), everything else replicated.

        Columns that do not divide the shard count stay replicated; the
        kernel wrapper pads and shards them per call instead (same rule as
        its last-block padding), so ragged layers still serve correctly.

        Leaves may be host (numpy) buffers — ``load(mesh=...)`` passes
        them through un-materialized, so ``device_put`` here sends each
        device only its own column slice and the full plane never lands
        on any single device.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        n_dev = int(mesh.shape[mesh_axis])
        rep = NamedSharding(mesh, P())

        def place(node):
            if isinstance(node, dict):
                if n_dev > 1 and any(k.endswith("_digits") for k in node):
                    return _shard_node(node, mesh, mesh_axis, n_dev, rep,
                                       place)
                return {k: place(v) for k, v in node.items()}
            if isinstance(node, (list, tuple)):
                return [place(v) for v in node]
            return jax.device_put(node, rep)
        return dataclasses.replace(self, params=place(self.params))


# ---------------------------------------------------------------------------
# generic model packing
# ---------------------------------------------------------------------------

_CIM_LAYER_KEYS = frozenset({"w", "s_w", "s_p", "s_a"})


def _is_cim_layer(node: Dict) -> bool:
    return (isinstance(node, dict) and _CIM_LAYER_KEYS <= set(node)
            and getattr(node["w"], "ndim", 0) >= 2)


# per-node key derivation shared with drift injection and delta fitting
_path_key = path_fold_key

_BANK_SCALES = ("s_w", "s_p", "s_a")


def _bank_names(node: Dict) -> list:
    """Expert-bank weights inside a dict node: array-valued keys ``nm`` of
    rank 3 ((E, K, N)) or 4 ((L, E, K, N) under ``stack_specs``) whose
    per-expert scales ride alongside as ``nm_s_w``/``nm_s_p``/``nm_s_a``
    (the ``models.layers.moe_specs`` flat-bank convention). The quartet
    convention never collides: a quartet's scales are unprefixed."""
    return [nm for nm, v in node.items()
            if getattr(v, "ndim", 0) in (3, 4)
            and all(f"{nm}_{s}" in node for s in _BANK_SCALES)]


def _pack_bank(node: Dict, nm: str, cfg: CIMConfig, vkey, variation_std,
               pack_lin=None):
    """Pack one expert bank: vmap the backend's linear packer over the
    flattened leading (layer-stack x expert) axes, then restore them.
    Outputs keep the flat-key convention (``nm_digits``/``nm_s_w``/... )
    so router and shared-expert siblings stay untouched in the same
    node."""
    if pack_lin is None:
        from .backends import packers_for
        pack_lin, _ = packers_for(cfg)
    bank = {"w": jnp.asarray(node[nm]).astype(jnp.float32),
            **{s: node[f"{nm}_{s}"] for s in _BANK_SCALES}}
    lead = bank["w"].shape[:-2]
    nl = len(lead)
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[nl:]), bank)
    if vkey is None:
        packed = jax.vmap(lambda p: pack_lin(p, cfg))(flat)
    else:
        keys = jax.random.split(vkey, flat["w"].shape[0])
        packed = jax.vmap(lambda p, k: pack_lin(
            p, cfg, variation_key=k,
            variation_std=variation_std))(flat, keys)
    packed = jax.tree.map(lambda a: a.reshape(lead + a.shape[1:]), packed)
    out = {f"{nm}_digits": packed["w_digits"],
           f"{nm}_k_logical": packed["k_logical"],
           **{f"{nm}_{s}": packed[s] for s in _BANK_SCALES}}
    if "w_occ" in packed:   # layout v4 standard pack; custom packs may omit
        out[f"{nm}_occ"] = packed["w_occ"]
    return out


def pack_model(params: Dict, cfg: CIMConfig, *,
               variation_key: Optional[jax.Array] = None,
               variation_std=None) -> Dict:
    """Walk a model param tree, packing every CIM layer for deployment.

    A node is a CIM layer iff it carries {w, s_w, s_p, s_a}: 2-D ``w`` is
    a linear layer, 4-D an HWIO conv; 3-D/5-D are their stacked
    (scan-over-layers) forms, packed with a vmap over the layer axis.
    MoE expert banks (flat ``nm``/``nm_s_w``/``nm_s_p``/``nm_s_a`` keys,
    rank 3/4 with leading expert/layer axes) pack per expert into
    ``nm_digits`` planes with per-expert column scales — router dispatch
    (``models.layers._expert_matmul``) picks the packed planes up at
    call time. Full-precision nodes (no scales) pass through, so the
    same walk handles ResNets (fp stem/fc, BN), transformers
    (embeddings, norms, stacked blocks), SSM scan stacks and routers.

    ``variation_key``/``variation_std`` bake ONE device realization into
    the planes, with an independent per-layer key folded from the tree
    path (deterministic across processes).

    The packers are the BACKEND's (``backends.packers_for``): a cfg on a
    hardware style with its own pack path (e.g. ``binary``'s sign-plane
    pack) walks the same tree into that style's plane format."""
    from .backends import packers_for
    pack_lin, pack_cv = packers_for(_packed_config(cfg))

    def walk(node, path):
        if _is_cim_layer(node):
            w = node["w"]
            vkey = (None if variation_key is None
                    else _path_key(variation_key, path))
            kw = dict(variation_key=vkey, variation_std=variation_std)
            layer = {k: node[k] for k in _CIM_LAYER_KEYS}
            # non-quartet keys (e.g. a bias) ride along untouched
            extras = {k: v for k, v in node.items()
                      if k not in _CIM_LAYER_KEYS}
            if w.ndim == 2:
                return {**extras, **pack_lin(layer, cfg, **kw)}
            if w.ndim == 4:
                return {**extras, **pack_cv(layer, cfg, **kw)}
            if w.ndim in (3, 5):
                pack = pack_lin if w.ndim == 3 else pack_cv
                # one layer at a time: a vmap over the stack would hold
                # every layer's float digit temporaries at once — several
                # GiB per projection at published widths, past one chip's
                # HBM
                if vkey is None:
                    packed = jax.lax.map(lambda p: pack(p, cfg), layer)
                else:
                    keys = jax.random.split(vkey, w.shape[0])
                    packed = jax.lax.map(lambda pk: pack(
                        pk[0], cfg, variation_key=pk[1],
                        variation_std=variation_std), (layer, keys))
                return {**extras, **packed}
            raise ValueError(f"CIM layer at {'/'.join(path)} has "
                             f"unsupported weight rank {w.ndim}")
        if isinstance(node, dict):
            banks = _bank_names(node)
            if banks:
                out: Dict = {}
                consumed = set()
                for nm in banks:
                    vkey = (None if variation_key is None
                            else _path_key(variation_key, path + (nm,)))
                    out.update(_pack_bank(node, nm, cfg, vkey, variation_std,
                                          pack_lin=pack_lin))
                    consumed |= {nm, *(f"{nm}_{s}" for s in _BANK_SCALES)}
                # siblings (router, shared experts, ...) walk as usual
                for k, v in node.items():
                    if k not in consumed:
                        out[k] = walk(v, path + (k,))
                return out
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            # recurse so CIM layers inside sequences are packed, and
            # normalize tuples to lists: checkpoint.restore_tree rebuilds
            # sequence nodes as lists, so normalizing here keeps the
            # in-memory pack and a loaded artifact structure-exact
            return [walk(v, path + (str(i),)) for i, v in enumerate(node)]
        return node
    return walk(params, ())


def col_shard_axes(packed: Dict) -> Dict[str, int]:
    """Map every packed CIM node ('/'-joined tree path) to the axis its
    digit planes shard over for column-parallel serving — always the last
    axis (N for linear planes, C_out for conv planes; the stacked 5-D/7-D
    forms keep it last too). Stamped into model artifacts as
    ``meta["col_shard"]`` so external serving tools can plan placement
    from ``artifact.json`` alone, without opening the leaf store.
    (``DeployArtifact.shard`` itself re-derives the same layout
    structurally from the params tree, so a stale meta can never
    misplace a plane.)"""
    out: Dict[str, int] = {}

    def walk(node, path):
        if isinstance(node, dict):
            if "w_digits" in node:
                out["/".join(path)] = -1
                return
            for k in node:
                # expert banks: one entry per bank, keyed path/<bank name>
                if k.endswith("_digits"):
                    out["/".join(path + (k[: -len("_digits")],))] = -1
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (str(i),))
    walk(packed, ())
    return out


def _shard_node(node: Dict, mesh, mesh_axis: str, n_dev: int, rep,
                place) -> Dict:
    """Place one packed CIM node: arrays carrying their bank's column axis
    (last dim == the planes' column count) shard over ``mesh_axis`` when
    the columns divide the device count; everything else replicates.
    Ragged banks stay replicated — the kernel wrapper pads and shards
    them per call (the last-shard padding rule, DESIGN.md §10).

    A quartet node has one bank (``w_digits`` owning the unprefixed
    ``s_w``/``s_p``/``s_a``/``deq_scale``); a MoE node carries several
    (``wg_digits`` owning ``wg_s_w``/... ). Sub-dict siblings (router,
    shared experts) recurse through ``place``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    banks = {k[: -len("_digits")]: int(node[k].shape[-1])
             for k in node if k.endswith("_digits")}

    def bank_cols(k):
        for nm, n in banks.items():
            if k == f"{nm}_digits" or (nm != "w" and k.startswith(f"{nm}_")):
                return n
        return banks.get("w")   # quartet: unprefixed scale keys

    out = {}
    for k, v in node.items():
        if isinstance(v, (dict, list, tuple)):
            out[k] = place(v)
            continue
        n = bank_cols(k)
        cols = (n is not None and hasattr(v, "ndim") and v.ndim >= 1
                and v.shape[-1] == n and n % n_dev == 0)
        sh = (NamedSharding(mesh, P(*([None] * (v.ndim - 1) + [mesh_axis])))
              if cols else rep)
        out[k] = jax.device_put(v, sh)
    return out


def model_artifact(params: Dict, cfg: CIMConfig, *,
                   meta: Optional[Dict[str, Any]] = None,
                   variation_key: Optional[jax.Array] = None,
                   variation_std=None) -> DeployArtifact:
    """``pack_model`` + wrap into a saveable model ``DeployArtifact``.
    The shardable column axis of every packed node is recorded in
    ``meta["col_shard"]`` (see ``col_shard_axes``)."""
    packed = pack_model(params, cfg, variation_key=variation_key,
                        variation_std=variation_std)
    # col_shard last: the computed map wins over a caller-supplied key
    m = {**(meta or {}), "col_shard": col_shard_axes(packed)}
    return DeployArtifact(kind="model", config=_packed_config(cfg),
                          params=packed, meta=m)
