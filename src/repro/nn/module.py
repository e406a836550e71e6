"""Minimal functional module system.

Models are pairs of pure functions:

  specs(cfg)  -> nested dict of ParamSpec   (shapes, dtypes, init, sharding)
  apply(params, inputs, cfg) -> outputs

ParamSpec carries *logical* axis names ("embed", "vocab", "heads", ...);
``resolve_pspec`` maps them onto mesh axes through a rules table, so the
same model runs on a (data, model) pod mesh, a (pod, data, model)
multi-pod mesh, or a single CPU device (empty rules). Parameters are only
ever materialized through ``init_params`` (real run) or
``eval_shape_params`` (allocation-free dry-run).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

InitFn = Callable[[jax.Array, Tuple[int, ...], Any], jnp.ndarray]


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any = jnp.float32
    init: Union[str, InitFn] = "normal:0.02"
    pspec: Optional[Tuple[Optional[str], ...]] = None  # logical axes

    def initializer(self) -> InitFn:
        if callable(self.init):
            return self.init
        kind, _, arg = self.init.partition(":")
        if kind == "zeros":
            return lambda k, s, d: jnp.zeros(s, d)
        if kind == "ones":
            return lambda k, s, d: jnp.ones(s, d)
        if kind == "const":
            v = float(arg)
            return lambda k, s, d: jnp.full(s, v, d)
        if kind == "normal":
            std = float(arg) if arg else 0.02
            return lambda k, s, d: (jax.random.normal(k, s, jnp.float32) * std).astype(d)
        if kind == "fan_in":
            # truncated-normal-ish scaled by 1/sqrt(fan_in) (last-2 dim)
            def f(k, s, d):
                fan = s[-2] if len(s) >= 2 else s[-1]
                return (jax.random.normal(k, s, jnp.float32)
                        * (float(arg) if arg else 1.0) / jnp.sqrt(fan)).astype(d)
            return f
        raise ValueError(f"unknown init {self.init!r}")


def _walk(tree, path=()):
    if isinstance(tree, ParamSpec):
        yield path, tree
        return
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _walk(tree[k], path + (k,))
        return
    raise TypeError(f"bad spec node at {path}: {type(tree)}")


def init_params(specs, key: jax.Array):
    """Materialize parameters; per-leaf keys are derived from the path, so
    adding/removing parameters never reshuffles other leaves."""
    def build(tree, path=()):
        if isinstance(tree, ParamSpec):
            leaf_key = jax.random.fold_in(key, _path_hash(path))
            return tree.initializer()(leaf_key, tree.shape, tree.dtype)
        return {k: build(v, path + (k,)) for k, v in tree.items()}
    return build(specs)


def _path_hash(path: Tuple[str, ...]) -> int:
    h = 0
    for part in path:
        for ch in str(part):
            h = (h * 131 + ord(ch)) % (2 ** 31 - 1)
        h = (h * 131 + 7) % (2 ** 31 - 1)
    return h


def eval_shape_params(specs):
    """ShapeDtypeStructs for every parameter — no allocation."""
    def build(tree):
        if isinstance(tree, ParamSpec):
            return jax.ShapeDtypeStruct(tree.shape, tree.dtype)
        return {k: build(v) for k, v in tree.items()}
    return build(specs)


def resolve_pspec(logical: Optional[Tuple[Optional[str], ...]],
                  rules: Dict[str, Any]) -> P:
    """Map logical axis names to mesh axes, dropping duplicates (a mesh
    axis may appear at most once in a PartitionSpec)."""
    if logical is None:
        return P()
    used = set()
    out = []
    for ax in logical:
        target = rules.get(ax) if ax is not None else None
        if target is None:
            out.append(None)
            continue
        taxes = tuple(target) if isinstance(target, (tuple, list)) else (target,)
        taxes = tuple(t for t in taxes if t not in used)
        for t in taxes:
            used.add(t)
        out.append(taxes if len(taxes) > 1 else (taxes[0] if taxes else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_to_mesh(specs, rules: Dict[str, Any]):
    """Tree of PartitionSpecs resolved from the logical annotations."""
    def build(tree):
        if isinstance(tree, ParamSpec):
            return resolve_pspec(tree.pspec, rules)
        return {k: build(v) for k, v in tree.items()}
    return build(specs)


def param_shardings(specs, mesh, rules: Dict[str, Any]):
    def build(tree):
        if isinstance(tree, ParamSpec):
            return NamedSharding(mesh, resolve_pspec(tree.pspec, rules))
        return {k: build(v) for k, v in tree.items()}
    return build(specs)


# ---------------------------------------------------------------------------
# activation sharding context: the launcher installs mesh rules; models call
# constrain() with logical axes and run unchanged on a single device (no-op).
# ---------------------------------------------------------------------------
_ACTIVATION_RULES: Dict[str, Any] = {}
_CURRENT_MESH = None


def set_activation_rules(rules: Optional[Dict[str, Any]], mesh=None) -> None:
    global _ACTIVATION_RULES, _CURRENT_MESH
    _ACTIVATION_RULES = dict(rules) if rules else {}
    _CURRENT_MESH = mesh


def current_mesh():
    return _CURRENT_MESH


@contextlib.contextmanager
def session_mesh(mesh, rules: Optional[Dict[str, Any]] = None):
    """Scope a session mesh: install ``mesh`` (+ optional activation
    rules) on entry, restore the previous mesh/rules on exit. The
    mesh-aware paths (column-sharded CIM deploy, EP MoE, flash decode)
    read ``current_mesh()`` at *trace* time, so run both tracing and
    execution inside the scope — or use ``set_activation_rules`` directly
    for a process-lifetime install (what serving processes do)."""
    prev_rules, prev_mesh = dict(_ACTIVATION_RULES), _CURRENT_MESH
    set_activation_rules(rules if rules is not None else prev_rules, mesh)
    try:
        yield mesh
    finally:
        set_activation_rules(prev_rules, prev_mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=None):
    """``jax.shard_map``, with ``check_vma`` passed only when given."""
    kw = {} if check_vma is None else {"check_vma": check_vma}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def current_rules() -> Dict[str, Any]:
    return dict(_ACTIVATION_RULES)


def constrain(x, logical: Tuple[Optional[str], ...]):
    if not _ACTIVATION_RULES:
        return x
    spec = resolve_pspec(logical, _ACTIVATION_RULES)
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError, TypeError):
        # no mesh in scope (single-device tracing): constraints are hints
        return x


def stack_specs(specs, n: int):
    """Prepend a layer axis (for lax.scan-over-layers parameter stacking)."""
    def build(tree):
        if isinstance(tree, ParamSpec):
            ps = (None,) + tree.pspec if tree.pspec is not None else None
            base_init = tree.initializer()

            def stacked_init(k, s, d, _init=base_init):
                keys = jax.random.split(k, s[0])
                return jax.vmap(lambda kk: _init(kk, s[1:], d))(keys)

            return ParamSpec(shape=(n,) + tree.shape, dtype=tree.dtype,
                             init=stacked_init, pspec=ps)
        return {k: build(v) for k, v in tree.items()}
    return build(specs)
