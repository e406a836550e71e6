"""CIM-mapped linear layer with column-wise weight + partial-sum quantization.

This is the paper's technique (§III-A, Eqs. 1-4) as a composable JAX module
usable by any architecture whose FLOPs live in stored-weight matmuls.

Execution backends (``CIMConfig.mode`` resolves through the
``repro.api.backends`` registry; the implementations live here):

  off      plain matmul in the compute dtype (full-precision baseline).
  emulate  paper-faithful QAT path: LSQ fake-quant of activations and
           weights (at the configured granularity), bit-split digits,
           per-array integer partial sums, ADC quantization of each
           (split, array, column) partial sum with learnable scales,
           fused dequantization s_a * s_w * s_p * 2^(c*s), shift-and-add.
  deploy   packed-int inference path: identical arithmetic evaluated by
           the Pallas kernel (kernels/cim_matmul) from pre-quantized int8
           digit planes - bit-exact with ``emulate`` (tests assert), but
           weights live in HBM as int8 so the memory-roofline term drops.
  ref      deploy arithmetic forced onto the jnp oracle (portable HLO).

The partial-sum tensor in ``emulate`` has shape (..., n_split, k_tiles, N);
the Pallas kernel never materializes it in HBM.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.obs import adc as obs_adc
from repro.obs import names

from .bitsplit import place_values, split_digits
from .granularity import ArrayTiling, Granularity
from .nibble import (can_pack_nibbles, is_nibble_packed, occupancy_map,
                     pack_nibbles)
from .quantizer import init_scale_from, lsq_fake_quant, qrange
from .variation import perturb_digits, perturb_packed, variation_wanted

# Execution-mode names CIMConfig accepts. The builtins are the modes the
# core forwards implement; ``repro.api.backends.register_backend`` adds
# custom backend names here so a registered backend is a valid
# ``CIMConfig.mode`` and a typo fails at construction, not trace time.
_BUILTIN_MODES = ("off", "emulate", "deploy", "ref")
_KNOWN_MODES = set(_BUILTIN_MODES)

_PACK_DTYPES = ("int8", "int4")


def _deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"{old} is deprecated; use {new} instead "
        "(see the migration table in README.md).",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True)
class CIMConfig:
    """Quantization + CIM-mapping configuration (paper Table II knobs).

    ``mode`` names the execution backend (``repro.api.backends``):
    ``off`` | ``emulate`` | ``deploy`` | ``ref`` plus anything registered
    via ``register_backend``. Unknown modes, granularities or pack dtypes
    raise at construction — never silently at trace time.
    """

    enabled: bool = False
    mode: str = "emulate"            # backend name (see repro.api.backends)
    weight_bits: int = 4
    cell_bits: int = 2
    act_bits: int = 8
    psum_bits: int = 4
    array_rows: int = 128
    array_cols: int = 128
    weight_granularity: Granularity = Granularity.COLUMN
    psum_granularity: Granularity = Granularity.COLUMN
    act_signed: bool = True
    psum_quant: bool = True          # False -> paper's "w/o PSQ" baselines
    variation_std: float = 0.0       # eval-time log-normal cell noise
    use_kernel: bool = True          # deploy: Pallas kernel vs jnp reference
    pack_dtype: str = "int8"         # deploy digit storage: int8 | int4

    def __post_init__(self):
        if self.mode not in _KNOWN_MODES:
            raise ValueError(
                f"unknown CIM mode {self.mode!r}; registered backends: "
                f"{sorted(_KNOWN_MODES)}. Custom backends must be "
                "registered via repro.api.backends.register_backend "
                "before a CIMConfig can name them.")
        if self.pack_dtype not in _PACK_DTYPES:
            raise ValueError(f"unknown pack_dtype {self.pack_dtype!r}; "
                             f"valid: {_PACK_DTYPES}")
        for field in ("weight_granularity", "psum_granularity"):
            val = getattr(self, field)
            if not isinstance(val, Granularity):
                try:
                    coerced = Granularity(val)
                except ValueError:
                    raise ValueError(
                        f"unknown {field} {val!r}; valid: "
                        f"{[g.value for g in Granularity]}") from None
                object.__setattr__(self, field, coerced)
        for field in ("weight_bits", "cell_bits", "act_bits", "psum_bits",
                      "array_rows", "array_cols"):
            if int(getattr(self, field)) < 1:
                raise ValueError(f"{field} must be >= 1, got "
                                 f"{getattr(self, field)!r}")

    def tiling(self, k: int, n: int) -> ArrayTiling:
        return ArrayTiling(
            k=k, n=n,
            array_rows=self.array_rows, array_cols=self.array_cols,
            weight_bits=self.weight_bits, cell_bits=self.cell_bits,
        )

    def replace(self, **kw) -> "CIMConfig":
        fields = {f.name for f in dataclasses.fields(self)}
        unknown = sorted(set(kw) - fields)
        if unknown:
            raise TypeError(
                f"CIMConfig.replace: unknown field(s) {unknown}; "
                f"valid fields: {sorted(fields)}")
        return dataclasses.replace(self, **kw)

    def store_dtype(self):
        """Deploy digit-plane storage dtype: int4 when requested and the
        sign-magnitude digits fit [-7, 7] (cells of <=3 bits), else int8."""
        return (jnp.int4 if (self.pack_dtype == "int4"
                             and self.cell_bits <= 3) else jnp.int8)


# ---------------------------------------------------------------------------
# parameter initialization
# ---------------------------------------------------------------------------

def _init_linear(
    key: jax.Array, k: int, n: int, cfg: CIMConfig, w_init_scale: float | None = None,
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    """Initialize {w, s_w, s_p, s_a} for a (k, n) CIM linear layer."""
    std = w_init_scale if w_init_scale is not None else (1.0 / jnp.sqrt(k))
    w = (jax.random.normal(key, (k, n), jnp.float32) * std).astype(dtype)
    params: Dict[str, jnp.ndarray] = {"w": w}
    if cfg.enabled:
        t = cfg.tiling(k, n)
        wg, pg = cfg.weight_granularity, cfg.psum_granularity
        params["s_w"] = weight_scales_from(w.astype(jnp.float32), cfg)
        # psum scale init: |P| ~ sqrt(rows)*E|a_int|*E|digit|; refined by
        # calibrate_cim() on the first batch and learned thereafter.
        _, qp_p = qrange(cfg.psum_bits, True)
        p_mag = jnp.sqrt(float(t.array_rows)) * (2 ** (cfg.act_bits - 2)) * (2 ** (cfg.cell_bits - 1)) / 2.0
        params["s_p"] = jnp.full(t.psum_scale_shape(pg), 2.0 * p_mag / jnp.sqrt(float(max(qp_p, 1))), jnp.float32)
        params["s_a"] = jnp.asarray([1.0], jnp.float32)
    return params


def weight_scales_from(w: jnp.ndarray, cfg: CIMConfig) -> jnp.ndarray:
    """Per-group LSQ scale init, s = 2 E|w|_group / sqrt(q_p) — the
    column-wise groups are each array column's weights (paper §III-A)."""
    k, n = w.shape
    t = cfg.tiling(k, n)
    _, qp = qrange(cfg.weight_bits, True)
    pad_k = t.k_padded - k
    w_abs = jnp.abs(jnp.pad(w, ((0, pad_k), (0, 0))))
    w_t = w_abs.reshape(t.k_tiles, t.array_rows, n)
    # real (unpadded) rows per tile
    rows = jnp.minimum(
        jnp.full((t.k_tiles,), t.array_rows),
        k - jnp.arange(t.k_tiles) * t.array_rows).astype(jnp.float32)
    m_col = w_t.sum(axis=1) / rows[:, None]
    g = cfg.weight_granularity
    if g == Granularity.COLUMN:
        s = m_col                                          # (kt, n)
    elif g == Granularity.ARRAY:
        pad_n = t.n_tiles * t.oc_per_array - n
        mc = jnp.pad(m_col, ((0, 0), (0, pad_n)))
        s = mc.reshape(t.k_tiles, t.n_tiles, t.oc_per_array).mean(-1)
    else:
        s = jnp.mean(m_col, keepdims=True).reshape(1, 1)
    return (2.0 * s / jnp.sqrt(float(max(qp, 1)))).astype(jnp.float32) + 1e-9


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------

def _full_weight_scale(params, t: ArrayTiling) -> jnp.ndarray:
    """(k_tiles, N) weight scale, differentiable w.r.t. the parameter."""
    return t.broadcast_weight_scale(params["s_w"])


def _full_psum_scale(params, t: ArrayTiling) -> jnp.ndarray:
    """(n_split, k_tiles, N) psum scale, differentiable w.r.t. the param."""
    return t.broadcast_psum_scale(params["s_p"])


def _quantize_weight_int(params, cfg: CIMConfig, t: ArrayTiling) -> jnp.ndarray:
    """Integer weight codes (K, N), float dtype, LSQ gradients attached."""
    w = params["w"].astype(jnp.float32)
    s_w = _full_weight_scale(params, t)                       # (kt, N)
    s_full = jnp.repeat(s_w, t.array_rows, axis=0)[: t.k]     # (K, N)
    w_hat = lsq_fake_quant(
        w, s_full, cfg.weight_bits, signed=True,
        group_size=t.weight_group_size(cfg.weight_granularity))
    return w_hat / jnp.maximum(s_full, 1e-9)


def _quantize_act(x, params, cfg: CIMConfig):
    """Returns (a_int, s_a) - integer activation codes and their scale."""
    s_a = params["s_a"]
    a_hat = lsq_fake_quant(x.astype(jnp.float32), s_a, cfg.act_bits,
                           signed=cfg.act_signed)
    return a_hat / jnp.maximum(s_a, 1e-9), s_a


@jax.named_scope(names.SCOPE_ACT_QUANT)
def deploy_act_codes(x, s_a, cfg: CIMConfig) -> jnp.ndarray:
    """Integer activation codes for the packed inference paths.

    Shared by every packed backend (deploy/ref/adc_free/binary): clip-round
    x to the act_bits grid and narrow to the smallest integer dtype so HBM
    traffic drops to 1 byte/activation (the byte width
    bench_kernel.traffic_model charges). Runs under the ``cim.act_quant``
    scope."""
    qn_a, qp_a = qrange(cfg.act_bits, cfg.act_signed)
    a_int = jnp.clip(
        jnp.round(x.astype(jnp.float32) / jnp.maximum(s_a, 1e-9)),
        qn_a, qp_a)
    if qn_a >= -128 and qp_a <= 127:
        a_int = a_int.astype(jnp.int8)
    elif qn_a >= 0 and qp_a <= 255:
        a_int = a_int.astype(jnp.uint8)   # unsigned 8-bit (post-ReLU) codes
    return a_int


def _tile_inputs(a_int: jnp.ndarray, t: ArrayTiling) -> jnp.ndarray:
    """(..., K) -> (..., k_tiles, rows) with zero padding."""
    pad = t.k_padded - a_int.shape[-1]
    if pad:
        a_int = jnp.pad(a_int, [(0, 0)] * (a_int.ndim - 1) + [(0, pad)])
    return a_int.reshape(a_int.shape[:-1] + (t.k_tiles, t.array_rows))


def _tile_digits(digits: jnp.ndarray, t: ArrayTiling) -> jnp.ndarray:
    """(S, K, N) -> (S, k_tiles, rows, N) with zero padding."""
    pad = t.k_padded - digits.shape[1]
    if pad:
        digits = jnp.pad(digits, ((0, 0), (0, pad), (0, 0)))
    return digits.reshape(t.n_split, t.k_tiles, t.array_rows, t.n)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _linear_forward(
    x: jnp.ndarray,
    params: Dict[str, jnp.ndarray],
    cfg: CIMConfig,
    *,
    variation_key: Optional[jax.Array] = None,
    variation_std=None,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Apply a CIM linear layer: x (..., K) @ w (K, N) -> (..., N).

    ``cfg.mode`` resolves to a registered backend (repro.api.backends)
    which owns the arithmetic; the builtins are ``off`` (plain matmul),
    ``emulate`` (QAT fake-quant), ``deploy`` (packed Pallas kernel) and
    ``ref`` (packed jnp oracle).

    ``variation_std`` overrides ``cfg.variation_std`` without rebuilding
    the (static) config — it may be a traced scalar, so a Monte-Carlo
    sweep can feed a sigma grid through one jitted function. Emulate and
    deploy draw cell noise in the same packed layout from the same key,
    so they agree bit-exactly under variation too (DESIGN.md §8).
    """
    if not cfg.enabled:
        return _forward_off(x, params, cfg, None, None, compute_dtype)
    from repro.api.backends import get_backend  # lazy: api builds on core
    sigma = cfg.variation_std if variation_std is None else variation_std
    return get_backend(cfg.mode).linear(x, params, cfg, variation_key,
                                        sigma, compute_dtype)


def _forward_off(x, params, cfg, variation_key, sigma, compute_dtype):
    w = params["w"].astype(compute_dtype)
    return jnp.dot(x.astype(compute_dtype), w)


def _forward_emulate(x, params, cfg, variation_key, sigma, compute_dtype):
    k, n = params["w"].shape
    t = cfg.tiling(k, n)

    a_int, s_a = _quantize_act(x, params, cfg)                # (..., K)
    w_int = _quantize_weight_int(params, cfg, t)              # (K, N)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)  # (S,K,N)

    a_t = _tile_inputs(a_int, t).astype(compute_dtype)        # (..., kt, r)
    d_t = _tile_digits(digits, t)                             # (S, kt, r, N)
    if variation_wanted(variation_key, sigma):
        # noise is drawn over the TILED layout — the same (S, kt, rows, N)
        # shape pack_deploy stores — so deploy sees identical theta per cell
        d_t = perturb_digits(d_t, variation_key, sigma)
    d_t = d_t.astype(compute_dtype)

    # integer column MACs: one per (split, array-tile, column)
    psum = jnp.einsum("...tr,strn->...stn", a_t, d_t,
                      preferred_element_type=jnp.float32)     # (...,S,kt,N)

    if cfg.psum_quant:
        # psums are integer-valued (int x int MACs); snap float roundoff to
        # the grid so ADC tie-breaking matches the deploy kernel bit-exactly
        psum = psum + jax.lax.stop_gradient(jnp.round(psum) - psum)
        s_p = _full_psum_scale(params, t)                     # (S, kt, N)
        if obs_adc.enabled():
            # exact counters: emulate materializes every partial sum
            obs_adc.record(psum, s_p, cfg.psum_bits)
        psum = lsq_fake_quant(psum, s_p, cfg.psum_bits, signed=True)

    # fused dequantization (paper Eq. 3 / Fig. 4d): one scale per column
    s_w = _full_weight_scale(params, t)                       # (kt, N)
    places = place_values(cfg.weight_bits, cfg.cell_bits)     # (S,)
    deq = (places[:, None, None] * s_w[None, :, :])           # (S, kt, N)
    # float32 dequant sum: HIGHEST keeps TPU from feeding it to the MXU
    # as bfloat16 (the deploy kernel accumulates it in float32)
    y = jnp.einsum("...stn,stn->...n", psum.astype(jnp.float32), deq,
                   precision=jax.lax.Precision.HIGHEST)
    y = y * jnp.maximum(s_a, 1e-9)
    return y.astype(compute_dtype)


def _forward_deploy(x, params, cfg, variation_key, sigma, compute_dtype,
                    adc_free: bool = False):
    """Inference from packed int digit planes (see ``_pack_linear``). Cell
    noise is injected by the kernel wrapper on the packed planes — the
    int planes themselves are never re-packed per sample.

    When a mesh with a >1-device ``"model"`` axis is installed
    (``repro.nn.module.set_activation_rules(rules, mesh)`` — the serving
    engine and launchers do this), the digit planes run column-sharded
    over that axis: each device evaluates its own output-column shard and
    one all-gather merges the dequantized activations (DESIGN.md §10).

    ``adc_free=True`` dispatches the same packed planes onto the ADC-free
    hardware style (DESIGN.md §13): digital psum accumulation, no ADC
    quantization — the ``adc_free`` backend registration wraps this."""
    from repro.kernels import ops as kops  # lazy: avoids import cycle
    from repro.nn.module import current_mesh

    digits = params["w_digits"]                               # int (S,kt,r,N)
    if not variation_wanted(variation_key, sigma):
        variation_key = sigma = None

    s_a = params["s_a"]
    a_int = deploy_act_codes(x, s_a, cfg)
    # logical K from the activation; tiling geometry from the digit planes
    t = cfg.tiling(x.shape[-1], digits.shape[-1])
    rows_stored = (t.array_rows // 2 if is_nibble_packed(digits)
                   else t.array_rows)    # uint8 planes: half-split pack
    assert t.k_tiles == digits.shape[1] and rows_stored == digits.shape[2], \
        (t.k_tiles, t.array_rows, digits.shape)
    with jax.named_scope(names.SCOPE_LAYOUT):
        a_t = _tile_inputs(a_int, t)

    with jax.named_scope(names.SCOPE_ACT_QUANT):
        s_p = _full_psum_scale(params, t)
        s_w = _full_weight_scale(params, t)
        places = place_values(cfg.weight_bits, cfg.cell_bits)
        deq = places[:, None, None] * s_w[None] * jnp.maximum(s_a, 1e-9)
        if "deq_scale" in params:
            # in-service recalibration correction (eval/recalibrate.py): a
            # per-column dequant gain shipped as a ScaleDelta, (S, kt, N)
            deq = deq * params["deq_scale"]

    y = kops.cim_matmul(
        a_t, digits, s_p, deq,
        psum_bits=cfg.psum_bits, psum_quant=cfg.psum_quant,
        use_kernel=cfg.use_kernel,
        variation_key=variation_key, variation_std=sigma,
        mesh=current_mesh(), adc_free=adc_free,
        occ=params.get("w_occ"),
    )
    return y.astype(compute_dtype)


# ---------------------------------------------------------------------------
# packing + calibration
# ---------------------------------------------------------------------------

def _pack_linear(params: Dict[str, jnp.ndarray], cfg: CIMConfig, *,
                 variation_key: Optional[jax.Array] = None,
                 variation_std=None) -> Dict[str, jnp.ndarray]:
    """Convert trained emulate-mode params into the packed deploy form.

    pack_dtype='int4' stores each digit plane as int4 (sign-magnitude
    digits of <=3-bit cells fit [-7, 7]) — halves weight HBM vs int8 and
    is the deploy dtype the decode roofline uses.

    ``variation_key``/``variation_std`` bake ONE log-normal device
    realization into the packed planes (float32) — useful to freeze a
    specific chip's noise. For Monte-Carlo sweeps keep the planes clean
    and perturb lazily per sample instead: ``perturb_packed(packed, key,
    sigma, sample=i)`` or the ``variation_key`` forward argument.

    Layout v4 extras (DESIGN.md §14): ``w_occ`` — a per-(split, array
    tile, column) uint8 occupancy map the deploy kernels use to skip
    all-zero digit planes bit-exactly — and, for ``pack_dtype='int4'``
    with an even array-row count, half-split nibble packing of the
    planes (two digits per uint8 byte, ``repro.core.nibble``)."""
    k, n = params["w"].shape
    t = cfg.tiling(k, n)
    w_int = _quantize_weight_int(params, cfg, t)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)
    d_t = _tile_digits(digits, t).astype(cfg.store_dtype())
    occ = occupancy_map(d_t)
    if can_pack_nibbles(t.array_rows, cfg.store_dtype()):
        d_t = pack_nibbles(d_t)
    out = {
        "w_digits": d_t,
        "w_occ": occ,
        "s_w": params["s_w"],
        "s_p": params["s_p"],
        "s_a": params["s_a"],
        "k_logical": jnp.asarray(k, jnp.int32),
    }
    if variation_wanted(variation_key, variation_std):
        out = perturb_packed(out, variation_key, variation_std)
    return out


def _calibrate_linear(x, params, cfg: CIMConfig) -> Dict[str, jnp.ndarray]:
    """One-batch calibration of s_a and s_p (LSQ-style init from stats)."""
    if not cfg.enabled:
        return params
    k, n = params["w"].shape
    t = cfg.tiling(k, n)
    p = dict(params)
    _, qp_a = qrange(cfg.act_bits, cfg.act_signed)
    p["s_a"] = (2.0 * jnp.mean(jnp.abs(x)) / jnp.sqrt(float(max(qp_a, 1)))
                ).reshape(1).astype(jnp.float32) + 1e-9

    a_int, _ = _quantize_act(x, p, cfg)
    w_int = _quantize_weight_int(p, cfg, t)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)
    a_t = _tile_inputs(a_int, t)
    d_t = _tile_digits(digits, t)
    psum = jnp.einsum("...tr,strn->...stn", a_t, d_t,
                      preferred_element_type=jnp.float32)
    flat = psum.reshape((-1,) + psum.shape[-3:])              # (B*, S, kt, N)
    _, qp_p = qrange(cfg.psum_bits, True)
    mean_abs = jnp.mean(jnp.abs(flat), axis=0)                # (S, kt, N)
    pg = cfg.psum_granularity
    if pg == Granularity.LAYER:
        s = jnp.mean(mean_abs, axis=(1, 2), keepdims=True)
    elif pg == Granularity.ARRAY:
        pad_n = t.n_tiles * t.oc_per_array - t.n
        ma = jnp.pad(mean_abs, ((0, 0), (0, 0), (0, pad_n)))
        s = jnp.mean(ma.reshape(t.n_split, t.k_tiles, t.n_tiles, t.oc_per_array), axis=-1)
    else:
        s = mean_abs
    p["s_p"] = (2.0 * s / jnp.sqrt(float(max(qp_p, 1)))).astype(jnp.float32) + 1e-9
    return p


# ---------------------------------------------------------------------------
# deprecated entry points (pre-`repro.api` surface)
# ---------------------------------------------------------------------------

def init_cim_linear(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.init_linear`` / ``QuantLinear.init``."""
    _deprecated("init_cim_linear", "repro.api.init_linear")
    return _init_linear(*args, **kw)


def cim_linear(*args, **kw) -> jnp.ndarray:
    """Deprecated: use ``repro.api.linear`` / ``QuantLinear.__call__``."""
    _deprecated("cim_linear", "repro.api.linear")
    return _linear_forward(*args, **kw)


def calibrate_cim(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.calibrate_linear``."""
    _deprecated("calibrate_cim", "repro.api.calibrate_linear")
    return _calibrate_linear(*args, **kw)


def pack_deploy(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.pack_linear`` / ``QuantLinear.pack``
    (which returns a versioned, saveable ``DeployArtifact``)."""
    _deprecated("pack_deploy", "repro.api.pack_linear")
    return _pack_linear(*args, **kw)
