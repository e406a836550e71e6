"""CIM-oriented convolution framework (paper §III-C, Fig. 5).

The paper's engineering contribution: implementing column-wise weight and
partial-sum quantization for conv layers *without* per-array sequential
indexing or im2col linear ops. Two ideas, both reproduced natively:

1. **Stretched-kernel tiling.** Instead of im2col'ing activations and
   tiling the resulting matrix arbitrarily, choose the tiling stride so
   each CIM array holds ``c_per_array = floor(array_rows / K^2)`` whole
   input channels with all their K^2 taps ("stretched kernels remain
   intact in each array"). The array's MAC is then itself a convolution
   over a channel slice.

2. **Group convolution.** All ``k_tiles`` channel-slice convolutions run
   as ONE grouped convolution (``feature_group_count = k_tiles``) by
   replicating the activation channel-slices into groups — no sequential
   array indexing. The grouped conv's output channels factor as
   (k_tiles, C_out): exactly the per-array partial sums, ready for
   column-wise ADC quantization, fused dequant and shift-and-add.

Bit-splits are the leading axis of the grouped-conv weight batch, as in
Fig. 5's "weight duplication".

A third backend, ``deploy``, evaluates the same arithmetic through the
fused Pallas conv kernel (kernels/cim_conv) from ``repro.api.pack_conv``'s
packed int digit planes: stretched-kernel patches are extracted once (no
``n_split``x activation tiling) and ADC quantization happens per
array-tile accumulator in VMEM — the grouped-conv path's HBM partial-sum
round-trip disappears (DESIGN.md §3, §7).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.obs import adc as obs_adc

from .bitsplit import place_values, split_digits
from .cim_linear import CIMConfig, _deprecated, _quantize_act, deploy_act_codes
from .granularity import Granularity, conv_tiling
from .nibble import (can_pack_nibbles, is_nibble_packed, occupancy_map,
                     pack_nibbles)
from .quantizer import init_scale_from, lsq_fake_quant, qrange
from .variation import perturb_packed, variation_noise, variation_wanted


def _init_conv(
    key: jax.Array,
    kh: int, kw: int, c_in: int, c_out: int,
    cfg: CIMConfig,
    dtype=jnp.float32,
) -> Dict[str, jnp.ndarray]:
    """Params for a CIM conv layer; weight layout HWIO."""
    fan_in = kh * kw * c_in
    w = (jax.random.normal(key, (kh, kw, c_in, c_out), jnp.float32)
         * jnp.sqrt(2.0 / fan_in)).astype(dtype)
    params: Dict[str, jnp.ndarray] = {"w": w}
    if cfg.enabled:
        t, _ = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows,
                           cfg.array_cols, cfg.weight_bits, cfg.cell_bits)
        params["s_w"] = conv_weight_scales_from(w.astype(jnp.float32), cfg)
        _, qp_p = qrange(cfg.psum_bits, True)
        p_mag = jnp.sqrt(float(t.array_rows)) * (2 ** (cfg.act_bits - 2)) \
            * (2 ** (cfg.cell_bits - 1)) / 2.0
        params["s_p"] = jnp.full(
            t.psum_scale_shape(cfg.psum_granularity),
            2.0 * p_mag / jnp.sqrt(float(max(qp_p, 1))), jnp.float32)
        params["s_a"] = jnp.asarray([1.0], jnp.float32)
    return params


def conv_weight_scales_from(w: jnp.ndarray, cfg: CIMConfig) -> jnp.ndarray:
    """Per-group LSQ init for conv weights: a column group is one output
    channel's taps within one channel-slice array (paper's tiling)."""
    kh, kw, c_in, c_out = w.shape
    t, cpa = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows, cfg.array_cols,
                         cfg.weight_bits, cfg.cell_bits)
    _, qp = qrange(cfg.weight_bits, True)
    pad_c = t.k_tiles * cpa - c_in
    w_abs = jnp.abs(jnp.pad(w.astype(jnp.float32),
                            ((0, 0), (0, 0), (0, pad_c), (0, 0))))
    w_t = w_abs.reshape(kh * kw, t.k_tiles, cpa, c_out)
    ch = jnp.minimum(jnp.full((t.k_tiles,), cpa),
                     c_in - jnp.arange(t.k_tiles) * cpa).astype(jnp.float32)
    m_col = w_t.sum(axis=(0, 2)) / (ch[:, None] * kh * kw)     # (kt, c_out)
    g = cfg.weight_granularity
    if g == Granularity.COLUMN:
        s = m_col
    elif g == Granularity.ARRAY:
        pad_n = t.n_tiles * t.oc_per_array - c_out
        mc = jnp.pad(m_col, ((0, 0), (0, pad_n)))
        s = mc.reshape(t.k_tiles, t.n_tiles, t.oc_per_array).mean(-1)
    else:
        s = jnp.mean(m_col, keepdims=True).reshape(1, 1)
    return (2.0 * s / jnp.sqrt(float(max(qp, 1)))).astype(jnp.float32) + 1e-9


def _quantize_conv_weight_int(params, cfg: CIMConfig, t, c_per_array, kh, kw,
                              c_in, c_out):
    """Integer codes (kh, kw, c_in, c_out) with per-(array, column) scales."""
    w = params["w"].astype(jnp.float32)
    s_w = t.broadcast_weight_scale(params["s_w"])            # (kt, C_out)
    # expand scale to HWIO: channel c belongs to array tile c // c_per_array
    tile_of_c = jnp.arange(c_in) // c_per_array              # (c_in,)
    s_full = s_w[tile_of_c]                                  # (c_in, C_out)
    s_full = jnp.broadcast_to(s_full[None, None], (kh, kw, c_in, c_out))
    w_hat = lsq_fake_quant(
        w, s_full, cfg.weight_bits, signed=True,
        group_size=t.weight_group_size(cfg.weight_granularity))
    return w_hat / jnp.maximum(s_full, 1e-9)


def _conv_forward(
    x: jnp.ndarray,                      # (B, H, W, C_in)  NHWC
    params: Dict[str, jnp.ndarray],
    cfg: CIMConfig,
    *,
    stride: int = 1,
    padding: str = "SAME",
    variation_key: Optional[jax.Array] = None,
    variation_std=None,
    compute_dtype=jnp.bfloat16,
) -> jnp.ndarray:
    """Conv2d through the CIM framework. Returns (B, H', W', C_out).

    ``cfg.mode`` resolves to a registered backend (repro.api.backends),
    mirroring the linear layer: ``off`` is a plain conv, ``emulate`` the
    paper-faithful QAT grouped-conv path, ``deploy`` packed-int inference
    through the fused Pallas conv kernel (from packed digit-plane
    params) — bit-exact with emulate, but the partial-sum tensor never
    reaches HBM and activations are not replicated ``n_split``x; ``ref``
    is the packed jnp oracle.

    ``variation_key``/``variation_std`` evaluate one Monte-Carlo device
    realization; noise is drawn in the packed 6-D layout on both modes,
    so emulate and deploy agree bit-exactly under a shared key
    (``variation_std=None`` falls back to ``cfg.variation_std``).
    """
    sigma = cfg.variation_std if variation_std is None else variation_std
    if not cfg.enabled:
        return _forward_conv_off(x, params, cfg, stride, padding,
                                 None, None, compute_dtype)
    from repro.api.backends import get_backend  # lazy: api builds on core
    return get_backend(cfg.mode).conv(x, params, cfg, stride, padding,
                                      variation_key, sigma, compute_dtype)


def _forward_conv_off(x, params, cfg, stride, padding, variation_key,
                      sigma, compute_dtype):
    return jax.lax.conv_general_dilated(
        x.astype(compute_dtype), params["w"].astype(compute_dtype),
        (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _forward_conv_emulate(x, params, cfg, stride, padding, variation_key,
                          sigma, compute_dtype):
    kh, kw, c_in, c_out = params["w"].shape
    dn = ("NHWC", "HWIO", "NHWC")
    t, c_per_array = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows,
                                 cfg.array_cols, cfg.weight_bits, cfg.cell_bits)
    k_tiles = t.k_tiles

    a_int, s_a = _quantize_act(x, params, cfg)               # (B,H,W,C_in)
    w_int = _quantize_conv_weight_int(params, cfg, t, c_per_array,
                                      kh, kw, c_in, c_out)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)  # (S,kh,kw,ci,co)
    n_split = digits.shape[0]

    # --- group-conv framework -------------------------------------------------
    # pad channels to k_tiles * c_per_array and replicate per group
    c_pad = k_tiles * c_per_array - c_in
    a_p = jnp.pad(a_int, ((0, 0), (0, 0), (0, 0), (0, c_pad)))
    d_p = jnp.pad(digits, ((0, 0), (0, 0), (0, 0), (0, c_pad), (0, 0)))

    # weights: (S, kh, kw, kt*cpa, co) -> grouped HWIO (kh, kw, cpa, S*kt*co)
    # group g in [0, S*kt): split s = g // kt, tile t = g % kt
    d_g = d_p.reshape(n_split, kh, kw, k_tiles, c_per_array, c_out)
    if variation_wanted(variation_key, sigma):
        # noise is drawn in the canonical PACKED layout (S, kt, kh, kw,
        # cpa, co) — the shape pack_deploy_conv stores — then transposed
        # into this path's grouping, so deploy sees identical theta per cell
        noise = variation_noise(
            variation_key, (n_split, k_tiles, kh, kw, c_per_array, c_out),
            sigma)
        d_g = d_g * jnp.transpose(noise, (0, 2, 3, 1, 4, 5))
    d_g = jnp.transpose(d_g, (1, 2, 4, 0, 3, 5))             # kh,kw,cpa,S,kt,co
    d_g = d_g.reshape(kh, kw, c_per_array, n_split * k_tiles * c_out)

    # activations: replicate the channel-slices once per split
    a_g = jnp.tile(a_p, (1, 1, 1, n_split))                  # (B,H,W,S*kt*cpa)

    psum = jax.lax.conv_general_dilated(
        a_g.astype(compute_dtype), d_g.astype(compute_dtype),
        (stride, stride), padding, dimension_numbers=dn,
        feature_group_count=n_split * k_tiles,
        preferred_element_type=jnp.float32,
    )                                                        # (B,H',W',S*kt*co)
    b, ho, wo, _ = psum.shape
    psum = psum.reshape(b, ho, wo, n_split, k_tiles, c_out)  # per-array psums

    if cfg.psum_quant:
        # psums are integer-valued (int x int MACs); snap float roundoff to
        # the grid so ADC tie-breaking matches the deploy kernel bit-exactly
        psum = psum + jax.lax.stop_gradient(jnp.round(psum) - psum)
        s_p = t.broadcast_psum_scale(params["s_p"])          # (S, kt, co)
        if obs_adc.enabled():
            # exact counters: emulate materializes every partial sum
            obs_adc.record(psum, s_p[None, None, None], cfg.psum_bits)
        psum = lsq_fake_quant(psum, s_p[None, None, None], cfg.psum_bits,
                              signed=True)

    # fused dequant + shift-and-add (paper Fig. 5 bottom)
    s_w = t.broadcast_weight_scale(params["s_w"])            # (kt, co)
    places = place_values(cfg.weight_bits, cfg.cell_bits)    # (S,)
    deq = places[:, None, None] * s_w[None]                  # (S, kt, co)
    # float32 dequant sum: HIGHEST keeps TPU from feeding it to the MXU
    # as bfloat16 (the deploy kernel accumulates it in float32)
    y = jnp.einsum("bhwstc,stc->bhwc", psum.astype(jnp.float32), deq,
                   precision=jax.lax.Precision.HIGHEST)
    y = y * jnp.maximum(s_a, 1e-9)
    return y.astype(compute_dtype)


def _forward_conv_deploy(x, params, cfg: CIMConfig, stride, padding,
                         variation_key, sigma, compute_dtype,
                         adc_free: bool = False):
    """Inference from packed conv digit planes (see ``_pack_conv``).

    The conv geometry (kh, kw, c_per_array) is carried statically by the
    6-D digit-plane shape, so packed params are self-describing under jit.
    Cell noise is injected by the kernel wrapper on the flattened packed
    planes (row-major identical to the 6-D layout) — the int planes are
    never re-packed per Monte-Carlo sample.

    When a mesh with a >1-device ``"model"`` axis is installed (see
    ``_forward_deploy``), the planes run column-sharded over C_out: every
    device extracts the same patches, evaluates its own output-channel
    shard, and one all-gather merges the activations (DESIGN.md §10).
    """
    from repro.kernels import ops as kops  # lazy: avoids import cycle
    from repro.nn.module import current_mesh

    d6 = params["w_digits"]              # (S, kt, kh, kw, cpa, C_out)
    n_split, k_tiles, kh, kw, cpa_stored, c_out = d6.shape
    # uint8 planes are nibble-packed along cpa (repro.core.nibble): the
    # stored channel-slice axis holds half the logical rows
    c_per_array = 2 * cpa_stored if is_nibble_packed(d6) else cpa_stored
    digits = d6.reshape(n_split, k_tiles, kh * kw * cpa_stored, c_out)
    if not variation_wanted(variation_key, sigma):
        variation_key = sigma = None

    s_a = params["s_a"]
    a_int = deploy_act_codes(x, s_a, cfg)

    # logical geometry from the activation; must match the packed planes
    c_in = x.shape[-1]
    t, cpa = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows, cfg.array_cols,
                         cfg.weight_bits, cfg.cell_bits)
    assert (t.k_tiles, cpa) == (k_tiles, c_per_array), (
        f"packed digit planes {d6.shape} were built for a different "
        f"geometry than x/cfg imply: expected (k_tiles, c_per_array)="
        f"{(t.k_tiles, cpa)}, packed {(k_tiles, c_per_array)}")

    s_p = t.broadcast_psum_scale(params["s_p"])              # (S, kt, co)
    s_w = t.broadcast_weight_scale(params["s_w"])            # (kt, co)
    places = place_values(cfg.weight_bits, cfg.cell_bits)    # (S,)
    deq = places[:, None, None] * s_w[None] * jnp.maximum(s_a, 1e-9)
    if "deq_scale" in params:
        # in-service recalibration correction (eval/recalibrate.py): a
        # per-column dequant gain shipped as a ScaleDelta, (S, kt, co)
        deq = deq * params["deq_scale"]

    y = kops.cim_conv(
        a_int, digits, s_p, deq,
        kh=kh, kw=kw, stride=stride, padding=padding,
        c_per_array=c_per_array,
        psum_bits=cfg.psum_bits, psum_quant=cfg.psum_quant,
        use_kernel=cfg.use_kernel,
        variation_key=variation_key, variation_std=sigma,
        mesh=current_mesh(), adc_free=adc_free,
        occ=params.get("w_occ"),
    )
    return y.astype(compute_dtype)


def _pack_conv(params: Dict[str, jnp.ndarray], cfg: CIMConfig, *,
               variation_key: Optional[jax.Array] = None,
               variation_std=None) -> Dict[str, jnp.ndarray]:
    """Convert trained emulate-mode conv params to the packed deploy form.

    Digit planes are stored 6-D — (S, k_tiles, kh, kw, c_per_array, C_out)
    — i.e. HWIO grouped by channel slice, row order (dh, dw, c) matching
    ``ref.extract_conv_patches``. The shape carries the conv geometry, so
    the deploy forward needs no side-channel metadata. pack_dtype='int4'
    stores each plane as int4 (sign-magnitude digits of <=3-bit cells fit
    [-7, 7]) — halves weight HBM vs int8.

    ``variation_key``/``variation_std`` bake ONE log-normal device
    realization into the planes (float32); for Monte-Carlo sweeps keep
    the planes clean and use ``perturb_packed``/the forward's
    ``variation_key`` instead (no re-packing per sample).

    Layout v4 extras (DESIGN.md §14): ``w_occ`` — per-(split, array tile,
    output channel) uint8 occupancy over the (kh, kw, cpa) cell block —
    and, for ``pack_dtype='int4'`` with an even ``c_per_array``,
    half-split nibble packing of the cpa axis (two digits per uint8
    byte, ``repro.core.nibble``)."""
    kh, kw, c_in, c_out = params["w"].shape
    t, cpa = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows, cfg.array_cols,
                         cfg.weight_bits, cfg.cell_bits)
    w_int = _quantize_conv_weight_int(params, cfg, t, cpa, kh, kw,
                                      c_in, c_out)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)
    n_split = digits.shape[0]
    c_pad = t.k_tiles * cpa - c_in
    d = jnp.pad(digits, ((0, 0), (0, 0), (0, 0), (0, c_pad), (0, 0)))
    d = d.reshape(n_split, kh, kw, t.k_tiles, cpa, c_out)
    d = jnp.transpose(d, (0, 3, 1, 2, 4, 5))     # (S, kt, kh, kw, cpa, co)
    d = d.astype(cfg.store_dtype())
    occ = occupancy_map(d, conv=True)
    if can_pack_nibbles(cpa, cfg.store_dtype()):
        d = pack_nibbles(d)                      # cpa axis, two per byte
    out = {
        "w_digits": d,
        "w_occ": occ,
        "s_w": params["s_w"],
        "s_p": params["s_p"],
        "s_a": params["s_a"],
    }
    if variation_wanted(variation_key, variation_std):
        out = perturb_packed(out, variation_key, variation_std)
    return out


def _calibrate_conv(x, params, cfg: CIMConfig, *, stride: int = 1,
                    padding: str = "SAME") -> Dict[str, jnp.ndarray]:
    """One-batch LSQ-style calibration of s_a and s_p for a conv layer."""
    if not cfg.enabled:
        return params
    kh, kw, c_in, c_out = params["w"].shape
    t, c_per_array = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows,
                                 cfg.array_cols, cfg.weight_bits, cfg.cell_bits)
    p = dict(params)
    _, qp_a = qrange(cfg.act_bits, cfg.act_signed)
    p["s_a"] = (2.0 * jnp.mean(jnp.abs(x)) / jnp.sqrt(float(max(qp_a, 1)))
                ).reshape(1).astype(jnp.float32) + 1e-9

    a_int, _ = _quantize_act(x, p, cfg)
    w_int = _quantize_conv_weight_int(p, cfg, t, c_per_array, kh, kw, c_in, c_out)
    digits = split_digits(w_int, cfg.weight_bits, cfg.cell_bits)
    n_split = digits.shape[0]
    k_tiles = t.k_tiles
    c_pad = k_tiles * c_per_array - c_in
    a_p = jnp.pad(a_int, ((0, 0), (0, 0), (0, 0), (0, c_pad)))
    d_p = jnp.pad(digits, ((0, 0), (0, 0), (0, 0), (0, c_pad), (0, 0)))
    d_g = d_p.reshape(n_split, kh, kw, k_tiles, c_per_array, c_out)
    d_g = jnp.transpose(d_g, (1, 2, 4, 0, 3, 5)).reshape(
        kh, kw, c_per_array, n_split * k_tiles * c_out)
    a_g = jnp.tile(a_p, (1, 1, 1, n_split))
    psum = jax.lax.conv_general_dilated(
        a_g.astype(jnp.float32), d_g.astype(jnp.float32), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=n_split * k_tiles)
    b, ho, wo, _ = psum.shape
    psum = psum.reshape(-1, n_split, k_tiles, c_out)
    mean_abs = jnp.mean(jnp.abs(psum), axis=0)               # (S, kt, co)
    _, qp_p = qrange(cfg.psum_bits, True)
    pg = cfg.psum_granularity
    if pg == Granularity.LAYER:
        s = jnp.mean(mean_abs, axis=(1, 2), keepdims=True)
    elif pg == Granularity.ARRAY:
        pad_n = t.n_tiles * t.oc_per_array - t.n
        ma = jnp.pad(mean_abs, ((0, 0), (0, 0), (0, pad_n)))
        s = jnp.mean(ma.reshape(t.n_split, t.k_tiles, t.n_tiles,
                                t.oc_per_array), axis=-1)
    else:
        s = mean_abs
    p["s_p"] = (2.0 * s / jnp.sqrt(float(max(qp_p, 1)))).astype(jnp.float32) + 1e-9
    return p


def conv_dequant_muls(params, cfg: CIMConfig) -> int:
    """Paper Fig. 8 x-axis: dequant scale multiplications for this layer."""
    kh, kw, c_in, c_out = params["w"].shape
    t, _ = conv_tiling(kh, kw, c_in, c_out, cfg.array_rows, cfg.array_cols,
                       cfg.weight_bits, cfg.cell_bits)
    return t.dequant_muls(cfg.weight_granularity, cfg.psum_granularity)


# ---------------------------------------------------------------------------
# deprecated entry points (pre-`repro.api` surface)
# ---------------------------------------------------------------------------

def init_cim_conv(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.init_conv`` / ``QuantConv2d.init``."""
    _deprecated("init_cim_conv", "repro.api.init_conv")
    return _init_conv(*args, **kw)


def cim_conv2d(*args, **kw) -> jnp.ndarray:
    """Deprecated: use ``repro.api.conv2d`` / ``QuantConv2d.__call__``."""
    _deprecated("cim_conv2d", "repro.api.conv2d")
    return _conv_forward(*args, **kw)


def calibrate_cim_conv(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.calibrate_conv``."""
    _deprecated("calibrate_cim_conv", "repro.api.calibrate_conv")
    return _calibrate_conv(*args, **kw)


def pack_deploy_conv(*args, **kw) -> Dict[str, jnp.ndarray]:
    """Deprecated: use ``repro.api.pack_conv`` / ``QuantConv2d.pack``
    (which returns a versioned, saveable ``DeployArtifact``)."""
    _deprecated("pack_deploy_conv", "repro.api.pack_conv")
    return _pack_conv(*args, **kw)
