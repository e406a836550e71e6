"""Pure-jnp oracles for the Pallas kernels.

These define the exact arithmetic the kernels must reproduce; tests sweep
shapes/dtypes and assert allclose against them. The psum contractions
ask for ``HIGHEST`` precision: TPU's default precision feeds float32
operands to the MXU as bfloat16, exact for integer codes and digits but
not for the float digits of a variation realization. (The CPU backend
computes float32 either way.) The dequant sums run in the kernels' order
(``shift_add``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def adc_quantize_ref(p: jnp.ndarray, s_p: jnp.ndarray, psum_bits: int) -> jnp.ndarray:
    """ADC model: uniform mid-rise quantization of a partial sum at scale
    s_p, clipped to the signed psum_bits range. psum_bits == 1 is the
    binary (sign) ADC-less mode. Partial sums are integer-valued (int x
    int MACs); snapping to the grid first makes tie-breaking summation-
    order independent."""
    p = jnp.round(p)
    s_p = jnp.maximum(s_p, 1e-9)
    if psum_bits == 1:
        return jnp.where(p >= 0, 1.0, -1.0) * s_p
    qn = -(2 ** (psum_bits - 1))
    qp = 2 ** (psum_bits - 1) - 1
    return jnp.clip(jnp.round(p / s_p), qn, qp) * s_p


def shift_add(psum: jnp.ndarray, deq: jnp.ndarray) -> jnp.ndarray:
    """Dequantize and accumulate the column psums, (M, S, k_tiles, N) x
    (S, k_tiles, N) -> (M, N), one (split, tile) term at a time in the
    deploy kernels' grid order: bit split outer, array tile inner — the
    row-major (s, t) order the emulate path's einsum takes on the CPU at
    the tests' shapes.

    Float addition is not associative. An einsum leaves the order to the
    compiler, and on TPU its order differs from the kernel's in the last
    bit of about three outputs in four; the next layer's activation
    rounding turns those bits into different codes, and a deep model's
    logits into different ones."""
    m, n = psum.shape[0], psum.shape[-1]
    p = jnp.transpose(psum, (1, 2, 0, 3)).reshape(-1, m, n)
    d = deq.astype(jnp.float32).reshape(-1, n)

    def add(y, term):
        p_i, d_i = term
        return y + p_i * d_i[None, :], None
    return jax.lax.scan(add, jnp.zeros((m, n), jnp.float32), (p, d))[0]


def cim_matmul_ref(
    a_t: jnp.ndarray,      # (M, k_tiles, rows)    integer-valued float
    digits: jnp.ndarray,   # (S, k_tiles, rows, N) int8 or float digits
    s_p: jnp.ndarray,      # (S, k_tiles, N)       psum (ADC) scales
    deq: jnp.ndarray,      # (S, k_tiles, N)       fused dequant scales
    *,
    psum_bits: int,
    psum_quant: bool = True,
) -> jnp.ndarray:
    """CIM matmul oracle: per-(split, array) integer MACs, ADC quantization
    of each column partial sum, fused dequant, shift-and-add. Returns
    (M, N) float32."""
    psum = jnp.einsum(
        "mtr,strn->mstn",
        a_t.astype(jnp.float32),
        digits.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    if psum_quant:
        psum = adc_quantize_ref(psum, s_p[None], psum_bits)
    return shift_add(psum, deq)


def cim_matmul_adc_free_ref(
    a_t: jnp.ndarray,      # (M, k_tiles, rows)    integer-valued float
    digits: jnp.ndarray,   # (S, k_tiles, rows, N) int8 or float digits
    deq: jnp.ndarray,      # (S, k_tiles, N)       fused dequant scales
) -> jnp.ndarray:
    """ADC-free CIM matmul oracle (HCiM-style hardware, DESIGN.md §13):
    per-(split, array) integer MACs leave the array exact — partial sums
    are accumulated digitally, so there is no ADC quantization stage and
    no s_p operand. Returns (M, N) float32."""
    psum = jnp.einsum(
        "mtr,strn->mstn",
        a_t.astype(jnp.float32),
        digits.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=HIGHEST,
    )
    psum = jnp.round(psum)  # same integer snap as the ADC oracle
    return shift_add(psum, deq)


def lsq_fake_quant_ref(x, s, qn: float, qp: float):
    s = jnp.maximum(s, 1e-9)
    return jnp.clip(jnp.round(x / s), qn, qp) * s


def conv_pads(h: int, w: int, kh: int, kw: int, stride: int, padding):
    """Resolve a conv padding spec to explicit ((lo,hi),(lo,hi)) pairs,
    identical to what XLA's conv_general_dilated computes for the same
    string — the deploy patch path must agree with the emulate conv."""
    if isinstance(padding, str):
        pads = jax.lax.padtype_to_pads((h, w), (kh, kw), (stride, stride),
                                       padding.upper())
        return tuple((int(lo), int(hi)) for lo, hi in pads)
    return tuple((int(lo), int(hi)) for lo, hi in padding)


def extract_conv_patches(
    a: jnp.ndarray,        # (B, H, W, C)
    kh: int, kw: int,
    stride: int,
    padding,
    k_tiles: int,
    c_per_array: int,
) -> jnp.ndarray:
    """Stretched-kernel patch extraction (paper §III-C, DESIGN.md §3).

    Returns (B, H', W', k_tiles, kh*kw*c_per_array): for every output
    position, tile t's row block holds exactly the activations its CIM
    array's stretched kernels see, flattened tap-major (dh, dw, c). This
    is NOT generic im2col — the contraction axis is tiled by the paper's
    ``c_per_array = floor(rows / K^2)`` rule so channel slices never
    straddle an array boundary. Channels are zero-padded to
    ``k_tiles * c_per_array`` (matching the emulate path's padding).
    """
    b, h, w, c = a.shape
    pads = conv_pads(h, w, kh, kw, stride, padding)
    c_pad = k_tiles * c_per_array - c
    a = jnp.pad(a, ((0, 0), pads[0], pads[1], (0, c_pad)))
    hp = h + pads[0][0] + pads[0][1]
    wp = w + pads[1][0] + pads[1][1]
    ho = (hp - kh) // stride + 1
    wo = (wp - kw) // stride + 1
    taps = []
    for dh in range(kh):
        for dw in range(kw):
            taps.append(jax.lax.slice(
                a, (0, dh, dw, 0),
                (b, dh + (ho - 1) * stride + 1,
                 dw + (wo - 1) * stride + 1, a.shape[3]),
                (1, stride, stride, 1)))
    p = jnp.stack(taps, axis=3)                     # (B,H',W',taps,kt*cpa)
    p = p.reshape(b, ho, wo, kh * kw, k_tiles, c_per_array)
    p = jnp.transpose(p, (0, 1, 2, 4, 3, 5))        # (B,H',W',kt,taps,cpa)
    return p.reshape(b, ho, wo, k_tiles, kh * kw * c_per_array)


def cim_conv_ref(
    a_int: jnp.ndarray,    # (B, H, W, C_in) integer-valued codes
    digits: jnp.ndarray,   # (S, k_tiles, kh*kw*cpa, C_out)
    s_p: jnp.ndarray,      # (S, k_tiles, C_out)
    deq: jnp.ndarray,      # (S, k_tiles, C_out)
    *,
    kh: int, kw: int,
    stride: int,
    padding,
    c_per_array: int,
    psum_bits: int,
    psum_quant: bool = True,
) -> jnp.ndarray:
    """CIM conv oracle: stretched-kernel patches, then the matmul oracle
    per output position. Returns (B, H', W', C_out) float32."""
    k_tiles = digits.shape[1]
    a_t = extract_conv_patches(a_int.astype(jnp.float32), kh, kw, stride,
                               padding, k_tiles, c_per_array)
    b, ho, wo = a_t.shape[:3]
    out = cim_matmul_ref(
        a_t.reshape(b * ho * wo, k_tiles, kh * kw * c_per_array),
        digits, s_p, deq, psum_bits=psum_bits, psum_quant=psum_quant)
    return out.reshape(b, ho, wo, digits.shape[-1])
