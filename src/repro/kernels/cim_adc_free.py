"""Pallas TPU kernel: ADC-free CIM matmul with digital psum accumulation.

The ``adc_free`` hardware style (HCiM-style hybrid analog-digital CIM,
PAPERS.md) removes the per-column ADC from the array pipeline: each
(split, array-tile, column) partial sum leaves the array as an exact
integer — bit-sliced MACs are accumulated *digitally* — so there is no
psum quantization step at all. The psum_bits knob stops being an ADC
resolution and becomes the digital accumulator width the cost model
charges (benchmarks/bench_hw_cost.layer_cost(style="adc_free")); the
kernel itself accumulates exactly.

Relative to the ADC kernel (``kernels/cim_matmul``) the epilogue drops
the ADC stage (round -> scale -> clip -> rescale in VMEM) *and* the s_p
operand — the per-column ADC scale stream never leaves HBM because it
does not exist on this hardware. Everything else is deliberately
identical: the same body and grid (``cim_matmul.fused_grid_call``:
(M/bm, N/bn, n_split, k_tiles/tk), the reduction dims iterating fastest
and the tile chunk walked in the body), same packed digit-plane layout,
same trailing-N column-shard contract (kernels/ops dispatches this
kernel per column shard under shard_map unchanged, DESIGN.md §10), and
cell variation is injected on the unpadded packed planes before the
pallas_call exactly like the ADC kernel — ``perturb_packed`` semantics
carry over untouched (§8).

Bit-exactness contract: psums are integer-valued (int x int MACs), so
``jnp.round`` on the f32 accumulator is the identity up to float
roundoff snapping — the same snap the ADC kernel applies before
quantizing. Consequently ``adc_free`` output == the ADC kernel's output
whenever the ADC is transparent (s_p == 1 and psum_bits wide enough
that no column clips), which is what the hypothesis property tests in
tests/test_backends.py pin down.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import names

from .cim_conv import nhwc_output, tile_major_patches
from .cim_matmul import BLOCK_M_MAX, BLOCK_N_MAX, fused_grid_call, tile_major


def _digital(p, deq):
    """Digital accumulation of one array tile's psum block: snap the
    integer-valued MACs (kills float roundoff, matching the ADC kernel's
    pre-quantize snap) and dequantize — no ADC stage. A dead block of the
    occupancy skip (``cim_matmul._grid_body``) has no sign-ADC subtlety
    here: an all-zero plane's exact digital psum is 0, so dense and skip
    both add +0.0 — bit-identical on a +0.0-initialized accumulator."""
    return jnp.round(p) * deq.astype(jnp.float32)[None, :]


def _adc_free_tiled(a_tm, digits, deq, variation_key, variation_std, occ,
                    *, nibble_groups: int, block_m: int, block_n: int,
                    interpret: bool) -> jnp.ndarray:
    """The ADC-free kernel on tile-major activations (k_tiles, M, rows),
    shared by the matmul and conv entry points. Returns (M, N) float32."""
    # reduction dims (s outer, t inner), iterating fastest: the digital
    # accumulator adds the dequantized words in the order of the ADC
    # kernel and of the oracle (``ref.shift_add``) — unquantized psums
    # carry full mantissas, so any reassociation is visible at 1 ulp and
    # amplifies through the next layer's activation-code rounding
    return fused_grid_call(_digital, a_tm, digits, ((deq, 0.0),),
                           variation_key, variation_std, occ,
                           name=names.KERNEL_CIM_ADC_FREE,
                           nibble_groups=nibble_groups, block_m=block_m,
                           block_n=block_n, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("nibble_groups", "block_m", "block_n", "interpret"),
)
def cim_matmul_adc_free_pallas(
    a_t: jnp.ndarray,      # (M, k_tiles, rows) integer-valued
    digits: jnp.ndarray,   # (S, k_tiles, rows, N); uint8 = nibble-packed
    deq: jnp.ndarray,      # (S, k_tiles, N) fused dequant scales
    variation_key=None,    # optional PRNG key: one MC device realization
    variation_std=None,    # log-normal sigma (float or traced scalar)
    occ=None,              # optional (S, k_tiles, N) uint8 occupancy map
    *,
    nibble_groups: int = 1,
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    interpret: bool = False,
) -> jnp.ndarray:
    """ADC-free CIM matmul: digital accumulation of bit-sliced psums.

    Same operands and tile-major layout as ``cim_matmul_pallas`` minus
    ``s_p`` (no ADC scale stream exists on this hardware style). Returns
    (M, N) float32.
    """
    with jax.named_scope(names.SCOPE_LAYOUT):
        a_tm = tile_major(a_t)
    return _adc_free_tiled(a_tm, digits, deq, variation_key, variation_std,
                           occ, nibble_groups=nibble_groups,
                           block_m=block_m, block_n=block_n,
                           interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "c_per_array",
                     "block_m", "block_n", "interpret"),
)
def cim_conv_adc_free_pallas(
    a_int: jnp.ndarray,    # (B, H, W, C_in) integer-valued codes
    digits: jnp.ndarray,   # (S, k_tiles, kh*kw*cpa, C_out); uint8 = nibble
    deq: jnp.ndarray,      # (S, k_tiles, C_out)
    variation_key=None,
    variation_std=None,
    occ=None,              # optional (S, k_tiles, C_out) occupancy map
    *,
    kh: int,
    kw: int,
    stride: int,
    padding: str,
    c_per_array: int,
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    interpret: bool = False,
) -> jnp.ndarray:
    """ADC-free CIM conv: same stretched-kernel lowering as
    ``kernels.cim_conv.cim_conv_pallas`` (tile-major patches once, then
    the tiled matmul grid) but onto the ADC-free kernel.

    Returns (B, H', W', C_out) float32.
    """
    k_tiles, rows_d = digits.shape[1:3]
    rows = kh * kw * c_per_array           # logical rows, from the geometry
    nibble = digits.dtype == jnp.uint8
    assert rows_d == (rows // 2 if nibble else rows), \
        (digits.shape, kh, kw, c_per_array, nibble)
    a_tm = tile_major_patches(a_int, kh, kw, stride, padding, k_tiles,
                              c_per_array)
    out = _adc_free_tiled(
        a_tm, digits, deq, variation_key, variation_std, occ,
        # each of the kh*kw taps is its own packed nibble block in the
        # flattened row layout (repro.core.nibble)
        nibble_groups=kh * kw,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return nhwc_output(out, a_int.shape, kh, kw, stride, padding)
