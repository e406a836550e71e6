"""Fused Pallas deploy path for the CIM convolution (DESIGN.md §3).

The paper's stretched-kernel tiling (§III-C, Fig. 5) makes each CIM
array's MAC a convolution over a ``c_per_array`` channel slice with all
``kh*kw`` taps resident in the array. The ``emulate`` backend
(``repro.api.backends`` registry — conv dispatch goes through
``get_backend(cfg.mode).conv``, not mode strings) realizes this as
one XLA grouped convolution, which costs two HBM round-trips the hardware
never pays: the activation channel-slices are *tiled* ``n_split``x into
the group axis, and the full (B, H', W', S, kt, C_out) partial-sum tensor
is materialized before ADC quantization.

The ``deploy`` backend's kernel here removes both:

(Cell variation rides the same lowering: ``variation_key``/
``variation_std`` pass through to the matmul kernel, which perturbs the
flattened digit planes (S, kt, kh*kw*cpa, C_out) — row-major identical to
the packed 6-D conv layout, so conv deploy and conv emulate draw the same
per-cell noise from a shared key; DESIGN.md §8.)

  1. ``ref.extract_conv_patches`` gathers each output position's
     receptive field ONCE per channel slice — (B, H', W', k_tiles, rows)
     with rows = kh*kw*c_per_array, row order (dh, dw, c) matching
     ``repro.api.pack_conv``'s digit layout. No n_split replication: the
     kernel re-reads the same patch block per bit-split via its BlockSpec
     index map (the a-operand map ignores the split index).
  2. The spatial axis flattens to M = B*H'*W' and lowers onto the fused
     CIM matmul kernel, whose grid (M/bm, C_out/bn, n_split,
     k_tiles/tk) applies ADC quantization to each array-tile
     accumulator in VMEM — the partial-sum tensor never touches HBM
     (DESIGN.md §7).

The block shape and its VMEM working set come from the same chooser
as the linear kernel's (``cim_matmul.block_shape``, DESIGN.md §6), with
rows = kh*kw*c_per_array <= array_rows.

Shard-axis invariant (DESIGN.md §10): the trailing C_out axis of the
flattened planes/scales is the column-parallel shard axis. Patches are
output-channel-independent, so the sharded serving path extracts them
once (replicated) and runs this same lowering one C_out shard per device
— keep any future patch/geometry change free of cross-output-channel
coupling or the shard_map dispatch in ``kernels/ops`` breaks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .cim_matmul import BLOCK_M_MAX, BLOCK_N_MAX, cim_matmul_pallas
from .ref import extract_conv_patches


@functools.partial(
    jax.jit,
    static_argnames=("kh", "kw", "stride", "padding", "c_per_array",
                     "psum_bits", "psum_quant", "block_m", "block_n",
                     "interpret"),
)
def cim_conv_pallas(
    a_int: jnp.ndarray,    # (B, H, W, C_in) integer-valued codes
    digits: jnp.ndarray,   # (S, k_tiles, kh*kw*cpa, C_out); uint8 = nibble
    s_p: jnp.ndarray,      # (S, k_tiles, C_out)
    deq: jnp.ndarray,      # (S, k_tiles, C_out)
    variation_key=None,    # optional PRNG key: one MC device realization
    variation_std=None,    # log-normal sigma (float or traced scalar)
    occ=None,              # optional (S, k_tiles, C_out) occupancy map
    *,
    kh: int,
    kw: int,
    stride: int,
    padding: str,
    c_per_array: int,
    psum_bits: int,
    psum_quant: bool = True,
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused CIM conv: stretched-kernel patches -> tiled matmul kernel.

    Returns (B, H', W', C_out) float32.
    """
    n_split, k_tiles, rows_d, n = digits.shape
    rows = kh * kw * c_per_array           # logical rows, from the geometry
    nibble = digits.dtype == jnp.uint8
    assert rows_d == (rows // 2 if nibble else rows), \
        (digits.shape, kh, kw, c_per_array, nibble)
    a_t = extract_conv_patches(a_int, kh, kw, stride, padding, k_tiles,
                               c_per_array)
    b, ho, wo = a_t.shape[:3]
    out = cim_matmul_pallas(
        a_t.reshape(b * ho * wo, k_tiles, rows),
        digits, s_p, deq, variation_key, variation_std, occ,
        psum_bits=psum_bits, psum_quant=psum_quant,
        # each of the kh*kw taps is its own packed nibble block in the
        # flattened row layout (repro.core.nibble)
        nibble_groups=kh * kw,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return out.reshape(b, ho, wo, n)
