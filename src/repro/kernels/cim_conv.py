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

  1. ``tile_major_patches`` gathers each output position's receptive
     field ONCE per channel slice, straight into the kernel's tile-major
     operand (k_tiles, M, rows), M = H'*W'*B, rows = kh*kw*c_per_array in
     the row order (dh, dw, c) of ``repro.api.pack_conv``'s digit layout
     (so packed digits and nibble groups are untouched). It moves the 1x
     code map, not the patches: the channel-padded codes are transposed
     once to (k_tiles*B, H, W, c_per_array), and ONE convolution with a
     constant 0/1 filter — tap (dh, dw), channel c to output feature
     (dh*kw + dw)*c_per_array + c — writes the patches. Each output sums
     one code times 1.0, so the result equals ``ref.extract_conv_patches``
     (the oracle) bit for bit: 8-bit integer codes are exact in bfloat16,
     wider or float codes take the float32 conv at HIGHEST precision. A
     1x1 conv has no taps to gather and takes a strided slice instead.
     The output positions run (h', w', b), batch last: that is how a TPU
     conv lays its output out, so the tile axis moves out in one copy
     and no other relayout of the patches follows; the kernel's output
     rows come back in the same order, which on TPU is already the
     layout XLA gives an NHWC activation (``nhwc_output``). No n_split
     replication:
     the kernel re-reads the same patch block per bit-split via its
     BlockSpec index map (the a-operand map ignores the split index).
  2. The fused CIM matmul kernel (``cim_matmul.cim_matmul_tiled``) takes
     that operand as it is; its grid (M/bm, C_out/bn, n_split,
     k_tiles/tk) applies ADC quantization to each array-tile accumulator
     in VMEM — the partial-sum tensor never touches HBM (DESIGN.md §7).

The block shape and its VMEM working set come from the same chooser
as the linear kernel's (``cim_matmul.block_shape``, DESIGN.md §6), with
rows = kh*kw*c_per_array <= array_rows. Tracing a conv adds the bytes of
its patch operand to the ``cim.patches.bytes`` counter
(``repro.obs.compiles``).

Shard-axis invariant (DESIGN.md §10): the trailing C_out axis of the
flattened planes/scales is the column-parallel shard axis. Patches are
output-channel-independent, so the sharded serving path runs this same
lowering one C_out shard per device on the replicated codes — keep any
future patch/geometry change free of cross-output-channel coupling or
the shard_map dispatch in ``kernels/ops`` breaks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import names

from .cim_matmul import BLOCK_M_MAX, BLOCK_N_MAX, cim_matmul_tiled
from .ref import conv_pads


def conv_out_hw(shape, kh: int, kw: int, stride: int, padding) -> tuple:
    """(H', W') of a conv over (B, H, W, C) ``shape``."""
    (t, b), (l, r) = conv_pads(shape[1], shape[2], kh, kw, stride, padding)
    return ((shape[1] + t + b - kh) // stride + 1,
            (shape[2] + l + r - kw) // stride + 1)


@jax.named_scope(names.SCOPE_PATCHES)
def tile_major_patches(
    a: jnp.ndarray,        # (B, H, W, C) codes
    kh: int, kw: int,
    stride: int,
    padding,
    k_tiles: int,
    c_per_array: int,
) -> jnp.ndarray:
    """Stretched-kernel patches in the kernel's tile-major layout:
    (k_tiles, M, kh*kw*c_per_array) in ``a``'s dtype, M = H'*W'*B with
    the output positions in (h', w', b) order and rows in (dh, dw, c)
    order — ``ref.extract_conv_patches`` with its tile axis moved first
    and its batch axis moved last, bit for bit (module docstring;
    ``nhwc_output`` puts the kernel's output rows back). Runs under the
    ``cim.patches`` scope, and tracing it adds the operand's bytes to
    the ``cim.patches.bytes`` counter."""
    b, h, w, c = a.shape
    cpa = c_per_array
    pads = conv_pads(h, w, kh, kw, stride, padding)
    ho, wo = conv_out_hw(a.shape, kh, kw, stride, padding)
    x = jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, k_tiles * cpa - c)))
    x = jnp.moveaxis(x.reshape(b, h, w, k_tiles, cpa), 3, 0)
    x = x.reshape(k_tiles * b, h, w, cpa)            # the 1x transpose
    if kh * kw == 1:
        x = jax.lax.pad(x, jnp.zeros((), x.dtype),
                        ((0, 0, 0),) + tuple(p + (0,) for p in pads)
                        + ((0, 0, 0),))
        p = jax.lax.slice(x, (0, 0, 0, 0),
                          (k_tiles * b, (ho - 1) * stride + 1,
                           (wo - 1) * stride + 1, cpa),
                          (1, stride, stride, 1))
    else:
        exact8 = (jnp.issubdtype(a.dtype, jnp.integer)
                  and a.dtype.itemsize == 1)
        dtype = jnp.bfloat16 if exact8 else jnp.float32
        rows = kh * kw * cpa
        taps = jnp.asarray(np.eye(rows).reshape(kh, kw, cpa, rows), dtype)
        p = jax.lax.conv_general_dilated(
            x.astype(dtype), taps, (stride, stride), pads,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=None if exact8 else jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(a.dtype)
    # a TPU conv lays its batch axis out second-minor, (h', w', tile, b,
    # row) in memory: with b last among the positions, moving the tile
    # axis out is the one copy, and the (k_tiles, M, rows) view of it
    # needs none
    p = jnp.transpose(p.reshape(k_tiles, b, ho, wo, kh * kw * cpa),
                      (0, 2, 3, 1, 4)).reshape(k_tiles, ho * wo * b, -1)
    jax.monitoring.record_scalar(names.CIM_PATCHES_BYTES,
                                 p.size * p.dtype.itemsize)
    return p


@jax.named_scope(names.SCOPE_LAYOUT)
def nhwc_output(out: jnp.ndarray, shape, kh: int, kw: int, stride: int,
                padding) -> jnp.ndarray:
    """(H'*W'*B, N) kernel output, rows in the (h', w', b) order of
    ``tile_major_patches`` -> (B, H', W', N) for a conv over (B, H, W, C)
    ``shape``. On TPU this is the batch-second-minor layout XLA gives an
    NHWC activation, so it moves nothing."""
    ho, wo = conv_out_hw(shape, kh, kw, stride, padding)
    return jnp.transpose(out.reshape(ho, wo, shape[0], out.shape[-1]),
                         (2, 0, 1, 3))


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
    """Fused CIM conv: tile-major stretched-kernel patches -> tiled
    matmul kernel.

    Returns (B, H', W', C_out) float32.
    """
    k_tiles, rows_d = digits.shape[1:3]
    rows = kh * kw * c_per_array           # logical rows, from the geometry
    nibble = digits.dtype == jnp.uint8
    assert rows_d == (rows // 2 if nibble else rows), \
        (digits.shape, kh, kw, c_per_array, nibble)
    a_tm = tile_major_patches(a_int, kh, kw, stride, padding, k_tiles,
                              c_per_array)
    out = cim_matmul_tiled(
        a_tm, digits, s_p, deq, variation_key, variation_std, occ,
        psum_bits=psum_bits, psum_quant=psum_quant,
        # each of the kh*kw taps is its own packed nibble block in the
        # flattened row layout (repro.core.nibble)
        nibble_groups=kh * kw,
        block_m=block_m, block_n=block_n, interpret=interpret,
    )
    return nhwc_output(out, a_int.shape, kh, kw, stride, padding)
