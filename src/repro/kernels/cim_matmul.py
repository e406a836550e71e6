"""Pallas TPU kernel: fused CIM matmul with partial-sum (ADC) quantization.

TPU-native realization of the paper's array pipeline (DESIGN.md §2).
This is the arithmetic behind the ``deploy`` backend of the
``repro.api.backends`` registry (``CIMConfig.mode`` is a backend name;
dispatch happens through ``get_backend``, not mode strings): the CIM
array boundary becomes the K-grid dimension of a tiled matmul, and the
ADC's per-column quantization is applied to each array-tile's accumulator
*in VMEM* before cross-array shift-and-add — the (M, S, kt, N) partial-sum
tensor never exists in HBM on this path (the ``emulate`` backend still
materializes it, deliberately, so LSQ gradients can flow through the ADC).

Grid: (M/bm, N/bn, n_split, k_tiles); the two reduction dims (bit-split
s, array tile t) iterate fastest so output-block revisits are consecutive
and the accumulation stays resident. Split outer, tile inner is the
order in which the oracle (``ref.shift_add``) adds the same terms, so
the two agree bit for bit on any backend; on the CPU it is also the
order the emulate path's einsum takes at the tests' shapes (not at
every width). The conv deploy path
(kernels/cim_conv) lowers onto this same grid with M = B*H'*W' and
rows = kh*kw*c_per_array (DESIGN.md §3).

Shard-axis invariants (DESIGN.md §10): the trailing N axis of ``digits``
/ ``s_p`` / ``deq`` is the column-parallel shard axis — each output
column's full pipeline (MACs, ADC quantization, dequant, shift-and-add)
reads only that column's planes and scales, and both reduction dims live
inside the grid of ONE kernel invocation. ``kernels/ops`` exploits this:
on a multi-device serving mesh it calls this kernel once per column
shard under ``shard_map`` (scales sliced with their columns, ragged N
padded like the last bn block) and all-gathers only the final f32
output. Nothing in this module may introduce cross-column coupling
(e.g. column-normalized arithmetic) without breaking that contract.

Cell variation (DESIGN.md §8): ``variation_key``/``variation_std`` make
the kernel evaluate one Monte-Carlo device realization — the digit
operand is multiplied by log-normal noise drawn over its *unpadded
packed* shape (S, k_tiles, rows, N) before the pallas_call, so the same
``jax.random`` stream perturbs the same physical cell as on the emulate
path (that is the bit-exactness contract; in-kernel pltpu PRNG could not
reproduce ``jax.random.normal`` draws). The psum-in-VMEM fusion is
unchanged; the digit operand streams as float32 instead of int8 for the
duration of the noisy evaluation.

Layout (what makes the grid lower on TPU at any k_tiles): the wrapper
lays activations out tile-major, (k_tiles, M, rows), and reshapes every
per-(split, tile, column) vector — ``s_p``, ``deq`` — to
(S*k_tiles, 1, N). Every block then keeps its last two dims either whole
or (8, 128)-aligned: the array-tile and bit-split indices only ever
select along leading axes. ``rows`` is the array's full last dim, so
conv row counts like 9*14 = 126 need no padding.

Block shapes (VMEM working set per step, bm=bn=128, rows=256, f32):
  a:      (1, bm, rows)        128*256*4   = 128 KiB (int8 in HBM)
  digits: (1, 1, rows, bn)     256*128*4   = 128 KiB (int8/uint8 in HBM)
  scales: 2 x (1, 1, bn)                  ~= 1 KiB
  out:    (bm, bn)             128*128*4   =  64 KiB
comfortably inside the ~16 MiB VMEM budget; MXU dims are multiples of 128.

Occupancy skip (DESIGN.md §14): the per-block "any column occupied"
decision is a wrapper-side int32 table (``block_occupancy``) that is
scalar-prefetched into SMEM, so the kernel branches on a scalar.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nibble import unpack_nibbles
from repro.core.variation import perturb_digits, variation_wanted


def _adc_quantize(p, sp, *, psum_bits: int):
    sp = jnp.maximum(sp.astype(jnp.float32), 1e-9)  # (bn,)
    if psum_bits == 1:
        return jnp.where(p >= 0, 1.0, -1.0) * sp[None, :]
    qn = float(-(2 ** (psum_bits - 1)))
    qp = float(2 ** (psum_bits - 1) - 1)
    return jnp.clip(jnp.round(p / sp[None, :]), qn, qp) * sp[None, :]


def _zero_at_start(o_ref, t, s):
    @pl.when(jnp.logical_and(t == 0, s == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)


def _mac(a, d, *, nibble: bool, groups: int):
    """One array tile's column MACs: (bm, rows) codes x a (rows_stored, bn)
    stored digit block, decoded in VMEM — uint8 nibble pairs when
    ``nibble`` (rows_stored = rows / 2, half-split per group along the
    row axis; ``repro.core.nibble``), else int8 or float digits.

    Integer codes (up to 8 bits) times integer digits are exact at the
    MXU's default precision (bfloat16 operands, float32 accumulation).
    Float digits — one variation realization — are not: they take the
    float32 contraction, or TPU rounds them to bfloat16 (about 2e-2
    relative off the oracle on a v5e chip)."""
    precision = (None if jnp.issubdtype(d.dtype, jnp.integer)
                 else jax.lax.Precision.HIGHEST)
    if nibble:
        d = unpack_nibbles(d, groups=groups)
    return jnp.dot(a.astype(jnp.float32), d.astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=precision)


def _block_psum(occ_ref, a_ref, d_ref, *, nibble: bool, groups: int,
                table, j, t, s):
    """The (bm, bn) psum block of grid step (j, s, t), shared by the ADC
    and ADC-free kernels.

    ``table=(k_tiles, n_blocks)`` turns on the occupancy skip:
    ``occ_ref`` is then the SMEM-prefetched int32 block table
    (``block_occupancy``), and a dead (split, tile, column-block) — every
    column's digit plane all-zero — skips the digit decode and the MXU
    dot, yielding the exact psum of an all-zero plane, +0.0. Everything
    downstream (ADC stage, dequant, accumulate) runs unconditionally, so
    a dead block goes through the verbatim dense expression graph and
    compiler fusion cannot diverge: the skip is bit-exact with the dense
    kernel (tests/test_sparse_skip.py) — including the sign ADC
    (psum_bits == 1), where a zero psum still contributes +s_p * deq."""
    def mac():
        return _mac(a_ref[0], d_ref[0, 0], nibble=nibble, groups=groups)
    if table is None:
        return mac()
    k_tiles, n_blocks = table
    live = occ_ref[(s * k_tiles + t) * n_blocks + j] > 0
    bm, bn = a_ref.shape[1], d_ref.shape[-1]
    return jax.lax.cond(live, mac,
                        lambda: jnp.zeros((bm, bn), jnp.float32))


def _contribution(p, sp, deq, *, psum_bits: int, psum_quant: bool):
    """ADC stage + fused dequant of one (bm, bn) psum block — the term the
    (s, t) reduction adds into the output block."""
    if psum_quant:
        p = jnp.round(p)    # integer-valued MACs: kill float roundoff
        p = _adc_quantize(p, sp, psum_bits=psum_bits)
    return p * deq.astype(jnp.float32)[None, :]


def _kernel(*refs, psum_bits: int, psum_quant: bool, nibble: bool = False,
            groups: int = 1, table=None):
    """Grid (i, j, s, t). With ``table`` (the occupancy skip) the first
    ref is the scalar-prefetched block table — see ``_block_psum``."""
    occ_ref = refs[0] if table is not None else None
    a_ref, d_ref, sp_ref, deq_ref, o_ref = refs[-5:]
    j, s, t = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    _zero_at_start(o_ref, t, s)
    p = _block_psum(occ_ref, a_ref, d_ref, nibble=nibble, groups=groups,
                    table=table, j=j, t=t, s=s)
    o_ref[...] += _contribution(p, sp_ref[0, 0, :], deq_ref[0, 0, :],
                                psum_bits=psum_bits, psum_quant=psum_quant)


def tile_major(a_t: jnp.ndarray, bm: int) -> jnp.ndarray:
    """(..., M, k_tiles, rows) activations -> (..., k_tiles, M', rows),
    M padded up to a multiple of ``bm`` — the layout whose (1, bm, rows)
    blocks lower on TPU for any k_tiles."""
    pad_m = (-a_t.shape[-3]) % bm
    if pad_m:
        a_t = jnp.pad(a_t, [(0, 0)] * (a_t.ndim - 3)
                      + [(0, pad_m), (0, 0), (0, 0)])
    return jnp.swapaxes(a_t, -3, -2)


def scale_slab(x: jnp.ndarray, pad_n: int, value: float = 0.0):
    """(..., S, k_tiles, N) column vectors -> (..., S*k_tiles, 1, N + pad_n):
    the split and tile indices fold into one leading block index
    ``s * k_tiles + t``, so a (1, 1, bn) block is whole in its
    second-minor dim."""
    if pad_n:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad_n)],
                    constant_values=value)
    lead = x.shape[:-3]
    return x.reshape(lead + (x.shape[-3] * x.shape[-2], 1, x.shape[-1]))


def block_occupancy(occ: jnp.ndarray, bn: int) -> jnp.ndarray:
    """(S, k_tiles, N') per-column occupancy (N' a multiple of ``bn``) ->
    flat int32 (S * k_tiles * N'/bn,) table, 1 iff any column of the
    (split, tile, column block) is occupied. Indexed
    ``(s * k_tiles + t) * n_blocks + j`` by the sparse kernels."""
    s, kt, n = occ.shape
    live = jnp.any(occ.reshape(s, kt, n // bn, bn) > 0, axis=-1)
    return live.astype(jnp.int32).reshape(-1)


def fused_grid_call(kernel, a_t, digits, cols, variation_key,
                    variation_std, occ, *, nibble_groups: int, block_m: int,
                    block_n: int, interpret: bool) -> jnp.ndarray:
    """The wrapper the ADC and ADC-free deploy kernels share: variation,
    padding, the tile-major layout, the grid and the occupancy table.

    ``kernel(*refs, nibble, groups, table)`` is the body; its refs are
    the skip table (with ``occ``), the activations, the digits, one ref
    per ``cols`` entry and the output. ``cols`` holds (S, k_tiles, N)
    per-column operands, each with the value its padded columns take.
    Returns (M, N) float32."""
    nibble = digits.dtype == jnp.uint8   # nibble-packed HBM planes (§14)
    if variation_wanted(variation_key, variation_std):
        # perturb BEFORE block padding: noise indices must match the
        # packed (unpadded) LOGICAL layout the emulate path perturbs (§8)
        # — nibble planes decode to that layout first, so a packed and a
        # dense artifact draw identical noise from the same key
        if nibble:
            digits = unpack_nibbles(digits, groups=nibble_groups)
            nibble = False
        digits = perturb_digits(digits, variation_key, variation_std)
    m, k_tiles, rows = a_t.shape
    n_split = digits.shape[0]
    n = digits.shape[-1]
    rows_d = digits.shape[2]             # stored rows: rows/2 when nibble
    assert rows_d == (rows // 2 if nibble else rows), \
        (digits.shape, a_t.shape, nibble)

    bm = min(block_m, m)
    bn = min(block_n, n)
    pad_n = (-n) % bn
    a_t = tile_major(a_t, bm)            # (k_tiles, mp, rows)
    mp, np_ = a_t.shape[1], n + pad_n
    if pad_n:
        digits = jnp.pad(digits, ((0, 0), (0, 0), (0, 0), (0, pad_n)))
    cols = tuple(scale_slab(c, pad_n, value) for c, value in cols)

    grid = (mp // bm, np_ // bn, n_split, k_tiles)
    col_spec = pl.BlockSpec((1, 1, bn),
                            lambda i, j, s, t, *_: (s * k_tiles + t, 0, j))
    in_specs = [
        pl.BlockSpec((1, bm, rows), lambda i, j, s, t, *_: (t, i, 0)),
        pl.BlockSpec((1, 1, rows_d, bn), lambda i, j, s, t, *_: (s, t, 0, j)),
    ] + [col_spec] * len(cols)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, s, t, *_: (i, j))
    body = functools.partial(kernel, nibble=nibble, groups=nibble_groups)
    args = (a_t, digits) + cols
    if occ is None:
        grid_spec = pl.GridSpec(grid=grid, in_specs=in_specs,
                                out_specs=out_spec)
    else:
        if pad_n:
            occ = jnp.pad(occ, ((0, 0), (0, 0), (0, pad_n)))  # dead: skip
        body = functools.partial(body, table=(k_tiles, np_ // bn))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec)
        args = (block_occupancy(occ, bn),) + args
    out = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("psum_bits", "psum_quant", "nibble_groups", "block_m",
                     "block_n", "interpret"),
)
def cim_matmul_pallas(
    a_t: jnp.ndarray,      # (M, k_tiles, rows) integer-valued
    digits: jnp.ndarray,   # (S, k_tiles, rows, N); uint8 = nibble-packed
    s_p: jnp.ndarray,      # (S, k_tiles, N)
    deq: jnp.ndarray,      # (S, k_tiles, N)
    variation_key=None,    # optional PRNG key: one MC device realization
    variation_std=None,    # log-normal sigma (float or traced scalar)
    occ=None,              # optional (S, k_tiles, N) uint8 occupancy map
    *,
    psum_bits: int,
    psum_quant: bool = True,
    nibble_groups: int = 1,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    # padded columns: s_p 1.0 keeps the ADC's divide finite, deq 0.0
    # zeroes them
    return fused_grid_call(
        functools.partial(_kernel, psum_bits=psum_bits,
                          psum_quant=psum_quant),
        a_t, digits, ((s_p, 1.0), (deq, 0.0)), variation_key,
        variation_std, occ, nibble_groups=nibble_groups, block_m=block_m,
        block_n=block_n, interpret=interpret)


# ---------------------------------------------------------------------------
# batched expert banks (MoE dispatch)
# ---------------------------------------------------------------------------

def _experts_kernel(a_ref, d_ref, sp_ref, deq_ref, o_ref, *, psum_bits: int,
                    psum_quant: bool):
    _zero_at_start(o_ref, pl.program_id(3), pl.program_id(4))
    p = _mac(a_ref[0, 0], d_ref[0, 0, 0], nibble=False, groups=1)
    o_ref[...] += _contribution(p, sp_ref[0, 0, 0, :], deq_ref[0, 0, 0, :],
                                psum_bits=psum_bits,
                                psum_quant=psum_quant)[None]


@functools.partial(
    jax.jit,
    static_argnames=("psum_bits", "psum_quant", "block_m", "block_n",
                     "interpret"),
)
def cim_matmul_experts_pallas(
    a_t: jnp.ndarray,      # (E, C, k_tiles, rows) integer-valued
    digits: jnp.ndarray,   # (E, S, k_tiles, rows, N)
    s_p: jnp.ndarray,      # (E, S, k_tiles, N)
    deq: jnp.ndarray,      # (E, S, k_tiles, N)
    *,
    psum_bits: int,
    psum_quant: bool = True,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Whole-bank MoE variant: all E experts' capacity buffers through ONE
    pallas_call with the expert index as the leading (slowest) grid
    dimension, instead of ``lax.map`` issuing E sequential calls
    (``pallas_call`` has no batching rule, so vmap can't do this).

    Per output block the (s, t) accumulation order, block shapes and
    last-block padding are IDENTICAL to ``cim_matmul_pallas`` on one
    expert's (C, K) slice — the batched path is bit-exact with the
    ``lax.map`` fallback, which is what keeps the model-zoo deploy-vs-
    emulate parity gates green. The layout is the same tile-major one,
    with the expert axis leading: activations (E, k_tiles, C, rows),
    scales (E, S*k_tiles, 1, N). Variation injection is not plumbed here:
    the packed expert dispatch (``models.layers._expert_matmul``) never
    injects per-call noise (bank noise is baked at pack time), and
    callers needing it take the ``lax.map`` path.
    """
    e, m, k_tiles, rows = a_t.shape
    n_split = digits.shape[1]
    n = digits.shape[-1]

    bm = min(block_m, m)
    bn = min(block_n, n)
    pad_n = (-n) % bn
    a_t = tile_major(a_t, bm)            # (E, k_tiles, mp, rows)
    mp, np_ = a_t.shape[2], n + pad_n
    if pad_n:
        digits = jnp.pad(digits,
                         ((0, 0), (0, 0), (0, 0), (0, 0), (0, pad_n)))
    s_p = scale_slab(s_p, pad_n, 1.0)
    deq = scale_slab(deq, pad_n)

    grid = (e, mp // bm, np_ // bn, n_split, k_tiles)
    col_spec = pl.BlockSpec(
        (1, 1, 1, bn), lambda ei, i, j, s, t: (ei, s * k_tiles + t, 0, j))
    out = pl.pallas_call(
        functools.partial(_experts_kernel, psum_bits=psum_bits,
                          psum_quant=psum_quant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, rows),
                         lambda ei, i, j, s, t: (ei, t, i, 0)),
            pl.BlockSpec((1, 1, 1, rows, bn),
                         lambda ei, i, j, s, t: (ei, s, t, 0, j)),
            col_spec, col_spec,
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda ei, i, j, s, t: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, mp, np_), jnp.float32),
        interpret=interpret,
    )(a_t, digits, s_p, deq)
    return out[:, :m, :n]
