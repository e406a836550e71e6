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

Grid: (M/bm, N/bn, n_split, k_tiles/tk). A step holds a chunk of ``tk``
array tiles and walks them in its body; the reduction dims (bit split
s, tile chunk) iterate fastest so output-block revisits are consecutive
and the accumulation stays resident. Split outer, tile inner is the
order in which the oracle (``ref.shift_add``) adds the same terms, so
the two agree bit for bit on any backend; on the CPU it is also the
order the emulate path's einsum takes at the tests' shapes (not at
every width). The block shape (bm, bn, tk) comes from the operand
shapes and dtypes (``block_shape``): every tile in one step whenever the
VMEM budget allows, and row blocks of thousands, so a ResNet-18 conv
runs tens of grid steps, not tens of thousands — a step's fixed
pipeline cost is paid per step, whatever its size. The conv deploy path
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

Layout (what makes the grid lower on TPU at any k_tiles): activations
reach the grid tile-major, (k_tiles, M, rows) — the linear entry point
swaps its (M, k_tiles, rows) operand, the conv entry points build their
patches in that layout (kernels/cim_conv) — and every
per-(split, tile, column) vector — ``s_p``, ``deq`` — as (S, k_tiles,
1, N). Every block then keeps its last two dims either whole or
(8, 128)-aligned: the array-tile and bit-split indices only ever select
along leading axes. ``rows`` is the array's full last dim, so conv row
counts like 9*14 = 126 need no padding.

Block shapes (``vmem_bytes``: double-buffered, tiles padded to
(sublane, 128 lanes); the ResNet-18 stage-2 3x3 conv at batch 128,
M = 25,088, rows = 126, int8 codes, nibble planes, bm = 1792, bn = 256,
tk = k_tiles = 19):
  a:      (tk, bm, rows)        19*1792*128*1  = 4.2 MiB
  digits: (1, tk, rows_d, bn)   19*64*256*1    = 304 KiB (uint8 pairs)
  scales: 2 x (1, tk, 1, bn)    2*19*8*256*4   = 304 KiB
  out:    (bm, bn)              1792*256*4     = 1.75 MiB
  body:   a decoded f32 digit tile and one 256-row chunk (at most
          ``CHUNK_VALUES`` psum values) of f32 activations, psum and
          epilogue values                           ~ 2.0 MiB
15.0 MiB in all, inside ``VMEM_BUDGET`` (24 MiB); the kernel is compiled
with ``VMEM_LIMIT`` (32 MiB). The body walks the row block in chunks so
each dot and epilogue pass works on a vreg-sized slab, and decodes each
tile's digits once per step for all of them.

Occupancy skip (DESIGN.md §14): the per-block "any column occupied"
decision is a wrapper-side int32 table (``block_occupancy``) that is
scalar-prefetched into SMEM, so the kernel branches on a scalar.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.nibble import unpack_nibbles
from repro.core.variation import perturb_digits, variation_wanted
from repro.obs import names


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


def _precision(digit_dtype):
    """Integer codes (up to 8 bits) times integer digits are exact at the
    MXU's default precision (bfloat16 operands, float32 accumulation).
    Float digits — one variation realization — are not: they take the
    float32 contraction, or TPU rounds them to bfloat16 (about 2e-2
    relative off the oracle on a v5e chip)."""
    return (None if jnp.issubdtype(digit_dtype, jnp.integer)
            else jax.lax.Precision.HIGHEST)


def _decode(d, *, nibble: bool, groups: int):
    """A stored (rows_stored, bn) digit tile as float32 digits — uint8
    nibble pairs when ``nibble`` (rows_stored = rows / 2, half-split per
    group along the row axis; ``repro.core.nibble``), else int8 or float
    digits."""
    if nibble:
        d = unpack_nibbles(d, groups=groups)
    return d.astype(jnp.float32)


def _mac(a, d, *, nibble: bool, groups: int):
    """One array tile's column MACs: (bm, rows) codes x a (rows_stored, bn)
    stored digit block, decoded in VMEM."""
    return jnp.dot(a.astype(jnp.float32),
                   _decode(d, nibble=nibble, groups=groups),
                   preferred_element_type=jnp.float32,
                   precision=_precision(d.dtype))


def _contribution(p, sp, deq, *, psum_bits: int, psum_quant: bool):
    """ADC stage + fused dequant of one psum block — the term the (s, t)
    reduction adds into the output block."""
    if psum_quant:
        p = jnp.round(p)    # integer-valued MACs: kill float roundoff
        p = _adc_quantize(p, sp, psum_bits=psum_bits)
    return p * deq.astype(jnp.float32)[None, :]


def _grid_body(*refs, epilogue, n_cols: int, nibble: bool, groups: int,
               tk: int, k_tiles: int, row_chunk: int, table=None):
    """Grid (i, j, s, c): output block (i, j), bit split s, and chunk c of
    ``tk`` array tiles, which the body walks in order. Shared by the ADC
    and ADC-free kernels; ``epilogue(p, *cols)`` turns one tile's psum
    block into the term it adds (``cols``: that tile's (bn,) column
    vectors, one per ``fused_grid_call`` ``cols`` entry).

    Per output element the terms arrive split outer, tile inner — the
    grid's (s, c) order, then the tile loop — which is the order of the
    oracle (``ref.shift_add``); the row chunks only split the block's
    rows, each element's arithmetic is untouched. Each tile's digits are
    decoded once per step and reused for every row chunk.

    ``table=n_blocks`` turns on the occupancy skip: the first ref is then
    the SMEM-prefetched int32 block table (``block_occupancy``), and a
    dead (split, tile, column block) — every column's digit plane
    all-zero — skips the MXU dot, yielding the exact psum of an all-zero
    plane, +0.0. Everything downstream (ADC stage, dequant, accumulate)
    runs unconditionally, so a dead block goes through the verbatim dense
    expression: the skip is bit-exact with the dense kernel
    (tests/test_sparse_skip.py) — including the sign ADC (psum_bits ==
    1), where a zero psum still contributes +s_p * deq."""
    occ_ref = refs[0] if table is not None else None
    a_ref, d_ref = refs[-n_cols - 3:-n_cols - 1]
    col_refs, o_ref = refs[-n_cols - 1:-1], refs[-1]
    j, s, c = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    _zero_at_start(o_ref, c, s)
    precision = _precision(d_ref.dtype)
    bm, bn = o_ref.shape

    def tile(u):
        t = c * tk + u
        d = _decode(d_ref[0, u], nibble=nibble, groups=groups)
        cols = [r[0, u, 0, :] for r in col_refs]
        live = (None if table is None
                else occ_ref[(s * k_tiles + t) * table + j] > 0)

        def rows(r, carry):
            at = (slice(None) if row_chunk == bm else
                  pl.ds(pl.multiple_of(r * row_chunk, row_chunk), row_chunk))

            def mac():
                return jnp.dot(a_ref[u, at, :].astype(jnp.float32), d,
                               preferred_element_type=jnp.float32,
                               precision=precision)
            p = mac() if live is None else jax.lax.cond(
                live, mac, lambda: jnp.zeros((row_chunk, bn), jnp.float32))
            o_ref[at, :] += epilogue(p, *cols)
            return carry
        jax.lax.fori_loop(0, bm // row_chunk, rows, 0)

    def step(u, carry):
        if k_tiles % tk:        # the last chunk is short
            pl.when(c * tk + u < k_tiles)(lambda: tile(u))
        else:
            tile(u)
        return carry
    jax.lax.fori_loop(0, tk, step, 0)


def tile_major(a_t: jnp.ndarray) -> jnp.ndarray:
    """(..., M, k_tiles, rows) activations -> (..., k_tiles, M, rows): the
    layout whose (tk, bm, rows) blocks lower on TPU for any k_tiles."""
    return jnp.swapaxes(a_t, -3, -2)


def pad_rows(a_tm: jnp.ndarray, bm: int) -> jnp.ndarray:
    """Tile-major (..., k_tiles, M, rows) activations with M padded up to a
    multiple of ``bm``; as they are when ``bm`` divides M."""
    pad_m = (-a_tm.shape[-2]) % bm
    if pad_m:
        a_tm = jnp.pad(a_tm, [(0, 0)] * (a_tm.ndim - 2)
                       + [(0, pad_m), (0, 0)])
    return a_tm


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


#: Upper bounds on the output block the chooser may pick (``block_m`` /
#: ``block_n`` defaults); the VMEM budget usually binds first.
BLOCK_M_MAX = 8192
BLOCK_N_MAX = 512
#: VMEM one grid step may fill: double-buffered blocks plus the body's
#: float32 temporaries (``vmem_bytes``) ...
VMEM_BUDGET = 24 * 2**20
#: ... and the limit the kernel is compiled with: the budget plus room
#: for Mosaic's own scratch
VMEM_LIMIT = 32 * 2**20
#: float32 values (lanes padded to 128) of the psum slab one dot and
#: epilogue pass covers: 256 KiB. Larger slabs ran slower on a v5e chip,
#: as did 128-row slabs of a 128-wide block.
CHUNK_VALUES = 512 * 128


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _sublanes(rows: int, itemsize: int) -> int:
    """Rows a second-minor dim takes in VMEM: tiles of 8 rows of 32-bit
    words, so 8 for float32, 16 for bfloat16, 32 for int8."""
    return _round_up(rows, 32 // itemsize)


def _row_chunk(bm: int, bn: int) -> int:
    """Rows of a (bm, bn) block the body's dot and epilogue take at a
    time: the whole block when its slab holds at most ``CHUNK_VALUES``,
    else the largest multiple of 32 (an int8 sublane tile) that divides
    bm and keeps the slab within it."""
    lanes = _round_up(bn, 128)
    if bm * lanes <= CHUNK_VALUES:
        return bm
    top = max(CHUNK_VALUES // lanes, 32)
    return max(c for c in range(32, top + 1, 32) if bm % c == 0)


def vmem_bytes(bm: int, bn: int, tk: int, *, rows: int, rows_d: int,
               a_itemsize: int, d_itemsize: int, n_cols: int) -> int:
    """VMEM one grid step of ``fused_grid_call`` holds, in bytes, with
    each block padded to its (sublane, 128-lane) tiles: the activation,
    digit, column-scale and output blocks, each double-buffered, plus
    the body's float32 temporaries — a decoded digit tile and one row
    chunk's activations, psum and epilogue values."""
    lanes_n, lanes_r = _round_up(bn, 128), _round_up(rows, 128)
    blocks = (tk * _sublanes(bm, a_itemsize) * lanes_r * a_itemsize
              + tk * _sublanes(rows_d, d_itemsize) * lanes_n * d_itemsize
              + n_cols * tk * 8 * lanes_n * 4
              + _sublanes(bm, 4) * lanes_n * 4)
    chunk = _sublanes(_row_chunk(bm, bn), 4)
    temps = (4 * _sublanes(rows, 4) * lanes_n * 4
             + 6 * chunk * max(lanes_n, lanes_r) * 4)
    return 2 * blocks + temps


def _block_sizes(extent: int, cap: int) -> list:
    """Block sizes over an axis of ``extent``, largest first: the whole
    axis when it is at most 128 long, else the multiples of 128 up to
    ``cap`` that divide the extent rounded up to 128 — so the padding
    never exceeds the 128-rounding."""
    if extent <= 128:
        return [extent]
    units = -(-extent // 128)
    top = max(cap // 128, 1)
    return [128 * d for d in range(min(units, top), 0, -1) if units % d == 0]


def block_shape(m: int, n: int, k_tiles: int, rows: int, rows_d: int,
                a_dtype, d_dtype, *, n_cols: int,
                block_m: int = BLOCK_M_MAX,
                block_n: int = BLOCK_N_MAX) -> tuple:
    """(bm, bn, tk) for ``fused_grid_call`` from the operand shapes and
    dtypes: an output block of (bm, bn) and ``tk`` array tiles a step.

    Within ``VMEM_BUDGET`` (``vmem_bytes``): the widest column block up
    to ``block_n``, then as many array tiles as fit — all ``k_tiles``
    whenever they do, else chunks as even as the budget allows — then
    the tallest row block up to ``block_m``."""
    def fits(bm, bn, tk):
        return vmem_bytes(bm, bn, tk, rows=rows, rows_d=rows_d,
                          a_itemsize=jnp.dtype(a_dtype).itemsize,
                          d_itemsize=jnp.dtype(d_dtype).itemsize,
                          n_cols=n_cols) <= VMEM_BUDGET
    bms, bns = _block_sizes(m, block_m), _block_sizes(n, block_n)
    bm = bms[-1]
    bn = next((b for b in bns if fits(bm, b, 1)), bns[-1])
    tk = next((t for t in range(k_tiles, 0, -1) if fits(bm, bn, t)), 1)
    tk = -(-k_tiles // -(-k_tiles // tk))     # even chunks
    bm = next((b for b in bms if fits(b, bn, tk)), bm)
    return bm, bn, tk


def fused_grid_call(epilogue, a_tm, digits, cols, variation_key,
                    variation_std, occ, *, name: str, nibble_groups: int,
                    block_m: int, block_n: int,
                    interpret: bool) -> jnp.ndarray:
    """The wrapper the ADC and ADC-free deploy kernels share: variation,
    padding, the block shape (``block_shape``, with ``block_m`` /
    ``block_n`` as upper bounds), the grid and the occupancy table.
    ``a_tm`` is already tile-major, (k_tiles, M, rows): the linear entry
    points swap their (M, k_tiles, rows) operand (``tile_major``), the
    conv entry points build their patches in that layout
    (``kernels/cim_conv.tile_major_patches``). Padding runs under the
    ``cim.layout`` scope and the kernel, named ``name`` in the trace,
    under ``cim.kernel``.

    ``epilogue(p, *cols)`` turns one array tile's psum block into the
    term it adds (``_grid_body``). ``cols`` holds (S, k_tiles, N)
    per-column operands, each with the value its padded columns take.
    Tracing the call adds its grid size to the ``cim.grid.steps``
    counter (``repro.obs.compiles``). Returns (M, N) float32."""
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
    k_tiles, m, rows = a_tm.shape
    n_split = digits.shape[0]
    n = digits.shape[-1]
    rows_d = digits.shape[2]             # stored rows: rows/2 when nibble
    assert rows_d == (rows // 2 if nibble else rows), \
        (digits.shape, a_tm.shape, nibble)

    bm, bn, tk = block_shape(m, n, k_tiles, rows, rows_d, a_tm.dtype,
                             digits.dtype, n_cols=len(cols),
                             block_m=block_m, block_n=block_n)
    pad_n = (-n) % bn
    np_ = n + pad_n
    with jax.named_scope(names.SCOPE_LAYOUT):
        a_tm = pad_rows(a_tm, bm)        # (k_tiles, mp, rows)
        if pad_n:
            digits = jnp.pad(digits, ((0, 0), (0, 0), (0, 0), (0, pad_n)))
            if occ is not None:
                occ = jnp.pad(occ, ((0, 0), (0, 0), (0, pad_n)))  # dead
        cols = tuple(scale_slab(c, pad_n, value).reshape(
            n_split, k_tiles, 1, np_) for c, value in cols)
        table = None if occ is None else block_occupancy(occ, bn)
    mp = a_tm.shape[1]

    grid = (mp // bm, np_ // bn, n_split, pl.cdiv(k_tiles, tk))
    jax.monitoring.record_scalar(names.CIM_GRID_STEPS, math.prod(grid))
    col_spec = pl.BlockSpec((1, tk, 1, bn),
                            lambda i, j, s, c, *_: (s, c, 0, j))
    in_specs = [
        pl.BlockSpec((tk, bm, rows), lambda i, j, s, c, *_: (c, i, 0)),
        pl.BlockSpec((1, tk, rows_d, bn),
                     lambda i, j, s, c, *_: (s, c, 0, j)),
    ] + [col_spec] * len(cols)
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, s, c, *_: (i, j))
    body = functools.partial(
        _grid_body, epilogue=epilogue, n_cols=len(cols), nibble=nibble,
        groups=nibble_groups, tk=tk, k_tiles=k_tiles,
        row_chunk=_row_chunk(bm, bn))
    args = (a_tm, digits) + cols
    if table is None:
        grid_spec = pl.GridSpec(grid=grid, in_specs=in_specs,
                                out_specs=out_spec)
    else:
        body = functools.partial(body, table=np_ // bn)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec)
        args = (table,) + args
    with jax.named_scope(names.SCOPE_KERNEL):
        out = pl.pallas_call(
            body,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name=name,
        )(*args)
    with jax.named_scope(names.SCOPE_LAYOUT):
        return out[:m, :n]


def cim_matmul_tiled(
    a_tm: jnp.ndarray,     # (k_tiles, M, rows) integer-valued, tile-major
    digits: jnp.ndarray,   # (S, k_tiles, rows, N); uint8 = nibble-packed
    s_p: jnp.ndarray,      # (S, k_tiles, N)
    deq: jnp.ndarray,      # (S, k_tiles, N)
    variation_key=None,
    variation_std=None,
    occ=None,
    *,
    psum_bits: int,
    psum_quant: bool,
    nibble_groups: int,
    block_m: int,
    block_n: int,
    interpret: bool,
) -> jnp.ndarray:
    """The ADC kernel on tile-major activations, shared by
    ``cim_matmul_pallas`` and the conv entry point
    (``kernels/cim_conv``). Returns (M, N) float32."""
    # padded columns: s_p 1.0 keeps the ADC's divide finite, deq 0.0
    # zeroes them
    return fused_grid_call(
        functools.partial(_contribution, psum_bits=psum_bits,
                          psum_quant=psum_quant),
        a_tm, digits, ((s_p, 1.0), (deq, 0.0)), variation_key,
        variation_std, occ, name=names.KERNEL_CIM_MATMUL,
        nibble_groups=nibble_groups, block_m=block_m, block_n=block_n,
        interpret=interpret)


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
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    interpret: bool = False,
) -> jnp.ndarray:
    with jax.named_scope(names.SCOPE_LAYOUT):
        a_tm = tile_major(a_t)
    return cim_matmul_tiled(
        a_tm, digits, s_p, deq, variation_key, variation_std, occ,
        psum_bits=psum_bits, psum_quant=psum_quant,
        nibble_groups=nibble_groups, block_m=block_m, block_n=block_n,
        interpret=interpret)


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
    with jax.named_scope(names.SCOPE_LAYOUT):
        a_t = pad_rows(tile_major(a_t), bm)  # (E, k_tiles, mp, rows)
        if pad_n:
            digits = jnp.pad(digits,
                             ((0, 0), (0, 0), (0, 0), (0, 0), (0, pad_n)))
        s_p = scale_slab(s_p, pad_n, 1.0)
        deq = scale_slab(deq, pad_n)
    mp, np_ = a_t.shape[2], n + pad_n

    grid = (e, mp // bm, np_ // bn, n_split, k_tiles)
    col_spec = pl.BlockSpec(
        (1, 1, 1, bn), lambda ei, i, j, s, t: (ei, s * k_tiles + t, 0, j))
    with jax.named_scope(names.SCOPE_KERNEL):
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
            name=names.KERNEL_CIM_EXPERTS,
        )(a_t, digits, s_p, deq)
    with jax.named_scope(names.SCOPE_LAYOUT):
        return out[:, :m, :n]
