"""Jitted public wrappers around the Pallas kernels.

``use_kernel=True`` runs the Pallas kernel — compiled by Mosaic on TPU, in
interpret mode on the CPU backend so the tests validate the kernel body
there (``interpret_mode``; any other backend is an error, never a silent
fallback); ``use_kernel=False`` runs the pure-jnp oracle — used for
allocation-free dry-runs where the HLO must be portable.

Both wrappers accept ``variation_key``/``variation_std``: when set, the
digit planes are evaluated under one Monte-Carlo realization of log-normal
cell noise (paper §IV-E). The kernel path draws the noise inside
``cim_matmul_pallas`` (before block padding); the oracle path perturbs
here with the same ``repro.core.variation.perturb_digits``, so kernel and
oracle stay bit-comparable under a shared key (DESIGN.md §8).

Both wrappers also accept ``mesh``/``mesh_axis``: when a mesh with more
than one device along ``mesh_axis`` (default ``"model"``) is given, the
packed digit planes and their column scales are sharded column-wise over
that axis via ``shard_map`` — each device runs the kernel on its own
output-column shard (per-column ADC + dequant scales are local by
construction, DESIGN.md §10), and the only cross-device collective is one
all-gather of the final dequantized activations. Ragged column counts pad
the last shard (scale 1, deq 0 — dead columns) and slice after the
gather, mirroring the kernel's own last-block padding. Cell-variation
noise is always drawn on the FULL unpadded packed planes *before*
sharding, so a sharded evaluation is bit-exact with the single-device
evaluation under the same key.

Observability (DESIGN.md §12): when the ``repro.obs.adc`` collector is
armed, both wrappers emit a per-column ADC saturation side-output — the
partial sums are recomputed by a jnp einsum next to the kernel call
(the fused kernel itself never materializes them; that is the point of
fusion) and reduced to per-column clipped-conversion counts. The main
output is untouched, bit-exact with the un-instrumented path, and the
disarmed path contains no side computation at all. Arming is a
trace-time decision — see ``repro.obs.adc``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.nibble import unpack_nibbles
from repro.core.variation import perturb_digits, variation_wanted
from repro.obs import adc as obs_adc

from . import ref
from .cim_adc_free import cim_conv_adc_free_pallas, cim_matmul_adc_free_pallas
from .cim_conv import cim_conv_pallas
from .cim_matmul import (BLOCK_M_MAX, BLOCK_N_MAX, cim_matmul_experts_pallas,
                         cim_matmul_pallas)

#: Mesh axis the packed column (output-channel) dimension shards over by
#: default — the tensor-parallel axis of the serving meshes (launch/serve
#: --mesh, DESIGN.md §10).
COL_SHARD_AXIS = "model"


def interpret_mode() -> bool:
    """Whether the kernel dispatches run Pallas in interpret mode: True on
    the CPU backend (what the tests use), False on TPU. Any other backend
    raises — the kernels are written for TPU, and quietly interpreting
    them elsewhere would hide the device."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"the Pallas deploy kernels run on TPU (or interpreted on the "
            f"CPU backend, for tests); the default backend is {backend!r}. "
            f"Serve on TPU, or set JAX_PLATFORMS=cpu, or use the jnp "
            f"oracle (CIMConfig(use_kernel=False) / the 'ref' backend).")
    return backend == "cpu"


def col_shards(mesh, mesh_axis: str = COL_SHARD_AXIS) -> int:
    """Number of column shards a mesh implies (1 = unsharded dispatch)."""
    if mesh is None or mesh_axis not in getattr(mesh, "axis_names", ()):
        return 1
    return int(mesh.shape[mesh_axis])


def pad_cols(digits, s_p, deq, n_shards: int, occ=None):
    """Pad the packed column axis to a multiple of ``n_shards``.

    Dead columns get digit 0, psum scale 1, dequant scale 0 and occupancy
    0 — exactly the kernel's last-block padding rule — so they contribute
    nothing (the sparse kernels skip them outright) and are sliced off
    after the output gather. Digit planes pad the same way whether dense
    or nibble-packed: the column axis is never the packed axis, so shard
    boundaries stay byte-aligned."""
    n = digits.shape[-1]
    pad = (-n) % n_shards
    if pad:
        digits = jnp.pad(digits, [(0, 0)] * (digits.ndim - 1) + [(0, pad)])
        s_p = jnp.pad(s_p, ((0, 0), (0, 0), (0, pad)), constant_values=1.0)
        deq = jnp.pad(deq, ((0, 0), (0, 0), (0, pad)))
        if occ is not None:
            occ = jnp.pad(occ, ((0, 0), (0, 0), (0, pad)))
    return digits, s_p, deq, occ


def _record_saturation(a2, digits, s_p, *, psum_bits, variation_key,
                       variation_std, nibble_groups: int = 1):
    """ADC saturation side-output for the fused paths (armed only).

    The deploy kernel never materializes partial sums, so the armed
    trace recomputes them with the reference einsum — including the
    cell-noise realization, so the counts describe the digits the
    kernel actually multiplied — and ships per-column clipped counts
    host-side. Nothing here feeds the main output."""
    d = digits
    if d.dtype == jnp.uint8:
        d = unpack_nibbles(d, groups=nibble_groups)
    elif d.dtype == jnp.int4:
        d = d.astype(jnp.int8)
    if variation_wanted(variation_key, variation_std):
        d = perturb_digits(d, variation_key, variation_std)
    psum = jnp.einsum("mtr,strn->mstn", a2.astype(jnp.float32),
                      d.astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    obs_adc.record(psum, s_p, psum_bits)


def _sharded(shard_op, a, digits, s_p, deq, mesh, mesh_axis, *,
             use_kernel, variation_key, variation_std, occ=None,
             nibble_groups=1):
    """Column-parallel dispatch: ``shard_op(a, d, sp, dq, occ)`` runs on
    every device with its own column shard of the planes.

    ``a`` is replicated; digits/s_p/deq (and the optional occupancy map)
    shard over their last (column) axis. Nibble-packed uint8 planes
    stream through shard_map at their packed byte width — the column
    axis is never the packed axis, so shard boundaries are byte-aligned
    by construction. No partial sum crosses a device boundary — the
    reduction dims (array tile, bit-split) live inside each shard's grid
    — so the single collective is the all-gather of the (..., N/D) f32
    outputs along their last axis.
    """
    from jax.sharding import PartitionSpec as P

    from repro.nn.module import shard_map  # lazy: avoids import cycle

    if digits.dtype == jnp.int4:
        # dense int4 is a legacy HBM storage dtype; the kernel loads int8
        digits = digits.astype(jnp.int8)
    if variation_wanted(variation_key, variation_std):
        # full unpadded packed LOGICAL layout, BEFORE shard padding: same
        # noise indices as the single-device paths (DESIGN.md §8, §10)
        if digits.dtype == jnp.uint8:
            digits = unpack_nibbles(digits, groups=nibble_groups)
        digits = perturb_digits(digits, variation_key, variation_std)
    if not use_kernel and digits.dtype == jnp.uint8:
        # the jnp oracles consume logical planes only
        digits = unpack_nibbles(digits, groups=nibble_groups)
    n = digits.shape[-1]
    n_shards = mesh.shape[mesh_axis]
    digits, s_p, deq, occ = pad_cols(digits, s_p, deq, n_shards, occ)

    def local(a_, d_, sp_, dq_, *rest):
        out = shard_op(a_, d_, sp_, dq_, rest[0] if rest else None)
        return jax.lax.all_gather(out, mesh_axis, axis=out.ndim - 1,
                                  tiled=True)

    col = P(*([None] * (digits.ndim - 1) + [mesh_axis]))
    col3 = P(None, None, mesh_axis)
    args = (a, digits, s_p, deq)
    in_specs = (P(), col, col3, col3)
    if occ is not None and use_kernel:
        args += (occ.astype(jnp.uint8),)
        in_specs += (col3,)
    out = shard_map(
        local, mesh=mesh,
        in_specs=in_specs,
        out_specs=P(), check_vma=False,
    )(*args)
    return out[..., :n]


def _matmul_shard(a_, d_, sp_, dq_, occ_, *, psum_bits, psum_quant,
                  use_kernel, block_m, block_n, adc_free):
    """One device's CIM matmul over the columns of its shard (``_sharded``:
    variation already drawn, planes logical on the oracle paths)."""
    if adc_free:
        # ADC-free style (DESIGN.md §13): no s_p stream — sp_ rides the
        # shard_map signature so the specs stay uniform, unused
        if use_kernel:
            return cim_matmul_adc_free_pallas(
                a_, d_, dq_, None, None, occ_,
                block_m=block_m, block_n=block_n,
                interpret=interpret_mode())
        return ref.cim_matmul_adc_free_ref(a_, d_, dq_)
    if use_kernel:
        return cim_matmul_pallas(
            a_, d_, sp_, dq_, None, None, occ_,
            psum_bits=psum_bits, psum_quant=psum_quant,
            block_m=block_m, block_n=block_n,
            interpret=interpret_mode())
    return ref.cim_matmul_ref(a_, d_, sp_, dq_, psum_bits=psum_bits,
                              psum_quant=psum_quant)


def cim_matmul(
    a_t: jnp.ndarray,
    digits: jnp.ndarray,
    s_p: jnp.ndarray,
    deq: jnp.ndarray,
    *,
    psum_bits: int,
    psum_quant: bool = True,
    use_kernel: bool = True,
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    variation_key=None,
    variation_std=None,
    mesh=None,
    mesh_axis: str = COL_SHARD_AXIS,
    adc_free: bool = False,
    occ=None,
) -> jnp.ndarray:
    """CIM matmul over pre-tiled inputs.

    a_t:    (..., k_tiles, rows) integer-valued activations
    digits: (S, k_tiles, rows, N) int8 cell planes — or nibble-packed
            uint8 (S, k_tiles, rows // 2, N), DESIGN.md §14
    s_p:    (S, k_tiles, N) ADC scales
    deq:    (S, k_tiles, N) fused dequant scales (2^{cs} * s_w * s_a)
    variation_key/std: optional log-normal cell-noise realization
    mesh/mesh_axis: column-shard the planes over this mesh axis (>1
        device: shard_map column-parallel dispatch, bit-exact with the
        single-device path; DESIGN.md §10)
    adc_free: dispatch the ADC-free hardware style (DESIGN.md §13) —
        exact digital psum accumulation, s_p ignored, no saturation
        side-output (there is no ADC to saturate)
    occ: optional (S, k_tiles, N) uint8 occupancy map — the kernels skip
        unoccupied digit planes, bit-exact with the dense evaluation
        (DESIGN.md §14); ignored by the jnp oracle paths
    returns (..., N) float32
    """
    batch_shape = a_t.shape[:-2]
    m = 1
    for d in batch_shape:
        m *= d
    a2 = a_t.reshape((m,) + a_t.shape[-2:])
    if obs_adc.enabled() and psum_quant and not adc_free:
        _record_saturation(a2, digits, s_p, psum_bits=psum_bits,
                           variation_key=variation_key,
                           variation_std=variation_std)
    if col_shards(mesh, mesh_axis) > 1:
        out = _sharded(
            functools.partial(
                _matmul_shard, psum_bits=psum_bits, psum_quant=psum_quant,
                use_kernel=use_kernel, block_m=block_m, block_n=block_n,
                adc_free=adc_free),
            a2, digits, s_p, deq, mesh, mesh_axis, use_kernel=use_kernel,
            variation_key=variation_key, variation_std=variation_std,
            occ=occ)
    elif adc_free and use_kernel:
        out = cim_matmul_adc_free_pallas(
            a2, digits, deq, variation_key, variation_std, occ,
            block_m=block_m, block_n=block_n,
            interpret=interpret_mode(),
        )
    elif adc_free:
        if digits.dtype == jnp.uint8:
            digits = unpack_nibbles(digits)
        if variation_wanted(variation_key, variation_std):
            digits = perturb_digits(digits, variation_key, variation_std)
        out = ref.cim_matmul_adc_free_ref(a2, digits, deq)
    elif use_kernel:
        out = cim_matmul_pallas(
            a2, digits, s_p, deq, variation_key, variation_std, occ,
            psum_bits=psum_bits, psum_quant=psum_quant,
            block_m=block_m, block_n=block_n,
            interpret=interpret_mode(),
        )
    else:
        if digits.dtype == jnp.uint8:
            digits = unpack_nibbles(digits)
        if variation_wanted(variation_key, variation_std):
            digits = perturb_digits(digits, variation_key, variation_std)
        out = ref.cim_matmul_ref(
            a2, digits, s_p, deq,
            psum_bits=psum_bits, psum_quant=psum_quant,
        )
    return out.reshape(batch_shape + (digits.shape[-1],))


def cim_matmul_experts(
    a_t: jnp.ndarray,      # (E, C, k_tiles, rows) integer-valued
    digits: jnp.ndarray,   # (E, S, k_tiles, rows, N) cell planes
    s_p: jnp.ndarray,      # (E, S, k_tiles, N)
    deq: jnp.ndarray,      # (E, S, k_tiles, N)
    *,
    psum_bits: int,
    psum_quant: bool = True,
    block_m: int = 128,
    block_n: int = 128,
) -> jnp.ndarray:
    """Batched MoE expert-bank dispatch: every expert's capacity buffer
    through ONE kernel launch (expert index = leading grid dimension),
    bit-exact with ``lax.map`` of ``cim_matmul`` over experts — same
    block shapes, same (s, t) accumulation order per output block.

    The caller (``models.layers._expert_matmul``) gates this to the
    plain deploy fast path: single-device (no column-sharded mesh),
    ``use_kernel``, no per-call variation, saturation collector unarmed,
    bank small enough to stream. Everything outside that gate falls back
    to ``lax.map``. Returns (E, C, N) float32."""
    if digits.dtype == jnp.uint8:
        # nibble-packed expert bank: unpack host-side — the batched
        # experts kernel streams logical int8 planes (the nibble win is
        # the artifact/HBM-resident layout; the bank gate already bounds
        # the bank to ≤4 MiB so the upcast stays cheap)
        digits = unpack_nibbles(digits)
    elif digits.dtype == jnp.int4:
        digits = digits.astype(jnp.int8)
    return cim_matmul_experts_pallas(
        a_t, digits, s_p, deq,
        psum_bits=psum_bits, psum_quant=psum_quant,
        block_m=block_m, block_n=block_n,
        interpret=interpret_mode(),
    )


def cim_conv(
    a_int: jnp.ndarray,
    digits: jnp.ndarray,
    s_p: jnp.ndarray,
    deq: jnp.ndarray,
    *,
    kh: int,
    kw: int,
    stride: int = 1,
    padding="SAME",
    c_per_array: int,
    psum_bits: int,
    psum_quant: bool = True,
    use_kernel: bool = True,
    block_m: int = BLOCK_M_MAX,
    block_n: int = BLOCK_N_MAX,
    variation_key=None,
    variation_std=None,
    mesh=None,
    mesh_axis: str = COL_SHARD_AXIS,
    adc_free: bool = False,
    occ=None,
) -> jnp.ndarray:
    """CIM conv over activation codes and packed conv digit planes.

    a_int:  (B, H, W, C_in) integer-valued activation codes
    digits: (S, k_tiles, kh*kw*c_per_array, C_out) cell planes in the
            stretched-kernel row layout (see repro.api.pack_conv) — or
            nibble-packed uint8 (S, k_tiles, kh*kw*(c_per_array // 2),
            C_out), each tap its own packed block (DESIGN.md §14)
    s_p:    (S, k_tiles, C_out) ADC scales
    deq:    (S, k_tiles, C_out) fused dequant scales
    variation_key/std: optional log-normal cell-noise realization
    mesh/mesh_axis: column-shard the planes over this mesh axis — the
        C_out axis for conv (DESIGN.md §10); bit-exact with single-device
    occ: optional (S, k_tiles, C_out) uint8 occupancy map (DESIGN.md §14)
    returns (B, H', W', C_out) float32
    """
    if digits.dtype == jnp.int4:
        # dense int4 is a legacy HBM storage dtype; the kernel loads int8
        digits = digits.astype(jnp.int8)
    if not isinstance(padding, str):
        # hashable for the jit static arg
        padding = tuple((int(lo), int(hi)) for lo, hi in padding)
    if obs_adc.enabled() and psum_quant and not adc_free:
        k_tiles = digits.shape[1]
        p_t = ref.extract_conv_patches(a_int, kh, kw, stride, padding,
                                       k_tiles, c_per_array)
        b_, ho_, wo_ = p_t.shape[:3]
        _record_saturation(
            p_t.reshape(b_ * ho_ * wo_, k_tiles, p_t.shape[-1]),
            digits, s_p, psum_bits=psum_bits,
            variation_key=variation_key, variation_std=variation_std,
            nibble_groups=kh * kw)
    run = functools.partial(
        _conv_dispatch, kh=kh, kw=kw, stride=stride, padding=padding,
        c_per_array=c_per_array, psum_bits=psum_bits,
        psum_quant=psum_quant, use_kernel=use_kernel, block_m=block_m,
        block_n=block_n, adc_free=adc_free)
    if col_shards(mesh, mesh_axis) > 1:
        # patches are output-channel-independent: every device runs the
        # same conv dispatch on the replicated codes and its C_out shard
        return _sharded(
            run, a_int, digits, s_p, deq, mesh, mesh_axis,
            use_kernel=use_kernel, variation_key=variation_key,
            variation_std=variation_std, occ=occ, nibble_groups=kh * kw)
    return run(a_int, digits, s_p, deq, occ, variation_key, variation_std)


def _conv_dispatch(a_int, digits, s_p, deq, occ=None, variation_key=None,
                   variation_std=None, *, kh, kw, stride, padding,
                   c_per_array, psum_bits, psum_quant, use_kernel, block_m,
                   block_n, adc_free):
    """One device's CIM conv over the columns ``digits`` holds: the conv
    kernels (patches built tile-major, ``kernels/cim_conv``), else the
    jnp oracles on logical planes."""
    geo = dict(kh=kh, kw=kw, stride=stride, padding=padding,
               c_per_array=c_per_array)
    if use_kernel:
        blocks = dict(block_m=block_m, block_n=block_n,
                      interpret=interpret_mode(), **geo)
        if adc_free:
            return cim_conv_adc_free_pallas(
                a_int, digits, deq, variation_key, variation_std, occ,
                **blocks)
        return cim_conv_pallas(
            a_int, digits, s_p, deq, variation_key, variation_std, occ,
            psum_bits=psum_bits, psum_quant=psum_quant, **blocks)
    if digits.dtype == jnp.uint8:
        digits = unpack_nibbles(digits, groups=kh * kw)
    if variation_wanted(variation_key, variation_std):
        digits = perturb_digits(digits, variation_key, variation_std)
    if adc_free:
        k_tiles, rows = digits.shape[1], digits.shape[2]
        a_t = ref.extract_conv_patches(a_int.astype(jnp.float32), kh, kw,
                                       stride, padding, k_tiles,
                                       c_per_array)
        b, ho, wo = a_t.shape[:3]
        out = ref.cim_matmul_adc_free_ref(
            a_t.reshape(b * ho * wo, k_tiles, rows), digits, deq)
        return out.reshape(b, ho, wo, digits.shape[-1])
    return ref.cim_conv_ref(a_int, digits, s_p, deq, psum_bits=psum_bits,
                            psum_quant=psum_quant, **geo)
