"""Bit-split decomposition properties (paper Fig. 5)."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bitsplit import place_values, recombine, split_digits
from repro.core.granularity import n_splits


@settings(max_examples=60, deadline=None)
@given(
    wb_cb=st.sampled_from([(2, 1), (3, 1), (3, 2), (3, 3), (4, 2), (4, 1),
                           (4, 4), (8, 2), (8, 3), (6, 2)]),
    seed=st.integers(0, 2 ** 16),
)
def test_roundtrip_exact(wb_cb, seed):
    wb, cb = wb_cb
    rng = np.random.RandomState(seed)
    w = rng.randint(-(2 ** (wb - 1)), 2 ** (wb - 1), size=(13, 7)
                    ).astype(np.float32)
    d = split_digits(jnp.asarray(w), wb, cb)
    assert d.shape == (n_splits(wb, cb),) + w.shape
    r = recombine(d, wb, cb)
    assert np.array_equal(np.asarray(r), w)


@settings(max_examples=30, deadline=None)
@given(
    wb_cb=st.sampled_from([(3, 1), (4, 2), (8, 3)]),
    seed=st.integers(0, 2 ** 16),
)
def test_digit_ranges_fit_cells(wb_cb, seed):
    """Sign-magnitude differential encoding: each physical cell stores an
    unsigned digit < 2^c; the sign is the G+/G- pair assignment, so all of
    a weight's digits share its sign."""
    wb, cb = wb_cb
    rng = np.random.RandomState(seed)
    w = rng.randint(-(2 ** (wb - 1)), 2 ** (wb - 1), size=(64,)
                    ).astype(np.float32)
    d = np.asarray(split_digits(jnp.asarray(w), wb, cb))
    assert np.abs(d).max() < 2 ** cb
    # sign consistency per weight: no digit opposes its weight's sign
    signs = np.sign(w)[None, :]
    assert np.all(d * signs >= 0)


@settings(max_examples=40, deadline=None)
@given(
    wb_cb=st.sampled_from([(2, 1), (3, 1), (3, 2), (4, 2), (4, 3), (6, 2),
                           (6, 3), (8, 2), (8, 3)]),
    store=st.sampled_from(["int8", "int4"]),
    seed=st.integers(0, 2 ** 16),
)
def test_store_dtype_roundtrip(wb_cb, store, seed):
    """Digit planes survive the deploy storage cast losslessly: int8
    always; int4 whenever cells are <= 3 bits (|digit| <= 7) — the
    exact rule CIMConfig.store_dtype applies."""
    wb, cb = wb_cb
    rng = np.random.RandomState(seed)
    w = rng.randint(-(2 ** (wb - 1)), 2 ** (wb - 1), size=(17, 5)
                    ).astype(np.float32)
    d = split_digits(jnp.asarray(w), wb, cb)
    dt = jnp.int4 if (store == "int4" and cb <= 3) else jnp.int8
    stored = d.astype(dt)
    r = recombine(stored.astype(jnp.float32), wb, cb)
    assert np.array_equal(np.asarray(r), w)


@settings(max_examples=30, deadline=None)
@given(
    kn=st.sampled_from([(7, 3), (13, 31), (32, 33), (33, 32), (1, 1),
                        (50, 17)]),
    seed=st.integers(0, 2 ** 16),
)
def test_roundtrip_ragged_shapes(kn, seed):
    """Round trip is exact for ragged (K, N) that don't divide the CIM
    array dims — packing pads tiles, but the digits themselves are
    shape-agnostic."""
    k, n = kn
    rng = np.random.RandomState(seed)
    w = rng.randint(-8, 8, size=(k, n)).astype(np.float32)
    d = split_digits(jnp.asarray(w), 4, 2)
    assert d.shape == (2, k, n)
    assert np.array_equal(np.asarray(recombine(d, 4, 2)), w)


def test_place_values():
    assert np.allclose(np.asarray(place_values(4, 2)), [1.0, 4.0])
    assert np.allclose(np.asarray(place_values(3, 1)), [1.0, 2.0, 4.0])


def test_binary_weight_single_split():
    w = jnp.asarray([-1.0, 1.0, -1.0])
    d = split_digits(w, 1, 1)
    assert d.shape == (1, 3)
    assert np.array_equal(np.asarray(recombine(d, 1, 1)), np.asarray(w))


@pytest.mark.parametrize("pack_dtype", ["int8", "int4"])
def test_packed_codes_equal_rounded_quotient(pack_dtype):
    """With float32 column scales ``w / s`` comes back from the quantizer
    a last bit below an integer code about as often as above it; the
    pack keeps the nearest integer, which float64 ``round(w / s)``
    witnesses (a truncating pack loses a code in about 1% of them on
    the CPU)."""
    from repro.core.cim_linear import (CIMConfig, _pack_linear,
                                       weight_scales_from)
    from repro.core.nibble import is_nibble_packed, unpack_nibbles
    k, n = 300, 256
    cfg = CIMConfig(enabled=True, weight_bits=4, cell_bits=2,
                    pack_dtype=pack_dtype)
    rng = np.random.RandomState(0)
    w = rng.randn(k, n).astype(np.float32) / np.sqrt(k)
    s_w = weight_scales_from(jnp.asarray(w), cfg)
    t = cfg.tiling(k, n)
    packed = _pack_linear({"w": jnp.asarray(w), "s_w": s_w,
                           "s_p": jnp.ones(t.psum_scale_shape(
                               cfg.psum_granularity)),
                           "s_a": jnp.ones((1,))}, cfg)
    d = packed["w_digits"]
    if is_nibble_packed(d):
        d = unpack_nibbles(d)
    codes = np.asarray(recombine(d.astype(jnp.float32), 4, 2))
    codes = codes.reshape(t.k_padded, n)[:k]
    s = np.repeat(np.asarray(s_w, np.float64), t.array_rows, axis=0)[:k]
    want = np.clip(np.round(w.astype(np.float64) / s), -8, 7)
    assert np.array_equal(codes, want)
