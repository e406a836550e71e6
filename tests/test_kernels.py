"""Pallas cim_matmul kernel vs the pure-jnp oracle: shape/dtype/bit sweeps
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk(m, k_tiles, rows, n, n_split, seed=0, digit_max=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = jnp.round(jax.random.normal(ks[0], (m, k_tiles, rows)) * 4)
    digits = jax.random.randint(ks[1], (n_split, k_tiles, rows, n),
                                -digit_max, digit_max + 1).astype(jnp.int8)
    s_p = jax.random.uniform(ks[2], (n_split, k_tiles, n), minval=0.5,
                             maxval=20.0)
    deq = jax.random.uniform(ks[3], (n_split, k_tiles, n), minval=0.01,
                             maxval=0.1)
    return a, digits, s_p, deq


SHAPES = [
    (8, 1, 32, 16, 1),
    (16, 2, 64, 24, 2),
    (64, 3, 128, 40, 2),
    (128, 2, 128, 128, 3),
    (5, 2, 33, 7, 2),        # awkward/non-aligned
    (130, 1, 256, 129, 1),   # > one block in both dims
]


@pytest.mark.parametrize("m,k_tiles,rows,n,n_split", SHAPES)
@pytest.mark.parametrize("psum_bits", [1, 4, 8])
def test_kernel_matches_ref(m, k_tiles, rows, n, n_split, psum_bits):
    a, digits, s_p, deq = _mk(m, k_tiles, rows, n, n_split)
    out_k = ops.cim_matmul(a, digits, s_p, deq, psum_bits=psum_bits,
                           use_kernel=True)
    out_r = ops.cim_matmul(a, digits, s_p, deq, psum_bits=psum_bits,
                           use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("psum_quant", [True, False])
def test_kernel_psum_quant_toggle(psum_quant):
    a, digits, s_p, deq = _mk(32, 2, 64, 32, 2)
    out_k = ops.cim_matmul(a, digits, s_p, deq, psum_bits=4,
                           psum_quant=psum_quant, use_kernel=True)
    out_r = ops.cim_matmul(a, digits, s_p, deq, psum_bits=4,
                           psum_quant=psum_quant, use_kernel=False)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-4)


def test_kernel_no_quant_equals_plain_matmul():
    """With psum quantization off and unit scales, the kernel is exactly a
    (bit-recombined) matmul."""
    from repro.core.bitsplit import place_values
    m, k_tiles, rows, n = 16, 2, 32, 8
    a, digits, _, _ = _mk(m, k_tiles, rows, n, 2)
    places = place_values(4, 2)
    deq = jnp.broadcast_to(places[:, None, None], (2, k_tiles, n))
    s_p = jnp.ones((2, k_tiles, n))
    out = ops.cim_matmul(a, digits, s_p, deq, psum_bits=8, psum_quant=False,
                         use_kernel=True)
    w = jnp.tensordot(places, digits.astype(jnp.float32), axes=(0, 0))
    expect = jnp.einsum("mtr,trn->mn", a, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-4)


def test_kernel_batch_dims():
    a, digits, s_p, deq = _mk(24, 2, 64, 16, 2)
    a3 = a.reshape(2, 3, 4, 2, 64)
    out = ops.cim_matmul(a3, digits, s_p, deq, psum_bits=4, use_kernel=True)
    assert out.shape == (2, 3, 4, 16)
    flat = ops.cim_matmul(a, digits, s_p, deq, psum_bits=4, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out).reshape(24, 16),
                               np.asarray(flat), rtol=1e-6)


def test_adc_ref_binary():
    p = jnp.asarray([[-3.0, 0.5]])
    s = jnp.asarray([[2.0, 2.0]])
    out = ref.adc_quantize_ref(p, s, 1)
    np.testing.assert_allclose(np.asarray(out), [[-2.0, 2.0]])


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_follows_the_backend(monkeypatch, backend, interpret):
    """Interpret mode is for the CPU backend (the tests) only: TPU compiles
    the kernels, and any other backend is refused rather than quietly
    interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret
