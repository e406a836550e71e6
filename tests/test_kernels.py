"""Pallas cim_matmul kernel vs the pure-jnp oracle: shape/dtype/bit sweeps
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.nibble import occupancy_map, pack_nibbles
from repro.kernels import cim_matmul, ops, ref
from repro.kernels.cim_adc_free import cim_matmul_adc_free_pallas


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


# ---------------------------------------------------------------------------
# block chooser and the in-step tile loop: bit-exact with the oracle
# ---------------------------------------------------------------------------

def _int4_planes(n_split, k_tiles, rows, n, seed=0):
    """int4-range digits with dead (split, tile, column-block) planes: split
    0 tile 1 all zero, split 1 tile 0 zero in its first 64 columns."""
    d = jax.random.randint(jax.random.PRNGKey(seed), (n_split, k_tiles, rows,
                                                      n), -8, 8)
    d = d.at[0, 1].set(0).at[1, 0, :, :64].set(0)
    return d.astype(jnp.int8)


def _stored(digits, store):
    return pack_nibbles(digits) if store == "nibble" else digits


def _force_tiles(monkeypatch, m, n, k_tiles, rows, rows_d, d_dtype, tk,
                 n_cols=2):
    """Shrink the VMEM budget until the chooser takes ``tk`` array tiles
    a step at its smallest row block; jit caches are cleared so no trace
    made under another budget is reused."""
    bm = cim_matmul._block_sizes(m, cim_matmul.BLOCK_M_MAX)[-1]
    bn = cim_matmul._block_sizes(n, cim_matmul.BLOCK_N_MAX)[0]
    monkeypatch.setattr(cim_matmul, "VMEM_BUDGET", cim_matmul.vmem_bytes(
        bm, bn, tk, rows=rows, rows_d=rows_d, a_itemsize=4,
        d_itemsize=jnp.dtype(d_dtype).itemsize, n_cols=n_cols))
    jax.clear_caches()


#: (m, k_tiles, rows, n, forced tk, the (bm, bn, tk) the chooser picks):
#: k_tiles not a multiple of tk, M not a multiple of bm and N = 64 (half
#: a lane block); several row chunks in a block and N padded to the
#: block; a decode-sized M
BLOCK_CASES = {
    "ragged-tiles": (300, 5, 32, 64, 2, (128, 64, 2)),
    "row-chunks": (1100, 3, 32, 200, None, (1152, 256, 3)),
    "decode": (8, 4, 64, 128, None, (8, 128, 4)),
}


@pytest.fixture
def fresh_jit():
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", list(BLOCK_CASES))
@pytest.mark.parametrize("psum_bits", [1, 6])
@pytest.mark.parametrize("store", ["int8", "nibble"])
@pytest.mark.parametrize("skip", [False, True], ids=["dense", "skip"])
def test_kernel_blocks_bit_exact(monkeypatch, fresh_jit, case, psum_bits,
                                 store, skip):
    """The fused kernel equals ``ref.cim_matmul_ref`` bit for bit whatever
    block shape the chooser takes: the tiles a step walks, the row chunks
    and the padded edges change no output element's arithmetic or
    order."""
    m, kt, rows, n, tk, blocks = BLOCK_CASES[case]
    digits = _int4_planes(2, kt, rows, n)
    stored = _stored(digits, store)
    if tk is not None:
        _force_tiles(monkeypatch, m, n, kt, rows, stored.shape[2],
                     stored.dtype, tk)
    assert cim_matmul.block_shape(m, n, kt, rows, stored.shape[2],
                                  jnp.float32, stored.dtype,
                                  n_cols=2) == blocks
    a, _, s_p, deq = _mk(m, kt, rows, n, 2, seed=1)
    occ = occupancy_map(digits) if skip else None
    out = cim_matmul.cim_matmul_pallas(a, stored, s_p, deq, None, None, occ,
                                       psum_bits=psum_bits, interpret=True)
    expect = ref.cim_matmul_ref(a, digits, s_p, deq, psum_bits=psum_bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("case", list(BLOCK_CASES))
@pytest.mark.parametrize("skip", [False, True], ids=["dense", "skip"])
def test_adc_free_kernel_blocks_bit_exact(monkeypatch, fresh_jit, case,
                                          skip):
    m, kt, rows, n, tk, blocks = BLOCK_CASES[case]
    digits = _int4_planes(2, kt, rows, n)
    stored = pack_nibbles(digits)
    if tk is not None:
        _force_tiles(monkeypatch, m, n, kt, rows, stored.shape[2],
                     stored.dtype, tk, n_cols=1)
    assert cim_matmul.block_shape(m, n, kt, rows, stored.shape[2],
                                  jnp.float32, stored.dtype,
                                  n_cols=1) == blocks
    a, _, _, deq = _mk(m, kt, rows, n, 2, seed=2)
    occ = occupancy_map(digits) if skip else None
    out = cim_matmul_adc_free_pallas(a, stored, deq, None, None, occ,
                                     interpret=True)
    expect = ref.cim_matmul_adc_free_ref(a, digits, deq)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def _vmem(bm, bn, tk, m_shape, d_dtype, n_cols=2):
    _, kt, rows, _ = m_shape
    rows_d = rows // 2 if d_dtype == jnp.uint8 else rows
    return cim_matmul.vmem_bytes(bm, bn, tk, rows=rows, rows_d=rows_d,
                                 a_itemsize=1,
                                 d_itemsize=jnp.dtype(d_dtype).itemsize,
                                 n_cols=n_cols)


#: (M, k_tiles, rows, N) of every distinct ResNet-18 CIM conv at batch
#: 128 (224x224 input): the 3x3 stride-1 convs of stages 0-3, the 3x3
#: stride-2 ``conv1`` and the 1x1 ``proj`` of stages 1-3
RESNET18 = [(128 * 56 * 56, 5, 126, 64), (128 * 28 * 28, 10, 126, 128),
            (128 * 14 * 14, 19, 126, 256), (128 * 7 * 7, 37, 126, 512),
            (128 * 28 * 28, 5, 126, 128), (128 * 14 * 14, 10, 126, 256),
            (128 * 7 * 7, 19, 126, 512), (128 * 28 * 28, 1, 64, 128),
            (128 * 14 * 14, 1, 128, 256), (128 * 7 * 7, 2, 128, 512)]
#: olmo-1b's projections at decode and prefill batches
OLMO = [(m, k // 128, 128, n) for m in (1, 8, 64, 512)
        for k, n in ((2048, 8192), (8192, 2048), (2048, 2048))]


@pytest.mark.parametrize("d_dtype", [jnp.int8, jnp.uint8, jnp.float32],
                         ids=["int8", "nibble", "float"])
def test_block_shape_within_vmem_budget(d_dtype):
    for shape in RESNET18 + OLMO:
        m, kt, rows, n = shape
        rows_d = rows // 2 if d_dtype == jnp.uint8 else rows
        bm, bn, tk = cim_matmul.block_shape(m, n, kt, rows, rows_d, jnp.int8,
                                            d_dtype, n_cols=2)
        assert _vmem(bm, bn, tk, shape, d_dtype) <= cim_matmul.VMEM_BUDGET
        assert cim_matmul.VMEM_BUDGET < cim_matmul.VMEM_LIMIT


@pytest.mark.parametrize("m", [1, 8, 64, 127, 128, 129, 300, 1100, 6272,
                               25088, 100352, 401408])
def test_block_shape_pads_m_no_further_than_128(m):
    bm, _, _ = cim_matmul.block_shape(m, 256, 4, 128, 64, jnp.int8,
                                      jnp.uint8, n_cols=2)
    padded = -(-m // bm) * bm
    assert padded == (m if m <= 128 else -(-m // 128) * 128)
    if m <= 128:                # decode-sized: the whole M, unpadded
        assert bm == m


def test_block_shape_resnet18_takes_every_tile_in_one_step():
    for m, kt, rows, n in RESNET18:
        bm, bn, tk = cim_matmul.block_shape(m, n, kt, rows, rows // 2,
                                            jnp.int8, jnp.uint8, n_cols=2)
        assert tk == kt and bn == n and bm > 128, (m, kt, n, bm, bn, tk)


def test_block_shape_float_digits_chunk_wide_k():
    """A variation realization's float32 digits at olmo-1b's K = 8192 (64
    array tiles) do not all fit a step: the chooser chunks them evenly."""
    bm, bn, tk = cim_matmul.block_shape(8, 2048, 64, 128, 128, jnp.int8,
                                        jnp.float32, n_cols=2)
    assert bm == 8 and tk < 64 and 64 % tk == 0
    assert cim_matmul.block_shape(8, 2048, 64, 128, 64, jnp.int8, jnp.uint8,
                                  n_cols=2)[2] == 64


def test_block_shape_caps():
    """``block_m`` / ``block_n`` are upper bounds on the block."""
    assert cim_matmul.block_shape(25088, 256, 19, 126, 63, jnp.int8,
                                  jnp.uint8, n_cols=2, block_m=128,
                                  block_n=128) == (128, 128, 19)
