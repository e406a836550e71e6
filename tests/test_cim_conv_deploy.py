"""Conv deploy path: fused Pallas kernel vs the emulate grouped conv.

The deploy contract (DESIGN.md §3): identical arithmetic to emulate
(tests assert to 1e-4), activations never tiled ``n_split``x (HLO
inspected), the partial-sum tensor never materialized in HBM.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import calibrate_conv as calibrate_cim_conv
from repro.api import conv2d as cim_conv2d
from repro.api import init_conv as init_cim_conv
from repro.api import pack_conv as pack_deploy_conv
from repro.api import pack_model
from repro.core import CIMConfig, Granularity, conv_tiling


def _cfg(**kw):
    base = dict(enabled=True, mode="emulate", weight_bits=4, cell_bits=2,
                act_bits=6, psum_bits=6, array_rows=64, array_cols=64,
                act_signed=False)
    base.update(kw)
    return CIMConfig(**base)


def _setup(cfg, kh=3, c_in=19, c_out=10, b=2, hw=8, stride=1,
           padding="SAME", seed=0):
    p = init_cim_conv(jax.random.PRNGKey(seed), kh, kh, c_in, c_out, cfg)
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed + 1),
                                      (b, hw, hw, c_in)))
    p = calibrate_cim_conv(x, p, cfg, stride=stride, padding=padding)
    return p, x


def _assert_deploy_matches(p, x, cfg, *, stride=1, padding="SAME",
                           use_kernel=True):
    y_e = cim_conv2d(x, p, cfg, stride=stride, padding=padding,
                     compute_dtype=jnp.float32)
    dp = pack_deploy_conv(p, cfg)
    y_d = cim_conv2d(x, dp, cfg.replace(mode="deploy", use_kernel=use_kernel),
                     stride=stride, padding=padding,
                     compute_dtype=jnp.float32)
    assert y_d.shape == y_e.shape
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_e),
                               rtol=1e-4, atol=1e-4)
    return y_d


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["SAME", "VALID"])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_deploy_matches_emulate_stride_padding(stride, padding, use_kernel):
    cfg = _cfg()
    p, x = _setup(cfg, stride=stride, padding=padding)
    _assert_deploy_matches(p, x, cfg, stride=stride, padding=padding,
                           use_kernel=use_kernel)


@pytest.mark.parametrize("g", list(Granularity))
def test_deploy_matches_emulate_granularity(g):
    cfg = _cfg(weight_granularity=g, psum_granularity=g)
    p, x = _setup(cfg)
    _assert_deploy_matches(p, x, cfg)


def test_deploy_sign_adc_psum_bits_1():
    """psum_bits == 1 is the binary (ADC-less) partial-sum mode."""
    cfg = _cfg(psum_bits=1)
    p, x = _setup(cfg)
    _assert_deploy_matches(p, x, cfg)


def test_deploy_odd_channel_slices():
    """c_in that doesn't fill k_tiles * c_per_array: array_rows=32, 3x3
    taps -> c_per_array=3; c_in=7 -> k_tiles=3 with 2 padded channels."""
    cfg = _cfg(array_rows=32, array_cols=32)
    t, cpa = conv_tiling(3, 3, 7, 6, 32, 32, 4, 2)
    assert cpa == 3 and t.k_tiles == 3 and t.k_tiles * cpa != 7
    p, x = _setup(cfg, c_in=7, c_out=6)
    _assert_deploy_matches(p, x, cfg)


def test_deploy_1x1_proj_stride2():
    """The ResNet downsampling projection: 1x1 kernel, stride 2."""
    cfg = _cfg(array_rows=16)
    p, x = _setup(cfg, kh=1, c_in=24, c_out=8, stride=2)
    _assert_deploy_matches(p, x, cfg, stride=2)


def test_deploy_int4_packing():
    cfg = _cfg(pack_dtype="int4")
    p, x = _setup(cfg)
    dp = pack_deploy_conv(p, cfg)
    assert dp["w_digits"].dtype == jnp.int4
    _assert_deploy_matches(p, x, cfg)


def test_packed_planes_carry_geometry():
    cfg = _cfg()
    p, _ = _setup(cfg)
    dp = pack_deploy_conv(p, cfg)
    t, cpa = conv_tiling(3, 3, 19, 10, cfg.array_rows, cfg.array_cols,
                         cfg.weight_bits, cfg.cell_bits)
    assert dp["w_digits"].shape == (t.n_split, t.k_tiles, 3, 3, cpa, 10)


def test_deploy_hlo_has_no_nsplit_activation_tile():
    """The emulate grouped conv materializes the activation channel-slices
    tiled n_split x (B, H, W, S*kt*cpa); the deploy lowering must not."""
    cfg = _cfg()                  # S=2, and for c_in=19: kt=3, cpa=7
    p, x = _setup(cfg)
    t, cpa = conv_tiling(3, 3, 19, 10, cfg.array_rows, cfg.array_cols,
                         cfg.weight_bits, cfg.cell_bits)
    # StableHLO shape text for the (B, H, W, S*kt*cpa) replicated tile
    marker = f"2x8x8x{t.n_split * t.k_tiles * cpa}x"

    hlo_e = jax.jit(lambda x_: cim_conv2d(
        x_, p, cfg, compute_dtype=jnp.float32)).lower(x).as_text()
    assert marker in hlo_e        # sanity: the marker identifies the tile

    dp = pack_deploy_conv(p, cfg)
    dcfg = cfg.replace(mode="deploy")
    hlo_d = jax.jit(lambda x_: cim_conv2d(
        x_, dp, dcfg, compute_dtype=jnp.float32)).lower(x).as_text()
    assert marker not in hlo_d


def test_deploy_variation_noise():
    """Cell variation applies to the packed digit planes too."""
    cfg = _cfg(variation_std=0.2)
    p, x = _setup(cfg)
    dp = pack_deploy_conv(p, cfg)
    dcfg = cfg.replace(mode="deploy")
    k = jax.random.PRNGKey(7)
    y1 = cim_conv2d(x, dp, dcfg, variation_key=k, compute_dtype=jnp.float32)
    y2 = cim_conv2d(x, dp, dcfg, variation_key=jax.random.PRNGKey(8),
                    compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(y1)))
    assert float(jnp.max(jnp.abs(y1 - y2))) > 0   # noise actually applied


def test_resnet_pack_deploy_forward():
    from repro.models import resnet
    cim = _cfg()
    cfg = resnet.ResNetConfig(name="tiny", depth=20, n_classes=10,
                              widths=(8, 16), in_hw=8, cim=cim)
    params, state = resnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 3))
    params = resnet.calibrate(params, state, x, cfg)
    y_e, _ = resnet.forward(params, state, x, cfg, train=False)

    dp = pack_model(params, cfg.cim)
    dcfg = dataclasses.replace(cfg, cim=cim.replace(mode="deploy"))
    y_d, _ = resnet.forward(dp, state, x, dcfg, train=False)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_e),
                               rtol=1e-4, atol=1e-4)


def test_layers_conv_specs_and_apply():
    from repro.models.layers import apply_conv, conv_specs
    from repro.nn.module import init_params

    cim = _cfg()
    sp = conv_specs(3, 3, 19, 10, cim=cim)
    assert set(sp) == {"w", "s_w", "s_p", "s_a"}
    dsp = conv_specs(3, 3, 19, 10, cim=cim.replace(mode="deploy"))
    t, cpa = conv_tiling(3, 3, 19, 10, cim.array_rows, cim.array_cols,
                         cim.weight_bits, cim.cell_bits)
    assert dsp["w_digits"].shape == (t.n_split, t.k_tiles, 3, 3, cpa, 10)

    # emulate params round-trip through pack + apply_conv deploy dispatch
    cfg = _cfg()
    p, x = _setup(cfg)
    y_e = apply_conv(p, x, cfg, compute_dtype=jnp.float32)
    dp = pack_deploy_conv(p, cfg)
    y_d = apply_conv(dp, x, cfg.replace(mode="deploy"),
                     compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y_d), np.asarray(y_e),
                               rtol=1e-4, atol=1e-4)
    # init_params materializes the deploy specs (zeros planes)
    dparams = init_params(dsp, jax.random.PRNGKey(0))
    assert dparams["w_digits"].dtype == jnp.int8


#: (kernel, stride, padding, c_per_array, C_in, codes): 3x3 at stride 1
#: and 2 under SAME, VALID and explicit asymmetric pads, 1x1 at stride 2,
#: channels that leave the last array tile part-filled, and int8, uint8
#: and float32 codes at the ends of their 8-bit ranges
PATCH_CASES = [
    (3, 1, "SAME", 14, 64, "int8"),
    (3, 2, "SAME", 14, 64, "int8"),
    (3, 2, "VALID", 14, 40, "uint8"),
    (3, 1, ((2, 0), (1, 3)), 5, 12, "float32"),
    (3, 2, "SAME", 3, 7, "float32"),
    (1, 2, "SAME", 128, 200, "int8"),
    (1, 1, "VALID", 4, 9, "float32"),
]


@pytest.mark.parametrize(
    "k,stride,padding,cpa,c_in,codes", PATCH_CASES,
    ids=[f"{k}x{k}s{st}-{p if isinstance(p, str) else 'pads'}-{ci}c-{d}"
         for k, st, p, _, ci, d in PATCH_CASES])
def test_tile_major_patches_bit_exact(k, stride, padding, cpa, c_in, codes):
    """The deploy path's patch operand (one 0/1 convolution over the
    tile-major code map) equals the oracle's stretched-kernel patches bit
    for bit, in the code dtype: ``ref.extract_conv_patches`` with its tile
    axis first and the output positions in (h', w', b) order."""
    from repro.kernels import ref
    from repro.kernels.cim_conv import tile_major_patches
    kt = -(-c_in // cpa)
    lo, hi = {"int8": (-128, 127), "uint8": (0, 255),
              "float32": (-128, 255)}[codes]
    a = jax.random.randint(jax.random.PRNGKey(k + stride), (3, 9, 11, c_in),
                           lo, hi + 1)
    a = a.at[0, 0, 0, :2].set(jnp.array([lo, hi])).astype(codes)
    got = jax.jit(lambda x: tile_major_patches(x, k, k, stride, padding, kt,
                                               cpa))(a)
    p = ref.extract_conv_patches(a, k, k, stride, padding, kt, cpa)
    want = jnp.transpose(p, (3, 1, 2, 0, 4)).reshape(kt, -1, k * k * cpa)
    assert got.dtype == a.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("k,stride", [(3, 1), (3, 2), (1, 2)])
@pytest.mark.parametrize("psum_bits", [1, 6, None],
                         ids=["adc1", "adc6", "adc_free"])
@pytest.mark.parametrize("store", ["int8", "nibble"])
@pytest.mark.parametrize("skip", [False, True], ids=["dense", "skip"])
@pytest.mark.parametrize("variation", [False, True],
                         ids=["clean", "variation"])
def test_conv_kernel_bit_exact_with_oracle(k, stride, psum_bits, store,
                                           skip, variation):
    """The conv deploy kernels equal ``ref.cim_conv_ref`` (ADC-free,
    ``psum_bits=None``: the unquantized oracle) bit for bit: 128-row
    arrays of 14 channels x 9 taps (3x3) or 128 channels (1x1), 64
    output columns (half a lane block), M = 2*9*9 = 162 or 2*5*5 = 50
    rows in one padded block, and dead planes for the occupancy skip.
    Under one variation realization the float digits make the column
    sums order-dependent, so the reference is the same matmul kernel fed
    the oracle's patches (``ref.extract_conv_patches``) with the same
    key: the conv path only builds the operand differently."""
    from repro.core.nibble import occupancy_map, pack_nibbles
    from repro.kernels import ref
    from repro.kernels.cim_adc_free import (cim_conv_adc_free_pallas,
                                            cim_matmul_adc_free_pallas)
    from repro.kernels.cim_conv import cim_conv_pallas
    from repro.kernels.cim_matmul import cim_matmul_pallas
    cpa = 128 // (k * k)
    c_in, c_out = 3 * cpa - 5, 64
    kt = -(-c_in // cpa)
    ks = jax.random.split(jax.random.PRNGKey(k * 10 + stride), 4)
    a = jax.random.randint(ks[0], (2, 9, 9, c_in), 0, 64).astype(jnp.int8)
    digits = jax.random.randint(ks[1], (2, kt, k, k, cpa, c_out), -8, 8)
    digits = digits.at[0, 1].set(0).astype(jnp.int8)
    flat = digits.reshape(2, kt, k * k * cpa, c_out)
    stored = (pack_nibbles(digits).reshape(2, kt, k * k * cpa // 2, c_out)
              if store == "nibble" else flat)
    occ = occupancy_map(digits, conv=True) if skip else None
    s_p = 2.0 ** jax.random.randint(ks[2], (2, kt, c_out), 3, 8)
    deq = jax.random.uniform(ks[3], (2, kt, c_out), minval=0.01, maxval=0.1)
    key, std = (jax.random.PRNGKey(5), 0.1) if variation else (None, None)
    geo = dict(kh=k, kw=k, stride=stride, padding="SAME", c_per_array=cpa)
    a_t = ref.extract_conv_patches(a.astype(jnp.float32), k, k, stride,
                                   "SAME", kt, cpa)
    a2 = a_t.reshape(-1, kt, k * k * cpa)
    if psum_bits is None:
        out = cim_conv_adc_free_pallas(a, stored, deq, key, std, occ,
                                       interpret=True, **geo)
        expect = (cim_matmul_adc_free_pallas(
            a2, stored, deq, key, std, occ, nibble_groups=k * k,
            interpret=True) if variation
            else ref.cim_matmul_adc_free_ref(a2, flat, deq))
        expect = expect.reshape(a_t.shape[:3] + (c_out,))
    else:
        out = cim_conv_pallas(a, stored, s_p, deq, key, std, occ,
                              psum_bits=psum_bits, interpret=True, **geo)
        expect = (cim_matmul_pallas(
            a2, stored, s_p, deq, key, std, occ, psum_bits=psum_bits,
            nibble_groups=k * k, interpret=True
        ).reshape(a_t.shape[:3] + (c_out,)) if variation
            else ref.cim_conv_ref(a, flat, s_p, deq, psum_bits=psum_bits,
                                  **geo))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
