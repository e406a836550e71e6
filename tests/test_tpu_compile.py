"""The deploy kernels compile for a TPU v5e at published widths.

Interpret mode (every other kernel test) runs the kernel body on the CPU
and cannot see what Mosaic refuses: a block whose second-minor dim is 1
over a longer axis, a vector reduce it cannot relayout, SMEM misuse.
These cases compile each kernel of the serving path with
``interpret=False`` for one chip of a *described* v5e:2x2 topology (no
chip attached) and assert the Pallas kernel is in the program
(``tpu_custom_call``). Nothing runs, so nothing here is a timing.

The topology is described inside a module fixture — never at import —
so every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.cim_adc_free import cim_matmul_adc_free_pallas
from repro.kernels.cim_conv import cim_conv_pallas
from repro.kernels.cim_matmul import (cim_matmul_experts_pallas,
                                      cim_matmul_pallas)

ROWS = 128          # CIM array rows of the serving configs
S = 2               # bit splits: 4-bit weights on 2-bit cells


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def _matmul_operands(sh, m, k, n, dtype):
    kt = k // ROWS
    rows_d = ROWS // 2 if dtype == jnp.uint8 else ROWS
    return (_spec((m, kt, ROWS), jnp.int8, sh),
            _spec((S, kt, rows_d, n), dtype, sh),
            _spec((S, kt, n), jnp.float32, sh),
            _spec((S, kt, n), jnp.float32, sh))


@pytest.mark.parametrize("m", [8, 64])
def test_matmul_dense_int8_d_model_to_d_ff(one_chip, m):
    """olmo-1b's up-projections: K=2048 (16 array tiles), N=8192 — at a
    decode batch and at a prefill batch."""
    a, d, sp, dq = _matmul_operands(one_chip, m, 2048, 8192, jnp.int8)
    _assert_kernel(cim_matmul_pallas.lower(a, d, sp, dq, psum_bits=6,
                                           interpret=False))


def test_matmul_variation_float_digits(one_chip):
    """One variation realization turns the digit block float32, which
    the kernel contracts at float32 precision."""
    a, d, sp, dq = _matmul_operands(one_chip, 8, 2048, 2048, jnp.int8)
    key = _spec((2,), jnp.uint32, one_chip)
    _assert_kernel(cim_matmul_pallas.lower(a, d, sp, dq, key, 0.05,
                                           psum_bits=6, interpret=False))


@pytest.mark.parametrize("psum_bits", [1, 6])
def test_matmul_nibble_occupancy_d_ff_to_d_model(one_chip, psum_bits):
    """olmo-1b's down-projection on int4 nibble planes with the
    occupancy skip: K=8192 (64 array tiles), N=2048; the sign ADC
    (psum_bits=1) is the case where a skipped block still contributes."""
    a, d, sp, dq = _matmul_operands(one_chip, 8, 8192, 2048, jnp.uint8)
    occ = _spec((S, 8192 // ROWS, 2048), jnp.uint8, one_chip)
    _assert_kernel(cim_matmul_pallas.lower(a, d, sp, dq, None, None, occ,
                                           psum_bits=psum_bits,
                                           interpret=False))


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.uint8],
                         ids=["int8", "nibble"])
def test_conv_resnet18_3x3(one_chip, dtype):
    """A ResNet-18 3x3 layer, 56x56x64 -> 64: c_per_array = 128 // 9 = 14,
    so each array holds 126 rows (63 stored rows when nibble-packed) and
    the 64 input channels need 5 array tiles."""
    cpa, kt = 14, 5
    rows_d = 9 * (cpa // 2 if dtype == jnp.uint8 else cpa)
    a = _spec((8, 56, 56, 64), jnp.int8, one_chip)
    d = _spec((S, kt, rows_d, 64), dtype, one_chip)
    sp = _spec((S, kt, 64), jnp.float32, one_chip)
    occ = _spec((S, kt, 64), jnp.uint8, one_chip)
    _assert_kernel(cim_conv_pallas.lower(
        a, d, sp, sp, None, None, occ, kh=3, kw=3, stride=1,
        padding="SAME", c_per_array=cpa, psum_bits=6, interpret=False))


def test_matmul_adc_free_nibble_occupancy(one_chip):
    a, d, _, dq = _matmul_operands(one_chip, 8, 2048, 2048, jnp.uint8)
    occ = _spec((S, 2048 // ROWS, 2048), jnp.uint8, one_chip)
    _assert_kernel(cim_matmul_adc_free_pallas.lower(
        a, d, dq, None, None, occ, interpret=False))


def test_experts_bank(one_chip):
    """One MoE bank at moonshot-v1-16b-a3b widths: 64 experts, d_model
    2048 -> expert d_ff 1408, a capacity of 16 tokens per expert."""
    e, c, k, n = 64, 16, 2048, 1408
    kt = k // ROWS
    a = _spec((e, c, kt, ROWS), jnp.int8, one_chip)
    d = _spec((e, S, kt, ROWS, n), jnp.int8, one_chip)
    sp = _spec((e, S, kt, n), jnp.float32, one_chip)
    _assert_kernel(cim_matmul_experts_pallas.lower(a, d, sp, sp, psum_bits=6,
                                                   interpret=False))
