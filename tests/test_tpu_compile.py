"""The deploy kernels compile for a TPU v5e at published widths.

Interpret mode (every other kernel test) runs the kernel body on the CPU
and cannot see what Mosaic refuses: a block whose second-minor dim is 1
over a longer axis, a vector reduce it cannot relayout, SMEM misuse.
These cases compile each kernel of the serving path with
``interpret=False`` for one chip of a *described* v5e:2x2 topology (no
chip attached) and assert the Pallas kernel is in the program
(``tpu_custom_call``), and that the grid and VMEM limit Mosaic got are
the ones ``cim_matmul.block_shape`` picks. Nothing runs, so nothing here
is a timing.

The topology is described inside a module fixture — never at import —
so every test worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""
import base64
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.extend.mlir import ir

from repro.kernels.cim_adc_free import cim_matmul_adc_free_pallas
from repro.kernels.cim_conv import cim_conv_pallas
from repro.kernels.cim_matmul import (VMEM_LIMIT, block_shape,
                                      cim_matmul_experts_pallas,
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


def _kernel_calls(text):
    """(instruction name, grid, scoped VMEM bytes) of every Pallas call in
    a compiled module: the grid is the ``iteration_bounds`` of the Mosaic
    kernel serialized in the call's backend config."""
    calls = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        config = line[line.index("backend_config=") + len("backend_config="):]
        config = json.JSONDecoder().raw_decode(config)[0]
        body = base64.b64decode(config["custom_call_config"]["body"])
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            main = next(op for op in ir.Module.parse(body).body.operations
                        if str(op.attributes["sym_name"]) == '"main"')
            grid = tuple(int(b) for b in re.findall(
                r"-?\d+", str(main.attributes["iteration_bounds"])
                .split(":", 1)[1]))
        vmem = int(config["scoped_memory_configs"][0]["size"])
        calls.append((name, grid, vmem))
    return calls


def _assert_grid(lowered, m, n, k_tiles, rows, rows_d, a_dtype, d_dtype,
                 n_cols=2):
    """One ``cim_matmul`` call whose grid is the chooser's, compiled with
    the kernel's VMEM limit; returns the chosen (bm, bn, tk)."""
    (name, grid, vmem), = _kernel_calls(lowered.compile().as_text())
    bm, bn, tk = block_shape(m, n, k_tiles, rows, rows_d, a_dtype, d_dtype,
                             n_cols=n_cols)
    assert re.fullmatch(r"cim_(matmul|adc_free)(\.\d+)?", name), name
    assert grid == (-(-m // bm), -(-n // bn), S, -(-k_tiles // tk)), grid
    assert vmem == VMEM_LIMIT
    return bm, bn, tk


def _matmul_operands(sh, m, k, n, dtype):
    kt = -(-k // ROWS)
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
    bm, _, tk = _assert_grid(
        cim_matmul_pallas.lower(a, d, sp, dq, psum_bits=6, interpret=False),
        m, 8192, 16, ROWS, ROWS, jnp.int8, jnp.int8)
    assert (bm, tk) == (m, 16)


@pytest.mark.parametrize("k", [2048, 8192])
def test_matmul_variation_float_digits(one_chip, k):
    """One variation realization turns the digit block float32, which
    the kernel contracts at float32 precision. At olmo-1b's
    down-projection (K=8192, 64 array tiles) the float digits of all
    tiles do not fit VMEM, so a step takes a chunk of them."""
    a, d, sp, dq = _matmul_operands(one_chip, 8, k, 2048, jnp.int8)
    key = _spec((2,), jnp.uint32, one_chip)
    kt = k // ROWS
    _, _, tk = _assert_grid(
        cim_matmul_pallas.lower(a, d, sp, dq, key, 0.05, psum_bits=6,
                                interpret=False),
        8, 2048, kt, ROWS, ROWS, jnp.int8, jnp.float32)
    assert (tk < kt) == (k == 8192), tk


@pytest.mark.parametrize("psum_bits", [1, 6])
def test_matmul_nibble_occupancy_d_ff_to_d_model(one_chip, psum_bits):
    """olmo-1b's down-projection on int4 nibble planes with the
    occupancy skip: K=8192 (64 array tiles), N=2048; the sign ADC
    (psum_bits=1) is the case where a skipped block still contributes."""
    a, d, sp, dq = _matmul_operands(one_chip, 8, 8192, 2048, jnp.uint8)
    occ = _spec((S, 8192 // ROWS, 2048), jnp.uint8, one_chip)
    _, _, tk = _assert_grid(
        cim_matmul_pallas.lower(a, d, sp, dq, None, None, occ,
                                psum_bits=psum_bits, interpret=False),
        8, 2048, 64, ROWS, ROWS // 2, jnp.int8, jnp.uint8)
    assert tk == 64


#: every CIM linear of Kimi-VL's MoonViT tower on the benchmark's pack of
#: 16,384 patches, (M, K, N): a block's fused QKV, output, MLP up and MLP
#: down (K = 4304: 34 array tiles, the last one part-filled), then the
#: projector's two at the 4,096 merged tokens
MOONVIT_LINEARS = [(16384, 1152, 3456), (16384, 1152, 1152),
                   (16384, 1152, 4304), (16384, 4304, 1152),
                   (4096, 4608, 4608), (4096, 4608, 2048)]


@pytest.mark.parametrize("m,k,n", MOONVIT_LINEARS,
                         ids=[f"{m}x{k}-{n}" for m, k, n in MOONVIT_LINEARS])
def test_matmul_nibble_occupancy_moonvit(one_chip, m, k, n):
    """The deploy path's call for each tower linear: int4 nibble planes
    with the occupancy table, as ``model_artifact`` packs them."""
    kt = -(-k // ROWS)
    a, d, sp, dq = _matmul_operands(one_chip, m, k, n, jnp.uint8)
    occ = _spec((S, kt, n), jnp.uint8, one_chip)
    _assert_grid(
        cim_matmul_pallas.lower(a, d, sp, dq, None, None, occ, psum_bits=6,
                                interpret=False),
        m, n, kt, ROWS, ROWS // 2, jnp.int8, jnp.uint8)


#: every distinct CIM conv of ResNet-18 at 224x224 input (stem out):
#: (input side, C_in, C_out, kernel, stride); 3x3 stride 1, the 3x3
#: stride-2 ``conv1`` of stages 1-3 and their 1x1 ``proj``
RESNET18_CONVS = [(56, 64, 64, 3, 1), (28, 128, 128, 3, 1),
                  (14, 256, 256, 3, 1), (7, 512, 512, 3, 1),
                  (56, 64, 128, 3, 2), (28, 128, 256, 3, 2),
                  (14, 256, 512, 3, 2), (56, 64, 128, 1, 2),
                  (28, 128, 256, 1, 2), (14, 256, 512, 1, 2)]


def _resnet18_conv(sharding, hw, c_in, c_out, k, stride):
    """The compiled text of one ResNet-18 CIM conv at batch 128 on int4
    nibble planes with the occupancy map, as the deploy path calls it,
    and its (M, k_tiles, rows, c_per_array). Compiled once per shape in
    the module (``_COMPILED``): two tests read each."""
    cpa = min(ROWS // (k * k), c_in)
    kt = -(-c_in // cpa)
    rows = k * k * cpa
    ho = -(-hw // stride)
    key = (hw, c_in, c_out, k, stride)
    if key not in _COMPILED:
        a = _spec((128, hw, hw, c_in), jnp.int8, sharding)
        d = _spec((S, kt, rows // 2, c_out), jnp.uint8, sharding)
        sp = _spec((S, kt, c_out), jnp.float32, sharding)
        occ = _spec((S, kt, c_out), jnp.uint8, sharding)
        _COMPILED[key] = cim_conv_pallas.lower(
            a, d, sp, sp, None, None, occ, kh=k, kw=k, stride=stride,
            padding="SAME", c_per_array=cpa, psum_bits=6,
            interpret=False).compile().as_text()
    return _COMPILED[key], (128 * ho * ho, kt, rows, cpa)


_COMPILED = {}
_RESNET18_IDS = [f"{h}x{ci}-{co}_{k}x{k}s{st}"
                 for h, ci, co, k, st in RESNET18_CONVS]


@pytest.mark.parametrize("hw,c_in,c_out,k,stride", RESNET18_CONVS,
                         ids=_RESNET18_IDS)
def test_conv_resnet18_batch128(one_chip, hw, c_in, c_out, k, stride):
    """Each ResNet-18 CIM conv at batch 128 on int4 nibble planes with the
    occupancy map — the benchmark's shapes — is one ``cim_matmul`` call
    on the grid the chooser picks, with every array tile in one step."""
    text, (m, kt, rows, _) = _resnet18_conv(one_chip, hw, c_in, c_out, k,
                                            stride)
    (name, grid, vmem), = _kernel_calls(text)
    bm, bn, tk = block_shape(m, c_out, kt, rows, rows // 2, jnp.int8,
                             jnp.uint8, n_cols=2)
    assert re.fullmatch(r"cim_matmul(\.\d+)?", name), name
    assert grid == (-(-m // bm), -(-c_out // bn), S, -(-kt // tk)), grid
    assert vmem == VMEM_LIMIT
    assert tk == kt


def _computations(text):
    """{computation name: [(instruction name, element count, opcode,
    op_name, the line)]} of a compiled module."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
            continue
        ins = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                       r"([\w\-]+)\(", line)
        if ins and cur is not None:
            count = math.prod(int(d) for d in ins.group(2).split(",")
                              if d)
            op = re.search(r'op_name="([^"]*)"', line)
            cur.append((ins.group(1), count, ins.group(3),
                        op.group(1) if op else "", line))
    return comps


@pytest.mark.parametrize("hw,c_in,c_out,k,stride", RESNET18_CONVS,
                         ids=_RESNET18_IDS)
def test_conv_resnet18_patches_written_once(one_chip, hw, c_in, c_out, k,
                                            stride):
    """The patch operand of each ResNet-18 CIM conv at batch 128 is built
    without a loop and written once: no ``while`` anywhere, and among the
    program's own instructions of the operand's size (k_tiles * M *
    rows elements) only the builder's convolution fusion (a strided
    slice for 1x1) and at most one copy, each under ``cim.patches``."""
    text, (m, kt, rows, _) = _resnet18_conv(one_chip, hw, c_in, c_out, k,
                                            stride)
    comps = _computations(text)
    assert not [i for instrs in comps.values() for i in instrs
                if i[2] == "while"]
    entry = re.search(r"^ENTRY %([\w.\-]+) ", text, re.M).group(1)
    big = [i for i in comps[entry]
           if i[1] == kt * m * rows and i[2] not in ("bitcast", "parameter")]
    builds = [i for i in big if i[2] != "copy"]
    assert len(builds) == 1 and len(big) <= 2, [i[:3] for i in big]
    (_, _, opcode, _, line), = builds
    want = "slice" if k == 1 else "convolution"
    if opcode == "fusion":
        called = re.search(r"calls=%([\w.\-]+)", line).group(1)
        assert any(i[2] == want for i in comps[called]), line
    else:
        assert opcode == want, line
    for _, _, _, op_name, line in big:
        assert "/cim.patches/" in op_name, line


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
    _assert_grid(cim_conv_pallas.lower(
        a, d, sp, sp, None, None, occ, kh=3, kw=3, stride=1,
        padding="SAME", c_per_array=cpa, psum_bits=6, interpret=False),
        8 * 56 * 56, 64, kt, 9 * cpa, rows_d, jnp.int8, dtype)


def test_conv_kernel_names_and_scopes(one_chip):
    """The trace names a kernel op after its HLO instruction: every
    Pallas call of the conv deploy path is named ``cim_...`` (the
    ``pallas_call(name=...)``, not the jitted wrapper's name) and carries
    the ``cim.kernel`` scope in its op_name, and the patch extraction
    and relayout keep their scopes through the TPU compile."""
    cpa, kt = 14, 5
    a = _spec((8, 56, 56, 64), jnp.int8, one_chip)
    d = _spec((S, kt, 9 * cpa // 2, 64), jnp.uint8, one_chip)
    sp = _spec((S, kt, 64), jnp.float32, one_chip)
    text = cim_conv_pallas.lower(
        a, d, sp, sp, kh=3, kw=3, stride=1, padding="SAME",
        c_per_array=cpa, psum_bits=6, interpret=False).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        name = line.split(" = ", 1)[0].split()[-1].lstrip("%")
        assert re.fullmatch(r"cim_matmul(\.\d+)?", name), name
        assert "/cim.kernel/" in re.search(r'op_name="([^"]*)"', line)[1]
    assert "/cim.patches/" in text and "/cim.layout/" in text


def test_matmul_adc_free_nibble_occupancy(one_chip):
    a, d, _, dq = _matmul_operands(one_chip, 8, 2048, 2048, jnp.uint8)
    occ = _spec((S, 2048 // ROWS, 2048), jnp.uint8, one_chip)
    _assert_grid(cim_matmul_adc_free_pallas.lower(
        a, d, dq, None, None, occ, interpret=False),
        8, 2048, 16, ROWS, ROWS // 2, jnp.int8, jnp.uint8, n_cols=1)


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
