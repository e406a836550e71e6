"""Device time put down to the program's scopes: the scope map of a
compiled program, and the ``patch_ms`` reduction over a trace."""
import types

import jax
import pytest

from perfbench import scopes, trace

# A compiled module as the TPU compiler prints it (abridged): a fusion
# under the patch scope, an async copy and a loop XLA made without an
# op_name, the kernel, the output slice, and a loop body whose ops run as
# device ops inside the loop's own event.
TEXT = """HloModule jit_forward, is_scheduled=true

%fused_computation (param_0: s8[4,8]) -> s8[4,9] {
  %param_0 = s8[4,8]{1,0} parameter(0)
  ROOT %pad.1 = s8[4,9]{1,0} pad(%param_0, %c), padding=0_0x0_1, metadata={op_name="jit(f)/resnet.s0b0/conv1/jit(cim_conv_pallas)/cim.patches/jit(_pad)/pad" stack_frame_id=3}
}

%body (p: (u32[], s8[36])) -> (u32[], s8[36]) {
  %p = (u32[], s8[36]) parameter(0)
  %dynamic-update-slice.5 = s8[36]{0} dynamic-update-slice(%gte, %ds, %i)
  ROOT %tuple.2 = (u32[], s8[36]) tuple(%add.6, %dynamic-update-slice.5)
}

%cond (p.1: (u32[], s8[36])) -> pred[] {
  %p.1 = (u32[], s8[36]) parameter(0)
  ROOT %compare.1 = pred[] compare(%gte.1, %c.1), direction=LT
}

ENTRY %main.7 (x.1: s8[4,8], s_p.1: f32[2,9]) -> f32[4,9] {
  %x.1 = s8[4,8]{1,0} parameter(0), metadata={op_name="x"}
  %s_p.1 = f32[2,9]{1,0} parameter(1), metadata={op_name="s_p"}
  %pad_fusion = s8[4,9]{1,0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/resnet.s0b0/conv1/jit(cim_conv_pallas)/cim.patches/jit(_pad)/pad"}
  %tuple.1 = (u32[], s8[36]) tuple(%zero, %pad_fusion)
  %while.1 = (u32[], s8[36]) while(%tuple.1), condition=%cond, body=%body
  %copy-start = (f32[2,9]{1,0}, f32[2,9]{1,0}, u32[]) copy-start(%s_p.1)
  %copy-done = f32[2,9]{1,0} copy-done(%copy-start)
  %reshape.2 = f32[18,1]{1,0} reshape(%copy-done), metadata={op_name="jit(f)/resnet.s0b0/conv1/jit(cim_matmul_pallas)/cim.layout/reshape"}
  %cim_matmul.3 = f32[8,128]{1,0} custom-call(%while.1, %reshape.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/resnet.s0b0/conv1/jit(cim_matmul_pallas)/cim.kernel/cim_matmul/pallas_call"}
  ROOT %slice.4 = f32[4,9]{1,0} slice(%cim_matmul.3), slice={[0:4], [0:9]}, metadata={op_name="jit(f)/resnet.s0b0/conv1/jit(cim_matmul_pallas)/cim.layout/slice;jit(f)/resnet.s0b0/conv1/reshape"}
}
"""


def test_scope_path_drops_wrappers_and_the_primitive():
    assert scopes.scope_path(
        "jit(<lambda>)/resnet.s1b0/proj/jit(cim_conv_pallas)/cim.patches/"
        "jit(_pad)/pad") == "resnet.s1b0/proj/cim.patches"
    assert scopes.scope_path("x") == ""
    assert scopes.scope_path("jit(g)/reshape;jit(g)/cim.layout/reshape") \
        == "cim.layout"


def test_scope_map_of_a_compiled_text():
    smap = scopes.scope_map(scopes.parse(TEXT))
    patches = "resnet.s0b0/conv1/cim.patches"
    layout = "resnet.s0b0/conv1/cim.layout"
    assert smap["pad_fusion"] == patches
    # no op_name: the loop from its operands, the copy from its users
    assert smap["while.1"] == patches
    assert smap["copy-start"] == layout
    assert smap["cim_matmul.3"] == "resnet.s0b0/conv1/cim.kernel/cim_matmul"
    # of merged op_names the longest path wins
    assert smap["slice.4"] == layout
    # the loop body's ops take the loop's scope
    assert smap["dynamic-update-slice.5"] == patches
    assert scopes.callers(scopes.parse(TEXT))["dynamic-update-slice.5"] \
        == "while.1"


def _ctx(ops, units=2):
    red = trace.Reduced(window_s=1.0, busy_s=0.5, devices=1, ops=ops,
                        op_counts={n: 1 for n in ops}, gaps=[],
                        pallas={"cim_matmul.3"})
    return types.SimpleNamespace(rate="img_s", trace=red, units=units)


def test_patch_ms_on_a_synthetic_trace():
    # the loop body's op is inside the loop's event: counted once
    ops = {"pad_fusion": 1e-3, "while.1": 4e-3,
           "dynamic-update-slice.5": 3e-3, "copy-done": 1e-4,
           "reshape.2": 2e-4, "cim_matmul.3": 9e-3, "slice.4": 7e-4}
    ms = scopes.patch_ms(_ctx(ops), "img_s", lambda: TEXT)
    assert ms == pytest.approx(1e3 * (1e-3 + 4e-3 + 1e-4 + 2e-4 + 7e-4) / 2)
    att = scopes.attribute(ops, TEXT)
    assert att.nested_s == pytest.approx(3e-3)
    assert att.scoped_s == pytest.approx(att.mapped_s)
    assert att.ops["dynamic-update-slice.5"][2] == "while.1"
    lines = scopes.describe(att)
    assert ("op cim_matmul.3 0.009s: resnet.s0b0/conv1/cim.kernel/"
            "cim_matmul") in lines
    assert scopes.patch_ms(_ctx(ops), "tok_s", lambda: TEXT) is None


def test_unmapped_op_names_are_reported():
    ops = {"cim_matmul.3": 9e-3, "fusion.99": 2e-4}
    att = scopes.attribute(ops, TEXT)
    assert att.unmapped == {"fusion.99": 2e-4}
    assert any("fusion.99" in line for line in scopes.describe(att))
    # a text in which most of the window's time is missing is not the
    # program that ran: nothing is read from it
    assert scopes.patch_ms(_ctx({"fusion.99": 1.0, "slice.4": 1e-3}),
                           "img_s", lambda: TEXT) is None


def test_deploy_conv_compiles_under_the_cim_scopes():
    """A deploy conv compiled on the CPU (the kernel in interpret mode)
    keeps the program's scopes in its compiled text."""
    from repro.api import CIMConfig, calibrate_conv, conv2d, init_conv, \
        pack_conv
    cfg = CIMConfig(enabled=True, mode="emulate", weight_bits=4, cell_bits=2,
                    act_bits=6, psum_bits=4, array_rows=32, array_cols=32)
    p = init_conv(jax.random.PRNGKey(2), 3, 3, 8, 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 8)) * 0.5
    p = calibrate_conv(x, p, cfg)
    dcfg = cfg.replace(mode="deploy")
    packed = pack_conv(p, dcfg)
    text = jax.jit(lambda v: conv2d(v, packed, dcfg)).lower(x).compile() \
        .as_text()
    parts = {scopes.layer_of(p)
             for p in scopes.scope_map(scopes.parse(text)).values()}
    assert {"cim.act_quant", "cim.patches", "cim.layout",
            "cim.kernel"} <= parts


def test_unit_text_compiles_the_metric_cell(monkeypatch):
    """``patch_ms.img`` reads the compiled text of its one cell, built by
    that cell's configuration at the cell's sizes and traffic."""
    real = scopes.loader.load_module
    seen = {}

    def load(path):
        seen["module"] = real(path)
        return types.SimpleNamespace(
            compiled_text=lambda sz, mix: seen.update(sz=sz, mix=mix)
            or "TEXT")
    monkeypatch.setattr(scopes.loader, "load_module", load)
    assert scopes.unit_text("patch_ms.img") == "TEXT"
    assert callable(seen["module"].compiled_text)
    assert seen["sz"]["name"] == "resnet18-imagenet"
    assert (seen["mix"]["batch"], seen["mix"]["hw"]) == (128, 56)
