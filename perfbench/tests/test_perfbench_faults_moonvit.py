"""kimi-vl-a3b-moonvit at small widths on the CPU: a sound run is
correct, the control (the reference in bfloat16, in the program's place)
is not, and neither is a run whose attention spans the images of a pack
or whose 2-D RoPE swaps rows and columns. Then the cell's counts at the
published sizes, and the ``attn_ms.vit`` reduction over a synthetic
trace."""
import types

import pytest

from perfbench import loader, scopes, trace, traffic
from repro.models import vit

import _tiny

CELL = "kimi-vl-a3b-moonvit.native-res"
LIMIT = loader.limits(CELL)
MOD = loader.config_module("kimi-vl-a3b-moonvit")
ATTN_MS = loader.metric_reader("attn_ms.vit")


def tiny():
    sz = loader.sizes(_tiny.BENCH, "kimi-vl-a3b-moonvit")
    v = dict(sz["vision_config"], hidden_size=64, num_attention_heads=4,
             intermediate_size=128, num_hidden_layers=2, patch_size=4,
             init_pos_emb_height=8, init_pos_emb_width=8)
    sz = dict(sz, vision_config=v,
              text_config=dict(sz["text_config"], hidden_size=32),
              calibration=dict(sz["calibration"], image=[16, 16]))
    mix = dict(traffic.load_mix("native-res"), distinct_packs=2, images=[
        {"height": 24, "width": 16, "count": 1},
        {"height": 16, "width": 16, "count": 2}])
    return sz, mix


@pytest.fixture(scope="module")
def sound():
    return _tiny.run_tiny(CELL, *tiny())


def test_sound_run_is_correct(sound):
    res, _ = sound
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] % 3 == 0
    assert set(res["checks"]) == set(LIMIT["limits"]) | {"failed"}


def test_control_is_not_correct(sound):
    _, cell = sound
    got = cell.check(LIMIT, controls=(LIMIT["control"],))[LIMIT["control"]]
    assert any(got[n] > limit for n, limit in LIMIT["limits"].items()), got


def _one_run(grids):
    """Every image of the pack in one attention run: a mask-free
    attention over the whole pack."""
    n = sum(h * w for h, w in grids)
    return [(0, 1, n)]


def _rows_for_cols(rope_2d):
    def swapped(x, rows, cols, theta):
        return rope_2d(x, cols, rows, theta)
    return swapped


@pytest.mark.parametrize("name,fault", [("_runs", _one_run),
                                        ("rope_2d", None)],
                         ids=["attention_across_images", "rope_axes_swapped"])
def test_a_broken_program_is_not_correct(monkeypatch, name, fault):
    if fault is None:
        fault = _rows_for_cols(vit.rope_2d)
    monkeypatch.setattr(vit, name, fault)
    res, _ = _tiny.run_tiny(CELL, *tiny())
    assert res["correct"] is False, res["checks"]


def test_unit_counts_at_the_published_sizes():
    bench = loader.benchmark()
    sz = loader.sizes(bench, "kimi-vl-a3b-moonvit")
    mix = traffic.load_mix("native-res")
    n, merged = MOD.tokens(sz, mix)
    assert (sum(n), sum(merged), len(n)) == (16384, 4096, 7)
    calls = MOD.unit_calls(sz, mix)
    assert [(c.m, c.k, c.n, c.k_tiles, c.count) for c in calls] == [
        (16384, 1152, 3456, 9, 27), (16384, 1152, 1152, 9, 27),
        (16384, 1152, 4304, 9, 27), (16384, 4304, 1152, 34, 27),
        (4096, 4608, 4608, 36, 1), (4096, 4608, 2048, 36, 1)]
    pairs = 6144 ** 2 + 2 * 3072 ** 2 + 4 * 1024 ** 2
    assert pairs == 60817408
    assert MOD.unit_flops(sz, mix) == pytest.approx(
        2 * 16384 * 1152 * (3456 + 1152 + 2 * 4304) * 27
        + 4 * pairs * 1152 * 27 + 2 * 4096 * 4608 * (4608 + 2048)
        + 2 * 16384 * 588 * 1152)
    assert MOD.unit_work(sz, mix) == {"img_s": 7}


def test_config_is_the_programs():
    """The benchmark's JSON and ``repro.configs.kimi_vl_a3b_moonvit``
    describe the same tower."""
    from repro.configs.kimi_vl_a3b_moonvit import config
    sz = loader.sizes(loader.benchmark(), "kimi-vl-a3b-moonvit")
    assert MOD.model_config(sz) == config().replace(cim=MOD.cim_config(sz))


# A compiled module (abridged): the scan over the blocks, whose body holds
# a fusion under the attention scope, a kernel, and the loop over key
# chunks, whose body holds one more attention op.
TEXT = """HloModule jit_forward, is_scheduled=true

%inner_body (p: (u32[], f32[8])) -> (u32[], f32[8]) {
  %p = (u32[], f32[8]) parameter(0)
  ROOT %exp_fusion = f32[8]{0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(f)/while/body/vit.block/vit.attn/while/body/exp"}
}

%outer_body (q: (u32[], f32[8])) -> (u32[], f32[8]) {
  %q = (u32[], f32[8]) parameter(0)
  %rope_fusion = f32[8]{0} fusion(%q), kind=kLoop, calls=%f2, metadata={op_name="jit(f)/while/body/vit.block/vit.attn/mul"}
  %while.2 = (u32[], f32[8]) while(%rope_fusion), condition=%c2, body=%inner_body, metadata={op_name="jit(f)/while/body/vit.block/vit.attn/while"}
  ROOT %cim_matmul.1 = f32[8]{0} custom-call(%while.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/vit.block/cim.kernel/cim_matmul/pallas_call"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %embed = f32[8]{0} fusion(%x), kind=kLoop, calls=%f3, metadata={op_name="jit(f)/vit.patch_embed/dot_general"}
  ROOT %while.1 = (u32[], f32[8]) while(%embed), condition=%c1, body=%outer_body, metadata={op_name="jit(f)/while"}
}
"""


def test_attn_ms_reads_leaf_ops(monkeypatch):
    ops = {"embed": 1e-3, "while.1": 20e-3, "rope_fusion": 2e-3,
           "while.2": 9e-3, "exp_fusion": 8e-3, "cim_matmul.1": 6e-3}
    red = trace.Reduced(window_s=1.0, busy_s=0.5, devices=1, ops=ops,
                        op_counts={n: 1 for n in ops}, gaps=[],
                        pallas={"cim_matmul.1"})
    ctx = types.SimpleNamespace(rate="img_s", trace=red, units=2)
    monkeypatch.setattr(scopes, "unit_text", lambda metric: TEXT)
    att = scopes.attribute(ops, TEXT)
    assert set(ATTN_MS.leaves(att)) == {"embed", "rope_fusion",
                                        "exp_fusion", "cim_matmul.1"}
    # the rope fusion and the chunk loop's op, not the loops themselves
    assert ATTN_MS.read(ctx) == pytest.approx(1e3 * (2e-3 + 8e-3) / 2)
    assert ATTN_MS.read(types.SimpleNamespace(
        rate="tok_s", trace=red, units=2)) is None
