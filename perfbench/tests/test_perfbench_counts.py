"""The roofline and MFU counts, against numbers worked by hand."""

import pytest

from perfbench import loader, roofline
from perfbench.peaks import PEAKS

V5E = PEAKS["TPU v5 lite"]
CIM = {"weight_bits": 4, "cell_bits": 2, "act_bits": 8, "psum_bits": 6,
       "array_rows": 128, "array_cols": 128}


def test_resnet_conv_call():
    # s1b0.conv1: 128 x 56 x 56 x 64 -> 28 x 28 x 128, 3x3 stride 2;
    # 14 channels with their 9 taps per 128-row array: 5 tiles
    c = roofline.conv_call(128, 56, 56, 64, 128, 3, 3, 2, CIM, out_bytes=4)
    assert (c.m, c.k, c.n, c.k_tiles) == (100_352, 576, 128, 5)
    assert c.ops(CIM) == 29_595_009_024
    # input feature map read once, not the 9x patch expansion
    assert c.bytes(CIM) == 36_864 + 25_690_112 + 51_380_224 + 10_240
    t, bound = c.least_seconds(CIM, V5E)
    assert bound == "bytes" and t == pytest.approx(77_117_440 / 819e9)


def _bench_cell(name):
    bench = loader.benchmark()
    wl = loader.workload(bench, name)
    from perfbench import traffic
    return (loader.sizes(bench, wl["config"]),
            traffic.load_mix(wl["traffic"]),
            loader.config_module(wl["config"]))


def test_resnet_unit_flops():
    sz, mix, mod = _bench_cell("resnet18-imagenet.eval")
    calls = mod.unit_calls(sz, mix)
    assert len(calls) == 19              # 16 block convs + 3 projections
    # the published ResNet-18 body per image, in MACs: stage 0 four
    # 64->64 3x3 convs at 56x56; stages 1-3 each a strided 3x3, three
    # 3x3 and a strided 1x1 projection, 57,802,752 + 3 x 115,605,504 +
    # 6,422,528 MACs
    macs = 4 * 56 * 56 * 576 * 64 + 3 * (57_802_752 + 3 * 115_605_504
                                         + 6_422_528)
    assert macs == 1_695_547_392
    per_image = sum(2 * (c.m // 128) * c.k * c.n for c in calls)
    assert per_image == 2 * macs
    stem = 2 * 56 * 56 * 27 * 64
    fc = 2 * 512 * 1000
    assert mod.unit_flops(sz, mix) == 128 * (per_image + stem + fc)


def test_mfu_and_roofline_readers():
    from perfbench import readers, run, trace
    red = trace.Reduced(window_s=2.0, busy_s=1.5, devices=1,
                        ops={"fusion.1": 0.5, "cim_conv_pallas.2": 1.0},
                        op_counts={}, gaps=[], pallas={"cim_conv_pallas.2"})
    ctx = run.Context(rate="img_s", trace=red, units=4, unit_flops=1e12,
                      least={"seconds": 0.01}, peaks=V5E)
    assert readers.idle_share(ctx, "img_s") == pytest.approx(25.0)
    assert readers.mfu(ctx, "img_s") == pytest.approx(
        100 * 4e12 / 2.0 / 393e12)
    assert readers.cim_roofline(ctx, "img_s") == pytest.approx(4.0)
    # another cell's rate: nothing to read
    assert readers.mfu(ctx, "decode_tok_s") is None
    red.pallas = set()
    assert readers.cim_roofline(ctx, "img_s") is None
