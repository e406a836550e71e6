"""The reduction from a profiler trace to busy time, operations and
labelled idle gaps."""
import os
import types

import pytest

from perfbench import readers, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "cim_matmul.xplane.pb")
KERNEL = ('%cim_matmul_pallas.1 = f32[256,256]{1,0} custom-call(f32[4,256,128]'
          ' %a), custom_call_target="tpu_custom_call"')
FUSION = "%fusion.3 = f32[8]{0} fusion(f32[256,256]{1,0} %cim_matmul_pallas.1)"


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


def test_merge_and_gaps():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert trace.gaps_between([(0, 3), (5, 9)], 0, 10) == [(3, 5), (9, 10)]


def test_labels_pick_the_innermost_open_span():
    host = [("outer", 0, 100), ("inner", 10, 20), ("late", 50, 60)]
    assert trace.labels([15, 55, 30, 150], host) == [
        "inner", "late", "outer", "(no host span)"]


def test_op_name_is_the_instruction():
    assert trace.op_name(KERNEL) == "cim_matmul_pallas.1"
    assert trace.op_name(FUSION) == "fusion.3"


def test_reduce_synthetic():
    # window 0..1000 ns; device busy 100-300 and 250-400 (overlap) and
    # 900-1100 (clipped to 1000); idle 0-100, 400-900. Gaps take their
    # labels from the thread that marked the window, not from others.
    host = _plane("/host:CPU", {
        "runtime": [("Execute", 0, 1000), ("ReadSyncFlag", 600, 100)],
        "python": [(trace.WINDOW, 0, 1000), ("perfbench.forward", 0, 420),
                   ("wait", 420, 60)]})
    dev = _plane("/device:TPU:0", {
        "XLA Ops": [(KERNEL, 100, 200), (FUSION, 250, 150),
                    (KERNEL, 900, 200)],
        "XLA Modules": [("jit_forward", 100, 1000)]})
    red = trace.reduce([host, dev])
    assert red.window_s == pytest.approx(1e-6)
    assert red.busy_s == pytest.approx(400e-9)
    assert red.ops == pytest.approx({"cim_matmul_pallas.1": 300e-9,
                                     "fusion.3": 150e-9})
    assert red.op_counts == {"cim_matmul_pallas.1": 2, "fusion.3": 1}
    # the fusion reads the kernel's output but is no kernel itself
    assert red.pallas == {"cim_matmul_pallas.1"}
    gaps = dict(red.gaps)
    # 0-100 under perfbench.forward; 400-900 has its midpoint (650) under
    # no span of the harness's thread but the window's
    assert gaps == pytest.approx({"perfbench.forward": 100e-9,
                                  "(no host span)": 500e-9})


def test_reduce_needs_the_window_and_a_device():
    dev = _plane("/device:TPU:0", {"XLA Ops": [("x", 0, 10)]})
    with pytest.raises(ValueError, match="perfbench.traced"):
        trace.reduce([_plane("/host:CPU", {"main": []}), dev])
    with pytest.raises(ValueError, match="TPU"):
        trace.reduce([_plane("/host:CPU", {"main": [(trace.WINDOW, 0, 5)]})])


def test_reduce_recorded_chip_trace():
    # Recorded on one TPU v5e by make_trace_fixture.py: the fused CIM
    # matmul at (256 x 512) x (512 x 256), three calls with a 50 ms host
    # sleep after each, all inside the traced window. The device clock
    # reads about 0.9 ms behind the host's, so the first call falls just
    # before the window's start and two remain inside it.
    red = trace.load(FIXTURE)
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.154777818)
    assert red.pallas == {"cim_matmul_pallas.1"}
    assert red.op_counts["cim_matmul_pallas.1"] == 2
    assert red.ops["cim_matmul_pallas.1"] == pytest.approx(16.279e-6)
    assert readers.cim_kernel_seconds(red) == pytest.approx(16.279e-6)
    # each call: a layout fusion, two scale broadcasts, a weight copy and
    # the kernel, back to back
    assert red.busy_s == pytest.approx(19.04e-6)
    # the device idles through the host's sleeps and nowhere else
    assert [name for name, _ in red.gaps] == ["fixture.host_wait"]
    assert red.gaps[0][1] == pytest.approx(red.window_s - red.busy_s)
