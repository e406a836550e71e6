"""Telemetry plane (DESIGN.md §12): metrics registry semantics, exact
percentiles, span nesting, engine lifecycle metrics, and the sampled
per-column ADC saturation counters — including the zero-overhead
contract: the deploy output with instrumentation armed is bit-exact with
the un-instrumented output, and counters match a numpy oracle."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CIMConfig, calibrate_conv, calibrate_linear, conv2d,
                       init_conv, init_linear, linear, pack_conv,
                       pack_linear)
from repro.obs import MetricsRegistry, Tracer, adc
from repro.obs import names as M


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_snapshot_and_reset(tmp_path):
    log = tmp_path / "events.jsonl"
    reg = MetricsRegistry(event_log_path=str(log))
    reg.counter("a.count").inc()
    reg.counter("a.count").inc(4)
    reg.gauge("a.depth").set(7)
    reg.histogram("a.lat").observe(1.0)
    reg.histogram("a.lat").observe(3.0)
    reg.log_event("thing", rid=1)

    snap = reg.snapshot()
    assert snap["counters"]["a.count"] == 5
    assert snap["gauges"]["a.depth"] == 7.0
    h = snap["histograms"]["a.lat"]
    assert h["count"] == 2 and h["sum"] == 4.0 and h["p50"] == 2.0
    assert json.dumps(snap)                      # JSON-safe by contract
    assert len(reg.events("thing")) == 1

    # counter/gauge/histogram objects handed out before reset keep
    # working; everything restarts from zero
    c = reg.counter("a.count")
    reg.reset()
    snap = reg.snapshot()
    assert snap["counters"]["a.count"] == 0
    assert snap["gauges"]["a.depth"] == 0.0
    assert snap["histograms"]["a.lat"] == {"count": 0, "sum": 0.0}
    assert reg.events() == []
    c.inc()
    assert reg.snapshot()["counters"]["a.count"] == 1

    # the JSONL file is append-only and survives the reset
    lines = [json.loads(s) for s in log.read_text().splitlines()]
    assert [e["kind"] for e in lines] == ["thing"]
    assert lines[0]["rid"] == 1 and "ts" in lines[0]


def test_histogram_percentiles_match_numpy():
    rng = np.random.RandomState(0)
    vals = rng.lognormal(size=500)
    reg = MetricsRegistry()
    h = reg.histogram("x")
    for v in vals:
        h.observe(v)
    for q in (0, 25, 50, 90, 99, 100):
        assert h.percentile(q) == pytest.approx(
            np.percentile(vals, q), rel=1e-12)
    s = h.summary()
    assert s["count"] == 500
    assert s["mean"] == pytest.approx(vals.mean())
    assert s["min"] == vals.min() and s["max"] == vals.max()


def test_histogram_cap_decimates_but_keeps_exact_count_sum():
    reg = MetricsRegistry()
    h = reg.histogram("x", max_samples=64)
    n = 1000
    for v in range(n):
        h.observe(float(v))
    assert h.count == n
    assert h.sum == float(n * (n - 1) // 2)
    assert h.min == 0.0 and h.max == float(n - 1)
    # decimated percentiles stay in range and ordered
    p50, p99 = h.percentile(50), h.percentile(99)
    assert 0.0 <= p50 <= p99 <= float(n - 1)
    assert len(h._values) < 2 * 64


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("serve.tokens.generated").inc(3)
    reg.gauge("serve.queue.depth").set(2)
    for v in (1.0, 2.0, 3.0):
        reg.histogram("serve.request.latency.seconds").observe(v)
    text = reg.to_prometheus()
    assert "# TYPE serve_tokens_generated counter" in text
    assert "serve_tokens_generated 3" in text
    assert "serve_queue_depth 2.0" in text
    assert 'serve_request_latency_seconds{quantile="0.5"} 2.0' in text
    assert "serve_request_latency_seconds_count 3" in text
    assert "." not in text.split("serve_tokens_generated")[1].split()[0]


def test_span_nesting_and_histogram():
    reg = MetricsRegistry()
    tr = Tracer(reg)
    with tr.span("outer", rid=1):
        with tr.span("inner"):
            pass
    assert [s.name for s in tr.spans] == ["inner", "outer"]
    inner, outer = tr.spans
    assert inner.parent == "outer" and outer.parent is None
    assert outer.duration >= inner.duration >= 0.0
    assert reg.histogram("outer.seconds").count == 1
    assert reg.histogram("inner.seconds").count == 1
    assert outer.attrs == {"rid": 1} and inner.attrs == {}


def test_span_opens_trace_annotation(monkeypatch):
    """Each span opens a profiler TraceAnnotation of its own name, so it
    lands in a device trace's host plane, nested as the spans are."""
    import repro.obs.tracing as tracing
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "TraceAnnotation", Annotation)
    tr = Tracer(MetricsRegistry())
    with tr.span("serve.decode.step"):
        with tr.span("inner"):
            pass
    assert seen == [("enter", "serve.decode.step"), ("enter", "inner"),
                    ("exit", "inner"), ("exit", "serve.decode.step")]


def test_compile_watch_counts_one_compile_per_new_program():
    """A fresh jit compile adds exactly one to ``jit.compiles`` and one
    observation to ``jit.compile.seconds``; calling it again adds none;
    after ``close()`` nothing is counted."""
    from repro.obs import CompileWatch
    reg = MetricsRegistry()
    x = jnp.arange(7.0)
    f = jax.jit(lambda v: v * 3.0 - 1.0)
    watch = CompileWatch(reg)
    try:
        f(x).block_until_ready()
        assert reg.counter(M.JIT_COMPILES).value == 1
        assert reg.histogram(M.JIT_COMPILE_SECONDS).count == 1
        f(x).block_until_ready()
        assert reg.counter(M.JIT_COMPILES).value == 1
    finally:
        watch.close()
    watch.close()                                 # a second close is a no-op
    jax.jit(lambda v: v + 2.0)(x).block_until_ready()
    assert reg.counter(M.JIT_COMPILES).value == 1


def test_compile_watch_counts_cim_grid_steps():
    """Tracing a CIM kernel adds its grid size to ``cim.grid.steps``:
    (M/bm, N/bn, S, k_tiles/tk) as ``block_shape`` picks it. A second
    call with the same shapes reuses the trace and adds nothing, and
    after ``close()`` nothing is counted."""
    from repro.kernels.cim_matmul import block_shape, cim_matmul_pallas
    from repro.obs import CompileWatch
    reg = MetricsRegistry()
    m, kt, rows, n = 300, 3, 24, 136       # shapes no other test traces
    a = jnp.ones((m, kt, rows))
    d = jnp.ones((2, kt, rows, n), jnp.int8)
    sc = jnp.ones((2, kt, n))
    watch = CompileWatch(reg)
    try:
        cim_matmul_pallas(a, d, sc, sc, psum_bits=4, interpret=True)
        bm, bn, tk = block_shape(m, n, kt, rows, rows, a.dtype, d.dtype,
                                 n_cols=2)
        steps = -(-m // bm) * -(-n // bn) * 2 * -(-kt // tk)
        assert reg.counter(M.CIM_GRID_STEPS).value == steps == 2
        cim_matmul_pallas(a, d, sc, sc, psum_bits=4, interpret=True)
        assert reg.counter(M.CIM_GRID_STEPS).value == steps
    finally:
        watch.close()
    cim_matmul_pallas(a[:200], d, sc, sc, psum_bits=4, interpret=True)
    assert reg.counter(M.CIM_GRID_STEPS).value == steps


def test_compile_watch_counts_cim_patch_bytes():
    """Tracing a CIM conv adds the bytes of its patch operand to
    ``cim.patches.bytes``: k_tiles * M * rows for int8 codes, four times
    that for float32 codes. A second call with the same shapes reuses
    the trace and adds nothing."""
    from repro.kernels.cim_conv import cim_conv_pallas
    from repro.obs import CompileWatch
    reg = MetricsRegistry()
    kt, cpa, n = 3, 5, 8                   # shapes no other test traces
    rows, m = 9 * cpa, 2 * 4 * 6           # 3x3 stride 2 over 7x11
    d = jnp.ones((2, kt, rows, n), jnp.int8)
    sc = jnp.ones((2, kt, n))
    geo = dict(kh=3, kw=3, stride=2, padding="SAME", c_per_array=cpa,
               psum_bits=4, interpret=True)
    watch = CompileWatch(reg)
    try:
        a = jnp.ones((2, 7, 11, 13), jnp.int8)
        cim_conv_pallas(a, d, sc, sc, **geo)
        assert reg.counter(M.CIM_PATCHES_BYTES).value == kt * m * rows
        cim_conv_pallas(a, d, sc, sc, **geo)
        assert reg.counter(M.CIM_PATCHES_BYTES).value == kt * m * rows
        cim_conv_pallas(a.astype(jnp.float32), d, sc, sc, **geo)
        assert reg.counter(M.CIM_PATCHES_BYTES).value == 5 * kt * m * rows
    finally:
        watch.close()


# ---------------------------------------------------------------------------
# ADC saturation collector
# ---------------------------------------------------------------------------

def _lin_setup(psum_bits=4, seed=0, k=70, n=24, b=8):
    cfg = CIMConfig(enabled=True, mode="emulate", weight_bits=4, cell_bits=2,
                    act_bits=6, psum_bits=psum_bits, array_rows=32,
                    array_cols=32)
    p = init_linear(jax.random.PRNGKey(seed), k, n, cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, k)) * 0.5
    return calibrate_linear(x, p, cfg), x, cfg


def test_saturation_stats_match_numpy_oracle():
    rng = np.random.RandomState(1)
    psum = rng.randint(-40, 40, size=(6, 2, 3, 10)).astype(np.float32)
    s_p = rng.uniform(0.5, 2.0, size=(2, 3, 10)).astype(np.float32)
    bits = 4
    sat, occ = adc.saturation_stats(jnp.asarray(psum), jnp.asarray(s_p), bits)
    q = np.round(np.round(psum) / s_p)
    qn, qp = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    exp_sat = ((q < qn) | (q > qp)).sum(axis=(0, 1, 2))
    assert np.array_equal(np.asarray(sat), exp_sat)
    exp_occ = (np.abs(np.clip(q, qn, qp)) / qp).mean(axis=(0, 1, 2))
    np.testing.assert_allclose(np.asarray(occ), exp_occ, rtol=1e-6)
    # sign ADC never clips
    sat1, occ1 = adc.saturation_stats(jnp.asarray(psum), jnp.asarray(s_p), 1)
    assert int(np.asarray(sat1).sum()) == 0
    assert np.all(np.asarray(occ1) == 1.0)


def test_emulate_counters_exact():
    """emulate materializes every psum, so armed counters are exact:
    conversions == B * n_split * k_tiles * N."""
    p, x, cfg = _lin_setup(psum_bits=3)   # narrow ADC: some clipping
    with adc.sampled() as reg:
        linear(x, p, cfg)
        adc.sync()
        s = adc.summary()
    assert s["conversions"] == 8 * 2 * 3 * 24   # b, S, k_tiles(70/32), n
    assert 0 <= s["saturated"] <= s["conversions"]
    assert reg.counter(M.ADC_CONVERSIONS).value == s["conversions"]
    assert reg.histogram(M.ADC_COL_SATURATION_RATE).count == 24


@pytest.mark.parametrize("pack_dtype", ["int8", "int4"])
def test_deploy_bit_exact_with_instrumentation(pack_dtype):
    """The zero-overhead contract (ISSUE acceptance): deploy output with
    the collector armed is bit-exact with instrumentation absent, and
    disarming restores the un-instrumented trace."""
    p, x, cfg = _lin_setup()
    dcfg = cfg.replace(mode="deploy", pack_dtype=pack_dtype)
    packed = pack_linear(p, dcfg)

    y_off = np.asarray(linear(x, packed, dcfg))
    with adc.sampled() as reg:
        y_on = np.asarray(linear(x, packed, dcfg))
        adc.sync()
        s = adc.summary()
    y_after = np.asarray(linear(x, packed, dcfg))

    assert np.array_equal(y_off, y_on)
    assert np.array_equal(y_off, y_after)
    assert s["conversions"] == 8 * 2 * 3 * 24
    # deploy counters agree with the emulate (materialized-psum) oracle
    with adc.sampled():
        linear(x, p, cfg)
        adc.sync()
        assert adc.summary()["saturated"] == s["saturated"]


def test_conv_deploy_bit_exact_with_instrumentation():
    cfg = CIMConfig(enabled=True, mode="emulate", weight_bits=4, cell_bits=2,
                    act_bits=6, psum_bits=4, array_rows=32, array_cols=32)
    p = init_conv(jax.random.PRNGKey(2), 3, 3, 8, 16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 8)) * 0.5
    p = calibrate_conv(x, p, cfg)
    dcfg = cfg.replace(mode="deploy")
    packed = pack_conv(p, dcfg)

    y_off = np.asarray(conv2d(x, packed, dcfg))
    with adc.sampled():
        y_on = np.asarray(conv2d(x, packed, dcfg))
        adc.sync()
        s = adc.summary()
    assert np.array_equal(y_off, y_on)
    assert s["conversions"] == 2 * 8 * 8 * 2 * 3 * 16  # b,ho,wo,S,kt,co
    # emulate agrees
    with adc.sampled():
        conv2d(x, p, cfg)
        adc.sync()
        assert adc.summary()["saturated"] == s["saturated"]


def test_every_n_decimates_folding():
    p, x, cfg = _lin_setup()
    with adc.sampled(every_n=3):
        for _ in range(7):
            linear(x, p, cfg)
        adc.sync()
        s = adc.summary()
    assert s["kernel_invocations"] == 7
    assert s["samples_folded"] == 3                    # calls 1, 4, 7
    assert s["conversions"] == 3 * 8 * 2 * 3 * 24


def test_disable_stops_stale_armed_trace():
    """A function traced while armed stops folding the moment the
    collector disarms (host-side check in the callback)."""
    p, x, cfg = _lin_setup()
    fwd = jax.jit(lambda xx: linear(xx, p, cfg))
    adc.enable()
    try:
        fwd(x)
        adc.sync()
        before = adc.totals()
        assert before[1] > 0
    finally:
        adc.disable()
    fwd(x)                                   # stale armed trace
    adc.sync()
    assert adc.totals() == before
    adc.reset()


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=4")
def test_sharded_deploy_counters_and_bit_exactness():
    """Armed counters on the column-sharded dispatch match the
    single-device counts (the side-output einsums the full pre-shard
    planes), and the sharded output stays bit-exact."""
    from repro.nn.module import session_mesh
    p, x, cfg = _lin_setup()
    dcfg = cfg.replace(mode="deploy", use_kernel=False)
    packed = pack_linear(p, dcfg)
    y1 = np.asarray(linear(x, packed, dcfg))
    with adc.sampled():
        linear(x, packed, dcfg)
        adc.sync()
        single = adc.summary()
    mesh = jax.make_mesh((4,), ("model",))
    with session_mesh(mesh):
        with adc.sampled():
            y4 = np.asarray(linear(x, packed, dcfg))
            adc.sync()
            sharded = adc.summary()
    assert np.array_equal(y1, y4)
    assert sharded["conversions"] == single["conversions"]
    assert sharded["saturated"] == single["saturated"]


# ---------------------------------------------------------------------------
# engine lifecycle metrics
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_setup():
    from repro.configs.registry import get_config
    from repro.models.registry import get_model
    from repro.nn import init_params
    cfg = get_config("qwen3-0.6b", reduced=True).replace(
        compute_dtype="float32", remat=False)
    model = get_model(cfg)
    params = init_params(model.specs(cfg), jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_metrics_scripted_requests(lm_setup):
    from repro.serve.engine import ServingEngine
    cfg, model, params = lm_setup
    eng = ServingEngine(model, cfg, params, batch_size=2, max_len=64)
    eng.submit([3, 5, 7], max_new_tokens=4)
    eng.submit([11, 13], max_new_tokens=2)
    eng.submit([2], max_new_tokens=3)
    done = 0
    for _ in range(30):
        done += len(eng.step())
        if done == 3:
            break
    assert done == 3

    m = eng.metrics()
    h = m["health"]
    assert h["submitted"] == 3 and h["retired"] == 3
    assert h["queue_depth"] == 0 and h["active_slots"] == 0
    assert h["slots"] == 2

    snap = m["metrics"]
    assert snap["counters"][M.REQUESTS_SUBMITTED] == 3
    assert snap["counters"][M.REQUESTS_COMPLETED] == 3
    assert snap["counters"][M.TOKENS_GENERATED] >= 4 + 2 + 3
    assert snap["histograms"][M.REQUEST_LATENCY_SECONDS]["count"] == 3
    assert snap["histograms"][M.QUEUE_WAIT_SECONDS]["count"] == 3
    assert snap["histograms"][M.PREFILL_SECONDS]["count"] == 3
    assert snap["histograms"][M.DECODE_STEP_SECONDS]["count"] >= 4
    assert m["throughput"]["tokens_generated"] == snap["counters"][
        M.TOKENS_GENERATED]
    assert m["throughput"]["decode_steps"] >= 4
    assert snap["counters"][M.JIT_COMPILES] >= 1  # the engine's own steps
    assert m["saturation"] is None               # collector not armed

    evs = eng.registry.events("request_completed")
    assert sorted(e["rid"] for e in evs) == [0, 1, 2]
    assert {e["tokens"] for e in evs} == {4, 2, 3}
    assert json.dumps(m["metrics"])              # JSON-safe end to end
