#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this process holds.

    python perfbench/run.py --workload resnet18-imagenet.eval --seed 7 \
        --seconds 30 --trace 0

In order: check that JAX sees a TPU listed in ``peaks.py`` (and as many
chips as the cell asks for) or exit non-zero; switch on the persistent
compilation cache; build the cell's model on the device from the seed
and warm up its shapes (all of this is ``setup_s``); run whole units of
work back to back until ``--seconds`` have passed, then finish the unit
in flight; free the program and compare a seeded sample of what the
window produced with the plain reference. The last line of standard
output is one JSON object. With ``--trace 1`` the profiler records the
first units of the window and the line carries the per-layer metrics
read from that trace instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from perfbench import loader, roofline, stats, trace, traffic  # noqa: E402
from perfbench.peaks import peaks_for  # noqa: E402

#: The traced part of a ``--trace 1`` window: whole units, at least this
#: long (the rest of the window runs untraced).
TRACE_SECONDS = 2.0
TRACE_DIR = os.path.join(ROOT, ".perfbench", "trace")


def log(msg: str) -> None:
    print(f"[perfbench] {msg}", file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chip(dev: dict, chips: int) -> None:
    if dev["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found no TPU (platform "
                         f"{dev['platform']!r}); this benchmark runs only "
                         f"on the chip")
    if dev["count"] < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                         f"sees {dev['count']}")
    peaks_for(dev["kind"])


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read (see readers.py)."""
    rate: str
    trace: object            # trace.Reduced, or None
    units: int               # units of work inside the traced window
    unit_flops: float
    least: dict              # roofline.least_time of one unit
    peaks: dict


def per_layer_metrics(bench: dict, cell_name: str, ctx: Context) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        value = loader.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(bench, wl, sz, mix, lim, mod, *, seed, seconds, traced,
             peaks, dev):
    """Everything after the look for a chip: build, window, check.
    Returns the result line as a dict."""
    import jax
    # the matmul precision the configuration states
    with jax.default_matmul_precision(sz.get("matmul_precision")):
        return _run_cell(bench, wl, sz, mix, lim, mod, seed=seed,
                         seconds=seconds, traced=traced, peaks=peaks, dev=dev)


def _run_cell(bench, wl, sz, mix, lim, mod, *, seed, seconds, traced, peaks,
              dev):
    import jax
    rate = mix["rate"]
    cell = mod.build(sz, mix, seed, log)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f}s")

    units, work, n_traced, t_trace = 0, 0.0, 0, None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans only: annotations
        opts.enable_hlo_proto = False     # and jax's own dispatch events
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        window = jax.profiler.TraceAnnotation(trace.WINDOW)
        window.__enter__()
        t_trace = time.perf_counter()
    t0 = time.perf_counter()
    while True:
        work += cell.unit(units)[rate]
        units += 1
        now = time.perf_counter()
        if t_trace is not None and (now - t_trace >= TRACE_SECONDS
                                    or now - t0 >= seconds):
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            n_traced, t_trace = units, None
        if now - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    log(f"window {elapsed:.3f}s, {units} units, {work:.0f} {rate} work")
    dev = dict(dev, memory_peak_bytes=memory_peak_bytes())
    cell.release()
    gc.collect()

    metrics = {}
    breakdown = None
    if traced:
        red = trace.load(trace.newest_xplane(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
        least = roofline.least_time(mod.unit_calls(sz, mix), sz["cim"], peaks)
        log(f"roofline of one unit: {least['ops']:.6g} ops, "
            f"{least['bytes']:.6g} bytes, least {least['seconds']:.6g}s: "
            f"{least['ops_bound_s']:.6g}s of calls bound by ops, "
            f"{least['bytes_bound_s']:.6g}s by bytes")
        log(f"traced {n_traced} units in {red.window_s:.6g}s, device busy "
            f"{red.busy_s:.6g}s")
        for name in sorted(red.pallas):
            log(f"Pallas kernel {name}: {red.op_counts[name]} events, "
                f"{red.ops[name]:.6g}s")
        ctx = Context(rate=rate, trace=red, units=n_traced,
                      unit_flops=mod.unit_flops(sz, mix), least=least,
                      peaks=peaks)
        metrics = per_layer_metrics(bench, wl["name"], ctx)
        top = sorted(red.ops.items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[n, s] for n, s in top],
                     "idle_gaps": [[n, s] for n, s in red.gaps[:10]]}
    else:
        metrics[rate] = {"value": stats.rate(work, elapsed),
                         "unit": _unit(bench, rate)}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    t_check = time.perf_counter()
    checked = cell.check(lim)
    log(f"reference check {time.perf_counter() - t_check:.2f}s: {checked}")
    checks = {n: {"value": checked["program"][n], "limit": limit}
              for n, limit in lim["limits"].items()}
    checks["failed"] = {"value": cell.failed, "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": bool(ok and units > 0),
              "attempted": int(cell.attempted), "failed": int(cell.failed),
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _unit(bench, name):
    for m in bench["end_to_end"]:
        if m["name"] == name:
            return m["unit"]
    raise KeyError(f"no end-to-end metric {name!r} in BENCHMARK.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = loader.benchmark()
    wl = loader.workload(bench, args.workload)
    dev = device_info()
    log(f"{dev}")
    require_chip(dev, wl["chips"])
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    sz = loader.sizes(bench, wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    lim = loader.limits(wl["name"])
    mod = loader.config_module(wl["config"])
    result = run_cell(bench, wl, sz, mix, lim, mod, seed=args.seed,
                      seconds=args.seconds, traced=bool(args.trace),
                      peaks=peaks_for(dev["kind"]), dev=dev)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
