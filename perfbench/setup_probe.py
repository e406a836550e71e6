#!/usr/bin/env python3
"""Set-up by phase, and compiles inside the window (see PERF.md).

    python perfbench/setup_probe.py --workload resnet18-imagenet.eval \
        --seed 7 --seconds 30

One run as ``run.py --trace 0`` makes it, with ``repro.obs.CompileWatch``
armed before anything is compiled. The config module's build steps are timed
where the harness calls them, each closed with ``block_until_ready`` so
that asynchronous work lands in its own phase:

- ``start``: process start to the build (imports, the look for a chip,
  the compile cache);
- ``weights_calibration``: the reference's weights and its calibration
  pass;
- ``program_trees``: the program's parameter trees and their structure
  check;
- ``model_artifact``: the pack;
- ``warmup``: the images and the first forward.

Logs one line per phase (wall seconds, compiles, compile seconds, cache
hits and misses) and prints one JSON object: the phases, ``setup_s`` as
the harness counts it, their sum, the compiles inside the window, and
the run's result line. The benchmark's own runs never time phases.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

# first: run.T_START, where the harness starts its set-up clock
from perfbench import loader, run, traffic  # noqa: E402
from perfbench.peaks import peaks_for  # noqa: E402
from repro.obs import CompileWatch, MetricsRegistry  # noqa: E402
from repro.obs import names  # noqa: E402


class Phases:
    """Contiguous phases from the harness's process start: each ends
    where the next begins."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self.t, self.c = run.T_START, dict.fromkeys(self.counters(), 0)
        self.out = {}

    def counters(self) -> dict:
        reg = self.registry
        return {"compiles": reg.counter(names.JIT_COMPILES).value,
                "compile_s": reg.histogram(names.JIT_COMPILE_SECONDS).sum,
                "cache_hits": reg.counter(names.JIT_CACHE_HITS).value,
                "cache_misses": reg.counter(names.JIT_CACHE_MISSES).value}

    def close(self, name: str) -> None:
        now, c = time.perf_counter(), self.counters()
        self.out[name] = {"s": now - self.t,
                          **{k: c[k] - self.c[k] for k in c}}
        run.log(f"phase {name}: " + ", ".join(
            f"{k} {v:.6g}" for k, v in self.out[name].items()))
        self.t, self.c = now, c


def timed(mod, phases: Phases):
    """The config module with its build steps closing phases."""
    import jax
    import repro.api as api
    real_weights = mod.weights_and_scales

    def weights_and_scales(*a):
        out = real_weights(*a)
        jax.block_until_ready(out)
        phases.close("weights_calibration")
        return out
    mod.weights_and_scales = weights_and_scales
    real_artifact = api.model_artifact

    def artifact(params, *a, **kw):
        jax.block_until_ready(params)
        phases.close("program_trees")
        art = real_artifact(params, *a, **kw)
        jax.block_until_ready(art.params)
        phases.close("model_artifact")
        return art
    api.model_artifact = artifact
    real_build = mod.build

    def build(*a):
        phases.close("start")
        cell = real_build(*a)
        phases.close("warmup")
        window = {}
        real_unit, real_release = cell.unit, cell.release

        def unit(i):
            if i == 0:
                window["from"] = phases.counters()
            return real_unit(i)

        def release():
            window["to"] = phases.counters()
            real_release()
        cell.unit, cell.release = unit, release
        phases.window = window
        return cell
    mod.build = build
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    registry = MetricsRegistry()
    watch = CompileWatch(registry)
    try:
        bench = loader.benchmark()
        wl = loader.workload(bench, args.workload)
        dev = run.device_info()
        run.require_chip(dev, wl["chips"])
        from repro.launch.compile_cache import enable_compile_cache
        run.log(f"compile cache: {enable_compile_cache()}")
        phases = Phases(registry)
        mod = timed(loader.config_module(wl["config"]), phases)
        result = run.run_cell(
            bench, wl, loader.sizes(bench, wl["config"]),
            traffic.load_mix(wl["traffic"]), loader.limits(wl["name"]), mod,
            seed=args.seed, seconds=args.seconds, traced=False,
            peaks=peaks_for(dev["kind"]), dev=dev)
    finally:
        watch.close()
    w = phases.window
    in_window = {k: w["to"][k] - w["from"][k] for k in w["to"]}
    run.log(f"compiles inside the window: {in_window}")
    setup = result["metrics"]["setup_s"]["value"]
    print(json.dumps({"workload": wl["name"], "seed": args.seed,
                      "phases": phases.out, "setup_s": setup,
                      "phases_s": sum(p["s"] for p in phases.out.values()),
                      "in_window": in_window, "result": result}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
