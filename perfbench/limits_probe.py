#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from (see PERF.md).

    python perfbench/limits_probe.py --workload resnet18-imagenet.eval \
        --seeds 11,12,13 --seconds 1 --controls bfloat16

For each seed, in one process: build the cell, run its window for
``--seconds`` as a benchmark run does, free the program, and compare a
seeded sample of what it produced with the reference, and with the
reference computed in each control precision in the program's place.
One JSON line per seed. The benchmark's own runs never run the controls.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import jax

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

from perfbench import loader, run, traffic  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="")
    args = ap.parse_args(argv)
    bench = loader.benchmark()
    wl = loader.workload(bench, args.workload)
    dev = run.device_info()
    run.require_chip(dev, wl["chips"])
    enable_compile_cache()
    sz = loader.sizes(bench, wl["config"])
    mix = traffic.load_mix(wl["traffic"])
    lim = loader.limits(wl["name"])
    mod = loader.config_module(wl["config"])
    controls = tuple(c for c in args.controls.split(",") if c)
    with jax.default_matmul_precision(sz.get("matmul_precision")):
        for seed in (int(s) for s in args.seeds.split(",")):
            probe(sz, mix, lim, mod, wl, seed, args.seconds, controls)
    return 0


def probe(sz, mix, lim, mod, wl, seed, seconds, controls):
    """One seed: build, run the window, free the program, compare."""
    t0 = time.perf_counter()
    cell = mod.build(sz, mix, seed, run.log)
    t1 = time.perf_counter()
    units = 0
    while time.perf_counter() - t1 < seconds or units == 0:
        cell.unit(units)
        units += 1
    cell.release()
    gc.collect()
    out = cell.check(lim, controls)
    print(json.dumps({"workload": wl["name"], "seed": seed, "units": units,
                      "failed": cell.failed, "build_s": t1 - t0,
                      "total_s": time.perf_counter() - t0, **out}),
          flush=True)


if __name__ == "__main__":
    sys.exit(main())
