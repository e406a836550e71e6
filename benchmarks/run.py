"""Benchmark driver: one function per paper table/figure + the framework's
own kernel/LM benches. Prints ``name,...`` CSV lines (tee'd by the final
deliverable run).

  PYTHONPATH=src python -m benchmarks.run [--steps N] [--fast]
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60,
                    help="QAT steps per scheme (paper uses 200 epochs; this"
                         " is the scaled-down CPU setting)")
    ap.add_argument("--fast", action="store_true",
                    help="minimal QAT steps")
    ap.add_argument("--smoke", action="store_true",
                    help="analytic + kernel benches only (CI smoke; skips "
                         "the QAT/LM training benches, which take tens of "
                         "minutes on CPU)")
    args = ap.parse_args(argv)
    steps = 30 if args.fast else args.steps

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (bench_backend_frontier, bench_conv_kernel,
                   bench_dequant_overhead, bench_drift_recal,
                   bench_granularity, bench_hw_cost, bench_kernel,
                   bench_lm_cim, bench_psum_range, bench_qat_stages,
                   bench_serve_load, bench_serve_sharded, bench_variation)

    csv = []
    t0 = time.time()
    bench_dequant_overhead.run(csv=csv)            # Fig. 8 (analytic)
    bench_psum_range.run(csv=csv)                  # Fig. 6
    bench_hw_cost.run(csv=csv)                     # analytic HW cost model
    bench_kernel.run(csv=csv)                      # kernel microbench
    bench_conv_kernel.run(csv=csv)                 # fused conv deploy bench
    bench_serve_sharded.run(csv=csv)               # column-parallel serving
    # load generator at tiny scale — the checked-in JSON artifact comes
    # from the module entry point, never from this tier
    bench_serve_load.run(csv=csv, concurrency=(2, 4, 8), batch=2,
                         prompt_len=2, new_tokens=2)
    # hardware-style frontier at tiny scale — the checked-in JSON comes
    # from the module entry point, never from this tier (no JSON churn)
    bench_backend_frontier.run(csv=csv, smoke=True)
    if not args.smoke:
        bench_granularity.run(steps=steps, csv=csv)   # Fig. 7 / Table III
        bench_qat_stages.run(steps=steps, csv=csv)    # Fig. 9
        bench_variation.run(steps=steps, csv=csv)     # Fig. 10 (MC deploy)
        bench_drift_recal.run(steps=steps, csv=csv)   # self-healing serving
        bench_lm_cim.run(steps=max(20, steps // 3), csv=csv)  # LM (beyond paper)

    print(f"\n== CSV summary ({time.time() - t0:.0f}s total) ==")
    for line in csv:
        print(line)


if __name__ == "__main__":
    main()
