"""Serving driver: batched generation with the slot engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --batch 4 --prompt-len 16 --new-tokens 32 [--cim deploy]

Column-parallel serving (DESIGN.md §10): ``--mesh N`` shards every packed
layer's digit planes over an N-device ``("model",)`` mesh — one kernel
shard per device, bit-exact with ``--mesh 1``. On a CPU host, emulate the
devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
      --reduced --cim deploy --mesh 4

``--artifact PATH`` serves a saved ``DeployArtifact`` instead of packing
fresh random-init weights; with ``--mesh`` the planes are placed
shard-by-shard as they come off disk.

Self-healing serving (DESIGN.md §11): ``--drift-col-rate`` /
``--drift-cell-rate`` / ``--drift-read-sigma`` serve a drifting chip
(one keyed realization per decode step, clocked from ``--drift-t0``),
``--health`` arms the ``DriftMonitor``, and ``--auto-recal`` closes the
loop — past the hard threshold the engine re-fits the per-column scales
in place instead of degrading to the digital fallback.

Telemetry (DESIGN.md §12): ``--metrics-out PATH`` dumps the engine's
folded ``metrics()`` view (health + throughput + registry snapshot, and
ADC saturation when ``--adc-sample`` arms the collector) as JSON after
generation; ``--report-every N`` prints a one-line operator report to
stderr every N decode steps.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--cim", default="off",
                    choices=["off", "emulate", "deploy"])
    ap.add_argument("--mesh", type=int, default=1,
                    help="devices along the 'model' axis: column-shard "
                         "packed digit planes (deploy/artifact serving "
                         "only; DESIGN.md §10)")
    ap.add_argument("--artifact", default=None,
                    help="path to a packed model DeployArtifact to serve "
                         "(implies the artifact's pinned deploy backend)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--drift-col-rate", type=float, default=0.0,
                    help="per-request column-gain drift rate "
                         "(core.variation.DriftSchedule.col_rate)")
    ap.add_argument("--drift-cell-rate", type=float, default=0.0,
                    help="per-request per-cell drift rate")
    ap.add_argument("--drift-read-sigma", type=float, default=0.0,
                    help="static read-noise sigma (re-drawn every step)")
    ap.add_argument("--drift-t0", type=int, default=0,
                    help="initial request count on the drift clock")
    ap.add_argument("--health", action="store_true",
                    help="arm the DriftMonitor and print the engine "
                         "health() snapshot after generation")
    ap.add_argument("--auto-recal", action="store_true",
                    help="recalibrate column scales automatically on "
                         "hard drift instead of serving the fallback")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write engine.metrics() (health + throughput + "
                         "metric snapshot) as JSON after generation")
    ap.add_argument("--report-every", type=int, default=0, metavar="N",
                    help="print a one-line metrics report to stderr every "
                         "N decode steps (0 = off)")
    ap.add_argument("--adc-sample", type=int, default=0, metavar="N",
                    help="arm the per-column ADC saturation collector, "
                         "folding every Nth kernel invocation (0 = off; "
                         "DESIGN.md §12)")
    return ap.parse_args(argv)


def build_engine(args: argparse.Namespace):
    """The engine ``main`` serves from, and the model config it serves:
    with ``--cim deploy`` (or ``--artifact``) a packed ``DeployArtifact``
    on the fused Pallas deploy kernels."""
    from repro.configs.registry import get_config
    from repro.core.cim_linear import CIMConfig
    from repro.core.variation import DriftSchedule
    from repro.models.registry import get_model
    from repro.nn.module import init_params
    from repro.serve.engine import ServingEngine, engine_from_artifact
    from repro.serve.health import DriftMonitor

    drift_kw = {}
    drifting = (args.drift_col_rate or args.drift_cell_rate
                or args.drift_read_sigma)
    if drifting:
        drift_kw["drift_key"] = jax.random.fold_in(
            jax.random.PRNGKey(args.seed), 0xD81F)
        drift_kw["drift_schedule"] = DriftSchedule(
            read_sigma=args.drift_read_sigma,
            cell_rate=args.drift_cell_rate,
            col_rate=args.drift_col_rate)
    if args.health or args.auto_recal:
        drift_kw["health"] = DriftMonitor()
        drift_kw["auto_recalibrate"] = args.auto_recal
    if args.report_every:
        drift_kw["report_every"] = args.report_every
    if args.adc_sample:
        # arm BEFORE the engine builds: instrumentation is a trace-time
        # decision (repro.obs.adc)
        from repro.obs import adc
        adc.enable(every_n=args.adc_sample)

    mesh = None
    if args.mesh > 1:
        if len(jax.devices()) < args.mesh:
            raise SystemExit(
                f"--mesh {args.mesh} needs {args.mesh} devices, found "
                f"{len(jax.devices())}. On a CPU host set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh}")
        mesh = jax.make_mesh((args.mesh,), ("model",))
        if args.artifact is None and args.cim != "deploy":
            raise SystemExit("--mesh shards packed digit planes; use it "
                             "with --cim deploy or --artifact")

    cim = None
    if args.cim != "off":
        # QAT-shaped config; deploy serving packs these params below
        cim = CIMConfig(enabled=True, mode="emulate", weight_bits=4,
                        cell_bits=2, act_bits=8, psum_bits=6,
                        array_rows=128, array_cols=128)
    cfg = get_config(args.arch, reduced=args.reduced, cim=cim)

    if args.artifact is not None:
        engine = engine_from_artifact(
            args.artifact, cfg, mesh=mesh, batch_size=args.batch,
            max_len=args.max_len, temperature=args.temperature,
            seed=args.seed, **drift_kw)
    elif args.cim == "deploy":
        # pack random-init emulate params into an in-memory artifact and
        # serve it — the same packed bytes + engine path a saved artifact
        # takes, so --mesh N is exercised end to end
        from repro.api import model_artifact
        model = get_model(cfg)
        params = init_params(model.specs(cfg), jax.random.PRNGKey(args.seed))
        artifact = model_artifact(params, cim, meta={"arch": args.arch})
        engine = engine_from_artifact(
            artifact, cfg, mesh=mesh, batch_size=args.batch,
            max_len=args.max_len, temperature=args.temperature,
            seed=args.seed, **drift_kw)
    else:
        if drifting:
            raise SystemExit("drift flags act on packed digit planes; use "
                             "them with --cim deploy or --artifact")
        model = get_model(cfg)
        params = init_params(model.specs(cfg), jax.random.PRNGKey(args.seed))
        engine = ServingEngine(model, cfg, params, batch_size=args.batch,
                               max_len=args.max_len,
                               temperature=args.temperature, seed=args.seed,
                               **drift_kw)
    engine.t = args.drift_t0
    return engine, cfg


def main(argv=None):
    from repro.launch.compile_cache import enable_compile_cache
    args = parse_args(argv)
    enable_compile_cache()
    engine, cfg = build_engine(args)
    rng = np.random.RandomState(args.seed)
    prompts = rng.randint(0, cfg.vocab, size=(args.batch, args.prompt_len)
                          ).astype(np.int32)
    t0 = time.time()
    out = engine.generate_batch(prompts, args.new_tokens)
    dt = time.time() - t0
    n_new = out.shape[0] * out.shape[1]
    print(f"[serve] arch={args.arch} mesh={args.mesh} generated {out.shape} "
          f"tokens in {dt:.2f}s ({n_new / dt:.1f} tok/s)")
    print(f"[serve] sample continuation: {out[0][:16].tolist()}")
    h = engine.health()
    print(f"[serve] admission: submitted={h['submitted']} "
          f"retired={h['retired']} queue_depth={h['queue_depth']} "
          f"active_slots={h['active_slots']}/{h['slots']}")
    if args.health or args.auto_recal:
        print(f"[serve] health: {h}")
    if args.metrics_out:
        with open(args.metrics_out, "w", encoding="utf-8") as f:
            json.dump(engine.metrics(), f, indent=2, default=str)
        print(f"[serve] metrics -> {args.metrics_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
