#!/usr/bin/env python3
"""Bring-up check: the packed deploy path runs on TPU, at published widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # column-sharded serving, four chips

One chip. olmo-1b at its published widths (16 layers, d_model 2048, d_ff
8192, vocab 50304; random weights from ``--seed``) is quantized the
paper's way (4-bit weights on 2-bit cells, 8-bit activations, 6-bit
column-wise ADC over 128-row arrays), packed by ``model_artifact`` into
int4 nibble digit planes with occupancy maps, and served through
``engine_from_artifact`` -> ``ServingEngine.generate_batch`` on the fused
Pallas deploy kernels, in the published bfloat16. The same artifact is
served on the ``ref`` backend (the jnp oracle): in bfloat16 the prefill
logits are held to it; in float32 compute the prefill logits, a
teacher-forced decode step and every greedy token are. The float
parameters served on ``emulate`` are reported, not held. A packed
ResNet-18 then runs its deploy conv forward at 224x224, batch 8, against
``ref``.

Four chips (``--chips 4``). The same olmo-1b artifact served column-
sharded on a ``("model",)`` mesh of four chips, against the artifact
served on one of those chips in this process: the tokens must match.

Checks: the platform is ``tpu`` (nothing falls back to the CPU), each
compiled program contains the Pallas kernel (``tpu_custom_call``), the
engine never degraded to its fallback backend, every result is finite,
and the results named above are within ``REL_TOL`` of ``ref``. The last line of the output is
one JSON object, ``{"ok": true, "device": {...}}``; any failed check or
phase exits non-zero before it is printed.

``--reduced`` rehearses every phase at the registry's reduced olmo-1b
and a 32x32 ResNet input, on any backend (``JAX_PLATFORMS=cpu`` runs the
kernels in interpret mode); it still ends at the platform check, so off
a TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: Largest max|Δlogit| / max|logit| admitted between two backends — the
#: bound the model-zoo parity gate holds deploy to (tests/test_zoo_parity).
REL_TOL = 5e-2

BATCH, PROMPT_LEN, NEW_TOKENS, MAX_LEN = 4, 16, 16, 64


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(dev: dict) -> None:
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev['platform']!r}); this check runs on the chip")


def cim_config():
    from repro.core import CIMConfig
    return CIMConfig(enabled=True, mode="emulate", weight_bits=4,
                     cell_bits=2, act_bits=8, psum_bits=6, array_rows=128,
                     array_cols=128, pack_dtype="int4", use_kernel=True)


def rel_err(y, ref) -> float:
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def check_kernel_in(compiled_text: str, what: str) -> None:
    if jax.default_backend() == "tpu":
        check("tpu_custom_call" in compiled_text,
              f"{what}: no Pallas kernel (tpu_custom_call) in the program")
    else:
        log(f"{what}: interpret mode on {jax.default_backend()}, no Mosaic "
            f"kernel to find")


def build_olmo(reduced: bool, seed: int, compute_dtype=None):
    """Random-init float (emulate) params and their packed artifact."""
    from repro.api import model_artifact
    from repro.configs.registry import get_config
    from repro.models.registry import get_model
    from repro.nn.module import init_params
    cfg = get_config("olmo-1b", reduced=reduced, cim=cim_config())
    if compute_dtype is not None:
        cfg = cfg.replace(compute_dtype=compute_dtype)
    model = get_model(cfg)
    t0 = time.perf_counter()
    params = init_params(model.specs(cfg), jax.random.PRNGKey(seed))
    artifact = model_artifact(params, cfg.cim, meta={"arch": "olmo-1b"})
    jax.block_until_ready(artifact.params)
    plane_bytes = sum(v.nbytes for v in jax.tree.leaves(artifact.params)
                      if v.dtype == jnp.uint8)
    log(f"olmo-1b: {cfg.n_layers} layers, d_model {cfg.d_model}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}, {cfg.compute_dtype}; packed in "
        f"{time.perf_counter() - t0:.1f}s, {plane_bytes / 2**30:.3f} GiB of "
        f"uint8 planes + occupancy")
    return cfg, model, params, artifact


def prompts_for(cfg, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab, size=(BATCH, PROMPT_LEN)).astype(np.int32)


def serve(engine, prompts, what: str, *, sharded: bool = False):
    """Compile the decode step (timed, checked for the kernel and, when
    ``sharded``, for the all-gather), then generate; returns (tokens,
    prefill logits, teacher-forced decode-step logits over the prompts'
    first tokens)."""
    t0 = time.perf_counter()
    text = engine.lowered_step().compile().as_text()
    t_compile = time.perf_counter() - t0
    if engine.cfg.cim.mode == "deploy":
        check_kernel_in(text, f"{what} decode step")
    if sharded:
        check("all-gather" in text, f"{what} decode step has no all-gather")
    t0 = time.perf_counter()
    toks = engine.generate_batch(prompts, NEW_TOKENS)
    t_first = time.perf_counter() - t0
    check(toks.shape == (BATCH, NEW_TOKENS), f"{what} token shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < engine.cfg.vocab)).all()),
          f"{what} tokens outside the vocabulary")
    check(not engine.fallback_active, f"{what} engine fell back to "
          f"{engine.fallback_backend!r}")
    logits = engine.prefill_logits(prompts)
    step_logits = engine.decode_logits(prompts, prompts[:, :1])
    check(bool(np.isfinite(logits).all() and np.isfinite(step_logits).all()),
          f"{what} logits not finite")
    log(f"{what}: decode-step compile {t_compile:.2f}s, first generate_batch "
        f"(prefill compile + {NEW_TOKENS} tokens x {BATCH}) {t_first:.2f}s, "
        f"{toks.size} tokens generated")
    return toks, logits, step_logits


def compare(name: str, got, want, *, checked=("prefill", "decode"),
            same_tokens: bool = False) -> None:
    """Report agreement of two ``serve`` results: prefill and decode-step
    logits, and greedy tokens (with the first step at which each row
    diverges, and the smallest top-2 logit gap of ``want``'s decode step
    — a gap below max|dlogit| lets a rounding difference flip a greedy
    token). Fail past ``REL_TOL`` on the logits named in ``checked``, and
    with ``same_tokens`` on any token difference."""
    toks, logits, step = got
    ref_toks, ref_logits, ref_step = want
    rel = {"prefill": rel_err(logits, ref_logits),
           "decode": rel_err(step, ref_step)}
    diverge = [int(np.argmin(row)) if not row.all() else NEW_TOKENS
               for row in toks == ref_toks]
    gap = np.diff(np.sort(ref_step, axis=-1)[:, -2:], axis=-1)
    log(f"{name}: prefill max|dlogit| "
        f"{np.max(np.abs(logits - ref_logits)):.6g} (rel {rel['prefill']:.3g}"
        f"), decode step max|dlogit| {np.max(np.abs(step - ref_step)):.6g} "
        f"(rel {rel['decode']:.3g}); tolerance {REL_TOL} on "
        f"{', '.join(checked) or 'neither'}")
    log(f"{name}: greedy tokens agree {np.mean(toks == ref_toks):.4f}, rows "
        f"first differ at step {diverge} of {NEW_TOKENS}; smallest top-2 "
        f"logit gap {float(gap.min()):.6g}")
    for what in checked:
        check(rel[what] <= REL_TOL,
              f"{name}: {what} rel {rel[what]} > {REL_TOL}")
    if same_tokens:
        check(bool(np.all(toks == ref_toks)), f"{name}: greedy tokens differ")


def olmo_one_chip(args) -> None:
    from repro.serve.engine import ServingEngine, engine_from_artifact
    cfg, model, params, art = build_olmo(args.reduced, args.seed)
    prompts = prompts_for(cfg, args.seed)
    kw = dict(batch_size=BATCH, max_len=MAX_LEN, seed=args.seed)
    ref_art = dataclasses.replace(art, config=art.config.replace(mode="ref"))

    # bfloat16, the published compute dtype: the program a user serves.
    # Only its prefill is held to ref. In bfloat16 XLA keeps some non-CIM
    # intermediates in float32 inside a fusion (excess precision) and
    # fuses the decode step differently around the Pallas call than
    # around the oracle's XLA ops; the random-init model's coarse
    # activation codes (s_a = 1) turn those last bits into other logits.
    deploy = engine_from_artifact(art, cfg, **kw)
    check(deploy.cfg.cim.mode == "deploy" and deploy.cfg.cim.use_kernel,
          f"artifact serves on {deploy.cfg.cim}")
    got = serve(deploy, prompts, "deploy")
    log(f"deploy greedy tokens[0]: {got[0][0].tolist()}")
    del deploy
    compare("deploy vs ref", got,
            serve(engine_from_artifact(ref_art, cfg, **kw), prompts, "ref"),
            checked=("prefill",))

    # float32 compute has no such intermediates: the same artifact must
    # match ref in every logit and every greedy token
    f32 = cfg.replace(compute_dtype="float32")
    compare("deploy vs ref, float32",
            serve(engine_from_artifact(art, f32, **kw), prompts,
                  "deploy float32"),
            serve(engine_from_artifact(ref_art, f32, **kw), prompts,
                  "ref float32"), same_tokens=True)

    # emulate (float params, fake-quant einsums) is reported only: its
    # dequant einsum adds the (split, tile) terms in an order the compiler
    # picks, last-bit apart from the kernel at these widths on any
    # backend, and the same activation codes amplify that
    del art, ref_art
    compare("deploy vs emulate", got,
            serve(ServingEngine(model, cfg, params, **kw), prompts,
                  "emulate"), checked=())


def resnet_one_chip(args) -> None:
    from repro.api import model_artifact
    from repro.models import resnet
    hw, batch = (32, 2) if args.reduced else (224, 8)
    rcfg = resnet.ResNetConfig(name="resnet18", depth=18, n_classes=1000,
                               in_hw=hw, cim=cim_config())
    k_init, k_x = jax.random.split(jax.random.PRNGKey(args.seed))
    params, state = resnet.init(k_init, rcfg)
    x = jax.random.normal(k_x, (batch, hw, hw, 3), jnp.float32)
    params = jax.jit(lambda p, s, x: resnet.calibrate(p, s, x, rcfg))(
        params, state, x[:2])
    art = model_artifact(params, rcfg.cim, meta={"arch": "resnet18"})

    def forward(cim):
        c = dataclasses.replace(rcfg, cim=cim)
        return jax.jit(lambda p, s, x: resnet.forward(p, s, x, c,
                                                      train=False)[0])

    t0 = time.perf_counter()
    compiled = forward(art.config).lower(art.params, state, x).compile()
    t_compile = time.perf_counter() - t0
    check_kernel_in(compiled.as_text(), "resnet-18 deploy forward")
    t0 = time.perf_counter()
    y = np.asarray(compiled(art.params, state, x))
    t_first = time.perf_counter() - t0
    y_ref = np.asarray(forward(art.config.replace(mode="ref"))(
        art.params, state, x))
    check(bool(np.isfinite(y).all()) and y.shape == (batch, 1000),
          f"resnet-18 logits {y.shape} finite={np.isfinite(y).all()}")
    rel = rel_err(y, y_ref)
    log(f"resnet-18 {batch}x{hw}x{hw}x3 deploy forward: compile "
        f"{t_compile:.2f}s, first call {t_first:.2f}s; vs ref max|dlogit| "
        f"{np.max(np.abs(y - y_ref)):.6g} (rel {rel:.3g}, tolerance "
        f"{REL_TOL}), argmax agree "
        f"{np.mean(y.argmax(-1) == y_ref.argmax(-1)):.3f}")
    check(rel <= REL_TOL, f"resnet-18 deploy vs ref: rel {rel} > {REL_TOL}")


def olmo_four_chips(args) -> None:
    from repro.nn.module import session_mesh
    from repro.serve.engine import engine_from_artifact
    n = len(jax.devices())
    check(n >= 4, f"--chips 4 needs four devices, JAX reports {n}")
    # float32 activations: the dtype the column-sharding bit-exactness
    # contract is stated and tested in (DESIGN.md §10). In bfloat16 the
    # non-CIM ops may round differently once the step is partitioned.
    cfg, _, params, art = build_olmo(args.reduced, args.seed, "float32")
    del params
    prompts = prompts_for(cfg, args.seed)
    kw = dict(batch_size=BATCH, max_len=MAX_LEN, seed=args.seed)

    one = serve(engine_from_artifact(art, cfg, **kw), prompts, "one chip")
    mesh = jax.make_mesh((4,), ("model",))
    with session_mesh(mesh):
        got = serve(engine_from_artifact(art, cfg, mesh=mesh, **kw), prompts,
                    "4-chip column-sharded", sharded=True)
    log(f"4-chip greedy tokens[0]: {got[0][0].tolist()}")
    compare("4 chips vs 1 chip", got, one, same_tokens=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the column-sharded serving phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="rehearsal sizes, any backend; still fails off TPU")
    args = ap.parse_args(argv)

    dev = device_info()
    log(f"device_kind {dev['kind']!r}, {dev['count']} device(s), platform "
        f"{dev['platform']}, jax {jax.__version__}")
    if not args.reduced:
        require_tpu(dev)          # before any full-width work
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        olmo_four_chips(args)
    else:
        olmo_one_chip(args)
        resnet_one_chip(args)
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    require_tpu(dev)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
