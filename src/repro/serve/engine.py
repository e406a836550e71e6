"""Batched serving engine: slot-based continuous batching over the model
zoo's cache API.

Prefill runs the cached forward over the whole prompt (causal attention
with per-slot offsets, one pass); decode advances every active slot one
token per engine step. Finished slots are retired and refilled from the
queue without stalling the running batch — the standard continuous-
batching pattern, kept deliberately simple (fixed max_len slab per slot;
a paged KV allocator is an optimization, not a correctness need, and the
SSM families carry O(1) state anyway).

Self-healing serving (DESIGN.md §11): the engine optionally models a
drifting chip (``drift_key`` + ``drift_schedule``) — every decode step
serves one drift realization of the packed planes at the current request
count — watches its own logit statistics through a ``DriftMonitor``
(``health=``), degrades to the digital reference backend on hard drift,
and re-fits per-column scales in place via ``recalibrate()``.

Telemetry (DESIGN.md §12): every engine owns a ``repro.obs``
``MetricsRegistry`` (pass ``metrics=`` to share one). Request lifecycle
is traced — queue wait, prefill and per-decode-step spans land in the
registry's histograms (and, under a running profiler, in its trace);
token/request counters and queue depth/active-slot gauges update as the
slots churn, and a ``CompileWatch`` counts compiles (``jit.*``) beside
them, so a recompile shows next to the step it slowed. ``metrics()``
folds all of it with ``health()``, token and step counts and — when the
``repro.obs.adc`` collector is armed — the ADC saturation summary into
one JSON-safe view; ``launch/serve.py --metrics-out`` writes exactly
that. When the collector is armed the monitor additionally ingests an
``adc_clip_rate`` statistic per step, so drift detection can trigger on
column clipping directly.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time
import weakref
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.variation import DriftSchedule, DriftState, drift_tree
from repro.models.registry import ModelFns
from repro.obs import adc as obs_adc
from repro.obs import names as M
from repro.obs.compiles import CompileWatch
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracing import Tracer


def engine_from_artifact(artifact, cfg: ModelConfig, *, mesh=None,
                         mesh_axis: str = "model",
                         **engine_kw) -> "ServingEngine":
    """Build a ``ServingEngine`` that serves a packed ``DeployArtifact``
    on its packed backend (the fused Pallas deploy path).

    ``artifact`` is a ``repro.api.DeployArtifact`` of kind "model" (or a
    path to one on disk); ``cfg`` is the architecture's ModelConfig — its
    ``cim`` field is replaced by the artifact's pinned deploy config, so
    the engine runs exactly the quantization state that was packed, and
    ``linear_specs``-style callers see a packed backend.

    ``mesh`` turns on column-parallel serving (DESIGN.md §10): every CIM
    layer's digit planes are placed column-sharded over ``mesh_axis`` as
    the artifact loads (each device receives only its own column slice),
    the mesh is installed as the session mesh (``set_activation_rules``)
    so the deploy forwards dispatch one kernel shard per device, and
    generation is bit-exact with the single-device engine serving the
    same artifact.

    The session mesh is process-global and stays installed after this
    call (a serving process serves one mesh for its lifetime);
    ``mesh=None`` does NOT clear a previously installed mesh. The engine
    records the mesh in scope at build time and **fails loudly** if a
    later ``step``/``generate_batch`` runs under a different one — its
    jitted functions trace against the build-time mesh, so silently
    inheriting another would serve wrong shardings. To mix sharded and
    unsharded engines in one process — tests, benchmarks — scope each
    engine's build *and* generation inside
    ``repro.nn.module.session_mesh(mesh)`` (or call
    ``set_activation_rules(None, None)`` to tear down).

    Drift/health keywords (``drift_key``, ``drift_schedule``, ``health``,
    ``auto_recalibrate``) pass through to ``ServingEngine``.
    """
    from repro.api import DeployArtifact
    from repro.models.registry import get_model
    if isinstance(artifact, (str, os.PathLike)):
        artifact = DeployArtifact.load(os.fspath(artifact), mesh=mesh,
                                       mesh_axis=mesh_axis)
    elif mesh is not None:
        artifact = artifact.shard(mesh, mesh_axis=mesh_axis)
    if artifact.kind != "model":
        raise ValueError(f"engine_from_artifact needs a 'model' artifact, "
                         f"got kind={artifact.kind!r}")
    if mesh is not None:
        from repro.nn.module import current_rules, set_activation_rules
        set_activation_rules(current_rules(), mesh)
    serve_cfg = dataclasses.replace(cfg, cim=artifact.config)
    model = get_model(serve_cfg)
    return ServingEngine(model, serve_cfg, artifact.params,
                         layout_version=artifact.layout_version, **engine_kw)


def make_prefill(model: ModelFns, cfg: ModelConfig):
    """(params, cache, tokens (B,T)) -> (logits (B,T,V), cache). Uses the
    decode path so caches fill in one pass."""
    def prefill(params, cache, tokens):
        return model.decode_step(params, cache, tokens, cfg)
    return jax.jit(prefill, donate_argnums=(1,))


def make_decode_step(model: ModelFns, cfg: ModelConfig,
                     temperature: float = 0.0):
    def step(params, cache, tokens, key):
        logits, cache = model.decode_step(params, cache, tokens, cfg)
        last = logits[:, -1, :].astype(jnp.float32)
        if temperature > 0:
            nxt = jax.random.categorical(key, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        return nxt[:, None].astype(jnp.int32), cache
    return jax.jit(step, donate_argnums=(1,))



def _make_engine_step(model: ModelFns, cfg: ModelConfig, temperature: float,
                     drift_key, schedule: Optional[DriftSchedule],
                     with_stats: bool):
    """Drift-aware decode step: injects one chip realization at request
    count ``t`` (a traced scalar — the clock advances with zero
    recompiles) and, when the health hook is armed, computes the logit
    statistics the monitor ingests inside the same jit."""
    drifting = (drift_key is not None and schedule is not None
                and not schedule.is_static_zero)

    def step(params, cache, tokens, key, t):
        p = params
        if drifting:
            p = drift_tree(params, drift_key, DriftState(schedule, t))
        logits, cache = model.decode_step(p, cache, tokens, cfg)
        last = logits[:, -1, :].astype(jnp.float32)
        if temperature > 0:
            nxt = jax.random.categorical(key, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        stats = {}
        if with_stats:
            t2 = jax.lax.top_k(last, 2)[0]
            stats = {"logit_mean": jnp.mean(last),
                     "logit_var": jnp.var(last),
                     "logit_margin": jnp.mean(t2[:, 0] - t2[:, 1])}
        return nxt[:, None].astype(jnp.int32), cache, stats
    return jax.jit(step, donate_argnums=(1,))


def _make_engine_prefill(model: ModelFns, cfg: ModelConfig, drift_key,
                         schedule: Optional[DriftSchedule]):
    drifting = (drift_key is not None and schedule is not None
                and not schedule.is_static_zero)

    def prefill(params, cache, tokens, t):
        p = params
        if drifting:
            p = drift_tree(params, drift_key, DriftState(schedule, t))
        return model.decode_step(p, cache, tokens, cfg)
    return jax.jit(prefill, donate_argnums=(1,))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # (T,) int32
    max_new_tokens: int
    eos_id: int = -1                     # -1: run to max_new_tokens
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_submit: float = 0.0                # wall clock at submit()
    t_admit: float = 0.0                 # wall clock at slot admission


class ServingEngine:
    """Fixed-B slot engine. Prompts are prefilled one slot at a time (the
    cache API is batched, so we prefill with a masked batch); decode steps
    advance all live slots together.

    With ``drift_key``/``drift_schedule`` the engine serves a drifting
    chip: each decode step evaluates the packed planes under the drift
    field at the current request count ``t`` (one tick per model
    invocation). With ``health`` (a ``serve.health.DriftMonitor``) the
    engine observes its logit statistics every step; past the monitor's
    hard threshold it degrades to ``fallback_backend`` — the digital
    ``ref`` oracle on the *pristine* planes (digit storage does not
    drift; only the analog evaluation does) — until ``recalibrate()``
    lands a fresh ``ScaleDelta``, after which the corrected analog path
    serves again. ``auto_recalibrate=True`` closes the loop without an
    operator."""

    def __init__(self, model: ModelFns, cfg: ModelConfig, params,
                 batch_size: int = 8, max_len: int = 1024,
                 temperature: float = 0.0, seed: int = 0, *,
                 drift_key: Optional[jax.Array] = None,
                 drift_schedule: Optional[DriftSchedule] = None,
                 health=None,
                 fallback_backend: str = "ref",
                 auto_recalibrate: bool = False,
                 layout_version: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 report_every: int = 0):
        from repro.nn.module import current_mesh
        self.model, self.cfg, self.params = model, cfg, params
        self.B, self.max_len = batch_size, max_len
        self.mesh = current_mesh()          # pinned: see _check_mesh
        self.cache = model.init_cache(cfg, batch_size, max_len)
        self.temperature = temperature
        self.drift_key = drift_key
        self.drift_schedule = drift_schedule
        self.monitor = health
        self.fallback_backend = fallback_backend
        self.auto_recalibrate = auto_recalibrate
        self.layout_version = layout_version
        self.fallback_active = False
        self.t = 0                          # request-count drift clock
        self._pristine = params             # pre-recalibration reference
        self._fallback_step = None          # built lazily on first fallback
        with_stats = health is not None
        self._step_fn = _make_engine_step(model, cfg, temperature,
                                          drift_key, drift_schedule,
                                          with_stats)
        self._prefill_fn = _make_engine_prefill(model, cfg, drift_key,
                                                drift_schedule)
        self.decode = make_decode_step(model, cfg, temperature)
        self.key = jax.random.PRNGKey(seed)
        self.slots: List[Optional[Request]] = [None] * batch_size
        self.queue: List[Request] = []
        self.last_tok = np.zeros((batch_size, 1), np.int32)
        self._next_rid = 0
        self.retired = 0                    # requests completed, ever
        self.registry = metrics if metrics is not None else MetricsRegistry()
        self.tracer = Tracer(self.registry)
        # process-wide listeners: unregistered when the engine goes
        weakref.finalize(self, CompileWatch(self.registry).close)
        self.report_every = report_every    # stderr line every N decode steps
        self._decode_steps = 0
        self._last_sat = 0                  # adc totals at last observation,
        self._last_conv = 0                 # for the per-step clip-rate delta

    def submit(self, prompt, max_new_tokens: int, eos_id: int = -1) -> int:
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, np.asarray(prompt, np.int32),
                      max_new_tokens, eos_id, t_submit=time.time())
        self.queue.append(req)
        self.registry.counter(M.REQUESTS_SUBMITTED).inc()
        self.registry.gauge(M.QUEUE_DEPTH).set(len(self.queue))
        self.registry.log_event("request_submitted", rid=rid,
                                prompt_len=int(req.prompt.shape[0]),
                                max_new_tokens=max_new_tokens)
        return rid

    # -- self-healing internals ----------------------------------------------

    def _check_mesh(self, where: str) -> None:
        """Fail loudly when generation runs under a different session
        mesh than the engine was built with — the jitted forwards traced
        against the build-time mesh, and silently inheriting another
        serves wrong shardings (the old ``mesh=None`` footgun)."""
        from repro.nn.module import current_mesh
        cur = current_mesh()
        if cur is self.mesh or cur == self.mesh:
            return
        raise RuntimeError(
            f"ServingEngine.{where}: the session mesh changed since this "
            f"engine was built (built under {self.mesh!r}, now {cur!r}). "
            "Rebuild the engine under the new mesh, or scope build and "
            "generation together in repro.nn.module.session_mesh(...).")

    def _invoke_step(self, tokens: jnp.ndarray, sub: jax.Array):
        """One model invocation: drift clock tick, fallback dispatch,
        health observation, optional auto-recalibration."""
        t = jnp.int32(self.t)
        self.t += 1
        if self.fallback_active:
            nxt, self.cache = self._fallback()(self.params_clean(),
                                               self.cache, tokens, sub)
            return nxt
        nxt, self.cache, stats = self._step_fn(self.params, self.cache,
                                               tokens, sub, t)
        self._observe_health(stats)
        return nxt

    def _observe_health(self, stats) -> None:
        """Feed one step's statistics to the drift monitor and react.
        When the ADC collector is armed, the folded saturation totals
        since the previous observation become an ``adc_clip_rate``
        statistic — the paper-native drift signal (DESIGN.md §12)."""
        if self.monitor is None or not stats:
            return
        host = {k: float(v) for k, v in stats.items()}
        if obs_adc.enabled():
            obs_adc.sync()
            sat, conv = obs_adc.totals()
            d_sat, d_conv = sat - self._last_sat, conv - self._last_conv
            self._last_sat, self._last_conv = sat, conv
            if d_conv > 0:
                host["adc_clip_rate"] = d_sat / d_conv
        self.monitor.observe(host)
        if self.monitor.hard_drifted and not self.fallback_active:
            self.monitor.hard_events += 1
            if self.auto_recalibrate:
                self.recalibrate()
            elif self.fallback_backend:
                self.fallback_active = True

    def params_clean(self):
        """The pristine packed tree (digit storage does not drift)."""
        return self._pristine

    def _fallback(self):
        if self._fallback_step is None:
            fcfg = dataclasses.replace(
                self.cfg, cim=self.cfg.cim.replace(mode=self.fallback_backend))
            self._fallback_step = make_decode_step(self.model, fcfg,
                                                   self.temperature)
        return self._fallback_step

    def recalibrate(self, *, probes: int = 64,
                    key: Optional[jax.Array] = None):
        """Re-fit per-column scales against the drift accumulated at the
        current request count and swap the corrected params in: fit a
        ``ScaleDelta`` from pristine planes to the drift realization at
        ``t`` (``eval/recalibrate.py``), apply it to the *pristine* tree
        (deltas are absolute), leave fallback, and re-arm the monitor.
        Returns the fitted delta (persist it with ``delta.save``)."""
        from repro.eval.recalibrate import (apply_scale_delta_params,
                                            fit_scale_delta)
        if key is None:
            self.key, key = jax.random.split(self.key)
        meta = {"t": int(self.t), "probes": probes}
        if (self.drift_key is not None and self.drift_schedule is not None
                and not self.drift_schedule.is_static_zero):
            observed = drift_tree(self._pristine, self.drift_key,
                                  DriftState(self.drift_schedule,
                                             jnp.int32(self.t)))
        else:
            observed = self._pristine   # no drift model: identity delta
        delta = fit_scale_delta(self._pristine, observed, key=key,
                                probes=probes, meta=meta)
        if self.layout_version is not None:
            delta = dataclasses.replace(delta,
                                        layout_version=self.layout_version)
        self.params = apply_scale_delta_params(self._pristine, delta)
        self.fallback_active = False
        if self.monitor is not None:
            self.monitor.note_recalibration()
        self.registry.counter(M.RECALIBRATIONS).inc()
        self.registry.log_event("recalibration", t=int(self.t), probes=probes)
        return delta

    def health(self) -> Dict:
        """Snapshot of the self-healing state: monitor counters (when a
        monitor is armed), the engine's own drift/fallback status, and
        the admission state — queue depth, active and retired slots."""
        snap = self.monitor.snapshot() if self.monitor is not None else {}
        snap.update({
            "t": self.t,
            "fallback_active": self.fallback_active,
            "drifting": (self.drift_key is not None
                         and self.drift_schedule is not None
                         and not self.drift_schedule.is_static_zero),
            "mesh": None if self.mesh is None else repr(self.mesh),
            "queue_depth": len(self.queue),
            "active_slots": sum(s is not None for s in self.slots),
            "slots": self.B,
            "submitted": self._next_rid,
            "retired": self.retired,
        })
        return snap

    def metrics(self) -> Dict:
        """One folded telemetry view (DESIGN.md §12): ``health()`` plus
        token and decode-step counts with the device count, the ADC
        saturation summary (when the collector is armed) and the full
        registry snapshot. JSON-safe — ``launch/serve.py --metrics-out``
        dumps it verbatim."""
        if obs_adc.enabled():
            obs_adc.sync()
        n_dev = 1 if self.mesh is None else int(self.mesh.devices.size)
        return {
            "health": self.health(),
            "throughput": {
                "tokens_generated":
                    self.registry.counter(M.TOKENS_GENERATED).value,
                "decode_steps":
                    self.registry.histogram(M.DECODE_STEP_SECONDS).count,
                "devices": n_dev,
            },
            "saturation": obs_adc.summary() if obs_adc.enabled() else None,
            "metrics": self.registry.snapshot(),
        }

    def _maybe_report(self) -> None:
        """Periodic one-line operator report on stderr (``report_every``
        decode steps; 0 = off)."""
        if not self.report_every:
            return
        if self._decode_steps % self.report_every:
            return
        toks = self.registry.counter(M.TOKENS_GENERATED).value
        dec = self.registry.histogram(M.DECODE_STEP_SECONDS)
        tps = toks / dec.sum if dec.sum > 0 else 0.0
        line = (f"[serve.metrics] t={self.t} tokens={toks} tok/s={tps:.1f} "
                f"queue={len(self.queue)} "
                f"active={sum(s is not None for s in self.slots)}/{self.B} "
                f"retired={self.retired}")
        if self.monitor is not None:
            line += (f" score={self.monitor.score:.2f}"
                     f" fallback={self.fallback_active}")
        if obs_adc.enabled():
            s = obs_adc.summary()
            line += f" clip_rate={s['clip_rate']:.4f}"
        print(line, file=sys.stderr)

    # -- internals -----------------------------------------------------------
    def _admit(self):
        """Fill empty slots: prefill the prompt token-by-token batched with
        zero-masked inactive slots (single-slot prefill keeps the engine
        simple; a bulk path would batch same-length prompts)."""
        for i in range(self.B):
            if self.slots[i] is None and self.queue:
                req = self.queue.pop(0)
                self.slots[i] = req
                req.t_admit = time.time()
                self.registry.histogram(M.QUEUE_WAIT_SECONDS).observe(
                    req.t_admit - req.t_submit)
                with self.tracer.span("serve.prefill", rid=req.rid,
                                      tokens=int(req.prompt.shape[0])):
                    for t in req.prompt:
                        tok = np.array(self.last_tok)
                        tok[i, 0] = t
                        self.key, sub = jax.random.split(self.key)
                        nxt = self._invoke_step(jnp.asarray(tok), sub)
                        nxt = np.asarray(nxt)
                        # only slot i's cache row advanced meaningfully;
                        # other slots consumed a dummy token -> rewind
                        self.last_tok[i, 0] = nxt[i, 0]
                self.registry.gauge(M.QUEUE_DEPTH).set(len(self.queue))
                self.registry.gauge(M.ACTIVE_SLOTS).set(
                    sum(s is not None for s in self.slots))
        # NOTE: per-slot prefill advances other slots' caches too; engine
        # correctness relies on all slots being empty or synchronized. For
        # mixed workloads use `ServingEngine.generate_batch` (lockstep).

    def step(self) -> List[Dict]:
        """One decode step for all active slots; returns finished requests."""
        self._check_mesh("step")
        self._admit()
        if all(s is None for s in self.slots):
            return []
        self.key, sub = jax.random.split(self.key)
        with self.tracer.span("serve.decode.step"):
            nxt = np.asarray(self._invoke_step(jnp.asarray(self.last_tok),
                                               sub))
        self._decode_steps += 1
        active = sum(s is not None for s in self.slots)
        self.registry.counter(M.TOKENS_GENERATED).inc(active)
        finished = []
        now = time.time()
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[i, 0])
            req.output.append(tok)
            self.last_tok[i, 0] = tok
            if tok == req.eos_id or len(req.output) >= req.max_new_tokens:
                req.done = True
                finished.append({"rid": req.rid, "tokens": req.output})
                self.slots[i] = None
                self.retired += 1
                self.registry.counter(M.REQUESTS_COMPLETED).inc()
                self.registry.histogram(M.REQUEST_LATENCY_SECONDS).observe(
                    now - req.t_submit)
                self.registry.log_event(
                    "request_completed", rid=req.rid,
                    tokens=len(req.output),
                    latency=now - req.t_submit,
                    queue_wait=req.t_admit - req.t_submit)
        if finished:
            self.registry.gauge(M.ACTIVE_SLOTS).set(
                sum(s is not None for s in self.slots))
        self._maybe_report()
        return finished

    # -- the simple, correct batched API --------------------------------------
    def generate_batch(self, prompts: np.ndarray, max_new_tokens: int
                       ) -> np.ndarray:
        """Lockstep batched generation: prompts (B, Tp) -> (B, Tnew)."""
        self._check_mesh("generate_batch")
        assert prompts.shape[0] == self.B
        cache = self.model.init_cache(self.cfg, self.B, self.max_len)
        with self.tracer.span("serve.prefill", tokens=int(prompts.shape[1]),
                              batch=self.B):
            logits, cache = self._prefill_fn(self.params, cache,
                                             jnp.asarray(prompts),
                                             jnp.int32(self.t))
            self.t += 1
            tok = jnp.argmax(logits[:, -1:, :].astype(jnp.float32), axis=-1
                             ).astype(jnp.int32)
            outs = [np.asarray(tok)]
        self.registry.counter(M.TOKENS_GENERATED).inc(self.B)
        for _ in range(max_new_tokens - 1):
            self.key, sub = jax.random.split(self.key)
            t = jnp.int32(self.t)
            self.t += 1
            with self.tracer.span("serve.decode.step"):
                if self.fallback_active:
                    tok, cache = self._fallback()(self.params_clean(), cache,
                                                  tok, sub)
                    stats = {}
                else:
                    tok, cache, stats = self._step_fn(self.params, cache,
                                                      tok, sub, t)
                outs.append(np.asarray(tok))
            self._decode_steps += 1
            self.registry.counter(M.TOKENS_GENERATED).inc(self.B)
            self._observe_health(stats)
            self._maybe_report()
        return np.concatenate(outs, axis=1)

    # -- inspection: the exact programs generate_batch runs --------------------
    def prefill_logits(self, prompts: np.ndarray) -> np.ndarray:
        """Logits (B, T, V) of a prefill over ``prompts`` (B, T) on a fresh
        cache, through the engine's own jitted prefill — a parity check
        compares exactly the program that serves."""
        self._check_mesh("prefill_logits")
        cache = self.model.init_cache(self.cfg, self.B, self.max_len)
        logits, _ = self._prefill_fn(self.params, cache, jnp.asarray(prompts),
                                     jnp.int32(self.t))
        return np.asarray(logits.astype(jnp.float32))

    def decode_logits(self, prompts: np.ndarray, tokens: np.ndarray
                      ) -> np.ndarray:
        """Logits (B, V) of one decode step over ``tokens`` (B, 1) after a
        prefill of ``prompts``: the decode-shaped model call, teacher-
        forced, so two engines can be compared on the same inputs."""
        self._check_mesh("decode_logits")
        cache = self.model.init_cache(self.cfg, self.B, self.max_len)
        t = jnp.int32(self.t)
        _, cache = self._prefill_fn(self.params, cache, jnp.asarray(prompts), t)
        logits, _ = self._prefill_fn(self.params, cache, jnp.asarray(tokens), t)
        return np.asarray(logits[:, -1].astype(jnp.float32))

    def lowered_step(self):
        """The decode step ``generate_batch`` runs, lowered for this
        engine's params and batch (``jax.stages.Lowered``): ``.compile()``
        it to time the compile or to read the program text."""
        self._check_mesh("lowered_step")
        cache = self.model.init_cache(self.cfg, self.B, self.max_len)
        tok = jnp.zeros((self.B, 1), jnp.int32)
        return self._step_fn.lower(self.params, cache, tok, self.key,
                                   jnp.int32(self.t))
