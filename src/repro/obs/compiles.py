"""Compile counters from ``jax.monitoring`` (DESIGN.md §12).

``CompileWatch(registry)`` registers listeners for JAX's own compile
events and records them on the registry:

* ``/jax/core/compile/backend_compile_duration`` -> ``jit.compiles``
  and ``jit.compile.seconds``. JAX times every backend compile request
  with it, including those the persistent compilation cache serves;
* ``/jax/compilation_cache/cache_hits`` / ``cache_misses`` ->
  ``jit.cache.hits`` / ``jit.cache.misses``. JAX records a miss when it
  writes the compile to the cache, so a compile faster than the cache's
  minimum compile time counts in ``jit.compiles`` alone;
* the ``cim.grid.steps`` scalar the CIM kernel wrapper
  (``kernels.cim_matmul.fused_grid_call``) records while it is traced ->
  the ``cim.grid.steps`` counter. A jitted kernel traces once per
  distinct signature in a process, so a repeated call with the same
  shapes counts once, as it compiles once;
* the ``cim.patches.bytes`` scalar the conv patch builder
  (``kernels.cim_conv.tile_major_patches``) records while it is traced
  -> the ``cim.patches.bytes`` counter, once per traced conv;
* the ``vit.attn.pairs`` scalar the vision tower (``models.vit``)
  records while it is traced -> the ``vit.attn.pairs`` counter, once
  per traced forward.

Listeners are process-wide: every compile in the process counts, not
only those of the code that armed the watch. ``close()`` unregisters
them.

>>> watch = CompileWatch(registry)
>>> ...                     # registry.counter("jit.compiles") counts
>>> watch.close()
"""
from __future__ import annotations

import jax

from . import names
from .metrics import MetricsRegistry

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": names.JIT_CACHE_HITS,
                "/jax/compilation_cache/cache_misses": names.JIT_CACHE_MISSES}


class CompileWatch:
    """``jax.monitoring`` listeners bound to one registry."""

    def __init__(self, registry: MetricsRegistry):
        self.registry = registry
        self._armed = True
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_scalar_listener(self._scalar)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.registry.counter(names.JIT_COMPILES).inc()
            self.registry.histogram(names.JIT_COMPILE_SECONDS).observe(
                duration_secs)

    def _event(self, event: str, **_) -> None:
        if event in CACHE_EVENTS:
            self.registry.counter(CACHE_EVENTS[event]).inc()

    def _scalar(self, event: str, value, **_) -> None:
        if event in (names.CIM_GRID_STEPS, names.CIM_PATCHES_BYTES,
                     names.VIT_ATTN_PAIRS):
            self.registry.counter(event).inc(int(value))

    def close(self) -> None:
        """Unregister the listeners; a second call does nothing."""
        if self._armed:
            self._armed = False
            jax.monitoring.unregister_event_duration_listener(self._duration)
            jax.monitoring.unregister_event_listener(self._event)
            jax.monitoring.unregister_scalar_listener(self._scalar)
