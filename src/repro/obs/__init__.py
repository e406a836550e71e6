"""repro.obs — the telemetry plane (DESIGN.md §12).

Four pieces, all host-side:

* ``metrics``: a ``MetricsRegistry`` of counters/gauges/histograms with
  JSON snapshot + reset, a JSONL event log, and Prometheus text
  exposition. Histogram percentiles are exact (numpy-compatible
  interpolation over raw samples).
* ``tracing``: ``Tracer``/``SpanRecord`` — nested host-side spans that
  record durations into the registry and open a profiler
  ``TraceAnnotation`` of the same name.
* ``compiles``: ``CompileWatch`` — ``jax.monitoring`` listeners that
  count compiles, compile seconds and persistent-cache hits and misses
  (``jit.*`` metrics) on a registry.
* ``adc``: the sampled per-column ADC saturation collector the kernel
  wrappers and emulate forwards feed (``cim.adc.*`` metrics) — the
  paper-native drift signal, off by default, zero-overhead when
  disarmed.

Canonical metric names, and the ``jax.named_scope`` and Pallas kernel
names that put device time down to the program's layers, live in
``names`` and nowhere else;
``tools/check_metrics.py`` holds DESIGN.md §12 to them.
"""
from . import adc, names
from .compiles import CompileWatch
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .tracing import SpanRecord, Tracer

__all__ = ["CompileWatch", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "SpanRecord", "Tracer", "adc", "names"]
