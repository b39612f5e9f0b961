"""repro.obs — zero-dependency observability: trace spans, typed metrics,
Chrome-trace export.

Three pieces, one knob:

* `repro.obs.trace` — host-side spans (`span` context manager / `traced`
  decorator) collected into a bounded ring buffer, thread-aware, with
  optional ``jax.profiler.TraceAnnotation`` pass-through; `stage` names a
  stage of jitted code as a ``jax.named_scope`` (op metadata a device
  trace shows) and, with the knob on, also as a span;
* `repro.obs.metrics` — Counter / Gauge / Histogram behind a process-global
  `Registry` with deterministic JSON snapshots;
* `repro.obs.export` — ``chrome://tracing`` / Perfetto JSON for spans,
  metrics JSON dumps, and the terminal pretty-printer drivers share.

The ``REPRO_OBS`` environment variable (or `set_enabled`/`configure` at
runtime) gates every instrumentation site in the repo.  Off (the default),
spans are shared no-op objects and instrumented hot loops skip the metrics
plumbing entirely — overhead is budgeted at < 1% of a training step by
``BENCH_obs_overhead.json`` and all bit-identity gates are untouched
(instrumentation never runs *inside* compiled code: spans wrapping jitted
regions execute at trace time, which is exactly the compile/execute split
the trainer reports).

See docs/OBSERVABILITY.md for the span taxonomy and metric name registry.
"""
from __future__ import annotations

from . import export, metrics, trace
from .metrics import REGISTRY, Counter, Gauge, Histogram, Registry
from .trace import clock, configure, enabled, instant, set_enabled, span, stage, traced

__all__ = [
    "export", "metrics", "trace",
    "REGISTRY", "Counter", "Gauge", "Histogram", "Registry",
    "clock", "configure", "enabled", "instant", "set_enabled", "span",
    "stage", "traced",
]


def reset() -> None:
    """Clear the span buffer and the metrics registry (test isolation)."""
    trace.clear()
    REGISTRY.reset()
