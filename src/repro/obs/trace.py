"""Host-side trace spans with a Chrome-trace-compatible event buffer.

A span marks a named region of *host* time:

    with trace.span("pipeline/shade"):
        ...                                # context-manager form

    @trace.traced("serve3d/render_drain")
    def drain(self): ...                   # decorator form (checks the knob
                                           # per call, not at decoration)

Events are (name, category, start, duration, thread) tuples appended to a
bounded process-global ring buffer; `repro.obs.export.chrome_trace` turns the
buffer into a ``chrome://tracing`` / Perfetto JSON document.  Spans are
thread-aware (thread id + name ride on every event) and nest freely — the
per-thread depth is recorded so consumers can reconstruct the stack without
timestamp arithmetic.

Everything is gated by one knob: the ``REPRO_OBS`` environment variable at
import time, or `set_enabled(...)` at runtime.  When the knob is off,
``span(...)`` returns one shared no-op object and ``traced`` functions call
straight through — the disabled cost is a single attribute check, budgeted
by ``BENCH_obs_overhead.json`` at < 1% of a training step.

The module's clock (`trace.clock`, a ``time.perf_counter`` alias) is the
single wall-time source for spans AND for the trainer/serve3d history
bookkeeping, so benchmark timings and telemetry can never disagree about
what a second is.

Instrumentation placement contract: spans never touch array values, so
wrapping code that runs under ``jax.jit`` is safe — the span then measures
*trace/compile* time (it executes while jax traces the function) and cached
executions of the compiled function produce no stage spans.  That is exactly
the compile-vs-execute split the trainer reports.  With
``jax_annotations`` on (``REPRO_OBS=jax``), spans also enter a
``jax.profiler.TraceAnnotation`` so host spans line up with XLA device
traces captured via ``jax.profiler.trace``.

Code that runs under ``jax.jit`` names its stages with `stage` instead: a
stage is always a ``jax.named_scope``, so XLA keeps its name in the
``op_name`` metadata of every op the stage emits (a device trace shows it
as each op's program path), and, when the knob is on, also the trace-time
host span above.  The scope writes HLO metadata only while jax traces; a
cached execution runs the same device program with no host work.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from collections import deque
from typing import Any, NamedTuple

#: The one wall-clock for spans, trainer histories, serve3d latencies and
#: benchmark timings.  Alias, not a wrapper: calling it is exactly
#: ``time.perf_counter()``.
clock = time.perf_counter
clock_ns = time.perf_counter_ns


def _env_enabled(val: str | None) -> bool:
    return (val or "").strip().lower() not in ("", "0", "off", "false", "no")


class _State:
    __slots__ = ("enabled", "jax_annotations", "events")


_STATE = _State()
_STATE.enabled = _env_enabled(os.environ.get("REPRO_OBS"))
_STATE.jax_annotations = (os.environ.get("REPRO_OBS", "").strip().lower() == "jax")
# bounded ring buffer: a long-lived service can trace forever without
# growing host memory; deque.append is atomic under the GIL, so concurrent
# render/train threads need no lock on the hot path
_STATE.events = deque(maxlen=int(os.environ.get("REPRO_OBS_BUFFER", 262144)))

_tls = threading.local()


class SpanEvent(NamedTuple):
    name: str
    cat: str
    ts_us: float          # start, microseconds on the perf_counter timeline
    dur_us: float | None  # None => instant event
    tid: int
    thread_name: str
    depth: int            # per-thread nesting depth at entry
    args: dict | None


def enabled() -> bool:
    return _STATE.enabled


def set_enabled(on: bool) -> None:
    _STATE.enabled = bool(on)


def configure(enabled: bool | None = None, jax_annotations: bool | None = None,
              buffer_size: int | None = None) -> None:
    """Runtime overrides for the env-var defaults."""
    if enabled is not None:
        _STATE.enabled = bool(enabled)
    if jax_annotations is not None:
        _STATE.jax_annotations = bool(jax_annotations)
    if buffer_size is not None:
        _STATE.events = deque(_STATE.events, maxlen=int(buffer_size))


def events() -> list[SpanEvent]:
    """Snapshot of the event buffer (oldest first)."""
    return list(_STATE.events)


def clear() -> None:
    _STATE.events.clear()


class _NullSpan:
    """Shared no-op span: the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL = _NullSpan()


def _jax_annotation(name: str):
    try:  # pragma: no cover - exercised only with REPRO_OBS=jax
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)
    except Exception:  # jax absent or profiler unavailable: host spans only
        return None


class Span:
    __slots__ = ("name", "cat", "args", "_t0", "_depth", "_ann")

    def __init__(self, name: str, cat: str = "obs", args: dict | None = None):
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = None

    def __enter__(self):
        depth = getattr(_tls, "depth", 0)
        _tls.depth = depth + 1
        self._depth = depth
        if _STATE.jax_annotations:
            self._ann = _jax_annotation(self.name)
            if self._ann is not None:
                self._ann.__enter__()
        self._t0 = clock_ns()
        return self

    def __exit__(self, *exc):
        t1 = clock_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _tls.depth = self._depth
        th = threading.current_thread()
        _STATE.events.append(SpanEvent(
            self.name, self.cat, self._t0 / 1e3, (t1 - self._t0) / 1e3,
            th.ident or 0, th.name, self._depth, self.args,
        ))
        return False


def span(name: str, cat: str = "obs", args: dict | None = None):
    """A context manager timing the wrapped region, or the shared no-op when
    observability is off.  `args` ride into the Chrome-trace event's args
    pane — keep them small, JSON-serializable host values (never jax
    arrays)."""
    if not _STATE.enabled:
        return NULL
    return Span(name, cat, args)


@contextlib.contextmanager
def stage(name: str, cat: str = "obs", args: dict | None = None):
    """A named stage of code that jax traces: ``jax.named_scope(name)``
    always, plus `span(name, cat, args)` when observability is on.

    A stage opened inside a stage whose name prefixes its own
    (``hash_grid/bwd/stream`` inside ``hash_grid/bwd``) scopes only the
    remainder, so the op path reads ``.../hash_grid/bwd/stream/...``."""
    import jax

    outer = getattr(_tls, "stage", "")
    scope = name[len(outer) + 1:] if outer and name.startswith(outer + "/") else name
    _tls.stage = name
    try:
        with jax.named_scope(scope), span(name, cat, args):
            yield
    finally:
        _tls.stage = outer


def traced(name: str | None = None, cat: str = "obs"):
    """Decorator form of `span`.  The knob is checked per *call*: decorating
    at import time never freezes a disabled state."""
    def deco(fn):
        label = name if name is not None else fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **k):
            if not _STATE.enabled:
                return fn(*a, **k)
            with Span(label, cat):
                return fn(*a, **k)

        return wrapper

    return deco


def record(name: str, start_s: float, end_s: float, cat: str = "obs",
           args: dict | None = None) -> None:
    """Append a completed span from explicit `clock()` timestamps (seconds).

    For regions whose start/stop cannot bracket a ``with`` block (e.g. a
    span closed in a different control-flow arm than it opened).  Shares the
    perf_counter timeline with `Span`, so recorded and context-managed spans
    interleave correctly in the exported trace.
    """
    if not _STATE.enabled:
        return
    th = threading.current_thread()
    _STATE.events.append(SpanEvent(
        name, cat, start_s * 1e6, max(0.0, (end_s - start_s)) * 1e6,
        th.ident or 0, th.name, getattr(_tls, "depth", 0), args,
    ))


def instant(name: str, cat: str = "obs", args: dict | None = None) -> None:
    """Zero-duration marker event (Chrome-trace phase "i")."""
    if not _STATE.enabled:
        return
    th = threading.current_thread()
    _STATE.events.append(SpanEvent(
        name, cat, clock_ns() / 1e3, None, th.ident or 0, th.name,
        getattr(_tls, "depth", 0), args,
    ))
