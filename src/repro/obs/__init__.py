"""End-to-end observability: metrics, request tracing, compile events.

Off by default, and cheap when off: every instrumented site in the serve /
dist / plan stack funnels through the module-level one-liners below
(:func:`inc`, :func:`observe`, :func:`span`, :func:`event`, ...), each of
which is a single global read plus a ``None`` check when
:func:`configure` has not been called — the hot path pays nanoseconds,
and the ``serve_obs`` benchmark gates the *enabled* overhead at <= 3% of
goodput.  The three sinks:

* :class:`~repro.obs.metrics.MetricsRegistry` — counters, gauges and
  mergeable fixed-bucket latency histograms (exact p50/p99/p999 from
  bucket counts), exported as Prometheus text or JSON;
* :class:`~repro.obs.trace.Tracer` — structured spans (request lifecycle
  on the server clock; the scheduler's step phases, engine dispatch and
  plan stages on the wall clock, each under the span that caused
  it) in a bounded flight recorder with JSONL export;
* :class:`~repro.obs.events.EventLog` — named, timestamped compile /
  retrace / cache-miss events, so an unexpected recompile under steady
  traffic is a fact in a log, not a latency mystery.

Typical session::

    from repro import obs
    obs.configure()                       # all three sinks on
    ... serve traffic ...
    print(obs.metrics().prometheus_text())           # scrape payload
    print(obs.metrics().summary())                   # p50/p99/p999 view
    obs.tracer().export_jsonl("trace.jsonl")         # flight recorder
    assert obs.events().count("retrace") == 0        # steady state held
    obs.disable()                         # back to zero-cost no-ops

``configure`` is idempotent-by-replacement: each call installs fresh
sinks (a clean measurement window); ``disable`` detaches them, and
:func:`detached` keeps the closed window readable until the next
``configure``.

While a tracer is installed, :func:`timed_span` also enters a
``jax.profiler.TraceAnnotation`` of the same name, so a phase of the
program sits on the profiler's host timeline beside the device's work.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any

from repro.obs.events import Event, EventLog
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, Counter, Gauge,
                               Histogram, HistogramData, MetricsRegistry)
from repro.obs.trace import Span, Tracer

__all__ = [
    "DEFAULT_LATENCY_BUCKETS",
    "Counter",
    "Event",
    "EventLog",
    "Gauge",
    "Histogram",
    "HistogramData",
    "MetricsRegistry",
    "ObsState",
    "Span",
    "Tracer",
    "active",
    "annotation",
    "configure",
    "detached",
    "disable",
    "enabled",
    "event",
    "events",
    "inc",
    "metrics",
    "new_trace_id",
    "observe",
    "set_gauge",
    "span",
    "timed_span",
    "tracer",
]


@dataclasses.dataclass
class ObsState:
    """The installed sinks; any of the three may be individually off."""

    metrics: MetricsRegistry | None = None
    tracer: Tracer | None = None
    events: EventLog | None = None


_ACTIVE: ObsState | None = None
_DETACHED: ObsState | None = None
# the enclosing wall spans of each thread, innermost last (their names)
_OPEN = threading.local()
# what an instrumented block enters when its sink is off: one shared
# context, so the off path builds nothing and reads no clock
_NULL = contextlib.nullcontext()


def configure(*, metrics: bool = True, tracing: bool = True,
              events: bool = True, namespace: str = "repro",
              trace_capacity: int = 4096,
              event_capacity: int = 2048) -> ObsState:
    """Install fresh sinks and enable instrumentation.  Returns the new
    state (also reachable via :func:`active` / the accessors)."""
    global _ACTIVE, _DETACHED
    _DETACHED = None
    _ACTIVE = ObsState(
        metrics=MetricsRegistry(namespace=namespace) if metrics else None,
        tracer=Tracer(capacity=trace_capacity) if tracing else None,
        events=EventLog(capacity=event_capacity) if events else None)
    return _ACTIVE


def disable() -> None:
    """Detach every sink: instrumented sites return to no-ops."""
    global _ACTIVE, _DETACHED
    if _ACTIVE is not None:
        _DETACHED = _ACTIVE
    _ACTIVE = None


def detached() -> ObsState | None:
    """The sinks the last :func:`disable` detached (``None`` before the
    first, and again after :func:`configure`): a closed window read after
    instrumentation is back to no-ops, so reading it perturbs nothing."""
    return _DETACHED


def active() -> ObsState | None:
    return _ACTIVE


def enabled() -> bool:
    return _ACTIVE is not None


def metrics() -> MetricsRegistry | None:
    return None if _ACTIVE is None else _ACTIVE.metrics


def tracer() -> Tracer | None:
    return None if _ACTIVE is None else _ACTIVE.tracer


def events() -> EventLog | None:
    return None if _ACTIVE is None else _ACTIVE.events


# -- hot-path one-liners (no-ops unless the matching sink is installed) ------
def inc(name: str, amount: float = 1.0, **labels) -> None:
    st = _ACTIVE
    if st is not None and st.metrics is not None:
        st.metrics.inc(name, amount, **labels)


def set_gauge(name: str, value: float, **labels) -> None:
    st = _ACTIVE
    if st is not None and st.metrics is not None:
        st.metrics.set(name, value, **labels)


def observe(name: str, value: float, **labels) -> None:
    st = _ACTIVE
    if st is not None and st.metrics is not None:
        st.metrics.observe(name, value, **labels)


def _open_spans() -> list:
    stack = getattr(_OPEN, "stack", None)
    if stack is None:
        stack = _OPEN.stack = []
    return stack


def span(name: str, start: float, end: float | None = None, *,
         trace_id: str | None = None, clock: str = "wall",
         **attrs: Any) -> None:
    """Record one finished span (no-op without a tracer), under the
    innermost :func:`timed_span` open on this thread."""
    st = _ACTIVE
    if st is not None and st.tracer is not None:
        stack = _open_spans()
        st.tracer.record(name, start, end, trace_id=trace_id, clock=clock,
                         parent=stack[-1] if stack else None, **attrs)


def event(kind: str, ts: float | None = None, **fields: Any) -> None:
    st = _ACTIVE
    if st is not None and st.events is not None:
        st.events.record(kind, ts=ts, **fields)


def new_trace_id() -> str | None:
    """A fresh request trace id, or ``None`` when tracing is off (callers
    simply don't thread an id then)."""
    st = _ACTIVE
    if st is not None and st.tracer is not None:
        return st.tracer.new_trace_id()
    return None


class _TimedSpan:
    """One open wall-clock span: its parent is the innermost span open on
    this thread at entry, and the profiler's host timeline holds it as an
    annotation of the same name.  ``attrs`` may grow inside the block."""

    __slots__ = ("_tracer", "name", "trace_id", "attrs", "_annotation",
                 "_start")

    def __init__(self, tracer: Tracer, name: str, trace_id: str | None,
                 attrs: dict):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs

    def __enter__(self) -> "_TimedSpan":
        self._annotation = _trace_annotation(self.name)
        self._annotation.__enter__()
        _open_spans().append(self.name)
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        stack = _open_spans()
        stack.pop()
        self._tracer.record(self.name, self._start, end,
                            trace_id=self.trace_id, clock="wall",
                            parent=stack[-1] if stack else None,
                            **self.attrs)


def _trace_annotation(name: str):
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def timed_span(name: str, *, trace_id: str | None = None, **attrs: Any):
    """Wall-clock span around a ``with`` block, entered as the block's
    context manager; ``as`` binds it (set ``.attrs`` inside the block) or
    ``None`` when tracing is off, which then reads no clock and builds no
    annotation."""
    st = _ACTIVE
    if st is None or st.tracer is None:
        return _NULL
    return _TimedSpan(st.tracer, name, trace_id, attrs)


def annotation(name: str):
    """Only the profiler half of :func:`timed_span`: a
    ``jax.profiler.TraceAnnotation`` while a tracer is installed, for a
    block whose span is recorded with :func:`span`."""
    st = _ACTIVE
    if st is None or st.tracer is None:
        return _NULL
    return _trace_annotation(name)
