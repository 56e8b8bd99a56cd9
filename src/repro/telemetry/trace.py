"""Structured run tracing — spans, counters, and Perfetto export.

The repo grew five disjoint observability fragments (``CommLedger``,
``ServeMetrics``, ``program_cache_stats()``, ``wire_kernel_hits``,
``telemetry.hlo.collective_stats``) with no common timeline.  This module
is the timeline: a ``Tracer`` collects named, tagged **spans** (wall-time
intervals) plus monotonic **counters** and last-value **gauges**, and
exports them as Chrome trace-event JSON loadable in Perfetto
(https://ui.perfetto.dev) or ``chrome://tracing``::

    from repro.telemetry.trace import Tracer

    tracer = Tracer()
    with tracer.span("round", round=3, nodes=8):
        ...                              # any host-side work
    tracer.count("program_cache/hit")
    tracer.export_chrome("run.trace.json")

Each span record carries an ``id`` and the ``parent`` id of the span
that was open on the same thread when it began (None at the top), so a
span's self time is its duration minus what its children cover.

**Zero overhead when off.**  Tracing is opt-in twice over: nothing in the
hot paths allocates or formats unless a tracer is *installed* (the
ambient ``current_tracer()`` is None by default) and *enabled*.  The
instrumented call sites (``api.fit``, the executors' program dispatch,
``repro.serve``) guard every span behind a single ``is None`` check, and
all spans are HOST-side — no tracing call ever runs inside a jitted /
scanned / shard_map'd region, so traced and untraced fits execute the
same compiled program bit-for-bit (``tests/test_trace.py`` proves it).

Spans must be context-managed: ``with tracer.span(...)``.  The low-level
``span_begin``/``span_end`` pair exists only so the context manager has
something to wrap — ``tools/reprolint``'s ``span-discipline`` rule flags
any orphaned use in ``src/repro``.

Device time: a host span around a dispatch measures submission, not
execution.  Call sites that want device-complete timings fence with
``jax.block_until_ready`` before closing the span (the engine does this
for the loop span; ``telemetry.phases`` does it per phase).  An enabled
tracer's span is also a ``jax.profiler.TraceAnnotation`` of the same
name, so whenever a profiler trace is running (``jax.profiler.trace``)
every program span lands on the profiler's host plane, on the same clock
as the device's operations.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext

__all__ = [
    "Tracer",
    "activated",
    "current_tracer",
    "span",
]

#: monotonic clock in microseconds (the trace-event time unit)
def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


class Tracer:
    """Collects spans/counters/gauges for one run.

    Thread-safe: serve-path spans arrive from batcher worker threads;
    each thread's spans carry its own ``tid`` so Perfetto renders one
    track per thread.

    ``enabled=False`` builds a permanently-off tracer: every ``span``
    returns a shared null context and counters are dropped — handy for
    keeping one code path when tracing is configuration-driven.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        #: dicts: name, ts (us), dur (us), tid, tags, id, parent
        self.spans: list = []
        self.counters: dict = {}
        self.gauges: dict = {}
        self._lock = threading.Lock()
        self._tids: dict = {}  # thread ident -> small stable int
        self._ids = itertools.count()
        self._open = threading.local()  # per thread: the open span records
        self.t0_us = _now_us()

    # -- recording -----------------------------------------------------------

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.setdefault(ident, len(self._tids))
        return tid

    def _stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def span_begin(self, name: str, **tags) -> dict:
        """Open a span record (low level — use ``with tracer.span(...)``;
        the reprolint ``span-discipline`` rule flags direct calls)."""
        stack = self._stack()
        rec = {
            "name": name,
            "ts": _now_us(),
            "dur": None,
            "tid": self._tid(),
            "tags": tags,
            "id": next(self._ids),
            "parent": stack[-1]["id"] if stack else None,
        }
        stack.append(rec)
        with self._lock:
            self.spans.append(rec)
        return rec

    def span_end(self, rec: dict) -> None:
        rec["dur"] = _now_us() - rec["ts"]
        stack = self._stack()
        if rec in stack:
            stack.remove(rec)

    @contextmanager
    def _span(self, name: str, tags: dict):
        from jax.profiler import TraceAnnotation  # lazy: importable without jax

        rec = self.span_begin(name, **tags)
        try:
            with TraceAnnotation(name):
                yield rec
        finally:
            self.span_end(rec)

    def span(self, name: str, **tags):
        """Context manager timing the enclosed block::

            with tracer.span("aggregate", hop="inter_pod") as rec:
                ...
                rec["tags"]["bytes"] = nbytes   # tags may be added inside

        A disabled tracer returns a null context (no allocation beyond
        the call itself)."""
        if not self.enabled:
            return nullcontext()
        return self._span(name, tags)

    def count(self, name: str, n: float = 1) -> None:
        """Monotonic counter (cache hits, padded slots, …)."""
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name: str, value) -> None:
        """Last-value-wins gauge (queue depth, cache size, …)."""
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value

    # -- reading -------------------------------------------------------------

    def wall_s(self, name: str) -> float:
        """Total wall seconds across all closed spans named ``name``."""
        return sum(
            s["dur"] for s in self.spans
            if s["name"] == name and s["dur"] is not None
        ) / 1e6

    def summary(self) -> dict:
        """Per-span-name aggregate: count, total/mean/max wall seconds."""
        agg: dict = {}
        for s in self.spans:
            if s["dur"] is None:
                continue
            e = agg.setdefault(
                s["name"], {"count": 0, "total_s": 0.0, "max_s": 0.0}
            )
            e["count"] += 1
            e["total_s"] += s["dur"] / 1e6
            e["max_s"] = max(e["max_s"], s["dur"] / 1e6)
        for e in agg.values():
            e["mean_s"] = e["total_s"] / e["count"]
        return agg

    # -- export --------------------------------------------------------------

    def chrome_events(self) -> list:
        """The run as Chrome trace-event dicts: one complete (``"X"``)
        event per closed span, one counter (``"C"``) sample per counter
        and gauge.  Every event carries the schema keys ``ph`` / ``ts`` /
        ``pid`` / ``tid`` / ``name``."""
        pid = os.getpid()
        events = [
            {
                "ph": "M", "ts": 0, "pid": pid, "tid": 0,
                "name": "process_name",
                "args": {"name": "repro"},
            }
        ]
        for s in self.spans:
            if s["dur"] is None:
                continue  # still open (or orphaned) — not exportable
            events.append({
                "ph": "X",
                "ts": s["ts"],
                "dur": s["dur"],
                "pid": pid,
                "tid": s["tid"],
                "name": s["name"],
                "cat": s["name"].split("/")[0],
                "args": {k: _arg(v) for k, v in s["tags"].items()},
            })
        t_end = _now_us()
        for name, value in {**self.counters, **self.gauges}.items():
            events.append({
                "ph": "C", "ts": t_end, "pid": pid, "tid": 0,
                "name": name, "args": {"value": _arg(value)},
            })
        return events

    def export_chrome(self, path: str) -> str:
        """Write the trace-event JSON; returns ``path``.  Load it in
        Perfetto (https://ui.perfetto.dev) or summarize it with
        ``python tools/traceview.py <path>``."""
        payload = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
        }
        with open(path, "w") as f:
            json.dump(payload, f)
        return path


def _arg(v):
    """Trace-event args must be JSON: pass primitives, stringify the rest."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    try:  # numpy / 0-d jax scalars
        return v.item()
    except Exception:
        return str(v)


# ----------------------------------------------------------------------------
# Ambient tracer — how instrumented layers find the active run's tracer
# ----------------------------------------------------------------------------

_active = threading.local()


def current_tracer() -> Tracer | None:
    """The tracer installed for the current thread, or None (the
    zero-overhead default — instrumented call sites guard on this)."""
    t = getattr(_active, "value", None)
    if t is not None and not t.enabled:
        return None
    return t


@contextmanager
def activated(tracer: Tracer | None):
    """Install ``tracer`` as the ambient tracer for the enclosed block
    (``api.fit(..., tracer=...)`` wraps the whole run in this, so the
    executors' program-cache/dispatch spans land on the same timeline).
    ``None`` is a no-op install that keeps the enclosing tracer, so call
    sites stay unconditional.  Yields the tracer in effect inside."""
    if tracer is None:
        yield current_tracer()
        return
    prev = getattr(_active, "value", None)
    _active.value = tracer
    try:
        yield tracer
    finally:
        _active.value = prev


def span(name: str, **tags):
    """Span on the AMBIENT tracer — a null context when none is
    installed, so library code can trace unconditionally::

        from repro.telemetry import trace

        with trace.span("fit/ledger", scenarios=S):
            ...
    """
    t = current_tracer()
    if t is None:
        return nullcontext()
    return t._span(name, tags)
