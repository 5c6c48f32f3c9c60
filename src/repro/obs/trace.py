"""Span tracing: monotonic, ring-buffered, thread-safe — Perfetto-ready.

The recorder is deliberately tiny: a span is ONE completed interval
``(name, t0_ns, dur_ns, thread, attrs)`` appended to a lock-protected ring
buffer at ``__exit__`` time.  ``time.perf_counter_ns`` gives a monotonic
clock shared by every thread, so pooled uplink workers and async dispatch
windows land on one coherent timeline; the ring bound means a multi-day
population run can leave tracing on without growing memory.

Ambient recorder
----------------
Instrumented code (``rounds.py`` stages, the codecs, the CABAC engine, the
sharded store) calls the MODULE-LEVEL :func:`span` helper::

    from repro.obs import trace
    with trace.span("uplink.roundtrip", client=3):
        ...

which forwards to the process-wide active recorder.  The default is
:data:`NOOP` — a singleton whose ``span()`` returns a shared no-op context
manager, so an un-activated program pays one global read, one method call
and one with-block per site and records nothing (the CI overhead guard in
``scripts/trace_smoke.py`` measures exactly this cost).  The active
recorder is a plain module global, NOT a contextvar: thread-pool workers
spawned by ``Uplink`` must inherit it, and contextvars do not cross
``ThreadPoolExecutor.map``.  Forkserver process-pool workers live in
another process and never see the parent recorder — their codec work is
accounted parent-side at chunk granularity (documented in obs/README.md).

Exporter
--------
:func:`export_chrome_trace` writes the Chrome trace-event format ("X"
complete events, microsecond timestamps) that https://ui.perfetto.dev and
chrome://tracing open directly.  Nesting needs no parent ids: Chrome infers
it from interval containment per (pid, tid) track, which is exactly what a
with-block guarantees.

The profiler bridge
-------------------
A live span also opens a ``jax.profiler.TraceAnnotation`` of the same name
and attributes, so while a ``jax.profiler`` session runs every span lands
on the profiler's host timeline (one line per thread), in the same file
and on the same clock as the device operations; the attributes become the
event's stats.  The recorder keeps its own ``perf_counter_ns`` interval,
which differs from the profiler's by one constant offset
(tests/test_obs.py).  The executors additionally wrap ``client_round`` in
``jax.named_scope`` at bind time, and ``core.protocol.client_round`` names
its stages, so compiled HLO carries the stage names.  Telemetry off never
touches jax: :func:`span` returns the shared no-op first.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any

__all__ = [
    "Span", "SpanRecorder", "NoopRecorder", "NOOP",
    "get_recorder", "use_recorder", "span", "export_chrome_trace",
]

DEFAULT_RING = 65536


class Span:
    """One completed interval.  ``t0_ns`` is ``perf_counter_ns`` at entry;
    ``attrs`` is the keyword metadata the call site attached."""

    __slots__ = ("name", "t0_ns", "dur_ns", "thread", "attrs")

    def __init__(self, name: str, t0_ns: int, dur_ns: int, thread: int,
                 attrs: dict[str, Any] | None):
        self.name = name
        self.t0_ns = t0_ns
        self.dur_ns = dur_ns
        self.thread = thread
        self.attrs = attrs

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        return (f"Span({self.name!r}, {self.dur_ns / 1e6:.3f} ms, "
                f"thread={self.thread})")


_annotation = None    # jax.profiler.TraceAnnotation, bound by the first live span


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _ActiveSpan:
    """The context manager one ``recorder.span()`` call returns.

    Opens a ``jax.profiler.TraceAnnotation`` of the same name and
    attributes around its own interval (the profiler bridge), and records
    at ``__exit__`` — children therefore land in the buffer BEFORE their
    parent, which exporters and tests rely on (a parent's interval
    strictly contains its children's)."""

    __slots__ = ("_rec", "name", "attrs", "_t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str,
                 attrs: dict[str, Any] | None):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_ActiveSpan":
        self._ann = _trace_annotation()(self.name, **(self.attrs or {}))
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **attrs) -> None:
        """Attach attributes known only once the work has run; they reach
        the recorded span and the profiler's event alike."""
        self.attrs = {**(self.attrs or {}), **attrs}
        self._ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._rec._record(Span(self.name, self._t0, t1 - self._t0,
                               threading.get_ident(), self.attrs))


class _NoopSpan:
    """Shared, reusable no-op span (the telemetry-off fast path)."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def set(self, **attrs) -> None:
        pass

    def __exit__(self, *exc) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class NoopRecorder:
    """Records nothing; every ``span()`` returns the one shared no-op."""

    enabled = False

    def span(self, name: str, **attrs) -> _NoopSpan:
        return _NOOP_SPAN

    def drain(self) -> list[Span]:
        return []

    def __len__(self) -> int:
        return 0


NOOP = NoopRecorder()


class SpanRecorder:
    """Thread-safe ring buffer of completed spans.

    ``ring`` bounds memory: when full, the oldest spans drop (a long run
    keeps its recent history).  ``dropped`` counts what the ring evicted so
    exporters can say the trace is a suffix, not the whole run.
    """

    enabled = True

    def __init__(self, ring: int = DEFAULT_RING):
        if ring < 1:
            raise ValueError(f"ring must be >= 1, got {ring}")
        self._buf: deque[Span] = deque(maxlen=ring)
        self._lock = threading.Lock()
        self.dropped = 0

    def span(self, name: str, **attrs) -> _ActiveSpan:
        return _ActiveSpan(self, name, attrs or None)

    def _record(self, s: Span) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(s)

    def drain(self) -> list[Span]:
        """Snapshot AND clear the buffer (completion order: children before
        parents; sort by ``t0_ns`` for a timeline)."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
        return out

    def snapshot(self) -> list[Span]:
        """Non-destructive copy of the buffer."""
        with self._lock:
            return list(self._buf)

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


# ---------------------------------------------------------------- ambient

_active: SpanRecorder | NoopRecorder = NOOP


def get_recorder() -> SpanRecorder | NoopRecorder:
    return _active


class _UseRecorder:
    """Push/pop the ambient recorder (re-entrant; restores the previous)."""

    def __init__(self, rec: SpanRecorder | NoopRecorder):
        self._rec = rec

    def __enter__(self):
        global _active
        self._prev = _active
        _active = self._rec
        return self._rec

    def __exit__(self, *exc) -> None:
        global _active
        _active = self._prev


def use_recorder(rec: SpanRecorder | NoopRecorder) -> _UseRecorder:
    return _UseRecorder(rec)


def span(name: str, **attrs):
    """Open a span on the ambient recorder (no-op when none is active).

    A live span also annotates the ``jax.profiler`` timeline; with no
    recorder active this returns before touching jax or storing
    ``attrs``."""
    if _active is NOOP:          # fast path: skip the attrs dict build
        return _NOOP_SPAN
    return _active.span(name, **attrs)


# ---------------------------------------------------------------- exporters

def chrome_trace_events(spans: list[Span], *,
                        counters: list[dict[str, Any]] | None = None,
                        pid: int | None = None) -> list[dict[str, Any]]:
    """Spans -> Chrome trace-event dicts ("X" complete events, ts/dur µs).

    Timestamps rebase to the earliest span so the trace opens at t=0;
    thread ids remap to small consecutive integers (Perfetto track names
    stay readable).  ``counters`` optionally appends "C" counter events —
    ``{"name": ..., "ts_ns": ..., "values": {series: number}}`` — which
    Perfetto renders as per-round counter tracks.
    """
    pid = pid if pid is not None else os.getpid()
    ordered = sorted(spans, key=lambda s: s.t0_ns)
    t_base = ordered[0].t0_ns if ordered else 0
    tids: dict[int, int] = {}
    events: list[dict[str, Any]] = []
    for s in ordered:
        tid = tids.setdefault(s.thread, len(tids))
        ev = {"name": s.name, "ph": "X", "pid": pid, "tid": tid,
              "ts": (s.t0_ns - t_base) / 1e3, "dur": s.dur_ns / 1e3}
        if s.attrs:
            ev["args"] = s.attrs
        events.append(ev)
    for c in counters or []:
        events.append({"name": c["name"], "ph": "C", "pid": pid, "tid": 0,
                       "ts": max(0.0, (c["ts_ns"] - t_base) / 1e3),
                       "args": c["values"]})
    return events


def export_chrome_trace(spans: list[Span], path: str, *,
                        counters: list[dict[str, Any]] | None = None) -> int:
    """Write Chrome trace-event JSON (open at https://ui.perfetto.dev).

    Returns the number of events written.  The file is the object form
    (``{"traceEvents": [...]}``) — both Perfetto and chrome://tracing
    accept it, and it leaves room for metadata.
    """
    events = chrome_trace_events(spans, counters=counters)
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
        f.write("\n")
    return len(events)
