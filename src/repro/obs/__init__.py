"""Round-lifecycle telemetry: span tracing + typed metrics, one bundle.

``repro.obs`` is the observability layer the FL engine threads through the
whole round lifecycle (ISSUE 7 / ROADMAP bottleneck hunts): *when* each
stage ran (``obs.trace`` spans, on the ``jax.profiler`` timeline and
exported in Chrome trace-event format for Perfetto) and *how much* it moved (``obs.metrics`` counters /
gauges / histograms, snapshotted per round into ``RoundRecord.telemetry``).

The two halves meet in :class:`Telemetry` — the bundle an engine owns:

    tel = make_telemetry("trace")            # "off" | "metrics" | "trace"
    with tel.activate():                     # ambient for the whole run
        ... instrumented code calls trace.span() / metrics.count() ...
        snap = tel.round_snapshot(round_idx)  # None when mode="off"
    tel.export_chrome_trace("/tmp/run.trace.json")

Modes:

  * ``"off"``     — the shared no-op bundle.  Every instrumented site costs
    one global read + one no-op with-block; nothing allocates, nothing is
    recorded, and the CI guard (``scripts/trace_smoke.py``) asserts the
    total stays under 2% of a round.
  * ``"metrics"`` — the registry records, spans stay no-op (per-round
    numbers without timeline overhead — the long-run default).
  * ``"trace"``   — spans AND metrics (the Perfetto workflow).

Telemetry is observational by construction: it never touches RNG and never
feeds back into the simulation, so the engine's records are bitwise
identical with telemetry on or off (guarded in tests/test_obs.py).

See obs/README.md for the span taxonomy, exporter formats and how to open
a trace in Perfetto.
"""
from __future__ import annotations

from typing import Any

from repro.obs import metrics, trace
from repro.obs.metrics import (MetricsJsonlSink, MetricsRegistry,
                               NOOP_METRICS, NoopMetrics)
from repro.obs.trace import (NOOP, NoopRecorder, Span, SpanRecorder,
                             export_chrome_trace)

__all__ = [
    "trace", "metrics",
    "Telemetry", "make_telemetry", "TELEMETRY_MODES",
    "Span", "SpanRecorder", "NoopRecorder", "NOOP",
    "MetricsRegistry", "NoopMetrics", "NOOP_METRICS", "MetricsJsonlSink",
    "export_chrome_trace",
]

TELEMETRY_MODES = ("off", "metrics", "trace")


class _Activation:
    """Activate recorder + registry together; restores both on exit."""

    def __init__(self, tel: "Telemetry"):
        self._tel = tel

    def __enter__(self) -> "Telemetry":
        self._rec = trace.use_recorder(self._tel.recorder)
        self._reg = metrics.use_registry(self._tel.metrics)
        self._rec.__enter__()
        self._reg.__enter__()
        return self._tel

    def __exit__(self, *exc) -> None:
        self._reg.__exit__(*exc)
        self._rec.__exit__(*exc)


class Telemetry:
    """One run's telemetry: a recorder, a registry, an optional JSONL sink.

    ``round_snapshot`` is what the engine calls once per aggregation: it
    closes the metrics round (counter deltas, gauge values, histogram
    summaries), streams the snapshot to the sink when one is attached,
    and remembers the wall-clock position so Chrome counter tracks line
    up with the span timeline.
    """

    def __init__(self, mode: str = "off", *, ring: int = trace.DEFAULT_RING,
                 metrics_out: str | None = None):
        if mode not in TELEMETRY_MODES:
            known = ", ".join(TELEMETRY_MODES)
            raise ValueError(f"unknown telemetry mode: {mode!r} "
                             f"(known: {known})")
        self.mode = mode
        self.recorder = trace.SpanRecorder(ring) if mode == "trace" else NOOP
        self.metrics = (MetricsRegistry() if mode in ("metrics", "trace")
                        else NOOP_METRICS)
        self.sink = (MetricsJsonlSink(metrics_out)
                     if metrics_out is not None and mode != "off" else None)
        self._counter_marks: list[dict[str, Any]] = []

    @property
    def on(self) -> bool:
        return self.mode != "off"

    def activate(self) -> _Activation:
        return _Activation(self)

    def round_snapshot(self, round_idx: int) -> dict[str, Any] | None:
        if not self.on:
            return None
        snap = self.metrics.snapshot_round()
        if self.sink is not None:
            self.sink.write(round_idx, snap)
        if self.mode == "trace":
            import time
            self._counter_marks.append({
                "ts_ns": time.perf_counter_ns(),
                "round": round_idx,
                "counters": snap["counters"],
            })
        return snap

    # -- exports -----------------------------------------------------------

    def _counter_events(self) -> list[dict[str, Any]]:
        """Per-round byte counters as Chrome "C" events (Perfetto tracks)."""
        events = []
        for mark in self._counter_marks:
            for name in ("uplink.bytes", "downlink.bytes"):
                if name in mark["counters"]:
                    events.append({"name": name, "ts_ns": mark["ts_ns"],
                                   "values": {"bytes":
                                              mark["counters"][name]}})
        return events

    def export_chrome_trace(self, path: str) -> int:
        """Write the recorded spans (+ per-round counters) as Chrome
        trace-event JSON; returns the event count (0 when mode != trace)."""
        if self.recorder is NOOP:
            return 0
        return export_chrome_trace(self.recorder.snapshot(), path,
                                   counters=self._counter_events())

    def close(self) -> None:
        if self.sink is not None:
            self.sink.close()


_OFF = Telemetry("off")


def make_telemetry(mode: str = "off", *, ring: int = trace.DEFAULT_RING,
                   metrics_out: str | None = None) -> Telemetry:
    """Build a bundle; ``"off"`` returns the shared no-op singleton."""
    if mode == "off" and metrics_out is None:
        return _OFF
    return Telemetry(mode, ring=ring, metrics_out=metrics_out)
