"""Behavioural contract of the telemetry layer (repro.obs):

* recorder semantics — span nesting/containment under a thread pool,
  ring-buffer drop accounting, ambient activation, zero-cost off path,
* export — the Chrome trace-event JSON is structurally valid (rebased
  monotone timeline, pid/tid on every event, counter tracks),
* the profiler bridge — under ``jax.profiler`` every span is a host event
  of the profile, one constant clock offset from the recorder; the host's
  waits on the device are spans of their own; telemetry off opens none,
  and ``"trace"`` fetches nothing from the device that ``"off"`` does not,
* engine wiring — on the three seed-pin scenarios the per-round
  ``uplink.bytes``/``downlink.bytes`` counters equal the RoundRecord
  fields EXACTLY while the pinned byte totals still hold (telemetry is
  observational: it cannot move a byte),
* codec anatomy — ``payload_sections`` sums to ``len(payload)`` for every
  registered codec across schema versions and ternary payloads,
* RunResult helpers — ``metric_series``/``mean_metric`` tolerate records
  missing a metric key (regression: early-exit rounds used to KeyError).
"""
import concurrent.futures
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro import comms
from repro.core import quant as quant_lib
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import EngineConfig, RoundRecord, RunResult, run_simulation
from repro.models import cnn
from repro.obs import Telemetry, make_telemetry
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

# ------------------------------------------------------------- fixtures

_PINS = {  # PR-2 pins (tests/test_rounds.py) — telemetry must not move them
    "fsfl": dict(cfg=dict(method="sparse", fixed_sparsity=0.9),
                 up_bytes=[727, 712]),
    "stc": dict(cfg=dict(method="ternary", error_feedback=True,
                         fixed_sparsity=0.9, structured=False),
                up_bytes=[561, 566]),
    "fedavg_nnc": dict(cfg=dict(method="none"), up_bytes=[3439, 3429]),
}


@pytest.fixture(scope="module")
def tiny2():
    task = synthetic.ImageTask("t", num_classes=4, channels=3, size=32,
                               prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=2)
    model = cnn.make_vgg("vgg_tiny_obs", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    return model, splits


def _spans_by_name(rec):
    out = {}
    for s in rec.snapshot():
        out.setdefault(s.name, []).append(s)
    return out


# ------------------------------------------------------------- recorder

def test_span_records_at_exit_with_containment():
    rec = obs_trace.SpanRecorder()
    with rec.span("outer", k=1):
        with rec.span("inner"):
            pass
    inner, outer = rec.drain()  # children complete (and record) first
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.t0_ns <= inner.t0_ns
    assert inner.t0_ns + inner.dur_ns <= outer.t0_ns + outer.dur_ns
    assert outer.attrs == {"k": 1}
    assert inner.thread == outer.thread


def test_span_nesting_under_thread_pool():
    """Worker threads inherit the ambient recorder; per-thread nesting is
    recoverable from (thread, interval) containment — the invariant the
    Chrome-trace tid lanes rely on under the parallel uplink pool."""
    import threading

    rec = obs_trace.SpanRecorder()
    gate = threading.Barrier(3)  # forces 3 genuinely concurrent workers

    def work(i):
        with rec.span("task", i=i):
            gate.wait(timeout=10)
            with rec.span("step", i=i):
                pass
        return i

    with rec.span("pool"):
        with concurrent.futures.ThreadPoolExecutor(max_workers=3) as pool:
            assert sorted(pool.map(work, range(6))) == list(range(6))
    by = _spans_by_name(rec)
    assert len(by["task"]) == len(by["step"]) == 6
    threads = {s.thread for s in by["task"]}
    assert len(threads) == 3  # actually ran on pool threads
    for step in by["step"]:  # each step nests in its own task, same thread
        parents = [t for t in by["task"]
                   if t.thread == step.thread and t.attrs == step.attrs
                   and t.t0_ns <= step.t0_ns
                   and step.t0_ns + step.dur_ns <= t.t0_ns + t.dur_ns]
        assert len(parents) == 1
    # the pool span on the main thread encloses every worker span in time
    (pool_span,) = by["pool"]
    for s in by["task"] + by["step"]:
        assert pool_span.t0_ns <= s.t0_ns
        assert s.t0_ns + s.dur_ns <= pool_span.t0_ns + pool_span.dur_ns


def test_ring_buffer_drops_oldest_and_counts():
    rec = obs_trace.SpanRecorder(ring=4)
    for i in range(10):
        with rec.span("s", i=i):
            pass
    spans = rec.drain()
    assert [s.attrs["i"] for s in spans] == [6, 7, 8, 9]
    assert rec.dropped == 6
    assert len(rec) == 0  # drain empties the ring


def test_ambient_activation_and_noop_fast_path():
    assert obs_trace.get_recorder() is obs_trace.NOOP
    # off: the module-level span() returns the shared no-op singleton
    a = obs_trace.span("x")
    b = obs_trace.span("y", k=2)
    assert a is b
    rec = obs_trace.SpanRecorder()
    with obs_trace.use_recorder(rec):
        assert obs_trace.get_recorder() is rec
        with obs_trace.span("live"):
            pass
    assert obs_trace.get_recorder() is obs_trace.NOOP
    assert [s.name for s in rec.drain()] == ["live"]


# ------------------------------------------------------------- metrics

def test_metrics_snapshot_deltas_and_histogram_reset():
    m = obs_metrics.MetricsRegistry()
    m.count("bytes", 100)
    m.gauge("acc", 0.5)
    m.observe("lat", 1.0)
    m.observe("lat", 3.0)
    s1 = m.snapshot_round()
    assert s1["counters"] == {"bytes": 100}
    assert s1["counters_total"] == {"bytes": 100}
    assert s1["gauges"] == {"acc": 0.5}
    assert s1["histograms"]["lat"] == {"count": 2, "sum": 4.0,
                                       "min": 1.0, "max": 3.0, "mean": 2.0}
    m.count("bytes", 7)
    s2 = m.snapshot_round()
    assert s2["counters"] == {"bytes": 7}          # per-round delta
    assert s2["counters_total"] == {"bytes": 107}  # cumulative
    assert "lat" in s1["histograms"] and not s2["histograms"]  # reset
    assert obs_metrics.get_registry() is obs_metrics.NOOP_METRICS
    obs_metrics.count("ignored", 5)  # off: must be a no-op, not an error


def test_metrics_jsonl_sink(tmp_path):
    out = tmp_path / "metrics.jsonl"
    tel = make_telemetry("metrics", metrics_out=str(out))
    with tel.activate():
        obs_metrics.count("uplink.bytes", 11)
        tel.round_snapshot(1)
        obs_metrics.count("uplink.bytes", 22)
        tel.round_snapshot(2)
    tel.close()
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert [ln["round"] for ln in lines] == [1, 2]
    assert [ln["counters"]["uplink.bytes"] for ln in lines] == [11, 22]


# ------------------------------------------------------------- export

def test_chrome_trace_export_is_valid(tmp_path):
    tel = make_telemetry("trace")
    with tel.activate():
        with obs_trace.span("round", n=1):
            with obs_trace.span("uplink.intake", n=2):
                pass
        obs_metrics.count("uplink.bytes", 123)
        tel.round_snapshot(1)
    out = tmp_path / "t.json"
    n = tel.export_chrome_trace(str(out))
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert n == len(events)
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"round", "uplink.intake"}
    for e in events:
        assert {"pid", "tid", "ts", "name"} <= set(e)
    ts = sorted(e["ts"] for e in xs)
    assert ts[0] == 0.0  # rebased to the earliest span
    rnd = next(e for e in xs if e["name"] == "round")
    kid = next(e for e in xs if e["name"] == "uplink.intake")
    assert rnd["ts"] <= kid["ts"]
    assert kid["ts"] + kid["dur"] <= rnd["ts"] + rnd["dur"] + 1e-9
    cs = [e for e in events if e["ph"] == "C"]
    assert any(e["name"] == "uplink.bytes"
               and e["args"] == {"bytes": 123} for e in cs)


def test_off_telemetry_is_inert(tmp_path):
    tel = make_telemetry("off")
    assert not tel.on
    with tel.activate():
        with obs_trace.span("ghost"):
            pass
        obs_metrics.count("ghost", 1)
        assert tel.round_snapshot(1) is None
    assert tel.export_chrome_trace(str(tmp_path / "e.json")) == 0
    assert tel.recorder is obs_trace.NOOP  # no span opens a profiler event


# ------------------------------------------------------------- engine wiring

@pytest.mark.parametrize("name", ["fsfl", "stc", "fedavg_nnc"])
def test_engine_counters_equal_round_records_on_pins(tiny2, name):
    """On each seed-pin scenario the snapshot counters equal the
    RoundRecord byte fields exactly AND the pins still hold — telemetry
    observes the simulation without perturbing it."""
    model, splits = tiny2
    pin = _PINS[name]
    cfg = ProtocolConfig(name=name, batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(telemetry="metrics"))
    assert [r.up_bytes for r in res.records] == pin["up_bytes"]
    for rec in res.records:
        snap = rec.telemetry
        assert snap["counters"]["uplink.bytes"] == rec.up_bytes
        assert snap["counters"].get("downlink.bytes", 0) == rec.down_bytes
        secs = {k: v for k, v in snap["counters"].items()
                if k.startswith("uplink.section.")}
        assert sum(secs.values()) == rec.up_bytes  # anatomy covers the wire
        assert not any(k.startswith(("update.sparsity.", "residual.norm."))
                       for k in snap["gauges"])  # no per-leaf device fetch


def test_async_windows_trace_and_batch_histogram(tiny2):
    """The async scheduler's dispatch windows show up as
    local_train.window spans and an async.batch_size histogram."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(mode="async",
                                             telemetry="trace"))
    tel = res.telemetry
    assert isinstance(tel, Telemetry)
    names = {s.name for s in tel.recorder.snapshot()}
    assert "local_train.window" in names
    assert "uplink.roundtrip_batch" in names
    hist = res.records[-1].telemetry["histograms"].get("async.batch_size")
    assert hist is not None and hist["count"] >= 1


def test_streaming_ingest_telemetry_counters_and_spans(tiny2):
    """Streaming ingest under telemetry: ingest.decode/ingest.fold spans
    appear, the payload counter equals the cohort, the queue-depth gauge
    is present — and the fsfl seed pin still holds (telemetry observes the
    ingest without perturbing it)."""
    model, splits = tiny2
    pin = _PINS["fsfl"]
    cfg = ProtocolConfig(name="fsfl", batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(ingest="streaming",
                                             telemetry="trace"))
    assert [r.up_bytes for r in res.records] == pin["up_bytes"]
    names = {s.name for s in res.telemetry.recorder.snapshot()}
    assert {"ingest.decode", "ingest.fold",
            "uplink.encode_batch"} <= names
    snap = res.records[0].telemetry
    assert snap["counters"]["ingest.payloads"] == 2     # both clients
    assert snap["counters"].get("ingest.rejected", 0) == 0
    assert "ingest.queue_depth" in snap["gauges"]
    assert "ingest.payloads_per_s" in snap["gauges"]


def test_streaming_ingest_telemetry_off_is_deterministic(tiny2):
    """The telemetry-off determinism pin extends to ingest: a streaming
    run with telemetry off equals the traced run record-for-record."""
    model, splits = tiny2
    pin = _PINS["fsfl"]
    cfg = ProtocolConfig(name="fsfl", batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    on = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                        engine=EngineConfig(ingest="streaming",
                                            telemetry="metrics"))
    off = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(ingest="streaming"))
    assert [r.up_bytes for r in off.records] == pin["up_bytes"]
    for a, b in zip(on.records, off.records):
        assert (a.up_bytes, a.test_acc, a.train_loss) == \
            (b.up_bytes, b.test_acc, b.train_loss)


def test_device_encode_telemetry_off_is_deterministic(tiny2):
    """The telemetry-off determinism pin extends to the device cohort
    encode: the uplink.device_encode span and uplink.kernel_dispatches
    counter observe the fused path without moving a byte — traced and
    silent device runs agree record-for-record on the frozen pin."""
    model, splits = tiny2
    pin = _PINS["fsfl"]
    cfg = ProtocolConfig(name="fsfl", batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    on = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                        engine=EngineConfig(device_encode=True,
                                            telemetry="trace"))
    off = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(device_encode=True))
    assert [r.up_bytes for r in off.records] == pin["up_bytes"]
    for a, b in zip(on.records, off.records):
        assert (a.up_bytes, a.test_acc, a.train_loss) == \
            (b.up_bytes, b.test_acc, b.train_loss)
    names = {s.name for s in on.telemetry.recorder.snapshot()}
    assert "uplink.device_encode" in names
    assert "uplink.fetch" not in names  # the bulk fetch is gone
    snap = on.records[0].telemetry
    assert snap["counters"]["uplink.kernel_dispatches"] == 1


# ------------------------------------------------------------- profiler bridge

def _fsfl_cfg():
    return ProtocolConfig(name="fsfl", batch_size=32, local_lr=2e-3,
                          **_PINS["fsfl"]["cfg"])


def _host_events(trace_dir) -> dict:
    """{name: [(start_ns, stats)]} of every host event in the profile."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                        recursive=True)
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (e.start_ns, dict(e.stats)))
    return out


def test_spans_land_on_the_profiler_timeline(tiny2, tmp_path):
    """Under ``jax.profiler`` every recorder span of a traced engine round
    is also a host event of the same name and attributes, and the two
    clocks differ by one constant offset: what lets a benchmark read the
    program's spans on the device timeline."""
    model, splits = tiny2
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        res = run_simulation(model, _fsfl_cfg(), splits, 1,
                             jax.random.PRNGKey(7),
                             engine=EngineConfig(telemetry="trace"))
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    by = _spans_by_name(res.telemetry.recorder)
    assert {"round", "cabac.decode", "golomb.decode", "uplink.fetch.wait",
            "evaluate.wait", "executor.run_shared"} <= set(by)
    offsets = []
    for name, spans in by.items():
        evs = sorted(events.get(name, []), key=lambda e: e[0])
        assert len(evs) == len(spans), name
        for s, (start, stats) in zip(sorted(spans, key=lambda s: s.t0_ns),
                                     evs):
            offsets.append(s.t0_ns - start)
            if name == "cabac.decode":
                assert stats["bins"] == s.attrs["bins"]
    assert max(offsets) - min(offsets) < 200_000  # ns


def test_fetch_wait_and_copy_nest_in_the_fetch(tiny2):
    """The host's wait on the device is its own span: ``uplink.fetch.wait``
    then ``uplink.fetch.copy`` inside each ``uplink.fetch``, and
    ``evaluate.wait`` inside ``evaluate``."""
    model, splits = tiny2
    res = run_simulation(model, _fsfl_cfg(), splits, 1,
                         jax.random.PRNGKey(7),
                         engine=EngineConfig(telemetry="trace"))
    by = _spans_by_name(res.telemetry.recorder)

    def inside(child, parent):
        return (child.thread == parent.thread
                and parent.t0_ns <= child.t0_ns
                and child.t0_ns + child.dur_ns
                <= parent.t0_ns + parent.dur_ns)

    for outer, kids in (("uplink.fetch", ("uplink.fetch.wait",
                                          "uplink.fetch.copy")),
                        ("evaluate", ("evaluate.wait",))):
        assert by[outer]
        for parent in by[outer]:
            got = [[c for c in by[k] if inside(c, parent)] for k in kids]
            assert all(len(g) == 1 for g in got), (outer, got)
            if len(got) == 2:  # the wait ends before the copy starts
                (wait,), (copy,) = got
                assert wait.t0_ns + wait.dur_ns <= copy.t0_ns


def test_off_spans_touch_no_jax_and_trace_fetches_nothing_more(
        tiny2, monkeypatch):
    """Telemetry off: no span site opens a recorder span or a profiler
    annotation.  Telemetry on: the same records, bit for bit, and no more
    device-to-host fetches than off."""
    model, splits = tiny2
    fetches = []
    real_get = jax.device_get

    def counting_get(x):
        fetches.append(1)
        return real_get(x)

    def refuse(*a, **k):
        raise AssertionError("telemetry off opened a live span")

    monkeypatch.setattr(jax, "device_get", counting_get)
    runs = {}
    for mode in ("off", "trace"):
        with monkeypatch.context() as m:
            if mode == "off":
                m.setattr(obs_trace, "_trace_annotation", refuse)
                m.setattr(obs_trace.SpanRecorder, "span", refuse)
            fetches.clear()
            res = run_simulation(model, _fsfl_cfg(), splits, 2,
                                 jax.random.PRNGKey(7),
                                 engine=EngineConfig(telemetry=mode))
            runs[mode] = (res.records, len(fetches))
    (off, n_off), (on, n_on) = runs["off"], runs["trace"]
    assert n_on == n_off > 0
    fields = [f.name for f in dataclasses.fields(RoundRecord)
              if f.name not in ("telemetry", "wall_s")]
    for a, b in zip(off, on):
        assert [getattr(a, f) for f in fields] == \
            [getattr(b, f) for f in fields]


# ------------------------------------------------------------- codec anatomy

def _mini_update(ternary=False, version=1):
    rng = np.random.default_rng(3)
    shapes = {"conv": {"w": (4, 3, 3, 3), "b": (4,)}}
    q = quant_lib.QuantConfig()
    params_t = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                            shapes, is_leaf=lambda x: isinstance(x, tuple))
    if ternary:
        lv = jax.tree.map(
            lambda s: rng.integers(-1, 2, s).astype(np.int32), shapes,
            is_leaf=lambda x: isinstance(x, tuple))
        recon = jax.tree.map(lambda l: np.float32(0.01) * np.sign(l), lv)
    else:
        lv = jax.tree.map(
            lambda s: (rng.integers(-20, 21, s)
                       * (rng.random(s) < 0.3)).astype(np.int32), shapes,
            is_leaf=lambda x: isinstance(x, tuple))
        recon = jax.tree.map(
            lambda l: l.astype(np.float32) * np.float32(q.step_size), lv)
    bn = ({"bn0": {"mean": jax.ShapeDtypeStruct((4,), np.float32)}}
          if version == 2 else None)
    spec = comms.WireSpec(params=params_t, step_size=q.step_size,
                          fine_step_size=q.fine_step_size, ternary=ternary,
                          bn=bn, version=version)
    bn_val = ({"bn0": {"mean": np.arange(4, dtype=np.float32)}}
              if version == 2 else None)
    upd = comms.ClientUpdate(lv, None, recon, None, bn=bn_val)
    return upd, spec


@pytest.mark.parametrize("codec_name", comms.list_codecs())
@pytest.mark.parametrize("ternary,version", [(False, 1), (False, 2),
                                             (True, 1)])
def test_payload_sections_sum_to_len(codec_name, ternary, version):
    codec = comms.get_codec(codec_name)
    upd, spec = _mini_update(ternary=ternary, version=version)
    payload = codec.encode(upd, spec)
    sections = codec.payload_sections(payload, spec)
    assert all(v >= 0 for v in sections.values()), sections
    assert sum(sections.values()) == len(payload), (codec_name, sections)


# ------------------------------------------------------------- RunResult

def _rec(n, train_loss=0.5, telemetry=None):
    return RoundRecord(round=n, test_acc=0.5, up_bytes=10, down_bytes=0,
                       cum_bytes=10 * n, mean_val_acc=0.5,
                       update_sparsity=0.9, train_loss=train_loss,
                       wall_s=0.1, participants=(0,), telemetry=telemetry)


def test_metric_helpers_tolerate_absent_metrics():
    """Regression: async rounds whose whole window churned carry NaN
    metrics — the helpers must skip those rounds, not propagate NaN."""
    res = RunResult("t", records=[_rec(1, train_loss=0.9),
                                  _rec(2, train_loss=float("nan")),
                                  _rec(3, train_loss=0.3)])
    assert res.metric_series("train_loss") == [(1, 0.9), (3, 0.3)]
    assert res.mean_metric("train_loss") == pytest.approx(0.6)
    assert res.metric_series("no_such_metric") == []
    assert np.isnan(res.mean_metric("no_such_metric"))


def test_round_record_telemetry_excluded_from_parity():
    a, b = _rec(1), _rec(1, telemetry={"counters": {"uplink.bytes": 10}})
    fields = [f.name for f in dataclasses.fields(RoundRecord)
              if f.name != "telemetry"]
    assert all(getattr(a, f) == getattr(b, f) for f in fields)
