"""The profile's program spans and device scopes (``bench/lib/profile_events.py``)
and the per-layer readers built on them, on hand-made events and on a
profile the CPU backend writes."""
import importlib.util
import types
from pathlib import Path

import pytest

from bench.lib import profile_events

METRICS = Path(__file__).resolve().parents[1] / "metrics"
TW = "jit(named_client_round)/vmap(fl.client_round)/train_weights/while"
TS = "jit(named_client_round)/vmap(fl.client_round)/train_scales/while/body"
CP = "jit(named_client_round)/vmap(fl.client_round)/compress/top_k"


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _events():
    """Two rounds, window [0, 200): host spans with their attributes and
    device operations with their scope paths."""
    host = [["bench.round", 0, 100, {}], ["bench.round", 100, 200, {}],
            ["nnc.decode", 10, 60, {"nbytes": 9}],
            ["cabac.decode", 12, 20, {"bins": 40}],
            ["golomb.decode", 20, 30, {"values": 7}],
            ["cabac.decode", 30, 50, {"bins": 60}],
            ["cabac.decode", 140, 150, {"bins": 100}],
            ["cabac.decode", 250, 260, {"bins": 999}],    # after the window
            ["uplink.fetch", 60, 80, {}],
            ["uplink.fetch.wait", 60, 75, {}],
            ["uplink.fetch.copy", 75, 80, {}],
            ["evaluate", 180, 195, {}], ["evaluate.wait", 181, 191, {}]]
    ops = {"0": [["while.1", 0, 40, TW + "/while"],
                 ["conv", 5, 15, TW + "/body/conv_general_dilated"],
                 ["select_and_scatter", 20, 30,
                  TW + "/body/transpose(jvp(vgg))/reduce_window_max"],
                 ["top_k.2", 40, 44, CP],
                 ["while.2", 44, 90, TS],
                 ["fusion.7", 50, 60, TS + "/mul"],
                 ["copy.3", 95, 97, ""],
                 ["fusion.9", 150, 210, TW + "/add"]]}
    return {"host": host, "ops": ops}


def _ctx(rounds=2):
    return types.SimpleNamespace(rounds=rounds, cell={"name": "cell"},
                                 red=types.SimpleNamespace(lo=0, hi=200))


def test_scopes_strip_transformation_wrappers():
    got = profile_events.scopes_of(
        "jit(f)/vmap(fl.client_round)/train_weights/while/body/"
        "transpose(jvp(vgg))/reduce_window_max")
    assert {"fl.client_round", "train_weights", "vgg",
            "reduce_window_max"} <= got
    assert "compress" not in got
    assert profile_events.scopes_of("") == {""}


def test_detail_on_hand_made_events():
    d = profile_events.Detail(_events(), 0, 200)
    assert d.span_union_ns({"cabac.decode"}) == 8 + 20 + 10
    assert d.span_attr_sum({"cabac.decode"}, "bins") == 200
    assert d.span_attr_sum({"golomb.decode"}, "values") == 7
    assert d.span_union_ns({"uplink.fetch.wait", "evaluate.wait"}) == 25
    by = d.scope_self_ns(profile_events.STAGES)
    # the while loops keep only the time their bodies leave; the last
    # operation is clipped to the window
    assert by == {"train_weights": (40 - 10 - 10) + 10 + 10 + 50,
                  "compress": 4, "train_scales": 46 - 10 + 10, "": 2}
    # self times partition the busy time of the first chip
    assert sum(by.values()) == 40 + 4 + 46 + 2 + 50


def test_readers_on_hand_made_events(monkeypatch):
    d = profile_events.Detail(_events(), 0, 200)
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    ctx = _ctx()
    assert _reader("cabac_decode_host_s")(ctx) == pytest.approx(38 / 2e9)
    assert _reader("cabac_decode_ns_per_bin")(ctx) == pytest.approx(38 / 200)
    assert _reader("host_wait_device_s")(ctx) == pytest.approx(25 / 2e9)
    assert _reader("train_weights_device_s")(ctx) == pytest.approx(90 / 2e9)
    assert _reader("train_scales_device_s")(ctx) == pytest.approx(46 / 2e9)


READERS = ("cabac_decode_host_s", "cabac_decode_ns_per_bin",
       "host_wait_device_s", "train_weights_device_s",
       "train_scales_device_s")


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_a_program_without_the_spans(
        name, monkeypatch):
    """A program that opens none of these spans and names no stage leaves
    every reader of them with nothing: None, not an error; so does a run
    that left no profile."""
    ev = _events()
    bare = {"host": [h for h in ev["host"] if h[0] == "bench.round"],
            "ops": {"0": [[n, s, e, ""] for n, s, e, _ in ev["ops"]["0"]]}}
    d = profile_events.Detail(bare, 0, 200)
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    assert _reader(name)(_ctx()) is None
    monkeypatch.setattr(profile_events, "of", lambda ctx: None)
    assert _reader(name)(_ctx()) is None


def test_op_scopes_reads_the_event_metadata(tmp_path):
    """The scope path is a stat of the operation's event metadata, held
    as a string or as a reference to a stat name; only TPU planes count."""
    xs = profile_events._xspace_message()()
    for name in ("/device:TPU:0", "/host:CPU"):
        plane = xs.planes.add(name=name.encode())
        for key, text in ((1, b"tf_op"), (2, TS.encode() + b"/mul:"),
                          (3, b"flops")):
            plane.stat_metadata.add(key=key).value.name = text
        md = plane.event_metadata.add(key=7).value
        md.name = b"%add.1 = f32[] add(f32[] %a, f32[] %b)"
        md.stats.add(metadata_id=3, str_value=b"")
        md.stats.add(metadata_id=1, str_value=CP.encode() + b":")
        md = plane.event_metadata.add(key=8).value
        md.name = b"%mul.2 = f32[] multiply(f32[] %a, f32[] %b)"
        md.stats.add(metadata_id=1, ref_value=2)
        plane.event_metadata.add(key=9).value.name = b"%copy.3 = f32[] copy()"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xs.SerializeToString())
    assert profile_events.op_scopes(path) == {
        "%add.1 = f32[] add(f32[] %a, f32[] %b)": CP + ":",
        "%mul.2 = f32[] multiply(f32[] %a, f32[] %b)": TS + "/mul:"}


def test_of_returns_none_without_a_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(profile_events, "OUT_DIR", tmp_path)
    assert profile_events.of(_ctx()) is None


def test_program_spans_read_back_from_a_cpu_profile(tmp_path):
    """The program's spans, with their attributes, are host events of the
    profile ``jax.profiler`` writes (CPU backend; the harness's options)."""
    import jax

    from repro.obs import trace as obs_trace

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    rec = obs_trace.SpanRecorder()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.round"):
            with obs_trace.use_recorder(rec):
                for n in (3, 5):
                    with obs_trace.span("cabac.decode", bins=n):
                        pass
                with obs_trace.span("uplink.fetch.wait"):
                    pass
    finally:
        jax.profiler.stop_trace()
    ev = profile_events.load(tmp_path)
    (rnd,) = [h for h in ev["host"] if h[0] == "bench.round"]
    d = profile_events.Detail(ev, rnd[1], rnd[2])
    assert d.span_attr_sum({"cabac.decode"}, "bins") == 8
    assert 0 < d.span_union_ns({"cabac.decode"}) < rnd[2] - rnd[1]
    assert d.span_union_ns({"uplink.fetch.wait"}) > 0
    assert ev["ops"] == {}      # no TPU plane on the CPU
    (xplane,) = tmp_path.glob("**/*.xplane.pb")
    assert profile_events.op_scopes(xplane) == {}
