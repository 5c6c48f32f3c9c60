"""The readers of the exp-Golomb parse's ``golomb.decode`` spans
(``golomb_decode_host_s``, ``golomb_decode_ns_per_value``) on hand-made
profile events and on a profile the CPU backend writes."""
import importlib.util
import types
from pathlib import Path

import numpy as np
import pytest

from bench.lib import profile_events

METRICS = Path(__file__).resolve().parents[1] / "metrics"
READERS = ("golomb_decode_host_s", "golomb_decode_ns_per_value")


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _events():
    """Two rounds, window [0, 200): parse spans inside decodes, two of them
    on concurrent threads (overlapping: their union counts once), one
    after the window."""
    host = [["bench.round", 0, 100, {}], ["bench.round", 100, 200, {}],
            ["nnc.decode", 10, 60, {"nbytes": 9}],
            ["cabac.decode", 12, 20, {"bins": 40}],
            ["golomb.decode", 20, 30, {"values": 3000, "vector": 1,
                                       "resync_steps": 12}],
            ["golomb.decode", 30, 32, {"values": 7, "vector": 0,
                                       "resync_steps": 0}],
            ["golomb.decode", 120, 140, {"values": 5000, "vector": 1,
                                         "resync_steps": 30}],
            ["golomb.decode", 130, 150, {"values": 2000, "vector": 1,
                                         "resync_steps": 9}],
            ["golomb.decode", 250, 260, {"values": 999}]]   # after the window
    return {"host": host, "ops": {}}


def _ctx(rounds=2):
    return types.SimpleNamespace(rounds=rounds, cell={"name": "cell"},
                                 red=types.SimpleNamespace(lo=0, hi=200))


def test_readers_on_hand_made_events(monkeypatch):
    d = profile_events.Detail(_events(), 0, 200)
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    union = 10 + 2 + 30                     # [120, 150) once
    assert _reader("golomb_decode_host_s")(_ctx()) == pytest.approx(
        union / 2e9)
    assert _reader("golomb_decode_ns_per_value")(_ctx()) == pytest.approx(
        union / (3000 + 7 + 5000 + 2000))


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_the_spans(name, monkeypatch):
    """A run whose program opens no parse span, or that left no profile,
    leaves both readers with nothing: None, not an error."""
    ev = _events()
    bare = {"host": [h for h in ev["host"] if h[0] != "golomb.decode"],
            "ops": {}}
    d = profile_events.Detail(bare, 0, 200)
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    assert _reader(name)(_ctx()) is None
    monkeypatch.setattr(profile_events, "of", lambda ctx: None)
    assert _reader(name)(_ctx()) is None


def test_parse_spans_read_back_from_a_cpu_profile(tmp_path, monkeypatch):
    """A real decode under the profiler (CPU backend, the harness's
    options): the readers see every parse span and its ``values``."""
    import jax

    from repro.coding import golomb
    from repro.coding.bitstream import BitReader, BitWriter
    from repro.obs import trace as obs_trace

    r = np.random.default_rng(0)
    plan = [(3, 5000), (5, 40)]
    w = BitWriter()
    for k, n in plan:
        golomb.encode_egk(w, r.geometric(1.0 / (1 << k), n) - 1, k)
    data = w.to_bytes()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.round"):
            with obs_trace.use_recorder(obs_trace.SpanRecorder()):
                rd = BitReader(data)
                for k, n in plan:
                    golomb.decode_egk(rd, n, k)
    finally:
        jax.profiler.stop_trace()
    ev = profile_events.load(tmp_path)
    (rnd,) = [h for h in ev["host"] if h[0] == "bench.round"]
    d = profile_events.Detail(ev, rnd[1], rnd[2])
    spans = [h for h in d.host if h[0] == "golomb.decode"]
    assert [int(h[3]["vector"]) for h in spans] == [1, 0]
    assert int(spans[0][3]["resync_steps"]) > 0
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    ctx = _ctx(rounds=1)
    host_s = _reader("golomb_decode_host_s")(ctx)
    assert 0 < host_s < (rnd[2] - rnd[1]) / 1e9
    assert _reader("golomb_decode_ns_per_value")(ctx) == pytest.approx(
        host_s * 1e9 / 5040)
