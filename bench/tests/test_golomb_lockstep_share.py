"""The ``golomb_lockstep_share`` reader: the share of the exp-Golomb
codewords parsed in lockstep with other messages' sections, on hand-made
profile events and on a profile the CPU backend writes."""
import importlib.util
import types
from pathlib import Path

import numpy as np
import pytest

from bench.lib import profile_events

METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "golomb_lockstep_share", METRICS / "golomb_lockstep_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx(rounds=2):
    return types.SimpleNamespace(rounds=rounds, cell={"name": "cell"},
                                 red=types.SimpleNamespace(lo=0, hi=200))


def _read(host, monkeypatch):
    d = (profile_events.Detail({"host": host, "ops": {}}, 0, 200)
         if host is not None else None)
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    return _reader()(_ctx())


def _span(t, values, lockstep=None):
    attrs = {"values": values}
    if lockstep is not None:
        attrs.update(streams=10 if lockstep else 1,
                     lockstep_values=values if lockstep else 0)
    return ["golomb.decode", t, t + 5, attrs]


def test_lockstep_share_on_hand_made_events(monkeypatch):
    """0 where the spans carry no ``lockstep_values`` (a program that
    parses each message alone), 100 where every parse was lockstep, and
    the values-weighted share of a mix; spans after the window do not
    count."""
    assert _read([_span(10, 3000), _span(120, 7)], monkeypatch) == 0
    assert _read([_span(10, 3000, True), _span(120, 7, True)],
                 monkeypatch) == 100
    assert _read([_span(10, 3000, True), _span(20, 1000, False),
                  _span(120, 4000, True), _span(250, 5000, False)],
                 monkeypatch) == pytest.approx(100 * 7000 / 8000)


def test_lockstep_share_finds_nothing_without_the_spans(monkeypatch):
    """No parse span, or no profile at all: None, not an error."""
    assert _read([["nnc.decode", 10, 60, {"nbytes": 9}]], monkeypatch) is None
    assert _read(None, monkeypatch) is None


def test_lockstep_share_reads_back_from_a_cpu_profile(tmp_path, monkeypatch):
    """A real single-stream and a real two-stream parse under the profiler
    (CPU backend, the harness's options): the spans carry ``streams`` and
    the reader weighs the lockstep values against all of them."""
    import jax

    from repro.coding import golomb
    from repro.coding.bitstream import BitReader, BitWriter, ReaderGroup
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
                group = ReaderGroup([BitReader(data), BitReader(data)])
                golomb.decode_egk_many(group, [(0, 5000, 3), (1, 5000, 3)])
    finally:
        jax.profiler.stop_trace()
    ev = profile_events.load(tmp_path)
    (rnd,) = [h for h in ev["host"] if h[0] == "bench.round"]
    d = profile_events.Detail(ev, rnd[1], rnd[2])
    spans = [h for h in d.host if h[0] == "golomb.decode"]
    assert [int(h[3]["streams"]) for h in spans] == [1, 1, 2]
    assert [int(h[3]["lockstep_values"]) for h in spans] == [0, 0, 10000]
    monkeypatch.setattr(profile_events, "of", lambda ctx: d)
    assert _reader()(_ctx(rounds=1)) == pytest.approx(100 * 10000 / 15040)
