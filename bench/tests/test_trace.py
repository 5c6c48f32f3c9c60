"""The trace reduction, on hand-made intervals and on an excerpt of a
traced chip run (``bench/tests/data/excerpt.json.gz``: the first traced
round of a ``--trace 1`` run of ``resnet18-voc32.sync-int8dev`` on a TPU
v5 lite)."""
import gzip
import json
from pathlib import Path

import pytest

from bench.lib import trace

EXCERPT = Path(__file__).parent / "data" / "excerpt.json.gz"


def test_union_merges_overlaps_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (28, 29), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 15 + 10 + 10
    assert trace.union_ns(iv, 8, 45) == 7 + 10 + 5


def test_gaps_are_the_complement():
    iv = [(10, 20), (15, 30), (40, 50)]
    assert trace.idle_gaps(iv, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert trace.idle_gaps([], 0, 5) == [(0, 5)]


def test_gap_goes_to_the_innermost_open_span():
    spans = [("round", 0, 100), ("uplink", 10, 60), ("codec.encode", 20, 40)]
    split = trace.innermost_split((15, 70), spans)
    assert split == {"uplink": 5 + 20, "codec.encode": 20, "round": 10}
    assert trace.innermost_split((100, 110), spans) == {"(no span)": 10}


def test_reduction_on_hand_made_events():
    events = {"rounds": [["bench.round", 0, 100], ["bench.round", 100, 200]],
              "ops": {"0": [["conv", 10, 50], ["conv", 40, 60],
                            ["delta_compress_batch.1", 150, 160]]},
              "modules": {"0": [["jit_named_client_round(1)", 5, 65]]}}
    spans = [("codec.decode", 70, 140), ("aggregate", 160, 190)]
    red = trace.Reduction(events, spans)
    assert red.window_ns == 200 and red.n_rounds == 2
    assert red.busy_ns() == 60
    idle = red.idle_by_span()
    assert sum(idle.values()) == red.window_ns - red.busy_ns()
    assert idle["codec.decode"] == 70 and idle["aggregate"] == 30
    assert red.top_ops() == {"conv": 50, "delta_compress_batch.1": 10}
    assert red.module_ns("jit_named_client_round") == 60
    assert red.op_ns(lambda n: "compress" in n) == (10, 1)
    assert red.span_union_ns({"codec.decode", "aggregate"}) == 100


def test_reduction_on_a_recorded_round():
    with gzip.open(EXCERPT, "rt") as f:
        ex = json.load(f)
    red = trace.Reduction(ex, [tuple(s) for s in ex["spans"]])
    assert red.n_rounds == 1 and red.chips == ["0"]
    busy = red.busy_ns()
    assert 0 < busy < red.window_ns
    idle = red.idle_by_span()
    assert sum(idle.values()) == pytest.approx(red.window_ns - busy,
                                               rel=1e-9)
    # self times partition the busy time: no operation counted twice
    assert sum(red.top_ops().values()) == pytest.approx(busy, rel=1e-6)
    # what this round was: one call of the cohort int8 kernel, 1.36 ms;
    # 1.91 s of the vmapped client round; the host fold (``aggregate``,
    # 1.14 s) the longest stretch in which the device idled
    assert red.op_ns(lambda n: n.startswith("delta_compress_batch")) == (
        pytest.approx(1.360032e6), 1)
    assert red.module_ns("jit_named_client_round") == pytest.approx(
        1.9131e9, rel=1e-4)
    assert max(idle, key=idle.get) == "aggregate"
    assert idle["aggregate"] == pytest.approx(red.span_ns("aggregate"),
                                              rel=0.01)


def test_self_time_leaves_out_nested_ops():
    evs = [("while", 0, 100), ("conv", 10, 40), ("add", 50, 60),
           ("copy", 120, 130)]
    assert trace.self_ns(evs) == {"while": 60, "conv": 30, "add": 10,
                                  "copy": 10}
