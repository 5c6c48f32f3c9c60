"""Behavioural contract of the streaming ingest stage (repro.fl.ingest):

* determinism — fold order is submission order whatever the worker count
  or chunk boundary, so threaded == inline == the gather-path weighted
  mean, bitwise, for every decode engine,
* O(1) memory — at no point do more than ``chunk`` decoded pytrees
  co-exist (``IngestStats.max_resident``), independent of cohort size,
* quarantine — a corrupt payload rejects ONE contribution with a typed
  :class:`RejectedPayload` record while the rest of the cohort aggregates,
* config — ``IngestConfig`` and the ``EngineConfig.ingest`` interactions
  fail at definition/construction time, not mid-round.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import comms
from repro.core import quant as quant_lib
from repro.fl import TreeAccumulator, weighted_mean_trees
from repro.fl.engine import EngineConfig
from repro.fl.ingest import (IngestConfig, RejectedPayload, StreamingIngest)

# ------------------------------------------------------------- fixtures


def _tree_of(fn, node):
    if isinstance(node, dict):
        return {k: _tree_of(fn, v) for k, v in node.items()}
    return fn(node)


_SHAPES = {"conv": {"w": (6, 4, 3, 3), "b": (6,)}, "fc": {"w": (5, 24)}}
_SCALE_SHAPES = {"s0": (6,), "s1": (5,)}


def _cohort(k, seed=0, version=1, with_scales=True):
    """K distinct encoded updates + the framing spec -> (payloads, spec,
    decoded gather trees)."""
    q = quant_lib.QuantConfig()
    fine = _tree_of(lambda s: len(s) < 2, _SHAPES)
    bn_t = ({"m": jax.ShapeDtypeStruct((7,), np.float32)}
            if version == 2 else None)
    spec = comms.WireSpec(
        params=_tree_of(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                        _SHAPES),
        scales=(_tree_of(lambda s: jax.ShapeDtypeStruct(s, np.float32),
                         _SCALE_SHAPES) if with_scales else None),
        fine_mask=fine, step_size=q.step_size,
        fine_step_size=q.fine_step_size, bn=bn_t, version=version)
    codec = comms.get_codec("nnc-cabac")
    payloads = []
    for i in range(k):
        rng = np.random.default_rng(seed * 100 + i)
        lv = _tree_of(lambda s: (rng.integers(-9, 10, s)
                                 * (rng.random(s) < 0.35)).astype(np.int32),
                      _SHAPES)
        recon = jax.tree.map(
            lambda l, f: l.astype(np.float32)
            * np.float32(q.fine_step_size if f else q.step_size), lv, fine)
        s_lv = (_tree_of(lambda s: rng.integers(-3, 4, s).astype(np.int32),
                         _SCALE_SHAPES) if with_scales else None)
        s_recon = (jax.tree.map(lambda l: l.astype(np.float32)
                                * np.float32(q.fine_step_size), s_lv)
                   if with_scales else None)
        bn = ({"m": rng.normal(size=(7,)).astype(np.float32)}
              if version == 2 else None)
        upd = comms.ClientUpdate(lv, s_lv, recon, s_recon, bn=bn)
        payloads.append(codec.encode(upd, spec))
    decs = codec.decode_batch(payloads, spec)
    return codec, payloads, spec, decs


def _ingest_all(codec, payloads, spec, cfg, weights=None):
    ing = StreamingIngest(codec, spec, cfg)
    for i, p in enumerate(payloads):
        ing.submit(i, p, weight=1.0 if weights is None else weights[i])
    return ing.finish()


# ------------------------------------------------------------- determinism


def test_inline_fold_equals_gather_weighted_mean():
    """The ingest mean IS weighted_mean_trees over the decoded cohort in
    submission order — same accumulator, bit-for-bit."""
    codec, payloads, spec, decs = _cohort(6)
    w = [0.5, 1.0, 2.0, 0.25, 1.5, 0.75]
    res = _ingest_all(codec, payloads, spec, IngestConfig(chunk=4), w)
    assert res.accepted == 6 and not res.rejected
    assert res.weight_sum == pytest.approx(sum(w))
    gather = weighted_mean_trees([d.params for d in decs], np.array(w))
    for a, b in zip(jax.tree.leaves(res.delta_params),
                    jax.tree.leaves(gather)):
        np.testing.assert_array_equal(a, b)
    g_scales = weighted_mean_trees([d.scales for d in decs], np.array(w))
    for a, b in zip(jax.tree.leaves(res.delta_scales),
                    jax.tree.leaves(g_scales)):
        np.testing.assert_array_equal(a, b)


def test_threaded_equals_inline_bitwise():
    """Decode may run on workers; folds drain FIFO, so any (workers, chunk)
    shape produces the identical aggregate."""
    codec, payloads, spec, _ = _cohort(11, seed=3)
    w = list(np.linspace(0.3, 2.0, 11))
    base = _ingest_all(codec, payloads, spec, IngestConfig(chunk=5), w)
    for cfg in (IngestConfig(chunk=3, workers=2, queue_depth=6),
                IngestConfig(chunk=1, workers=3, queue_depth=4),
                IngestConfig(chunk=11, workers=1, queue_depth=11)):
        res = _ingest_all(codec, payloads, spec, cfg, w)
        assert res.accepted == 11
        for a, b in zip(jax.tree.leaves(base.delta_params),
                        jax.tree.leaves(res.delta_params)):
            np.testing.assert_array_equal(a, b)


def test_speculative_engine_folds_identically():
    codec, payloads, spec, _ = _cohort(5, seed=4)
    a = _ingest_all(codec, payloads, spec,
                    IngestConfig(decode_engine="vectorized"))
    b = _ingest_all(codec, payloads, spec,
                    IngestConfig(decode_engine="speculative"))
    for x, y in zip(jax.tree.leaves(a.delta_params),
                    jax.tree.leaves(b.delta_params)):
        np.testing.assert_array_equal(x, y)
    # the engine override copies the codec, never mutates the registry one
    assert comms.get_codec("nnc-cabac").decode_engine == "vectorized"


def test_bn_section_folds_under_schema_v2():
    codec, payloads, spec, decs = _cohort(4, seed=5, version=2,
                                          with_scales=False)
    w = [1.0, 0.5, 2.0, 1.5]
    res = _ingest_all(codec, payloads, spec, IngestConfig(chunk=2), w)
    g_bn = weighted_mean_trees([d.bn for d in decs], np.array(w))
    for a, b in zip(jax.tree.leaves(res.bn), jax.tree.leaves(g_bn)):
        np.testing.assert_array_equal(a, b)
    assert res.delta_scales is None        # no scales section on this spec


# ------------------------------------------------------------- O(1) memory


def test_resident_trees_bounded_by_chunk_not_cohort():
    codec, payloads, spec, _ = _cohort(24, seed=6)
    for cfg in (IngestConfig(chunk=4, queue_depth=8),
                IngestConfig(chunk=4, queue_depth=8, workers=2)):
        res = _ingest_all(codec, payloads, spec, cfg)
        assert res.accepted == 24
        assert res.stats.max_resident <= 4     # never O(K) decoded pytrees
    # and the result carries means, not per-client lists
    assert not isinstance(res.delta_params, (list, tuple))


def test_backpressure_bounds_the_queue():
    """A fast producer cannot outrun the decoder into unbounded pending
    state: submit blocks once queue_depth is exceeded."""
    codec, payloads, spec, _ = _cohort(16, seed=7)
    cfg = IngestConfig(chunk=2, queue_depth=4, workers=1)
    ing = StreamingIngest(codec, spec, cfg)
    for i, p in enumerate(payloads):
        ing.submit(i, p)
        assert ing._pending() <= cfg.queue_depth + cfg.chunk
    res = ing.finish()
    assert res.accepted == 16


# ------------------------------------------------------------- quarantine


def test_corrupt_payload_quarantined_rest_of_cohort_aggregates():
    """K=8 with one truncated payload: 7 aggregate, 1 typed reject."""
    codec, payloads, spec, decs = _cohort(8, seed=8)
    bad = list(payloads)
    bad[3] = bad[3][:-3]                       # truncation: deterministic
    res = _ingest_all(codec, bad, spec, IngestConfig(chunk=4))
    assert res.accepted == 7
    assert res.stats.rejected == 1
    [rej] = res.rejected
    assert isinstance(rej, RejectedPayload)
    assert rej.seq == 3 and rej.client == 3
    assert rej.nbytes == len(bad[3])
    assert "CorruptPayloadError" in rej.error
    keep = [d.params for i, d in enumerate(decs) if i != 3]
    gather = weighted_mean_trees(keep, np.ones(7))
    for a, b in zip(jax.tree.leaves(res.delta_params),
                    jax.tree.leaves(gather)):
        np.testing.assert_array_equal(a, b)


def test_parse_failure_in_a_lockstep_chunk_quarantines_only_its_payload():
    """A payload whose bypass stream is zeroed after its first gap header
    passes the frame checks and fails inside its chunk's lockstep decode;
    the chunk is decoded again one payload at a time, so only that payload
    is rejected and the other seven aggregate."""
    codec, payloads, spec, decs = _cohort(8, seed=8)
    bad = list(payloads)
    body = bytearray(bad[5])
    byp = 16 + int.from_bytes(body[:8], "big")
    body[byp + 5:] = bytes(len(body) - byp - 5)
    bad[5] = bytes(body)
    with pytest.raises(comms.CorruptPayloadError):
        codec.decode_batch(bad[4:], spec)
    res = _ingest_all(codec, bad, spec, IngestConfig(chunk=4))
    assert res.accepted == 7
    assert [(r.seq, r.client) for r in res.rejected] == [(5, 5)]
    assert "CorruptPayloadError" in res.rejected[0].error
    keep = [d.params for i, d in enumerate(decs) if i != 5]
    gather = weighted_mean_trees(keep, np.ones(7))
    for a, b in zip(jax.tree.leaves(res.delta_params),
                    jax.tree.leaves(gather)):
        np.testing.assert_array_equal(a, b)


def test_header_corruption_quarantined_on_threaded_ingest():
    codec, payloads, spec, _ = _cohort(8, seed=9)
    bad = list(payloads)
    flipped = bytearray(bad[5])
    flipped[1] ^= 0xFF                         # length-header corruption
    bad[5] = bytes(flipped)
    res = _ingest_all(codec, bad, spec,
                      IngestConfig(chunk=3, workers=2, queue_depth=6))
    assert res.accepted == 7 and res.rejected[0].seq == 5


def test_all_rejected_returns_empty_means():
    codec, payloads, spec, _ = _cohort(2, seed=10)
    res = _ingest_all(codec, [p[:4] for p in payloads], spec, IngestConfig())
    assert res.accepted == 0 and len(res.rejected) == 2
    assert res.delta_params is None and res.bn is None
    assert res.weight_sum == 0.0


# ------------------------------------------------------------- accumulator


def test_tree_accumulator_k2_equal_weight_is_bitwise_jnp_mean():
    """The fold the sync seed pins ride on: for two equal-weight f32 trees
    the f64 single-pass mean is bit-identical to the stacked jnp.mean."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    a = {"w": rng.normal(size=(257,)).astype(np.float32),
         "b": {"x": rng.normal(size=(6, 9)).astype(np.float32)}}
    b = jax.tree.map(lambda l: (l * np.float32(-1.7)
                                + np.float32(0.3)).astype(np.float32), a)
    acc = TreeAccumulator()
    acc.add(a, 1.0)
    acc.add(b, 1.0)
    ref = jax.tree.map(
        lambda x, y: np.asarray(jnp.mean(jnp.stack([x, y]), axis=0)), a, b)
    for m, r in zip(jax.tree.leaves(acc.mean()), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(m, r)
    assert acc.count == 2 and acc.weight_sum == pytest.approx(2.0)


def test_tree_accumulator_weighted_mean_is_stable():
    """Single-pass f64 accumulation: on an adversarial cancellation mix
    (magnitudes ~1e8 hiding deltas ~1e-1) the running fold tracks the f64
    batch reference where a float32 accumulator would lose the signal."""
    rng = np.random.default_rng(2)
    k = 33
    big = np.float32(1e8)
    trees = [{"w": (big * (-1.0 if i % 2 else 1.0)
                    + rng.normal(scale=0.1, size=(128,))).astype(np.float32)}
             for i in range(k)]
    w = (rng.random(k) * 0.9 + 0.1)
    acc = TreeAccumulator()
    f32 = np.zeros(128, np.float32)
    for t, wi in zip(trees, w):
        acc.add(t, float(wi))
        f32 += np.float32(wi) * t["w"]
    ref = (np.sum([wi * t["w"].astype(np.float64)
                   for t, wi in zip(trees, w)], axis=0)
           / w.sum()).astype(np.float32)
    np.testing.assert_allclose(acc.mean()["w"], ref, rtol=1e-6, atol=1e-6)
    # the f32 running fold drifts by orders of magnitude more
    f32_err = np.abs(f32 / np.float32(w.sum()) - ref)
    f64_err = np.abs(acc.mean()["w"] - ref)
    assert f64_err.max() <= f32_err.max()


def test_weighted_mean_trees_host_path_equals_accumulator():
    """weighted_mean_trees over host trees IS the TreeAccumulator fold —
    the identity that makes gather and streaming bitwise-interchangeable."""
    rng = np.random.default_rng(3)
    trees = [{"w": rng.normal(size=(40,)).astype(np.float32)}
             for _ in range(5)]
    w = np.array([0.2, 1.0, 0.4, 2.0, 0.9])
    acc = TreeAccumulator()
    for t, wi in zip(trees, w):
        acc.add(t, float(wi))
    got = weighted_mean_trees(trees, w)
    np.testing.assert_array_equal(got["w"], acc.mean()["w"])


# ------------------------------------------------------------- config


def test_ingest_config_validation():
    with pytest.raises(ValueError, match="chunk"):
        IngestConfig(chunk=0).validate()
    with pytest.raises(ValueError, match="queue_depth"):
        IngestConfig(chunk=8, queue_depth=4).validate()
    with pytest.raises(ValueError, match="workers"):
        IngestConfig(workers=-1).validate()
    IngestConfig().validate()


def test_engine_config_ingest_interactions():
    with pytest.raises(ValueError, match="unknown ingest"):
        EngineConfig(ingest="firehose").validate()
    # streaming consumes real payloads: the no-wire fast path has none
    with pytest.raises(ValueError, match="measure_bytes"):
        EngineConfig(ingest="streaming", measure_bytes=False).validate()
    # decode parallelism lives in IngestConfig.workers, not the uplink pool
    with pytest.raises(ValueError, match="IngestConfig.workers"):
        EngineConfig(ingest="streaming", uplink_workers=2).validate()
    # ingest_opts without streaming is a silent no-op -> rejected
    with pytest.raises(ValueError, match="ingest_opts"):
        EngineConfig(ingest_opts=IngestConfig(chunk=4)).validate()
    EngineConfig(ingest="streaming",
                 ingest_opts=IngestConfig(chunk=4)).validate()


def test_streaming_ingest_is_single_use():
    codec, payloads, spec, _ = _cohort(2, seed=11)
    ing = StreamingIngest(codec, spec, IngestConfig())
    ing.submit(0, payloads[0])
    ing.finish()
    with pytest.raises(RuntimeError, match="single-use"):
        ing.submit(1, payloads[1])
    with pytest.raises(RuntimeError, match="already"):
        ing.finish()


def test_bad_engine_codec_pair_fails_at_construction():
    codec, _, spec, _ = _cohort(1, seed=12)
    with pytest.raises(ValueError):
        StreamingIngest(codec, spec, IngestConfig(decode_engine="warp"))
    # raw-fp32 has no engine choices: any non-default engine is rejected
    with pytest.raises((ValueError, NotImplementedError)):
        StreamingIngest(comms.get_codec("raw-fp32"), spec,
                        IngestConfig(decode_engine="speculative"))
