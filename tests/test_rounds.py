"""Behavioural contract of the round-lifecycle API (repro.fl.rounds):

* parity suite — the redesigned FederatedEngine reproduces the PR-2 pinned
  byte totals AND accuracies for fsfl / stc / fedavg_nnc through the real
  wire (the pins were captured from the pre-redesign engine),
* structure — sync and async are scheduling policies over the SAME
  Uplink/Aggregate/ServerStep stage instances (no duplicated aggregation
  math), and ``engine.py`` contains no ``_run_*`` fork,
* wire schema v2 — the BN section round-trips bit-exactly through every
  registered codec, and the engine's Aggregate stage consumes BN state
  only via decoded codec messages,
* parallel uplink — thread/process pools produce bitwise-identical
  payloads and decodes in client order,
* config satellites — EngineConfig/Scenario validation at definition time,
  RunResult.final_acc on empty records.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import comms
from repro.core import fsfl as fsfl_lib
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import (Aggregate, AsyncConfig, BufferedAsyncScheduler,
                      Contribution, EngineConfig, FederatedEngine,
                      RoundRecord, RunResult, SamplingConfig, Scenario,
                      ServerStep, SyncScheduler, Uplink, run_simulation,
                      validate_scenario)
from repro.fl import engine as engine_lib
from repro.models import cnn

# ------------------------------------------------------------- fixtures

_PINS = {
    # captured from the PR-2 engine (tests/test_comms.py byte pins + the
    # fedavg_nnc row captured immediately before this redesign)
    "fsfl": dict(cfg=dict(method="sparse", fixed_sparsity=0.9),
                 up_bytes=[727, 712], acc=[0.166667, 0.208333]),
    "stc": dict(cfg=dict(method="ternary", error_feedback=True,
                         fixed_sparsity=0.9, structured=False),
                up_bytes=[561, 566], acc=None),
    "fedavg_nnc": dict(cfg=dict(method="none"),
                       up_bytes=[3439, 3429], acc=[0.25, 0.25]),
}


def _tiny_setting(num_clients):
    task = synthetic.ImageTask("t", num_classes=4, channels=3, size=32,
                               prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=num_clients)
    model = cnn.make_vgg("vgg_tiny_comms", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    return model, splits


@pytest.fixture(scope="module")
def tiny2():
    return _tiny_setting(2)


# ------------------------------------------------------------- parity suite

@pytest.mark.parametrize("name", ["fsfl", "stc", "fedavg_nnc"])
def test_redesigned_engine_reproduces_pr2_pins(tiny2, name):
    """The stage/scheduler redesign must not move a single byte or
    accuracy bit on the schema-v1 compat path."""
    model, splits = tiny2
    pin = _PINS[name]
    cfg = ProtocolConfig(name=name, batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    res = fsfl_lib.run_federated(model, cfg, splits, 2, jax.random.PRNGKey(7))
    assert [r.up_bytes for r in res.records] == pin["up_bytes"]
    if pin["acc"] is not None:
        assert [round(r.test_acc, 6) for r in res.records] == pin["acc"]


def test_engine_module_has_no_sync_async_fork():
    """One orchestrator + two scheduler policies; the duplicated
    _run_sync/_run_async monoliths must not come back."""
    import inspect

    src = inspect.getsource(engine_lib)
    assert "def _run_" not in src
    assert "FederatedEngine" in src


# ------------------------------------------------------------- structure

def _spy(stage, calls, key):
    orig = stage.__call__

    def spy(*a, **k):
        calls.append(key)
        return orig(*a, **k)

    return spy


def test_sync_and_async_drive_the_same_stage_instances(tiny2):
    """Both schedulers must route through the engine's single
    Uplink/Aggregate/ServerStep instances — aggregation math exists once."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="eqs23", method="sparse", error_feedback=True,
                         fixed_sparsity=0.9, structured=False,
                         batch_size=32, local_lr=2e-3)
    for mode, sched_cls in [("sync", SyncScheduler),
                            ("async", BufferedAsyncScheduler)]:
        ecfg = EngineConfig(mode=mode)
        eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(3),
                              engine_cfg=ecfg)
        assert type(eng.scheduler) is sched_cls
        # the scheduler is bound to the engine itself: the stages it drives
        # ARE the engine's instances, not copies
        assert eng.scheduler.eng is eng
        assert isinstance(eng.uplink, Uplink)
        assert isinstance(eng.aggregate, Aggregate)
        assert isinstance(eng.server_step, ServerStep)
        calls = []
        eng.aggregate = _spy(eng.aggregate, calls, "aggregate")
        eng.server_step = _spy(eng.server_step, calls, "server_step")
        res = eng.run(1)
        assert calls == ["aggregate", "server_step"]
        assert len(res.records) == 1 and res.records[0].up_bytes > 0


def test_aggregate_stage_is_the_only_mean(tiny2):
    """Plain-mean (sync) and staleness-weighted (async) flavours of the one
    Aggregate stage agree when the weights are uniform-fresh."""
    agg = Aggregate()
    tree = lambda v: {"w": np.full((3,), v, np.float32)}
    contribs = [Contribution(client=i, delta_params=tree(float(i)),
                             delta_scales=tree(0.0), bn_state=tree(1.0))
                for i in range(4)]
    plain = agg(contribs)
    weighted = agg(contribs, weights=np.full(4, 0.25))
    np.testing.assert_allclose(np.asarray(plain.delta_params["w"]),
                               np.asarray(weighted.delta_params["w"]),
                               rtol=1e-6)
    assert plain.weights is None and weighted.weights is not None
    assert plain.survivors == (0, 1, 2, 3)
    with pytest.raises(ValueError, match="zero contributions"):
        agg([])


# ------------------------------------------------------------- wire schema v2

def _consistent_update(seed, with_bn=True):
    rng = np.random.default_rng(seed)
    import repro.core.quant as quant_lib
    q = quant_lib.QuantConfig()
    shapes = {"conv": (6, 8), "b": (6,)}
    lv = {k: (rng.integers(-9, 10, s) * (rng.random(s) < 0.4))
          .astype(np.int32) for k, s in shapes.items()}
    fine = {k: len(s) < 2 for k, s in shapes.items()}
    recon = {k: lv[k].astype(np.float32)
             * np.float32(q.fine_step_size if fine[k] else q.step_size)
             for k in lv}
    bn = {"m": rng.normal(size=(5,)).astype(np.float32),
          "v": rng.random((5,)).astype(np.float32)}
    spec = comms.WireSpec(
        params={k: jax.ShapeDtypeStruct(s, np.float32)
                for k, s in shapes.items()},
        scales=None, fine_mask=fine,
        bn=comms.shape_template(bn) if with_bn else None,
        version=2)
    return comms.ClientUpdate(lv, None, recon, None, bn=bn), spec


@pytest.mark.parametrize("name", ["raw-fp32", "fp16", "int8-blockscale",
                                  "golomb", "nnc-cabac"])
def test_schema_v2_bn_roundtrips_exactly_for_every_codec(name):
    """The BN section is raw float32 for ALL codecs (precision-critical):
    decode must reproduce it bit-exactly, and the v2 payload must be
    exactly header + v1 body + 4 bytes per BN scalar."""
    codec = comms.get_codec(name)
    upd, spec = _consistent_update(0)
    v1_spec = dataclasses.replace(spec, bn=None, version=1)
    p1 = codec.encode(upd, v1_spec)
    p2 = codec.encode(upd, spec)
    assert len(p2) == 1 + len(p1) + spec.bn_nbytes
    assert p2[0] == 2
    dec = codec.decode(p2, spec)
    for a, b in zip(jax.tree.leaves(upd.bn), jax.tree.leaves(dec.bn)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # v1 decode never fabricates a bn section
    assert codec.decode(p1, v1_spec).bn is None


def test_schema_v2_rejects_mismatched_header():
    codec = comms.get_codec("nnc-cabac")
    upd, spec = _consistent_update(1)
    payload = codec.encode(upd, spec)
    with pytest.raises(ValueError, match="schema mismatch"):
        codec.decode(b"\x07" + payload[1:], spec)


def test_v1_spec_refuses_bn_section():
    bn = {"m": np.zeros((2,), np.float32)}
    with pytest.raises(ValueError, match="version=2"):
        comms.WireSpec(params={"w": jax.ShapeDtypeStruct((2,), np.float32)},
                       bn=comms.shape_template(bn), version=1)


def test_engine_aggregates_bn_from_decoded_wire_only(tiny2):
    """Structural proof that under schema v2 the server's BN state comes
    from the DECODED payload: poisoning the codec's decoded bn (and nothing
    else) must change the server bn_state, while the device-side path would
    have been identical."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    honest = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                            engine=EngineConfig(wire_schema=2))
    v1 = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                        engine=EngineConfig(wire_schema=1))
    # raw-f32 BN section: schema v2 reproduces the v1 (device-side) bn state
    for a, b in zip(jax.tree.leaves(honest.server.bn_state),
                    jax.tree.leaves(v1.server.bn_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    class PoisonBn(comms.Codec):
        """Wraps nnc-cabac but zeroes the decoded bn tree."""
        name = "poison-bn"
        lossless = True
        needs = ("levels",)

        def __init__(self):
            self.inner = comms.get_codec("nnc-cabac")

        def _encode_body(self, upd, spec):
            return self.inner._encode_body(upd, spec)

        def _decode_body(self, payload, spec):
            return self.inner._decode_body(payload, spec)

        def decode(self, payload, spec):
            dec = super().decode(payload, spec)
            return dec._replace(bn=jax.tree.map(np.zeros_like, dec.bn))

    poisoned = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                              engine=EngineConfig(codec=PoisonBn(),
                                                  wire_schema=2))
    for leaf in jax.tree.leaves(poisoned.server.bn_state):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    # ... and the byte totals grew by exactly header + bn tail per client
    bn_scalars = sum(int(np.prod(np.shape(l)))
                     for l in jax.tree.leaves(v1.server.bn_state))
    per_client_overhead = 1 + 4 * bn_scalars
    assert (honest.records[0].up_bytes
            == v1.records[0].up_bytes + 2 * per_client_overhead)


def test_async_schema_v2_runs_and_matches_v1_accuracy(tiny2):
    """BufferedAsyncScheduler under schema v2: BN arrives via decoded
    messages; the raw-f32 section keeps numerics identical to v1."""
    model, splits = tiny2
    s2 = Scenario("async_v2_test", mode="async", buffer_size=2, concurrency=2,
                  num_clients=2, wire_schema=2)
    s1 = dataclasses.replace(s2, name="async_v1_test", wire_schema=1)
    from repro.fl import run_scenario
    a = run_scenario(s2, rounds=1, model=model, splits=splits)
    b = run_scenario(s1, rounds=1, model=model, splits=splits)
    assert a.records[0].test_acc == b.records[0].test_acc
    assert a.records[0].up_bytes > b.records[0].up_bytes


# ------------------------------------------------------------- parallel uplink

@pytest.mark.parametrize("executor", ["thread", "process"])
def test_pooled_uplink_is_bitwise_identical_to_serial(tiny2, executor):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    serial = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                            engine=EngineConfig())
    pooled = run_simulation(
        model, cfg, splits, 1, jax.random.PRNGKey(7),
        engine=EngineConfig(uplink_workers=2, uplink_executor=executor))
    assert serial.records[0].up_bytes == pooled.records[0].up_bytes
    assert serial.records[0].test_acc == pooled.records[0].test_acc


@pytest.fixture(scope="module")
def tiny8():
    return _tiny_setting(8)


def _run_capturing(model, splits, cfg, ecfg):
    """One engine round; returns (RunResult, contributions, pool_tasks)."""
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=ecfg)
    seen = []
    orig = eng.aggregate

    def capture(contribs, weights=None):
        seen.extend(contribs)
        return orig(contribs, weights)

    eng.aggregate = capture
    res = eng.run(1)
    return res, seen, eng.uplink.pool_tasks


def test_batched_uplink_chunks_cohort_into_at_most_worker_tasks(tiny8):
    """K clients through W workers: the batch intake submits <= W pool
    tasks (one per contiguous chunk) where per-client dispatch submits K —
    and both are Contribution-identical to the unpooled serial intake."""
    model, splits = tiny8
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    base, base_c, t0 = _run_capturing(model, splits, cfg, EngineConfig())
    assert t0 == 0
    batch, batch_c, t1 = _run_capturing(
        model, splits, cfg,
        EngineConfig(uplink_workers=2, uplink_batch=True))
    assert 0 < t1 <= 2                      # K=8, W=2 => at most W tasks
    per, per_c, t2 = _run_capturing(model, splits, cfg,
                                    EngineConfig(uplink_workers=2))
    assert t2 == 8                          # per-client: one task per update
    # Contribution equality: bytes, clients and decoded trees bitwise
    for other in (batch_c, per_c):
        assert [c.client for c in other] == [c.client for c in base_c]
        assert ([c.payload_bytes for c in other]
                == [c.payload_bytes for c in base_c])
        for a, b in zip(base_c, other):
            for x, y in zip(jax.tree.leaves(a.delta_params),
                            jax.tree.leaves(b.delta_params)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert batch.records[0].up_bytes == base.records[0].up_bytes
    assert batch.records[0].test_acc == base.records[0].test_acc


def test_gather_intake_decodes_the_cohort_together(tiny8):
    """At ``uplink_workers`` 0 the gather intake encodes the cohort, then
    decodes it in one batch: the same payload bytes and decoded
    contributions, in client order, as one ``_roundtrip`` per client, and
    each exp-Golomb section of the eight messages is parsed together
    (``golomb.decode`` spans with ``streams`` = 8, all values lockstep)."""
    model, splits = tiny8
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(telemetry="trace"))
    up = eng.uplink
    calls = []
    orig = up.roundtrip_all

    def spy(upds, clients=None):
        out = orig(upds, clients)
        calls.append((upds, clients, out))
        return out

    up.roundtrip_all = spy
    res = eng.run(1)
    [(upds, clients, got)] = calls
    assert clients == list(range(8))
    assert (up.codec.encode_batch(upds, up.spec)
            == [up.codec.encode(u, up.spec) for u in upds])
    per = [up._roundtrip(u) for u in upds]
    assert [n for n, _ in got] == [n for n, _ in per]
    assert res.records[0].up_bytes == sum(n for n, _ in per)
    for (_, a), (_, b) in zip(got, per):
        for x, y in zip(jax.tree.leaves((a.params, a.scales)),
                        jax.tree.leaves((b.params, b.scales))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    parses = [s.attrs for s in res.telemetry.recorder.snapshot()
              if s.name == "golomb.decode"]
    assert parses and all(a["streams"] == 8
                          and a["lockstep_values"] == a["values"]
                          for a in parses)


def test_batched_uplink_forkserver_contributions_equal_serial(tiny2):
    """The flat-array transport (no pytree pickling) through the forkserver
    pool reassembles bitwise-identical Contributions."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    base, base_c, _ = _run_capturing(model, splits, cfg, EngineConfig())
    fork, fork_c, tasks = _run_capturing(
        model, splits, cfg,
        EngineConfig(uplink_workers=2, uplink_batch=True,
                     uplink_executor="process"))
    assert 0 < tasks <= 2
    assert [c.payload_bytes for c in fork_c] == [c.payload_bytes
                                                 for c in base_c]
    for a, b in zip(base_c, fork_c):
        for x, y in zip(jax.tree.leaves(a.delta_params),
                        jax.tree.leaves(b.delta_params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert fork.records[0].test_acc == base.records[0].test_acc


def test_up_bytes_pin_through_batch_path(tiny2):
    """Byte accounting through the batch intake reproduces the frozen
    fsfl seed pin: batching cannot move a single payload byte."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(uplink_workers=2,
                                             uplink_batch=True))
    assert [r.up_bytes for r in res.records] == _PINS["fsfl"]["up_bytes"]


def test_process_executor_refuses_non_fork_safe_codec(tiny2):
    """int8-blockscale is fork-safe since its single-dispatch encode (one
    kernel launch per message, forkserver workers own a fresh XLA
    runtime), so the refusal is asserted with a synthetic codec."""
    from repro import comms

    class _Unsafe(type(comms.get_codec("raw-fp32"))):
        fork_safe = False

    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse",
                         fixed_sparsity=0.9, batch_size=32,
                         local_lr=2e-3)
    with pytest.raises(ValueError, match="fork"):
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                       engine=EngineConfig(
                           codec=_Unsafe("test-unsafe", "<f4", True),
                           uplink_workers=2,
                           uplink_executor="process"))


def test_int8_codec_is_fork_safe_now(tiny2):
    """Satellite re-evaluation: with ONE kernel dispatch per message and a
    forkserver (fork+exec) pool, int8-blockscale runs under the process
    executor — and still holds the serial payload bytes."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    from repro import comms
    assert comms.get_codec("int8-blockscale").fork_safe
    base = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                          engine=EngineConfig(codec="int8-blockscale"))
    pooled = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                            engine=EngineConfig(codec="int8-blockscale",
                                                uplink_workers=2,
                                                uplink_executor="process"))
    assert ([r.up_bytes for r in pooled.records]
            == [r.up_bytes for r in base.records])


@pytest.mark.parametrize("tpu_parent", [False, True])
def test_process_pool_warns_on_a_tpu_parent(tiny2, monkeypatch, tpu_parent):
    """The pool's workers run JAX on the CPU, so under a TPU parent an
    int8-blockscale encode there runs the Pallas kernel interpreted: the
    uplink warns when it starts that pool, and only then."""
    import warnings

    from repro.core.protocol import make_protocol
    from repro.kernels import platform

    model, _ = tiny2
    cfg = ProtocolConfig(name="fedavg", method="none", quantize=False)
    server, _ = make_protocol(model, cfg, 1)[0](jax.random.PRNGKey(0))
    monkeypatch.setattr(platform, "interpret", lambda: not tpu_parent)
    up = Uplink(cfg, EngineConfig(codec="int8-blockscale", uplink_workers=2,
                                  uplink_executor="process"), server)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        up._executor().shutdown()
    said = [w for w in caught if "Pallas interpreter" in str(w.message)]
    assert len(said) == int(tpu_parent)


# ------------------------------------------------------------- device encode

@pytest.mark.parametrize("name", ["fsfl", "stc", "fedavg_nnc"])
def test_device_encode_reproduces_pins(tiny2, name):
    """The device cohort encode holds the three frozen seed pins
    bit-for-bit: the fused kernels change WHERE the payload is computed,
    never a single byte of it."""
    model, splits = tiny2
    pin = _PINS[name]
    cfg = ProtocolConfig(name=name, batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(device_encode=True))
    assert [r.up_bytes for r in res.records] == pin["up_bytes"]
    if pin["acc"] is not None:
        assert [round(r.test_acc, 6) for r in res.records] == pin["acc"]


def test_device_encode_streaming_reproduces_pins(tiny2):
    """device_encode composes with streaming ingest: payload-only intake
    from the device path, same frozen bytes."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(device_encode=True,
                                             ingest="streaming"))
    assert [r.up_bytes for r in res.records] == _PINS["fsfl"]["up_bytes"]


def test_device_encode_one_dispatch_per_cohort(tiny8):
    """O(1) fused dispatches in cohort size: the whole K-client cohort
    costs ONE device program, observable via uplink.kernel_dispatches."""
    from repro.comms import device as comms_device

    model, splits = tiny8
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    for k in (2, 8):
        before = comms_device.dispatch_count()
        res = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                             engine=EngineConfig(
                                 device_encode=True, telemetry="metrics",
                                 sampling=SamplingConfig(cohort_size=k)))
        # one fused program for the whole cohort, independent of K
        assert comms_device.dispatch_count() - before == 1
        snap = res.records[0].telemetry
        assert snap["counters"]["uplink.kernel_dispatches"] == 1


def test_device_encode_requires_wire(tiny2):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    with pytest.raises(ValueError, match="measure_bytes"):
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                       engine=EngineConfig(device_encode=True,
                                           measure_bytes=False))


def test_device_encode_falls_back_for_codecs_without_fast_path(tiny2):
    """raw-fp32 has no encode_cohort override: the uplink silently takes
    the host path and bytes match the non-device run."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fedavg_raw", method="none", quantize=False,
                         batch_size=32, local_lr=2e-3)
    base = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7))
    dev = run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                         engine=EngineConfig(device_encode=True))
    assert ([r.up_bytes for r in dev.records]
            == [r.up_bytes for r in base.records])


# ------------------------------------------------------------- streaming ingest

@pytest.mark.parametrize("name", ["fsfl", "stc", "fedavg_nnc"])
def test_streaming_ingest_reproduces_pins(tiny2, name):
    """The decode-and-accumulate intake holds the three frozen seed pins
    bit-for-bit: streaming is a memory shape, not a numerics change."""
    model, splits = tiny2
    pin = _PINS[name]
    cfg = ProtocolConfig(name=name, batch_size=32, local_lr=2e-3,
                         **pin["cfg"])
    res = run_simulation(model, cfg, splits, 2, jax.random.PRNGKey(7),
                         engine=EngineConfig(ingest="streaming"))
    assert [r.up_bytes for r in res.records] == pin["up_bytes"]
    if pin["acc"] is not None:
        assert [round(r.test_acc, 6) for r in res.records] == pin["acc"]


def test_streaming_ingest_never_calls_gather_aggregate(tiny8):
    """Structural O(1) proof: under streaming the scheduler hands the
    engine a pre-folded aggregate — the Aggregate stage (which stacks K
    pytrees) is never invoked, and contributions carry encoded payloads
    instead of decoded host trees."""
    model, splits = tiny8
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(ingest="streaming"))
    calls, seen = [], []
    orig_make = eng.make_ingest

    def make():
        ing = orig_make()
        orig_submit = ing.submit

        def submit(client, payload, weight=1.0):
            seen.append(payload)
            orig_submit(client, payload, weight)

        ing.submit = submit
        return ing

    eng.make_ingest = make
    eng.aggregate = _spy(eng.aggregate, calls, "aggregate")
    res = eng.run(1)
    assert calls == []                     # no K-wide gather mean ever ran
    assert len(seen) == 8 and all(isinstance(p, bytes) for p in seen)
    assert res.records[0].up_bytes == sum(len(p) for p in seen)


def test_streaming_contributions_carry_payloads_and_device_rows(tiny2):
    """Streaming contributions ship the encoded payload plus a device-row
    view for EF re-injection — no decoded host trees at the uplink."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(ingest="streaming"))
    seen = []
    orig = eng.scheduler._fold_streaming

    def capture(contribs, survivors, clients):
        seen.extend(contribs)
        return orig(contribs, survivors, clients)

    eng.scheduler._fold_streaming = capture
    eng.run(1)
    for c in seen:
        assert isinstance(c.payload, bytes) and c.payload_bytes == len(
            c.payload)
        assert c.delta_scales is None
        for leaf in jax.tree.leaves(c.delta_params):
            assert isinstance(leaf, jax.Array)


def test_streaming_quarantine_keeps_rest_of_cohort(tiny8):
    """One corrupted payload in a K=8 round: the round completes with the
    7 surviving clients aggregated and the reject recorded."""
    model, splits = tiny8
    cfg = ProtocolConfig(name="fsfl", method="sparse", error_feedback=True,
                         fixed_sparsity=0.9, batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(ingest="streaming"))
    orig_make = eng.make_ingest
    rejected = []

    def make():
        ing = orig_make()
        orig_submit = ing.submit
        counter = {"n": 0}

        def submit(client, payload, weight=1.0):
            if counter["n"] == 2:          # corrupt the third submission
                payload = payload[:-3]
            counter["n"] += 1
            orig_submit(client, payload, weight)

        ing.submit = submit
        orig_finish = ing.finish

        def finish():
            res = orig_finish()
            rejected.extend(res.rejected)
            return res

        ing.finish = finish
        return ing

    eng.make_ingest = make
    res = eng.run(1)
    assert len(rejected) == 1 and rejected[0].seq == 2
    assert len(res.records[0].participants) == 7
    assert rejected[0].client not in res.records[0].participants


def test_async_streaming_equals_gather_bitwise(tiny8):
    """BufferedAsyncScheduler: the decode-at-flush streaming fold and the
    gather path produce identical records (bytes, accuracy, sim time)."""
    model, splits = tiny8
    base = Scenario("async_gather_t", mode="async", buffer_size=3,
                    concurrency=3, num_clients=8)
    stream = dataclasses.replace(base, name="async_stream_t",
                                 ingest="streaming")
    spec = dataclasses.replace(stream, name="async_stream_spec_t",
                               ingest_engine="speculative")
    from repro.fl import run_scenario
    runs = [run_scenario(s, rounds=3, model=model, splits=splits)
            for s in (base, stream, spec)]
    for other in runs[1:]:
        for a, b in zip(runs[0].records, other.records):
            assert a.up_bytes == b.up_bytes
            assert a.test_acc == b.test_acc
            assert a.participants == b.participants


def test_streaming_engine_rejects_bad_pairs_at_construction(tiny2):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    from repro.fl.ingest import IngestConfig
    with pytest.raises(ValueError, match="decode engine"):
        FederatedEngine(
            model, cfg, splits, jax.random.PRNGKey(7),
            engine_cfg=EngineConfig(
                ingest="streaming", codec="raw-fp32",
                ingest_opts=IngestConfig(decode_engine="speculative")))


# ------------------------------------------------------------- satellites

def test_final_acc_is_nan_on_empty_records():
    """rounds=0 (or an early-exit sweep) must not raise IndexError."""
    res = RunResult("empty", [])
    assert np.isnan(res.final_acc)
    assert res.rounds_to_acc(0.5) is None and res.bytes_to_acc(0.5) is None
    rec = RoundRecord(round=1, test_acc=0.5, up_bytes=1, down_bytes=0,
                      cum_bytes=1, mean_val_acc=0.5, update_sparsity=0.9,
                      train_loss=1.0, wall_s=0.1)
    assert RunResult("one", [rec]).final_acc == 0.5


def test_run_simulation_zero_rounds(tiny2):
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", batch_size=32)
    res = run_simulation(model, cfg, splits, 0, jax.random.PRNGKey(0))
    assert res.records == [] and np.isnan(res.final_acc)


def test_engine_config_defaults_are_per_instance():
    """field(default_factory=...) — no shared mutable-default instances."""
    a, b = EngineConfig(), EngineConfig()
    assert a.sampling is not b.sampling
    assert a.server_opt is not b.server_opt
    assert a.async_cfg is not b.async_cfg


def test_scenario_registration_validates_conflicts():
    with pytest.raises(ValueError, match="cohort"):
        validate_scenario(Scenario("bad_async_cohort", mode="async",
                                   cohort_size=4))
    with pytest.raises(ValueError, match="drop"):
        validate_scenario(Scenario(
            "bad_async_drop", mode="async",
            channel=comms.ChannelConfig(drop_rate=0.5)))
    with pytest.raises(ValueError, match="one weight per client"):
        validate_scenario(Scenario("bad_weights", cohort_size=2,
                                   sampling_strategy="weighted",
                                   sampling_weights=(1.0, 2.0),
                                   num_clients=8))
    with pytest.raises(ValueError, match="unknown protocol"):
        validate_scenario(Scenario("bad_proto", protocol="no_such"))
    with pytest.raises(ValueError, match="wire schema"):
        validate_scenario(Scenario("bad_schema", wire_schema=3))
    # a good one passes silently
    validate_scenario(Scenario("ok", cohort_size=4))


def test_engine_config_validate_rejects_unknown_mode():
    with pytest.raises(ValueError, match="unknown engine mode"):
        EngineConfig(mode="semi-sync").validate()
    with pytest.raises(ValueError, match="uplink_executor"):
        EngineConfig(uplink_executor="greenlet").validate()
    with pytest.raises(ValueError, match=">= 0"):
        EngineConfig(uplink_workers=-1).validate()
    # a pool on the one-completion-at-a-time async path (dispatch_window=0)
    # would still be a silent no-op — rejected; dispatch windows batch
    # completions through the pooled Uplink.intake, so window > 0 unlocks it
    with pytest.raises(ValueError, match="no-op"):
        EngineConfig(mode="async", uplink_workers=2).validate()
    EngineConfig(mode="async", uplink_workers=2,
                 async_cfg=AsyncConfig(dispatch_window=0.5)).validate()
    EngineConfig(sampling=SamplingConfig(cohort_size=3)).validate(8)


def test_no_wire_fast_path_stays_on_device(tiny2):
    """measure_bytes=False is the fast path: contributions must carry
    device rows (no host sync for the delta trees), and the run must match
    the wired path's accuracies exactly (level-lossless codec)."""
    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(measure_bytes=False))
    seen = []
    orig = eng.aggregate

    def capture(contribs, weights=None):
        seen.extend(contribs)
        return orig(contribs, weights)

    eng.aggregate = capture
    res = eng.run(1)
    assert res.records[0].up_bytes == 0
    for c in seen:
        for leaf in jax.tree.leaves(c.delta_params):
            assert isinstance(leaf, jax.Array), type(leaf)


# ------------------------------------------------------- empty cohorts


def test_sync_scheduler_empty_cohort_is_all_drop_round(tiny2):
    """A zero-size cohort selection surfaces as a typed EmptyCohortError
    that the sync scheduler converts into an all-drop round: no
    contributions, no server step, clock advanced — the run keeps going."""
    from repro.fl import EmptyCohortError
    from repro.fl.sampling import pad_clients

    with pytest.raises(EmptyCohortError):
        pad_clients({"w": jax.numpy.zeros((0, 3))}, 2)

    model, splits = tiny2
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         batch_size=32, local_lr=2e-3)
    eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                          engine_cfg=EngineConfig(
                              sampling=SamplingConfig(cohort_size=1)))
    orig = eng.cohort.select
    calls = {"n": 0}

    def select_empty_first(key):
        calls["n"] += 1
        if calls["n"] == 1:
            key, _ = jax.random.split(key)
            return np.array([], dtype=np.int64), key
        return orig(key)

    eng.cohort.select = select_empty_first
    res = eng.run(2)
    first, second = res.records
    assert first.participants == () and first.up_bytes == 0
    assert first.down_bytes == 0  # no server step happened
    assert second.participants != () and second.up_bytes > 0
    assert second.sim_time_s >= first.sim_time_s > 0.0  # clock advanced


def test_device_encode_counts_host_handovers(tiny2):
    """Every cohort the device path hands back to the host encoder is
    counted (a chip run asserts 0); a codec with a fast path hands none
    over and launches one device dispatch instead."""
    from repro.comms import device as comms_device

    model, splits = tiny2
    cfg = ProtocolConfig(name="fedavg", method="none", quantize=False,
                         batch_size=32, local_lr=2e-3)
    for codec, handed in (("raw-fp32", 1), ("int8-blockscale", 0)):
        before = (comms_device.handover_count(),
                  comms_device.dispatch_count())
        run_simulation(model, cfg, splits, 1, jax.random.PRNGKey(7),
                       engine=EngineConfig(device_encode=True, codec=codec))
        assert comms_device.handover_count() - before[0] == handed
        assert comms_device.dispatch_count() - before[1] == 1 - handed
