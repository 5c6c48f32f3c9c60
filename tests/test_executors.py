"""Behavioural contract of the cohort execution layer (repro.fl.executors):

* gather/scatter/pad round-trips on ragged and padded cohorts,
* the three backends (serial / vmap / sharded) are interchangeable — the
  engine produces matching Contribution trees for a fixed cohort
  (tolerance-pinned) whichever one is injected,
* the stacked-server entry point (async windows) matches the shared-server
  path when every row carries the same snapshot,
* async dispatch windows batch concurrently-finishing clients into ONE
  executor call, deterministically ordered by (arrival_time, client_id)
  and reproducible across backends,
* EngineConfig/Scenario validation rejects conflicting executor/mesh axes
  at registration time,
* the sharded backend really shards: a subprocess with two forced host
  devices pads a ragged cohort of 3 to the 2-device mesh and matches the
  single-device vmap results.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant as quant_lib
from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import (AsyncConfig, EmptyCohortError, EngineConfig,
                      FederatedEngine, SamplingConfig, Scenario,
                      SerialExecutor, ShardedExecutor, VmapExecutor,
                      gather_clients, make_executor, pad_clients,
                      scatter_clients, validate_scenario)
from repro.fl.rounds import stack_trees
from repro.models import cnn

# ------------------------------------------------------------- fixtures

_PROTO = dict(method="sparse", fixed_sparsity=0.9, batch_size=32,
              local_lr=2e-3)

# Decoded client deltas live on the uniform quantization grid; different
# backends compile different (but equally valid) arithmetic, so a value
# sitting exactly on a bin boundary may legally flip ONE level.  The
# equivalence contract is therefore "within one step of the grid".
_STEP = quant_lib.QuantConfig().step_size
_FINE_STEP = quant_lib.QuantConfig().fine_step_size


def _tiny_setting(num_clients):
    task = synthetic.ImageTask("t", num_classes=4, channels=3, size=32,
                               prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=num_clients)
    model = cnn.make_vgg("vgg_tiny_exec", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    return model, splits


@pytest.fixture(scope="module")
def tiny4():
    return _tiny_setting(4)


def _engine(tiny, **ecfg):
    model, splits = tiny
    cfg = ProtocolConfig(name="exec", **_PROTO)
    return FederatedEngine(model, cfg, splits, jax.random.PRNGKey(5),
                           engine_cfg=EngineConfig(**ecfg))


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ------------------------------------------------------- gather/scatter/pad

def test_gather_scatter_roundtrip_on_ragged_cohort():
    tree = {"a": jnp.arange(15.0).reshape(5, 3), "b": jnp.arange(5.0)}
    idx = np.array([0, 2, 4])
    cohort = gather_clients(tree, idx)
    np.testing.assert_array_equal(np.asarray(cohort["b"]), [0.0, 2.0, 4.0])
    # scatter(gather) is the identity
    _assert_trees_close(scatter_clients(tree, cohort, idx), tree, rtol=0)
    # a modified cohort lands only on its own rows
    out = scatter_clients(tree, jax.tree.map(lambda x: x + 100.0, cohort),
                          idx)
    np.testing.assert_array_equal(np.asarray(out["b"]),
                                  [100.0, 1.0, 102.0, 3.0, 104.0])


def test_pad_clients_repeats_last_row_and_roundtrips():
    tree = {"w": jnp.arange(6.0).reshape(3, 2), "s": jnp.arange(3.0)}
    padded = pad_clients(tree, 5)
    assert padded["w"].shape == (5, 2) and padded["s"].shape == (5,)
    np.testing.assert_array_equal(np.asarray(padded["w"][3]),
                                  np.asarray(tree["w"][2]))
    np.testing.assert_array_equal(np.asarray(padded["s"][3:]), [2.0, 2.0])
    # pad -> slice recovers the cohort exactly
    _assert_trees_close(jax.tree.map(lambda x: x[:3], padded), tree, rtol=0)
    # already-at-size trees come back unchanged
    _assert_trees_close(pad_clients(tree, 3), tree, rtol=0)


def test_pad_clients_empty_cohort_raises():
    """Regression: ``jnp.repeat(x[-1:], n)`` on a 0-row tree used to return
    0 rows silently, so an empty cohort sailed into the executor and blew
    up (or padded wrong) far from the cause.  Now it's a typed error the
    schedulers catch as an all-drop round."""
    empty = {"w": jnp.zeros((0, 2)), "s": jnp.zeros((0,))}
    with pytest.raises(EmptyCohortError, match="empty cohort"):
        pad_clients(empty, 4)
    # padding an empty tree TO zero rows stays a no-op, not an error
    _assert_trees_close(pad_clients(empty, 0), empty, rtol=0)


def test_executor_registry():
    assert isinstance(make_executor("serial"), SerialExecutor)
    assert isinstance(make_executor("vmap"), VmapExecutor)
    sh = make_executor("sharded", mesh_shape=(1,))
    assert isinstance(sh, ShardedExecutor) and sh.mesh_size == 1
    with pytest.raises(ValueError, match="unknown executor"):
        make_executor("warp")


# ------------------------------------------------------------- equivalence

def test_executors_produce_matching_contributions(tiny4):
    """ISSUE acceptance: serial/vmap/sharded are interchangeable — one
    fixed cohort, matching decoded Contribution trees (tolerance-pinned)
    and byte totals whichever backend the engine injects."""
    got = {}
    for ex in ("serial", "vmap", "sharded"):
        eng = _engine(tiny4, executor=ex,
                      sampling=SamplingConfig(cohort_size=3))
        seen = []
        orig = eng.aggregate

        def capture(contribs, weights=None, _o=orig, _s=seen):
            _s.extend(contribs)
            return _o(contribs, weights)

        eng.aggregate = capture
        res = eng.run(1)
        got[ex] = (seen, res.records[0])

    ref, ref_rec = got["vmap"]
    assert len(ref) == 3 and ref_rec.up_bytes > 0
    for ex in ("serial", "sharded"):
        seen, rec = got[ex]
        assert [c.client for c in seen] == [c.client for c in ref]
        for a, b in zip(seen, ref):
            _assert_trees_close(a.delta_params, b.delta_params,
                                rtol=0, atol=1.5 * _STEP)
            _assert_trees_close(a.delta_scales, b.delta_scales,
                                rtol=0, atol=1.5 * _FINE_STEP)
            _assert_trees_close(a.bn_state, b.bn_state)
        # payload lengths track the (near-identical) levels: allow the
        # odd boundary-rounding flip a byte or two of entropy coding
        assert abs(rec.up_bytes - ref_rec.up_bytes) <= 0.02 * ref_rec.up_bytes
        np.testing.assert_allclose(rec.test_acc, ref_rec.test_acc, atol=0.02)


def test_stacked_server_entry_point_matches_shared(tiny4):
    """run_stacked with every row carrying the same snapshot must agree
    with run_shared — the async-window path cannot drift from the sync
    barrier numerics."""
    eng = _engine(tiny4)
    lt = eng.local_train
    splits = lt.splits
    from repro.data.federated import client_epoch_batches
    bidx = client_epoch_batches(jax.random.PRNGKey(3), 4, lt.n_train,
                                lt.batch_size)
    args = (lt.persistent, splits.client_x, splits.client_y,
            splits.client_val_x, splits.client_val_y, bidx)
    shared = lt.executor.run_shared(eng.server, *args)
    stacked = lt.executor.run_stacked(stack_trees([eng.server] * 4), *args)
    _assert_trees_close(shared.recon_delta_params, stacked.recon_delta_params,
                        rtol=0, atol=1.5 * _STEP)
    _assert_trees_close(shared.bn_state, stacked.bn_state)
    # continuous metrics pin tightly; accuracies are discrete (1/n_val
    # granularity), so a borderline sample may legally flip one step
    for key, atol in [("train_loss", 1e-4), ("update_sparsity", 1e-6),
                      ("val_acc", 0.06)]:
        np.testing.assert_allclose(np.asarray(shared.metrics[key]),
                                   np.asarray(stacked.metrics[key]),
                                   rtol=1e-4, atol=atol)


# ------------------------------------------------------------- async windows

def test_async_window_batches_into_one_executor_call(tiny4):
    """A window wider than the latency spread trains the whole in-flight
    set as ONE executor call; the buffer aggregates everything that
    arrived (staleness weights renormalise)."""
    model, splits = tiny4
    cfg = ProtocolConfig(name="exec_async", **_PROTO)
    eng = FederatedEngine(
        model, cfg, splits, jax.random.PRNGKey(5),
        engine_cfg=EngineConfig(
            mode="async",
            async_cfg=AsyncConfig(buffer_size=4, concurrency=4,
                                  dispatch_window=100.0)))
    res = eng.run(2)
    assert eng.scheduler.batch_sizes == [4, 4]
    assert all(len(r.participants) == 4 for r in res.records)
    assert res.records[0].sim_time_s < res.records[1].sim_time_s


def test_window_zero_pops_one_at_a_time_even_on_latency_ties(tiny4):
    """Homogeneous latencies (sigma=0) tie every finish time exactly;
    dispatch_window=0 must still pop ONE completion per executor call so
    ``buffer_size`` keeps its FedBuff meaning (a tie-batching window would
    silently aggregate the whole in-flight set)."""
    model, splits = tiny4
    cfg = ProtocolConfig(name="exec_ties", **_PROTO)
    eng = FederatedEngine(
        model, cfg, splits, jax.random.PRNGKey(5),
        engine_cfg=EngineConfig(
            mode="async",
            async_cfg=AsyncConfig(buffer_size=2, concurrency=4,
                                  latency_sigma=0.0)))
    res = eng.run(2)
    assert all(s == 1 for s in eng.scheduler.batch_sizes)
    assert all(len(r.participants) == 2 for r in res.records)


def test_async_windowed_deterministic_across_backends(tiny4):
    """Same key -> identical schedules; and the (arrival_time, client_id)
    intake order makes the schedule a function of the SIMULATED clock, so
    serial and vmap backends replay the same participants, batch shapes
    and simulated times (satellite: tie-break determinism)."""
    model, splits = tiny4
    cfg = ProtocolConfig(name="exec_async_det", **_PROTO)

    def run(executor):
        eng = FederatedEngine(
            model, cfg, splits, jax.random.PRNGKey(9),
            engine_cfg=EngineConfig(
                mode="async", executor=executor,
                async_cfg=AsyncConfig(buffer_size=2, concurrency=3,
                                      dispatch_window=0.75)))
        res = eng.run(2)
        return ([r.participants for r in res.records],
                [r.sim_time_s for r in res.records],
                list(eng.scheduler.batch_sizes))

    a, b = run("vmap"), run("vmap")
    assert a == b
    parts, times, sizes = run("serial")
    assert parts == a[0] and sizes == a[2]
    np.testing.assert_allclose(times, a[1], rtol=1e-12)


# ------------------------------------------------------------- validation

def test_engine_config_validates_executor_axes():
    with pytest.raises(ValueError, match="unknown executor"):
        EngineConfig(executor="warp").validate()
    with pytest.raises(ValueError, match="mesh_shape"):
        EngineConfig(executor="serial", mesh_shape=(1,)).validate()
    with pytest.raises(ValueError, match="1-D"):
        EngineConfig(executor="sharded", mesh_shape=(1, 1)).validate()
    with pytest.raises(ValueError, match="devices"):
        EngineConfig(executor="sharded", mesh_shape=(4096,)).validate()
    with pytest.raises(ValueError, match="dispatch_window"):
        EngineConfig(async_cfg=AsyncConfig(dispatch_window=-0.5)).validate()
    # a window on the sync barrier is a silent no-op — reject it
    with pytest.raises(ValueError, match="dispatch_window"):
        EngineConfig(mode="sync",
                     async_cfg=AsyncConfig(dispatch_window=0.5)).validate()
    # an uplink pool on one-at-a-time async completions is a no-op too;
    # a dispatch window unlocks it (batches flow through pooled intake)
    with pytest.raises(ValueError, match="no-op"):
        EngineConfig(mode="async", uplink_workers=2).validate()
    EngineConfig(mode="async", uplink_workers=2,
                 async_cfg=AsyncConfig(dispatch_window=0.5)).validate()
    EngineConfig(executor="sharded", mesh_shape=(1,)).validate()
    EngineConfig(executor="sharded").validate()   # mesh over all devices


def test_scenario_registration_validates_executor_axes():
    with pytest.raises(ValueError, match="unknown executor"):
        validate_scenario(Scenario("bad_exec", executor="warp"))
    with pytest.raises(ValueError, match="mesh_shape"):
        validate_scenario(Scenario("bad_mesh", mesh_shape=(1,)))
    with pytest.raises(ValueError, match="devices"):
        validate_scenario(Scenario("bad_mesh_size", executor="sharded",
                                   mesh_shape=(4096,)))
    with pytest.raises(ValueError, match="dispatch_window"):
        validate_scenario(Scenario("bad_sync_window", dispatch_window=0.5))
    validate_scenario(Scenario("ok_sharded", executor="sharded"))
    validate_scenario(Scenario("ok_window", mode="async",
                               dispatch_window=0.5))


# ------------------------------------------------------------- real sharding

def test_cohort_mesh_axes_are_auto():
    """``jax.make_mesh`` defaults to Explicit axes, under which slicing the
    sharded cohort output (the padded rows dropped) raises; every mesh the
    engine builds must use Auto axes."""
    from jax.sharding import AxisType

    from repro.launch.mesh import make_cohort_mesh, make_mesh

    for mesh in (make_cohort_mesh(), make_mesh((1, 1), ("data", "model"))):
        assert set(mesh.axis_types) == {AxisType.Auto}


_MULTIDEV_SCRIPT = r'''
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 2, jax.devices()
from repro.fl.executors import ShardedExecutor, VmapExecutor

def toy_round(server, pers, cx, cy, cvx, cvy, bidx):
    h = jnp.tanh(cx @ server["w"] + pers["r"][None, :])
    return {"out": h,
            "pers": {"r": pers["r"] + (bidx.sum() % 7).astype(jnp.float32)}}

C, D = 3, 4   # ragged: the 2-device mesh pads 3 -> 4
server = {"w": jnp.eye(D) * 0.5}
pers = {"r": jnp.arange(float(C * D)).reshape(C, D)}
cx = jnp.linspace(-1.0, 1.0, C * 2 * D).reshape(C, 2, D)
cy = cvx = cvy = jnp.zeros((C, 1))
bidx = jnp.arange(C * 3, dtype=jnp.int32).reshape(C, 3)

vm, sh = VmapExecutor(), ShardedExecutor()
assert sh.mesh_size == 2
vm.bind(toy_round); sh.bind(toy_round)
args = (pers, cx, cy, cvx, cvy, bidx)
a = vm.run_shared(server, *args)
b = sh.run_shared(server, *args)
for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
    assert la.shape[0] == C and lb.shape[0] == C
    np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-6)
servers = jax.tree.map(lambda x: jnp.stack([x] * C), server)
c = sh.run_stacked(servers, *args)
for la, lc in zip(jax.tree.leaves(a), jax.tree.leaves(c)):
    np.testing.assert_allclose(np.asarray(la), np.asarray(lc), rtol=1e-6)
print("MULTIDEV_OK")
'''


def test_sharded_executor_pads_ragged_cohort_across_two_devices():
    """Force 2 host devices in a subprocess: the sharded backend must pad
    the ragged cohort to the mesh, shard the client axis, and reproduce
    the single-device vmap results after dropping the padded rows."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.abspath(src)
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _MULTIDEV_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0 and "MULTIDEV_OK" in proc.stdout, (
        proc.stdout + "\n" + proc.stderr)
