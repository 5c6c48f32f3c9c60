"""Chip smoke: the federated round on a TPU, at the paper's model widths.

Drives the engine's main path through its user entry point
(``repro.fl.run_scenario``, what ``examples/federated_cifar.py --full
--scenario ...`` calls) on the thinned VGG-11 of the paper at its published
widths ([32, 64, 128 x 6], 849,834 parameters) with CIFAR-10-shaped
synthetic data made from ``--seed``, and checks the results:

  kernels         the Pallas kernels of the wire path, compiled for the
                  chip, against the ``repro.kernels.ref`` oracles at the
                  model's flattened update size (tolerances of
                  tests/test_kernels*.py: int8 codes and int32 levels
                  bitwise, scales rtol 1e-6, carry atol 2e-7, apply rtol
                  1e-6), and the cohort kernel row-by-row against the
                  per-client kernel (bitwise)
  client_round    one cohort's jitted client round on the chip against the
                  same jitted round on the host CPU device (float32
                  reference), conditioned as ``conditioned_round`` says:
                  relative L2 of the reconstructed update
                  <= CLIENT_RTOL_DEFAULT at the chip's default matmul
                  precision and <= CLIENT_RTOL_HIGHEST at "highest"
  fsfl_round      the engine's fsfl client round (top-10% selection,
                  quantization, filter-scale sub-epochs, accept-if-improves)
                  on the chip against the host CPU at "highest", made
                  comparable as ``fsfl_round`` says: the share of the
                  reference's nonzero parameter levels that differ
                  <= FSFL_LEVELS_TOL, and the relative L2 of the
                  reconstructed filter-scale update <= FSFL_SCALES_TOL
  engine          the default engine path: vmap executor, the "auto" codec
                  (nnc-cabac, host CABAC), sync FedAvg, CLIENTS clients in
                  full participation, ROUNDS rounds
  device_encode   the same run with the int8-blockscale codec encoded on
                  the device (``EngineConfig.device_encode``), the path on
                  which the Pallas cohort kernel runs

Each engine phase must show finite losses, payload bytes in every round and
a final accuracy above chance by three binomial standard deviations of the
test set; the device phase must show compiled (not interpreted) kernel
dispatches and no cohort handed back to the host encoder.

``--chips 4`` runs only the cohort-sharding phase: the same cohort through
``ShardedExecutor`` on a 4-chip mesh and through ``VmapExecutor`` on one
chip, in one process (the conditioned round, bounds as for client_round);
the outputs must live on 4 distinct devices.

Without a TPU (or outside a checkout of this repository) the script exits
non-zero and prints no result.  The last line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Relative L2 bounds on the reconstructed client update of the conditioned
# round (``conditioned_round``).  A wrong program gives ~1 or more
# (unrelated updates, or none).  On the CPU, that round answers a relative
# nudge of the weights of 4e-3 (the order of a bf16 pass) with ~0.3 and one
# of 1e-6 (float32 rounding) with ~1e-3.
CLIENT_RTOL_DEFAULT = 0.6
CLIENT_RTOL_HIGHEST = 1e-2

# Bounds of the fsfl check (``fsfl_round``) at "highest": the share of the
# reference's nonzero parameter levels that differ, and the relative L2 of
# the reconstructed filter-scale update.  On the CPU a relative nudge of
# the weights of 1e-6 reads 0.0037 / 9.5e-4 and one of 1e-4 reads
# 0.060 / 4.2e-3; one of 4e-3 (the order of a bf16 pass) reads
# 0.455 / 0.020, a top-20% selection for top-10% 0.36 / 0.019, twice the
# quantization step 0.88 / 0.017, a scale learning rate 1% off 0 / 9.3e-3
# and no scale training 0 / 1.0.
FSFL_LEVELS_TOL = 0.05
FSFL_SCALES_TOL = 5e-3

# The run: the synthetic CIFAR-like images are split 70/15/15 into client
# train / client val / test across CLIENTS clients.
ROUNDS = 3
CLIENTS = 10
SAMPLES = 6400

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations (seconds) while registered."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


def rel_l2(got, want) -> float:
    import jax
    import numpy as np
    g = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(got)])
    w = np.concatenate([np.asarray(x, np.float64).ravel()
                        for x in jax.tree.leaves(want)])
    return float(np.linalg.norm(g - w) / max(np.linalg.norm(w), 1e-30))


# ------------------------------------------------------------------ setup

def make_task(seed: int, num_clients: int, n_samples: int):
    import jax

    from repro.data import federated, synthetic
    from repro.models import cnn
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(seed),
                                        synthetic.CIFAR_LIKE, n_samples)
    splits = federated.split_federated(jax.random.PRNGKey(seed + 1), x, y,
                                       num_clients)
    return cnn.vgg11_thinned(synthetic.CIFAR_LIKE.num_classes), splits


def cohort_inputs(model, splits, scenario, rounds: int, k: int, seed: int):
    """(client_round, args) for the first ``k`` clients' first round,
    built the way ``FederatedEngine``/``LocalTrain`` build them."""
    import jax
    import jax.numpy as jnp

    from repro.core.protocol import make_protocol
    from repro.data.federated import client_epoch_batches
    from repro.fl.scenarios import build_protocol

    cfg = build_protocol(scenario, rounds)
    n_train = splits.client_x.shape[1]
    init, client_round, _ = make_protocol(
        model, cfg, max(1, n_train // cfg.batch_size))
    key_init, key_batch = jax.random.split(jax.random.PRNGKey(seed))
    server, pers0 = init(key_init)
    pers = jax.tree.map(lambda x: jnp.broadcast_to(x, (k,) + x.shape), pers0)
    bidx = client_epoch_batches(key_batch, k, n_train, cfg.batch_size)
    args = (server, pers, splits.client_x[:k], splits.client_y[:k],
            splits.client_val_x[:k], splits.client_val_y[:k], bidx)
    return client_round, args


def update_of(out):
    """The reconstructed client update the server aggregates."""
    return (out.recon_delta_params, out.recon_delta_scales)


# ----------------------------------------------------------------- phases

def phase_kernels(n: int, k: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.delta_compress import (delta_apply, delta_compress,
                                              delta_compress_batch)
    from repro.kernels.level_assign import level_assign

    key = jax.random.PRNGKey(seed + 100)
    d = jax.random.normal(key, (k, n)) * 0.3
    q, s = delta_compress_batch(d, 0.1, block=128)
    q_ref, s_ref = jax.jit(ref.delta_compress_batch,
                           static_argnums=2)(d, 0.1, 128)
    q, s, q_ref, s_ref = jax.device_get((q, s, q_ref, s_ref))
    check(np.array_equal(q, q_ref),
          f"delta_compress_batch codes differ from the oracle at "
          f"{int((q != q_ref).sum())} of {q.size}")
    np.testing.assert_allclose(s, s_ref, rtol=1e-6)
    rows_equal = True
    for i in (0, k - 1):
        qi, si = jax.device_get(delta_compress(d[i], 0.1, block=128))
        rows_equal &= (np.array_equal(qi, q[i])
                       and np.array_equal(si.view(np.uint32),
                                          s[i].view(np.uint32)))
    check(rows_equal, "cohort kernel rows differ from the per-client kernel")

    cpu = jax.devices("cpu")[0]
    q_cpu, _ = jax.device_get(jax.jit(ref.delta_compress_batch,
                                      static_argnums=2)(
        jax.device_put(d, cpu), 0.1, 128))

    w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    dd = jax.random.normal(jax.random.fold_in(key, 2), (n,)) * 0.1
    q1, s1 = delta_compress(dd, 0.0, block=128)
    out = delta_apply(w, q1, s1, 0.5, block=128)
    pad = (-n) % 128    # the oracle takes whole blocks
    want = jax.jit(ref.delta_apply, static_argnums=3)(
        jnp.pad(w, (0, pad)), jnp.pad(q1, (0, pad)), s1, 128, 0.5)[:n]
    np.testing.assert_allclose(*jax.device_get((out, want)), rtol=1e-6)

    dl = jax.random.normal(jax.random.fold_in(key, 3), (k, n)) * 1e-2
    rl = jax.random.normal(jax.random.fold_in(key, 4), (k, n)) * 1e-3
    step = 4.8828125e-4
    lv, carry = level_assign(dl, rl, 2e-3, step)
    lv_ref, c_ref = jax.jit(ref.level_assign)(dl, rl, 2e-3, step)
    lv, carry, lv_ref, c_ref = jax.device_get((lv, carry, lv_ref, c_ref))
    check(np.array_equal(lv, lv_ref),
          f"level_assign levels differ from the oracle at "
          f"{int((lv != lv_ref).sum())} of {lv.size}")
    np.testing.assert_allclose(carry, c_ref, atol=2e-7)
    emit("kernels", n=n, cohort=k, checks="passed",
         int8_codes_differing_from_host_cpu_oracle=int((q != q_cpu).sum()))


def one_step_round(model, splits, scenario, rounds: int, k: int, seed: int,
                   **changes):
    """``cohort_inputs`` for ``scenario`` with ``changes`` made to it, cut
    to one local step."""
    scenario = dataclasses.replace(scenario, **changes)
    client_round, args = cohort_inputs(model, splits, scenario, rounds, k,
                                       seed)
    return client_round, args[:-1] + (args[-1][:, :1],)


def conditioned_round(model, splits, scenario, rounds: int, k: int,
                      seed: int):
    """The client round the reference checks compare, and its inputs.

    The engine's fsfl round (Adam, every batch, top-10% selection, 2**-11
    quantization, accept-if-improves) is chaotic in float32: a 1e-6
    relative nudge of the server weights moves its reconstructed update by
    a relative L2 of ~0.7 on the CPU alone, so no tolerance separates a
    right program from a wrong one there.  The checks run the same
    ``client_round`` on the same model, data and cohort where the
    comparison is conditioned: the dense protocol (no sparsify / quantize
    stages) and one local SGD step.
    """
    return one_step_round(model, splits, scenario, rounds, k, seed,
                          protocol="fedavg",
                          protocol_overrides=(("local_optimizer", "sgd"),))


def fsfl_round(model, splits, scenario, rounds: int, k: int, seed: int):
    """The engine's fsfl client round, every stage of it (error feedback,
    top-10% selection, quantization, filter-scale sub-epochs with
    accept-if-improves, fine quantization of the scales), with both
    optimizers SGD and one local step.  A first Adam step moves every
    weight by about its learning rate whatever the gradient, so under Adam
    which weights the top-10% keeps is decided by float32 rounding
    (``conditioned_round``); under SGD the kept set follows the gradient.
    """
    return one_step_round(model, splits, scenario, rounds, k, seed,
                          protocol_overrides=(("local_optimizer", "sgd"),
                                              ("scale_optimizer", "sgd")))


def compare(name: str, run_test, run_ref, **fields) -> None:
    """Reconstructed updates of ``run_test()`` vs ``run_ref()``, at the
    chip's default matmul precision and at "highest"."""
    import jax

    err = rel_l2(update_of(run_test()), update_of(run_ref()))
    with jax.default_matmul_precision("highest"):
        err_hi = rel_l2(update_of(run_test()), update_of(run_ref()))
    emit(name, rel_l2_default=err, rel_l2_highest=err_hi,
         tol_default=CLIENT_RTOL_DEFAULT, tol_highest=CLIENT_RTOL_HIGHEST,
         **fields)
    check(err <= CLIENT_RTOL_DEFAULT,
          f"{name} at default precision: relative L2 {err} > "
          f"{CLIENT_RTOL_DEFAULT}")
    check(err_hi <= CLIENT_RTOL_HIGHEST,
          f"{name} at highest precision: relative L2 {err_hi} > "
          f"{CLIENT_RTOL_HIGHEST}")


def phase_client_round(model, splits, scenario, rounds: int, k: int,
                       seed: int) -> None:
    import jax

    client_round, args = conditioned_round(model, splits, scenario, rounds,
                                           k, seed)
    vround = jax.jit(jax.vmap(client_round, in_axes=(None, 0, 0, 0, 0, 0, 0)))
    host_args = jax.device_put(args, jax.devices("cpu")[0])
    compare("client_round", lambda: vround(*args),
            lambda: vround(*host_args), cohort=k, reference="cpu")


def phase_fsfl_round(model, splits, scenario, rounds: int, k: int,
                     seed: int) -> None:
    import jax
    import numpy as np

    client_round, args = fsfl_round(model, splits, scenario, rounds, k, seed)
    vround = jax.jit(jax.vmap(client_round, in_axes=(None, 0, 0, 0, 0, 0, 0)))
    host_args = jax.device_put(args, jax.devices("cpu")[0])
    with jax.default_matmul_precision("highest"):
        got, want = jax.device_get((vround(*args), vround(*host_args)))
    lv = np.concatenate([x.ravel() for x in jax.tree.leaves(got.levels_params)])
    lv_ref = np.concatenate([x.ravel()
                             for x in jax.tree.leaves(want.levels_params)])
    kept = int((lv_ref != 0).sum())
    levels_off = float((lv != lv_ref).sum() / max(kept, 1))
    scales_err = rel_l2(got.recon_delta_scales, want.recon_delta_scales)
    scales_moved = any(np.any(x != 0)
                       for x in jax.tree.leaves(want.recon_delta_scales))
    emit("fsfl_round", cohort=k, reference="cpu", precision="highest",
         nonzero_levels=kept, levels_differing_share=levels_off,
         scales_rel_l2=scales_err, tol_levels=FSFL_LEVELS_TOL,
         tol_scales=FSFL_SCALES_TOL)
    check(kept > 0 and scales_moved,
          "fsfl_round: the CPU reference sent no levels or no scale update")
    check(levels_off <= FSFL_LEVELS_TOL,
          f"fsfl_round: {levels_off} of the nonzero levels differ > "
          f"{FSFL_LEVELS_TOL}")
    check(scales_err <= FSFL_SCALES_TOL,
          f"fsfl_round: scale update relative L2 {scales_err} > "
          f"{FSFL_SCALES_TOL}")


def phase_engine(name: str, scenario, model, splits, rounds: int,
                 seed: int) -> None:
    import jax
    import numpy as np

    from repro.comms import device as comms_device
    from repro.fl import run_scenario
    from repro.kernels import platform

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    before = (comms_device.dispatch_count(), comms_device.handover_count())
    t0 = time.perf_counter()
    try:
        res = run_scenario(scenario, rounds=rounds, model=model,
                           splits=splits, key=jax.random.PRNGKey(seed))
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
    wall = time.perf_counter() - t0
    dispatches, handovers = (comms_device.dispatch_count() - before[0],
                             comms_device.handover_count() - before[1])
    interpret = platform.interpret()
    for r in res.records:
        emit(name, round=r.round, wall_s=r.wall_s, up_bytes=r.up_bytes,
             test_acc=r.test_acc, train_loss=r.train_loss)
    n_test = int(splits.test_y.shape[0])
    chance = 1.0 / int(np.asarray(splits.test_y).max() + 1)
    acc_bound = chance + 3.0 * math.sqrt(chance * (1 - chance) / n_test)
    emit(name, compile_s=clock.seconds, compiles=clock.count, run_s=wall,
         kernel_dispatches=dispatches,
         compiled_dispatches=0 if interpret else dispatches,
         host_handovers=handovers, interpret=interpret,
         final_acc=res.final_acc, acc_bound=acc_bound)
    check(all(math.isfinite(r.train_loss) for r in res.records),
          f"{name}: non-finite training loss")
    check(all(r.up_bytes > 0 for r in res.records),
          f"{name}: a round sent no payload bytes")
    check(res.final_acc > acc_bound,
          f"{name}: final accuracy {res.final_acc} not above chance "
          f"({acc_bound})")
    check(not interpret, f"{name}: the kernels ran in the Pallas interpreter")
    check(handovers == 0, f"{name}: {handovers} cohorts fell back to host "
          "encode")
    if scenario.device_encode:
        check(dispatches > 0, f"{name}: no device encode dispatch ran")


def phase_sharded(model, splits, scenario, rounds: int, k: int, seed: int,
                  chips: int) -> None:
    import jax

    from repro.fl.executors import ShardedExecutor, VmapExecutor

    client_round, args = conditioned_round(model, splits, scenario, rounds,
                                           k, seed)
    vm, sh = VmapExecutor(), ShardedExecutor(mesh_shape=(chips,))
    vm.bind(client_round)
    sh.bind(client_round)
    one_chip = jax.device_put(args, jax.devices()[0])
    spread = sh.run_shared(*args)
    devices = {d for leaf in jax.tree.leaves(update_of(spread))
               for d in leaf.sharding.device_set}
    emit("sharded", cohort=k, mesh=chips, devices=len(devices))
    check(len(devices) == chips,
          f"sharded outputs live on {len(devices)} devices, not {chips}")
    compare("sharded", lambda: sh.run_shared(*args),
            lambda: vm.run_shared(*one_chip), cohort=k, mesh=chips,
            reference="vmap on one chip")


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cohort-sharding phase, on 4 chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax

    from repro.fl import get_scenario
    from repro.kernels import platform

    try:
        devices = platform.require_tpu()
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    dev = devices[0]
    emit("device", platform=dev.platform, kind=dev.device_kind,
         count=len(devices), jax=jax.__version__)

    model, splits = make_task(args.seed, CLIENTS, SAMPLES)
    n_params = sum(int(x.size) for x in
                   jax.tree.leaves(jax.eval_shape(model.init,
                                                  jax.random.PRNGKey(0))[0]))
    emit("model", name=model.name, params=n_params, clients=CLIENTS,
         train_per_client=int(splits.client_x.shape[1]),
         test=int(splits.test_y.shape[0]))
    base = get_scenario("sync_full_fedavg_fsfl")
    if args.chips == 4:
        phases = [lambda: phase_sharded(model, splits, base, ROUNDS, CLIENTS,
                                        args.seed, args.chips)]
    else:
        device_int8 = dataclasses.replace(base, name="device_encode_int8_full",
                                          codec="int8-blockscale",
                                          device_encode=True)
        phases = [
            lambda: phase_kernels(n_params, CLIENTS, args.seed),
            lambda: phase_client_round(model, splits, base, ROUNDS, 2,
                                       args.seed),
            lambda: phase_fsfl_round(model, splits, base, ROUNDS, 2,
                                     args.seed),
            lambda: phase_engine("engine", base, model, splits, ROUNDS,
                                 args.seed),
            lambda: phase_engine("device_encode", device_int8, model, splits,
                                 ROUNDS, args.seed),
        ]
    failed = 0
    for phase in phases:     # every phase runs; any failure fails the smoke
        try:
            phase()
        except (SmokeFailure, AssertionError) as e:
            print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
            failed += 1
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
