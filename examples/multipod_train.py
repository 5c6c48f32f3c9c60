"""Multi-host federated training on a real ``jax.distributed`` mesh.

Self-spawning demo of the engine's ``executor="dist"`` backend
(``repro.dist``): run with no arguments and the parent

  1. runs a single-process reference on a simulated mesh of ``--procs``
     local devices (``sharded`` backend — the same device topology the
     distributed job will have),
  2. relaunches itself ``--procs`` times as coordinated worker processes
     (localhost coordination service, one CPU device each, gloo
     collectives), every worker running the IDENTICAL engine loop with the
     cohort axis sharded across the multi-process mesh and persistent
     client state partitioned by training ownership
     (``repro.dist.CrossHostClientStore``),
  3. checks the workers' round records against the reference bit-for-bit.

    PYTHONPATH=src python examples/multipod_train.py [--rounds N] [--procs P]

Workers see only their own shard of the stacked client arrays
(``jax.make_array_from_process_local_data``); when cohort sampling moves a
client between hosts, its error-feedback state hands off through one
host collective.  The records printed by every process are identical —
the engine is one SPMD program, and process topology must not move a byte.
"""
import argparse
import json
import os
import socket
import subprocess
import sys

REPRO_ENV = ("REPRO_DIST_COORD", "REPRO_DIST_NPROCS", "REPRO_DIST_PID")


def run_engine(executor: str, rounds: int):
    import jax

    from repro.core.protocol import ProtocolConfig
    from repro.data import federated, synthetic
    from repro.fl import EngineConfig, SamplingConfig, run_simulation
    from repro.fl.server_opt import ServerOptConfig
    from repro.launch.cache import enable_compile_cache
    from repro.models import cnn

    enable_compile_cache()

    task = synthetic.ImageTask("multipod", num_classes=4, channels=3,
                               size=32, prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=8)
    model = cnn.make_vgg("vgg_tiny_comms", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    cfg = ProtocolConfig(name="fsfl", method="sparse", fixed_sparsity=0.9,
                         error_feedback=True, batch_size=32, local_lr=2e-3)
    eng = EngineConfig(sampling=SamplingConfig(cohort_size=2),
                       server_opt=ServerOptConfig(name="fedavg", lr=1.0),
                       mode="sync", measure_bytes=True, executor=executor)
    res = run_simulation(model, cfg, splits, rounds, jax.random.PRNGKey(11),
                         engine=eng)
    return [dict(round=r.round, up_bytes=r.up_bytes,
                 acc=round(r.test_acc, 6), participants=list(r.participants))
            for r in res.records]


def worker_main(rounds: int) -> None:
    """One coordinated process: context FIRST, then the shared loop."""
    from repro.launch import require_dist
    dist = require_dist()
    ctx = dist.init_from_env()
    records = run_engine("dist", rounds)
    print(f"[worker {ctx.process_index}/{ctx.process_count}] "
          f"{len(ctx.local_devices)} local / {len(ctx.global_devices)} "
          "global devices")
    for r in records:
        print(f"[worker {ctx.process_index}] round {r['round']}: "
              f"clients={r['participants']} up={r['up_bytes']}B "
              f"acc={r['acc']:.4f}")
    print("RECORDS " + json.dumps(records), flush=True)


def parent_main(rounds: int, procs: int) -> int:
    from repro.launch import require_dist
    require_dist()  # fail early with the friendly message if dist is broken

    print(f"== reference: 1 process, {procs} simulated devices, "
          "sharded backend ==")
    env = {k: v for k, v in os.environ.items() if k not in REPRO_ENV}
    # the gloo demo is CPU-only: children never reach for an accelerator
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={procs}",
               PYTHONPATH=os.pathsep.join(
                   p for p in (os.environ.get("PYTHONPATH"), "src") if p))
    ref = subprocess.run(
        [sys.executable, "-c",
         "from examples.multipod_train import run_engine; import json; "
         f"print('RECORDS ' + json.dumps(run_engine('sharded', {rounds})))"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if ref.returncode != 0:
        print(ref.stderr[-2000:])
        return 1
    expected = json.loads(
        [l for l in ref.stdout.splitlines()
         if l.startswith("RECORDS ")][-1][len("RECORDS "):])
    for r in expected:
        print(f"[reference] round {r['round']}: clients={r['participants']} "
              f"up={r['up_bytes']}B acc={r['acc']:.4f}")

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    print(f"== spawning {procs} worker processes "
          f"(coordinator localhost:{port}) ==")
    children = []
    for pid in range(procs):
        wenv = dict(env, REPRO_DIST_COORD=f"localhost:{port}",
                    REPRO_DIST_NPROCS=str(procs), REPRO_DIST_PID=str(pid),
                    XLA_FLAGS="--xla_force_host_platform_device_count=1")
        children.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__),
             "--rounds", str(rounds)],
            env=wenv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    ok = True
    for pid, p in enumerate(children):
        out, err = p.communicate(timeout=900)
        sys.stdout.write(out)
        if p.returncode != 0:
            print(f"worker {pid} failed (rc={p.returncode}):\n{err[-2000:]}")
            ok = False
            continue
        got = json.loads([l for l in out.splitlines()
                          if l.startswith("RECORDS ")][-1][len("RECORDS "):])
        if got != expected:
            print(f"worker {pid} records DIVERGED from the reference:"
                  f"\n  ref: {expected}\n  got: {got}")
            ok = False
    if ok:
        print(f"OK: {procs}-process records match the single-process "
              "reference bit-for-bit")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--procs", type=int, default=2)
    args = ap.parse_args()
    if os.environ.get("REPRO_DIST_NPROCS"):
        worker_main(args.rounds)
        return 0
    return parent_main(args.rounds, args.procs)


if __name__ == "__main__":
    sys.exit(main())
