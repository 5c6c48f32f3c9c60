"""CI smoke for the multi-process federated backend (``executor="dist"``).

Self-spawning: the parent runs the ``sharded_cohort_full`` scenario in a
single-process reference subprocess (2 simulated devices — the same device
topology the distributed job gets), then relaunches itself as 2 coordinated
``jax.distributed`` worker processes running ``dist_cohort_full`` on a
localhost coordination service, and asserts record equality bit-for-bit.

The demo is CPU-only (gloo collectives): every child runs with
``JAX_PLATFORMS=cpu``, so none reaches for an accelerator.  Sandboxes that
forbid the coordination-service socket (bind failure, or connection /
deadline errors in a worker) print ``SKIPPED: ...`` and exit 0; workers
that do not finish within ``TIMEOUT_S`` fail the smoke.

    PYTHONPATH=src python scripts/dist_smoke.py
"""
import json
import os
import socket
import subprocess
import sys

ROUNDS = 2
PROCS = 2
TIMEOUT_S = 540
_SKIP_PATTERNS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "PERMISSION_DENIED",
                  "Connection refused", "barrier timed out",
                  "jax.distributed.initialize failed")


def run_records(scenario: str):
    import jax

    from repro.data import federated, synthetic
    from repro.fl import run_scenario
    from repro.models import cnn

    task = synthetic.ImageTask("dist_smoke", num_classes=4, channels=3,
                               size=32, prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=4)
    model = cnn.make_vgg("vgg_tiny_comms", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    res = run_scenario(scenario, rounds=ROUNDS, model=model, splits=splits)
    return [[r.up_bytes, round(r.test_acc, 6)] for r in res.records]


def worker_main() -> None:
    from repro.dist import init_from_env
    init_from_env()
    print("RECORDS " + json.dumps(run_records("dist_cohort_full")),
          flush=True)


def _records_line(stdout: str):
    lines = [l for l in stdout.splitlines() if l.startswith("RECORDS ")]
    return json.loads(lines[-1][len("RECORDS "):]) if lines else None


def parent_main() -> int:
    # the gloo demo is CPU-only: children never reach for an accelerator
    base = {k: v for k, v in os.environ.items()
            if not k.startswith("REPRO_DIST_")}
    base["JAX_PLATFORMS"] = "cpu"
    base["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.environ.get("PYTHONPATH"), "src") if p)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    ref = subprocess.run(
        [sys.executable, "-c",
         "import runpy, sys; "
         "mod = runpy.run_path(sys.argv[1]); "
         "import json; print('RECORDS ' + json.dumps("
         "mod['run_records']('sharded_cohort_full')))",
         os.path.abspath(__file__)],
        capture_output=True, text=True, cwd=repo, timeout=TIMEOUT_S,
        env=dict(base,
                 XLA_FLAGS=f"--xla_force_host_platform_device_count={PROCS}"))
    if ref.returncode != 0:
        print(ref.stderr[-3000:])
        print("dist smoke FAILED: single-process reference crashed")
        return 1
    expected = _records_line(ref.stdout)
    print(f"reference (sharded, 1 process x {PROCS} devices): {expected}")

    try:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
    except OSError as e:
        print(f"SKIPPED: cannot bind a localhost socket here ({e})")
        return 0

    children = []
    for pid in range(PROCS):
        env = dict(base, REPRO_DIST_COORD=f"localhost:{port}",
                   REPRO_DIST_NPROCS=str(PROCS), REPRO_DIST_PID=str(pid),
                   XLA_FLAGS="--xla_force_host_platform_device_count=1")
        children.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs, timed_out = [], False
    for p in children:
        try:
            out, err = p.communicate(timeout=TIMEOUT_S)
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in children:
                q.kill()
            out, err = p.communicate()
        outs.append((p.returncode, out, err))

    if timed_out:
        print(f"dist smoke FAILED: workers did not finish in {TIMEOUT_S} s")
        return 1
    for pid, (rc, out, err) in enumerate(outs):
        if rc != 0:
            if any(pat in err for pat in _SKIP_PATTERNS):
                print("SKIPPED: coordination service unavailable in this "
                      f"sandbox ({err[-300:]!r})")
                return 0
            print(f"worker {pid} failed (rc={rc}):\n{err[-3000:]}")
            print("dist smoke FAILED")
            return 1
    ok = True
    for pid, (_, out, _) in enumerate(outs):
        got = _records_line(out)
        print(f"worker {pid} (dist, {PROCS} processes): {got}")
        if got != expected:
            print(f"worker {pid} records diverged from the reference")
            ok = False
    print("dist smoke OK: records identical across the 2-process mesh"
          if ok else "dist smoke FAILED: record mismatch")
    return 0 if ok else 1


if __name__ == "__main__":
    if os.environ.get("REPRO_DIST_NPROCS"):
        worker_main()
        sys.exit(0)
    sys.exit(parent_main())
