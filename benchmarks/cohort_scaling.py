"""Cohort execution scaling: rounds/s per executor backend + async batching.

Two measurements, emitted together as ``BENCH_cohort.json``:

(a) **Sync cohort ladder** — full-participation rounds at cohort sizes
    4 / 8 / 16 through each :mod:`repro.fl.executors` backend
    (serial jit loop, vmapped, mesh-sharded).  Reported as steady-state
    rounds/s (the first round carries the jit compile and is excluded),
    so the number is the executor's throughput, not XLA's tracer.

(b) **Async dispatch-window batching** — the ``BufferedAsyncScheduler``
    in the cross-device regime (32 clients, ~20-sample shards, windows of
    16 concurrent finishers) vs. the one-completion-at-a-time baseline
    (window 0).  Reports the executor-call batch sizes, the batch-fill
    ratio (mean batch size / concurrency), and the measured speedup —
    batched (size > 1) calls win where per-completion overhead is a big
    slice of each client's round, which is exactly the many-client
    small-shard setting async FL targets.

``--smoke`` shrinks the ladder (cohorts 4/8, fewer rounds) for CI.
"""
from __future__ import annotations

import argparse
import gc
import sys

import jax
import numpy as np

from repro.core.protocol import ProtocolConfig
from repro.data import federated, synthetic
from repro.fl import AsyncConfig, EngineConfig, FederatedEngine
from repro.launch.cache import enable_compile_cache
from repro.models import cnn

from _harness import steady_round_s as _steady_s, write_report

_PROTO = dict(method="sparse", fixed_sparsity=0.9, batch_size=32,
              local_lr=2e-3)


def _setting(num_clients: int, n_samples: int = 480):
    task = synthetic.ImageTask("cohort_bench", num_classes=4, channels=3,
                               size=32, prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task,
                                        n_samples)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=num_clients)
    model = cnn.make_vgg("vgg_cohort_bench", [8, 16], 4, 3,
                         dense_width=16, pool_after=(0, 1))
    return model, splits


# ------------------------------------------------------------- sync ladder

def bench_sync(cohorts, executors=("serial", "vmap", "sharded"),
               rounds: int = 3):
    rows = []
    for n in cohorts:
        model, splits = _setting(n, n_samples=60 * n + 240)
        cfg = ProtocolConfig(name=f"cohort{n}", **_PROTO)
        for ex in executors:
            eng = FederatedEngine(model, cfg, splits, jax.random.PRNGKey(7),
                                  engine_cfg=EngineConfig(executor=ex))
            res = eng.run(rounds)
            steady = _steady_s(res.records)
            rows.append({"cohort": n, "executor": ex,
                         "steady_round_s": round(steady, 3),
                         "rounds_per_s": round(1.0 / steady, 3),
                         "first_round_s": round(res.records[0].wall_s, 3)})
            print(f"# sync {ex:7s} C={n:2d}: {rows[-1]['rounds_per_s']} "
                  "rounds/s", file=sys.stderr, flush=True)
            # this container is memory-tight: keeping the previous engine's
            # programs + 16x client state alive while the next one compiles
            # visibly distorts the next measurement
            del eng, res
            gc.collect()
    return rows


# ------------------------------------------------------------- async batching

def bench_async(num_clients: int = 32, concurrency: int = 16,
                aggregations: int = 4, window: float = 100.0):
    """Windowed batching vs one-completion-at-a-time at 8+ clients.

    The workload is the cross-device regime that motivates async batching
    (the paper's 100+ client Chest X-Ray splits): MANY clients, each with
    a sub-epoch shard (~20 samples, one real SGD batch of 16), so the
    per-completion dispatch/framework overhead is a large fraction of each
    client's round and folding a window of completions into ONE executor
    call pays.  A window wider than the lognormal latency spread batches
    the whole in-flight set (= concurrency) per call; window 0 is the
    pre-batching serial-completion behaviour over the same scenario.
    Measured twice: on the no-wire fast path (pure cohort execution — the
    quantity this benchmark is about) and end-to-end with the default
    DeepCABAC wire, whose per-client encode+decode cost is identical on
    both sides and dilutes the ratio (codec throughput has its own
    benchmark, ``engine_throughput.py``).
    """
    model, splits = _setting(num_clients, n_samples=29 * num_clients)
    cfg = ProtocolConfig(name="cohort_async",
                         **dict(_PROTO, batch_size=16))
    report = {"clients": num_clients, "concurrency": concurrency,
              "train_samples_per_client": int(splits.client_x.shape[1])}
    for tag, transmit in [("no_wire", False), ("wire", True)]:
        rows = {}
        for label, win in [("windowed", window),
                           ("serial_completions", 0.0)]:
            eng = FederatedEngine(
                model, cfg, splits, jax.random.PRNGKey(7),
                engine_cfg=EngineConfig(
                    mode="async", measure_bytes=transmit,
                    async_cfg=AsyncConfig(buffer_size=concurrency,
                                          concurrency=concurrency,
                                          dispatch_window=win)))
            res = eng.run(aggregations)
            sizes = list(eng.scheduler.batch_sizes)
            rows[label] = {
                "dispatch_window_s": win,
                "executor_calls": len(sizes),
                "batch_sizes": sizes,
                "batch_fill_ratio": round(float(np.mean(sizes))
                                          / eng.scheduler.concurrency, 3),
                "steady_agg_s": round(_steady_s(res.records), 3),
            }
            print(f"# async {tag}/{label}: calls={len(sizes)} "
                  f"sizes={sizes[:8]} "
                  f"steady={rows[label]['steady_agg_s']}s",
                  file=sys.stderr, flush=True)
            del eng, res
            gc.collect()
        rows["windowed_speedup"] = round(
            rows["serial_completions"]["steady_agg_s"]
            / rows["windowed"]["steady_agg_s"], 2)
        report[tag] = rows
    report["batched_calls"] = sum(
        1 for s in report["no_wire"]["windowed"]["batch_sizes"] if s > 1)
    return report


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="cohorts 4/8 and fewer rounds (CI)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--out", default="BENCH_cohort.json")
    args = ap.parse_args()

    cohorts = (4, 8) if args.smoke else (4, 8, 16)
    rounds = args.rounds or (2 if args.smoke else 4)
    report = {
        "mode": "smoke" if args.smoke else "full",
        "devices": len(jax.devices()),
        "sync": bench_sync(cohorts, rounds=rounds),
        "async": bench_async(num_clients=16 if args.smoke else 32,
                             concurrency=8 if args.smoke else 16,
                             aggregations=3 if args.smoke else 4),
    }
    write_report(args.out, report)
    if report["async"]["batched_calls"] == 0:
        print("WARNING: async scheduler issued no batched executor calls",
              file=sys.stderr)
    # the speedup claim is a full-run statement; smoke runs are too short
    # (and often share the CI box) for the ratio to mean anything
    if (not args.smoke
            and report["async"]["no_wire"]["windowed_speedup"] < 1.0):
        print("WARNING: windowed batching slower than serial completions",
              file=sys.stderr)


if __name__ == "__main__":
    main()
