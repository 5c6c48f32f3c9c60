"""Population-scale benchmark: rounds/s and peak RSS vs. population size.

The claim the :mod:`repro.fl.population` subsystem makes is architectural:
with the sharded lazy client-state store and streaming cohort sampling,
simulating K=32 cohorts out of 10^3 / 10^4 / 10^5 virtual clients costs
O(cohort) memory — peak RSS must NOT scale with the population.  This
benchmark measures exactly that and ``--guard`` turns it into a CI
assertion (wired into ``scripts/ci.sh --smoke``).

Each population size runs in its OWN subprocess so ``getrusage(RU_MAXRSS)``
is a clean per-population high-water mark (RSS peaks are not resettable
within a process).  The child runs a short sync simulation (K=32 cohorts,
sharded store with a small LRU so spills actually happen), asserts the
store-level bound (``max_hot_seen <= max_hot_shards``), and reports

    {population, rounds_per_s, steady_round_s, peak_rss_mb, store: {...}}

Results land in ``BENCH_population.json``.  The guard fails when the
largest population's peak RSS exceeds the smallest's by more than slack
(15% + 64 MB) — i.e. when memory grew with the population instead of the
cohort.

    PYTHONPATH=src python benchmarks/population_scale.py [--smoke] [--guard]
"""
from __future__ import annotations

import argparse
import json
import resource
import subprocess
import sys

from repro.launch.cache import enable_compile_cache

COHORT = 32
SHARD_SIZE = 16
HOT_SHARDS = 8


def _child(population: int, rounds: int) -> None:
    """One population size, measured in isolation; JSON on stdout."""
    import jax

    from repro.core.protocol import ProtocolConfig
    from repro.data import federated, synthetic
    from repro.fl import (EngineConfig, FederatedEngine, SamplingConfig,
                          StoreConfig)
    from repro.models import cnn

    task = synthetic.ImageTask("pop_bench", num_classes=4, channels=3,
                               size=32, prototypes_per_class=2, noise=0.25)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, 480)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y,
                                       num_clients=8)
    model = cnn.make_vgg("vgg_pop_bench", [8, 16], 4, 3, dense_width=16,
                         pool_after=(0, 1))
    cfg = ProtocolConfig(name="pop_bench", method="sparse",
                         fixed_sparsity=0.9, batch_size=32, local_lr=2e-3,
                         total_rounds=rounds)
    eng = FederatedEngine(
        model, cfg, splits, jax.random.PRNGKey(7),
        engine_cfg=EngineConfig(
            sampling=SamplingConfig(cohort_size=COHORT),
            population=population,
            store=StoreConfig(backend="sharded", shard_size=SHARD_SIZE,
                              max_hot_shards=HOT_SHARDS)))
    from _harness import steady_round_s

    res = eng.run(rounds)
    stats = eng.local_train.store.stats()
    # the store-level O(cohort) bound, independent of the RSS guard
    assert stats["max_hot_seen"] <= HOT_SHARDS, stats
    steady = steady_round_s(res.records)
    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss  # KB (linux)
    print(json.dumps({
        "population": population,
        "cohort": COHORT,
        "rounds": rounds,
        "steady_round_s": round(steady, 3),
        "rounds_per_s": round(1.0 / steady, 3) if steady > 0 else None,
        "final_acc": round(res.final_acc, 4),
        "peak_rss_mb": round(peak_kb / 1024.0, 1),
        "store": stats,
    }))


def _run_child(population: int, rounds: int) -> dict:
    out = subprocess.run(
        [sys.executable, __file__, "--child", str(population),
         "--rounds", str(rounds)],
        capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(
            f"population {population} child failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="two populations, fewer rounds (CI)")
    ap.add_argument("--guard", action="store_true",
                    help="fail if peak RSS scales with population size")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--child", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default="BENCH_population.json")
    args = ap.parse_args()

    if args.child is not None:
        _child(args.child, args.rounds if args.rounds else 2)
        return

    populations = [1_000, 100_000] if args.smoke else [1_000, 10_000, 100_000]
    rounds = args.rounds if args.rounds else (2 if args.smoke else 3)

    results = []
    for pop in populations:
        r = _run_child(pop, rounds)
        results.append(r)
        print(f"population {pop:>7d}: {r['steady_round_s']:.3f} s/round, "
              f"peak RSS {r['peak_rss_mb']:.1f} MB, "
              f"hot shards <= {r['store']['max_hot_seen']} "
              f"(spills {r['store']['spills']})", flush=True)

    lo, hi = results[0], results[-1]
    ratio = hi["peak_rss_mb"] / max(lo["peak_rss_mb"], 1.0)
    growth_mb = hi["peak_rss_mb"] - lo["peak_rss_mb"]
    report = {
        "mode": "smoke" if args.smoke else "full",
        "cohort": COHORT,
        "shard_size": SHARD_SIZE,
        "max_hot_shards": HOT_SHARDS,
        "results": results,
        "rss_ratio_hi_over_lo": round(ratio, 3),
        "rss_growth_mb": round(growth_mb, 1),
    }
    from _harness import write_report

    write_report(args.out, report, echo=False)
    print(f"wrote {args.out}")

    if args.guard:
        # O(cohort) memory: a 100x population may cost at most 15% + 64 MB
        # over the smallest run (allocator noise + spill-dir bookkeeping);
        # O(population) growth (the eager store would add ~100s of MB of
        # stacked residuals at 10^5) fails loudly
        limit = lo["peak_rss_mb"] * 1.15 + 64.0
        if hi["peak_rss_mb"] > limit:
            print(f"GUARD FAIL: peak RSS {hi['peak_rss_mb']:.1f} MB at "
                  f"population {hi['population']} exceeds "
                  f"{limit:.1f} MB (15% + 64 MB over the "
                  f"{lo['population']}-client run's {lo['peak_rss_mb']:.1f} "
                  "MB) — memory is scaling with the population",
                  file=sys.stderr)
            sys.exit(1)
        print(f"guard OK: RSS {lo['peak_rss_mb']:.1f} -> "
              f"{hi['peak_rss_mb']:.1f} MB over a "
              f"{hi['population'] // lo['population']}x population")


if __name__ == "__main__":
    main()
