"""Server ingest rate: gather vs. streaming vs. streaming+speculative.

Times the three uplink-intake paths over the SAME cohort of paper-regime
ternary payloads (sparse +-1 differentials, the 561/566-pin workload):

* ``gather``      — the PR 5 baseline: one ``Codec.decode_batch`` over the
                    whole cohort (two-pass vectorized CABAC), then a batch
                    mean over the K materialised pytrees.
* ``streaming``   — ``repro.fl.ingest.StreamingIngest`` with the same
                    vectorized decoder: chunked decode folding into running
                    accumulators, O(1) resident trees.
* ``streaming_spec`` — streaming with ``decode_engine="speculative"``:
                    the multi-symbol CABAC decoder on the decode stage.

Reports payloads/s and wire MB/s at K=8 and K=32 into
``BENCH_ingest.json``.  ``--guard`` gates CI: streaming+speculative must
hold >= 1.5x payloads/s over the gather block-decode baseline at K=32
(measured headroom of the speculative decoder on this regime is ~2x, so
1.5 leaves noise margin without letting a regression through).

Timings are strictly interleaved (rotate contenders each repetition,
best-of-N) — the container's clock drifts under throttling, so
back-to-back blocks bias whichever ran in the fast phase.

    PYTHONPATH=src python benchmarks/ingest_rate.py [--smoke] [--guard]
        [--out BENCH_ingest.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro import comms
from repro.fl.ingest import IngestConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.ingest_serve import serve_cohort, synthetic_cohort

DENSITY = 0.04      # sparsity 0.96 — the regime the speculative decoder
                    # targets (STC-style ternary differentials)
GUARD_MIN_SPEEDUP = 1.5


def _race_n(fns, reps):
    """Best-of-N for a list of contenders, strictly interleaved."""
    best = [float("inf")] * len(fns)
    outs = [None] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            outs[i] = fn()
            best[i] = min(best[i], time.perf_counter() - t0)
    return best, outs


def _gather_intake(codec, payloads, spec):
    """PR 5 baseline: block decode -> K resident trees -> batch mean."""
    decs = codec.decode_batch(payloads, spec)
    mean = jax.tree.map(
        lambda *ls: np.mean(np.stack([np.asarray(l, np.float64)
                                      for l in ls]), axis=0).astype(
            np.float32),
        *[d.params for d in decs])
    return mean


def ingest_bench(k: int, reps: int = 5, chunk: int = 8) -> dict:
    codec = comms.get_codec("nnc-cabac")
    upds, spec, raw = synthetic_cohort(k, density=DENSITY)
    payloads = codec.encode_batch(upds, spec, clients=list(range(k)))
    wire = sum(len(p) for p in payloads)
    cfg_vec = IngestConfig(chunk=chunk, decode_engine="vectorized")
    cfg_spec = IngestConfig(chunk=chunk, decode_engine="speculative")

    def stream(cfg):
        res = serve_cohort(codec, payloads, spec, cfg)
        assert res.accepted == k and not res.rejected
        assert res.stats.max_resident <= chunk
        return res

    (t_g, t_s, t_p), (m_g, r_s, r_p) = _race_n(
        [lambda: _gather_intake(codec, payloads, spec),
         lambda: stream(cfg_vec),
         lambda: stream(cfg_spec)], reps)

    # all three intakes agree bit-for-bit on the aggregate
    for res in (r_s, r_p):
        for a, b in zip(jax.tree.leaves(m_g),
                        jax.tree.leaves(res.delta_params)):
            np.testing.assert_array_equal(a, b)

    out = {"K": k, "chunk": chunk, "reps": reps,
           "wire_bytes": wire, "raw_bytes": raw,
           "density": DENSITY}
    for name, t in [("gather", t_g), ("streaming", t_s),
                    ("streaming_spec", t_p)]:
        out[name] = {"ms": round(t * 1e3, 1),
                     "payloads_per_s": round(k / t, 1),
                     "wire_MBps": round(wire / 1e6 / t, 3)}
    out["speedup_spec_vs_gather"] = round(t_g / t_p, 2)
    out["speedup_stream_vs_gather"] = round(t_g / t_s, 2)
    return out


def run(guard: bool = False, smoke: bool = False) -> dict:
    reps = 3 if smoke else 7
    rows = {f"K{k}": ingest_bench(k, reps=reps) for k in (8, 32)}
    speedup = rows["K32"]["speedup_spec_vs_gather"]
    if guard and speedup < GUARD_MIN_SPEEDUP:
        # one retry at higher reps: a throttled phase can depress the
        # ratio before the guard judges it
        rows["K32"] = ingest_bench(32, reps=reps + 6)
        speedup = rows["K32"]["speedup_spec_vs_gather"]
    result = {
        "cohorts": rows,
        "guard": {
            "min_speedup_spec_vs_gather_K32": GUARD_MIN_SPEEDUP,
            "speedup_spec_vs_gather_K32": speedup,
            "ok": speedup >= GUARD_MIN_SPEEDUP,
        },
    }
    return result


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="fewer reps (CI)")
    ap.add_argument("--guard", action="store_true",
                    help="fail (exit 1) unless streaming+speculative is "
                         f">= {GUARD_MIN_SPEEDUP}x gather block-decode "
                         "payloads/s at K=32")
    ap.add_argument("--out", default="BENCH_ingest.json")
    args = ap.parse_args()
    result = run(guard=args.guard, smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(f"# ingest rate bench -> {args.out}")
    print(json.dumps(result, indent=2))
    if args.guard and not result["guard"]["ok"]:
        print("INGEST GUARD FAILED: streaming+speculative must be >= "
              f"{GUARD_MIN_SPEEDUP}x gather block-decode payloads/s at "
              "K=32", file=sys.stderr)
        sys.exit(1)
    if args.smoke:
        print("smoke OK")


if __name__ == "__main__":
    main()
