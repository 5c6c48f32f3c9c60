"""Drive the FL ingest server: encode a cohort of sparse ternary client
updates, then serve them through the streaming decode-and-accumulate
pipeline (`repro.fl.ingest`) twice — block-decode vectorized vs.
speculative multi-symbol CABAC — and check both produce the identical
aggregate the gather path would.

This fronts the same StreamingIngest stage the federated engine runs
behind ``EngineConfig.ingest = "streaming"``; here it is isolated so the
server-side decode rate is visible (no training in the loop).

    PYTHONPATH=src python examples/serve_decode.py [--k 16] [--chunk 8]
"""
import argparse

import jax
import numpy as np

from repro import comms
from repro.fl.ingest import IngestConfig
from repro.launch.cache import enable_compile_cache
from repro.launch.ingest_serve import serve_cohort, synthetic_cohort


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=16, help="cohort size")
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--density", type=float, default=0.04)
    args = ap.parse_args()

    codec = comms.get_codec("nnc-cabac")
    upds, spec, raw = synthetic_cohort(args.k, density=args.density)
    payloads = codec.encode_batch(upds, spec, clients=list(range(args.k)))
    wire = sum(len(p) for p in payloads)
    print(f"encoded K={args.k} ternary updates: {raw / 1e6:.1f} MB raw -> "
          f"{wire / 1e6:.3f} MB wire ({raw / wire:.0f}x)")

    results = {}
    for engine in ("vectorized", "speculative"):
        cfg = IngestConfig(chunk=args.chunk, decode_engine=engine)
        res = serve_cohort(codec, payloads, spec, cfg)
        assert res.accepted == args.k and not res.rejected
        s = res.stats
        print(f"{engine:>12}: {s.payloads_per_s:8.1f} payloads/s  "
              f"{s.mb_per_s:5.2f} MB/s  (resident<={s.max_resident}, "
              f"cohort K={args.k} never materialised)")
        results[engine] = res

    # both engines fold to the bit-identical aggregate — and the ingest
    # mean equals the gather-path mean over the same decoded trees
    a, b = results["vectorized"], results["speculative"]
    for la, lb in zip(jax.tree.leaves(a.delta_params),
                      jax.tree.leaves(b.delta_params)):
        np.testing.assert_array_equal(la, lb)
    decs = codec.decode_batch(payloads, spec)
    gather = jax.tree.map(
        lambda *ls: np.mean(np.stack([np.asarray(l, np.float64) for l in ls]),
                            axis=0).astype(np.float32),
        *[d.params for d in decs])
    for la, lg in zip(jax.tree.leaves(a.delta_params),
                      jax.tree.leaves(gather)):
        np.testing.assert_array_equal(la, lg)
    print("aggregates identical: vectorized == speculative == gather mean")


if __name__ == "__main__":
    main()
