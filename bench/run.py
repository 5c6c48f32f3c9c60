"""Chip benchmark of the federated engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell (``BENCHMARK.json``
``workloads``) names a configuration (``bench/configs``) and a traffic mix
(``bench/mixes``); ``bench/lib/harness.py`` says what a run does.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace
(``bench/metrics/<name>.py``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), then ``checks``, each compared
number beside its limit.  Without a TPU, or with fewer chips than the cell
asks for, the run exits 1 and prints no result.

JAX's compilation cache lives in ``<checkout>/.jax_cache``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        import jax

        import repro  # noqa: F401  (the system under test)
        from bench.lib import cell as cell_lib
        from bench.lib import harness
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the program: {e}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        cell = cell_lib.load(args.workload)
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             out_dir=OUT_DIR / args.workload, t0=T0)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
