"""Readings that set a cell's correctness limits (``bench/limits``).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
        [--control-seeds 3] [--out FILE]

For each seed, in one process: the program's first rounds at the cell's
own size (set-up as a run makes it, no window), the plain reference, and
the comparison (``bench/lib/check.py``): the lower readings.  For the
first ``--control-seeds`` seeds also the control, the reference computed
in bfloat16 in the program's place, and the faults planted in the
reference (``bench/ref/federated.py`` ``FAULTS``), each compared with the
sound reference: the upper readings.  A state left unchanged reads 1 by
construction and is not run.  One JSON line per reading; needs the chip,
like a run.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp

    from bench.lib import cell as cell_lib
    from bench.lib import check, data, harness
    from bench.ref import federated

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = cell_lib.load(args.workload)
    harness.require_chips(cell["chips"])
    out = open(args.out, "a") if args.out else sys.stdout

    def emit(**fields):
        print(json.dumps({"cell": cell["name"], **fields}), file=out,
              flush=True)

    def norms(r):
        """Per-leaf changes after the first and the last round (norm,
        dimensions)."""
        return {"d1": check.leaf_stats(r["state0"], r["state1"]),
                "dl": check.leaf_stats(r["state0"], r["state_last"])}

    for i, seed in enumerate(args.seeds):
        k_data, k_engine = jax.random.split(data.root_key(seed))
        splits = jax.block_until_ready(data.make_splits(
            k_data, cell["config"]["data"], cell["mix"]["clients"]))
        t = time.perf_counter()
        with harness._precision(cell["config"]):
            engine = cell_lib.build_engine(cell, splits, k_engine, "off")
            prog = harness.first_rounds(engine)
        engine.uplink.close()
        del engine
        gc.collect()
        t_prog = time.perf_counter() - t
        t = time.perf_counter()
        ref = harness.reference_readings(cell, splits, k_engine)
        t_ref = time.perf_counter() - t
        emit(seed=seed, run="reference", loss=ref["loss"], acc=ref["acc"],
             **norms(ref))
        emit(seed=seed, run="program", **check.compare(prog, ref),
             loss=prog["loss"], acc=prog["acc"], bytes=prog["bytes"],
             program_s=t_prog, reference_s=t_ref, **norms(prog))
        if i >= args.control_seeds:
            continue
        ctl = harness.reference_readings(cell, splits, k_engine,
                                         dtype=jnp.bfloat16)
        emit(seed=seed, run="control_bf16", **check.compare(ctl, ref),
             loss=ctl["loss"], acc=ctl["acc"], **norms(ctl))
        for fault in federated.FAULTS[1:]:
            bad = harness.reference_readings(cell, splits, k_engine,
                                             fault=fault)
            emit(seed=seed, run=f"fault_{fault}", **check.compare(bad, ref),
                 loss=bad["loss"], acc=bad["acc"], **norms(bad))
    emit(run="done", seconds=time.perf_counter() - T0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
