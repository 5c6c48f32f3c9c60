"""Compile a cell's cohort client round for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py --workload <cell>

Builds the configuration's model and protocol as a run does, and compiles
the engine's vmapped client round (``fl/executors.py`` ``VmapExecutor``)
over the cell's cohort at its real shapes for one chip of a described
``v5e:2x2`` topology.  Prints the compile time and the compiler's memory
analysis as one JSON line: what the chip's compiler would refuse, and
whether the program fits, before any chip time is spent.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.lib import cell as cell_lib
    from bench.lib import data
    from repro.core.protocol import make_protocol
    from repro.fl.executors import _VMAP_AXES, _named
    from repro.models import cnn

    jax.config.update("jax_enable_compilation_cache", False)
    cell = cell_lib.load(args.workload)
    cfg, mix = cell["config"], cell["mix"]
    model = getattr(cnn, cfg["model"]["builder"])(**cfg["model"]["args"])
    proto = cell_lib.protocol_config(cfg, mix, cell["name"])
    k = mix["clients"]
    sizes = data.split_sizes(cfg["data"]["samples"], k,
                             cfg["data"]["train_frac"],
                             cfg["data"]["val_frac"])
    steps = max(1, sizes["train"] // proto.batch_size)
    init, client_round, _ = make_protocol(model, proto, steps)
    server, pers0 = jax.eval_shape(init, jax.random.PRNGKey(0))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(shape, dtype, lead=()):
        return jax.ShapeDtypeStruct(tuple(lead) + tuple(shape), dtype,
                                    sharding=one)

    t = cfg["data"]["task"]
    img = (t["size"], t["size"], t["channels"])
    args_ = (
        jax.tree.map(lambda s: shaped(s.shape, s.dtype), server),
        jax.tree.map(lambda s: shaped(s.shape, s.dtype, (k,)), pers0),
        shaped((k, sizes["train"]) + img, jnp.float32),
        shaped((k, sizes["train"]), jnp.int32),
        shaped((k, sizes["val"]) + img, jnp.float32),
        shaped((k, sizes["val"]), jnp.int32),
        shaped((k, steps, proto.batch_size), jnp.int32),
    )
    t0 = time.perf_counter()
    compiled = jax.jit(jax.vmap(_named(client_round), **_VMAP_AXES)).lower(
        *args_).compile()
    mem = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    print(json.dumps({"cell": cell["name"], "target": "v5e (described)",
                      "cohort": k, "steps": steps,
                      "compile_s": time.perf_counter() - t0,
                      **{f: getattr(mem, f, None) for f in fields}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
