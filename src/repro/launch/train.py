"""Training launcher: `--arch <id>` selects any assigned architecture.

On real hardware this drives the production mesh; in this container it runs
REDUCED configs on a small simulated mesh (the same shard_map step the
dry-run lowers at 512 chips).

    PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --steps 20
    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x22b \
        --reduced --steps 10 --dense
"""
import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp


def main():
    # 8 simulated host devices for the (4, 2) mesh; read at first backend init
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="full config (production mesh; dry-run container "
                         "cannot execute this, only lower it)")
    ap.add_argument("--dense", action="store_true",
                    help="dense FedAvg exchange instead of FSFL compression")
    ap.add_argument("--no-scale-step", action="store_true")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()

    from repro.launch import require_dist
    require_dist()
    from repro import checkpoint
    from repro.configs import get, make_inputs
    from repro.data.synthetic import make_markov_lm
    from repro.dist.collectives import MeshCompression
    from repro.dist.sharding import MeshLayout, make_plan
    from repro.dist import train_step as train_lib
    from repro.launch.mesh import make_mesh

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    mesh = make_mesh((4, 2), ("data", "model"))
    layout = MeshLayout(1, 4, 2, clients_per_pod=2)
    plan = make_plan(cfg, 2)
    settings = train_lib.TrainSettings(
        lr=args.lr, microbatches=args.microbatches,
        compression=MeshCompression(enabled=not args.dense, block=64,
                                    sparsity=0.9),
        scale_step=not args.no_scale_step)

    make, sds, sh, specs = train_lib.make_train_step(cfg, layout, plan, mesh,
                                                     settings)
    B, S = args.batch, args.seq
    batch = make_inputs(jax.random.PRNGKey(1), cfg, B, S)
    batch_sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in batch.items()}
    fn = make(batch_sds)
    batch_sh = train_lib.batch_shardings(cfg, layout, mesh, batch_sds)
    run = jax.jit(fn, in_shardings=(sh, batch_sh), out_shardings=(sh, None))
    state = train_lib.init_state(jax.random.PRNGKey(0), cfg, layout, plan,
                                 mesh, settings)
    x, y = make_markov_lm(jax.random.PRNGKey(2), cfg.vocab, B, S)
    batch["tokens"], batch["labels"] = x, y
    for i in range(args.steps):
        state, metrics = run(state, batch)
        print(f"[{cfg.name}] step {i:3d} loss={float(metrics['loss']):.4f} "
              f"payload={float(metrics['payload_bytes'])/1e3:.1f}kB",
              flush=True)
    if args.ckpt:
        n = checkpoint.save(args.ckpt, jax.device_get(state.buckets))
        print(f"saved {args.ckpt} ({n/1e6:.2f} MB)")


if __name__ == "__main__":
    main()
