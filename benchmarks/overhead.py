"""Table 1 analogue: scaling-parameter counts and training-time overhead.

Paper: #params_add is 0.009-0.748% of the network; S-training costs
1.17-1.68x one W-iteration.  We measure both on the paper's model families
(CPU wall time; ratios are the comparable quantity, not absolutes).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import scaling as scaling_lib
from repro.launch.cache import enable_compile_cache
from repro.models import cnn


def measure(model, batch=16, iters=5):
    params, state = model.init(jax.random.PRNGKey(0))
    scales = scaling_lib.init_scales(params)
    mask = scaling_lib.scale_mask(params)
    n_orig = sum(l.size for l in jax.tree.leaves(params))
    n_add = scaling_lib.num_scale_params(scales, mask)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32,
                                                  params["conv0"]["w"].shape[1]
                                                  if "conv0" in params else 3))
    first = [k for k in params if "stem" in k or "conv0" in k]
    in_ch = jax.tree.leaves(params[first[0]])[0].shape[1] if first else 3
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, in_ch))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 4)

    def loss_w(p):
        logits, _ = model.apply(scaling_lib.apply_scales_tree(p, scales),
                                state, x, train=True)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(batch), y])

    def loss_s(s):
        logits, _ = model.apply(scaling_lib.apply_scales_tree(params, s),
                                state, x, train=False)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(batch), y])

    gw = jax.jit(jax.grad(loss_w))
    gs = jax.jit(jax.grad(loss_s))
    gw(params); gs(scales)  # compile

    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(gw(params))
    t_w = (time.time() - t0) / iters
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(gw(params))
        jax.block_until_ready(gs(scales))
    t_ws = (time.time() - t0) / iters
    return {"model": model.name, "params_orig": n_orig, "params_add": n_add,
            "add_pct": round(100 * n_add / n_orig, 3),
            "t_overhead": round(t_ws / t_w, 2)}


def transformer_scale_counts():
    """#S for the assigned transformer archs (from the mesh bucket specs).

    Needs the optional ``repro.dist`` mesh runtime; returns no rows (with a
    stderr note) when it is absent so the CNN table still prints.
    """
    try:
        from repro.dist.sharding import MeshLayout
        from repro.dist.train_step import compute_specs, num_scale_params
    except ImportError:
        import sys
        print("# transformer rows skipped: repro.dist mesh runtime absent",
              file=sys.stderr)
        return []
    from repro.configs import all_configs
    from repro.models.transformer import ShardPlan
    out = []
    for name, cfg in sorted(all_configs().items()):
        cfgr = cfg.reduced()
        specs = compute_specs(cfgr, MeshLayout(1, 1, 1, 1), ShardPlan())
        import jax
        n = sum(int(jnp.prod(jnp.asarray(l.shape))) for l in jax.tree.leaves(
            jax.eval_shape(lambda k: __import__("repro.models.transformer",
                                                fromlist=["x"]).init_params(
                k, cfgr, ShardPlan()),
            jax.ShapeDtypeStruct((2,), jnp.uint32))))
        ns = num_scale_params(specs)
        out.append({"model": name + "(reduced)", "params_orig": n,
                    "params_add": ns, "add_pct": round(100 * ns / n, 3),
                    "t_overhead": ""})
    return out


def main():
    enable_compile_cache()
    rows = [measure(cnn.mobilenetv2_small(num_classes=4)),
            measure(cnn.resnet18_small(num_classes=4)),
            measure(cnn.vgg11_thinned(num_classes=4))]
    rows += transformer_scale_counts()
    cols = ["model", "params_orig", "params_add", "add_pct", "t_overhead"]
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))


if __name__ == "__main__":
    main()
