"""Fig. 3 analogue: scaling-factor statistics by network depth.

The paper observes (§5.3) that scaling factors in shallow layers stay near 1,
deeper layers amplify some filters (s -> 6) while suppressing others
(s -> 0), and the dense output layer amplifies broadly.  We run the FSFL
simulation and report per-layer S statistics (min / mean / max / fraction
suppressed below 0.5 / fraction amplified above 1.5) at the final round.

Also reports the Fig. 2 bidirectional and partial-update settings (paper
§5.2): FSFL with server->client compression, and classifier-only updates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scaling as scaling_lib
from repro.core.fsfl import run_federated
from repro.core.protocol import ProtocolConfig, ServerState, make_protocol
from repro.data import federated, synthetic
from repro.launch.cache import enable_compile_cache
from repro.models import cnn


def _setting(n=640, clients=2):
    task = synthetic.ImageTask("s", 10, 3, prototypes_per_class=2, noise=0.3)
    x, y = synthetic.make_image_dataset(jax.random.PRNGKey(0), task, n)
    splits = federated.split_federated(jax.random.PRNGKey(1), x, y, clients)
    model = cnn.make_vgg("vgg_fig3", [8, 16, 32, 32], 10, 3, dense_width=16,
                         pool_after=(0, 1, 2, 3))
    return model, splits


def fig3_scale_statistics(rounds=8):
    model, splits = _setting()
    cfg = ProtocolConfig(name="fsfl", method="sparse", scaling=True,
                         error_feedback=True, fixed_sparsity=0.9,
                         structured=False, scale_lr=5e-2, scale_subepochs=2,
                         batch_size=32, local_lr=2e-3, total_rounds=rounds)
    # run rounds manually to keep the final server scales
    n_train = splits.client_x.shape[1]
    steps = n_train // cfg.batch_size
    init, round_fn, _ = make_protocol(model, cfg, steps)
    server, pers = init(jax.random.PRNGKey(0))
    C = splits.num_clients
    pers = jax.tree.map(lambda v: jnp.broadcast_to(v, (C,) + v.shape), pers)
    vround = jax.jit(jax.vmap(round_fn, in_axes=(None, 0, 0, 0, 0, 0, 0)))
    key = jax.random.PRNGKey(7)
    for _ in range(rounds):
        key, kb = jax.random.split(key)
        bidx = federated.client_epoch_batches(kb, C, n_train, cfg.batch_size)
        out = vround(server, pers, splits.client_x, splits.client_y,
                     splits.client_val_x, splits.client_val_y, bidx)
        pers = out.persistent
        server = ServerState(
            params=jax.tree.map(lambda p, d: p + jnp.mean(d, 0),
                                server.params, out.recon_delta_params),
            scales=jax.tree.map(lambda s, d: s + jnp.mean(d, 0),
                                server.scales, out.recon_delta_scales),
            bn_state=jax.tree.map(lambda x: jnp.mean(x, 0), out.bn_state))

    mask = scaling_lib.scale_mask(server.params)
    rows = []
    flat = jax.tree_util.tree_flatten_with_path(server.scales)[0]
    fmask = jax.tree.leaves(mask)
    for (kp, s), m in zip(flat, fmask):
        if not m:
            continue
        path = "/".join(str(getattr(k, "key", k)) for k in kp)
        sv = jnp.asarray(s)
        rows.append({
            "layer": path, "n": int(sv.size),
            "s_min": round(float(jnp.min(sv)), 3),
            "s_mean": round(float(jnp.mean(sv)), 3),
            "s_max": round(float(jnp.max(sv)), 3),
            "frac_suppressed": round(float(jnp.mean(sv < 0.5)), 3),
            "frac_amplified": round(float(jnp.mean(sv > 1.5)), 3),
        })
    return rows


def bidirectional_and_partial(rounds=6):
    model, splits = _setting()
    base = dict(method="sparse", error_feedback=True, fixed_sparsity=0.9,
                structured=False, scale_lr=2e-2, scale_subepochs=2,
                batch_size=32, local_lr=2e-3, total_rounds=rounds)
    rows = []
    uni = ProtocolConfig(name="fsfl_uni", scaling=True, **base)
    r = run_federated(model, uni, splits, rounds, jax.random.PRNGKey(42))
    rows.append({"setting": "unidirectional", "acc": round(r.final_acc, 3),
                 "up_MB": round(r.records[-1].cum_bytes / 1e6, 4), "down_MB": 0.0})
    bi = ProtocolConfig(name="fsfl_bi", scaling=True, **base)
    r = run_federated(model, bi, splits, rounds, jax.random.PRNGKey(42),
                      bidirectional=True)
    up = sum(rec.up_bytes for rec in r.records)
    down = sum(rec.down_bytes for rec in r.records)
    rows.append({"setting": "bidirectional", "acc": round(r.final_acc, 3),
                 "up_MB": round(up / 1e6, 4), "down_MB": round(down / 1e6, 4)})
    part = ProtocolConfig(
        name="fsfl_partial", scaling=True,
        trainable_predicate=lambda path, leaf: path.startswith("fc"), **base)
    r = run_federated(model, part, splits, rounds, jax.random.PRNGKey(42))
    rows.append({"setting": "partial(classifier)", "acc": round(r.final_acc, 3),
                 "up_MB": round(r.records[-1].cum_bytes / 1e6, 4), "down_MB": 0.0})
    return rows


def main():
    enable_compile_cache()
    print("# Fig.3 analogue: scaling-factor statistics by depth (final round)")
    rows = fig3_scale_statistics()
    cols = list(rows[0].keys())
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))
    print("# Fig.2 settings: bidirectional / partial updates")
    rows = bidirectional_and_partial()
    cols = list(rows[0].keys())
    print(",".join(cols))
    for r in rows:
        print(",".join(str(r[c]) for c in cols))


if __name__ == "__main__":
    main()
