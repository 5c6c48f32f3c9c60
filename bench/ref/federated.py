"""The plain reference of a cell's first rounds: synchronous FedAvg.

Every client of the cohort trains from the same server state with its own
minibatch order, the wire delivers its update as the cell's codec would
(``bench/ref/wire/<codec>.py``), the server adds the plain mean of the
delivered updates to its weights and scales (FedAvg with learning rate 1),
takes the mean of the clients' batch-norm statistics, and measures its
test accuracy.  Clients run one at a time, so the reference holds one
client's activations at once.

Keys follow the engine's documented discipline: the engine key splits
into the initialisation key and the round key; each round splits the
round key into the next one and a batch key, which splits into one key
per client for its epoch's shuffle.

Nothing here imports the program under test.
"""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

REF = Path(__file__).resolve().parent

# faults planted in the reference to read what the comparison makes of
# them: "half" aggregates the first half of the cohort only, "double"
# delivers every update twice as large as it was sent, "scales" leaves the
# server's filter scales unchanged (the scale sub-epochs' work is lost),
# "bn" leaves its batch-norm statistics unchanged (no fold), and
# "stale_eval" measures the test accuracy of the server before the round
FAULTS = (None, "half", "double", "scales", "bn", "stale_eval")


def load(kind: str, name: str):
    """``bench/ref/<kind>/<name>.py`` as a module (names may hold '-')."""
    path = REF / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reference {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_supported(config: dict, mix: dict) -> None:
    eng = mix["engine"]
    if (eng.get("mode", "sync") != "sync"
            or mix.get("cohort_size") is not None
            or eng.get("server_opt", "fedavg") != "fedavg"):
        raise ValueError("the reference runs synchronous full-participation "
                         "FedAvg only")
    if config["dtype"] != "float32":
        raise ValueError("the reference states float32 configurations only")


def host_state(params, scales, bn) -> dict:
    return jax.device_get({"params": params, "scales": scales, "bn": bn})


def run(config: dict, mix: dict, splits: dict, engine_key, rounds: int, *,
        dtype=jnp.float32, fault=None) -> dict:
    """Losses, test accuracies and server states (initial, after round 1,
    after round ``rounds``) of the reference, computed in ``dtype``:
    float32 at the highest matmul precision, or a lower type as is (the
    control)."""
    check_supported(config, mix)
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    model = config["model"]
    init, apply = load("models", model["builder"]).build(**model["args"])
    proto = config["protocol"]
    wire = load("wire", mix["engine"].get("codec", "auto"))
    k = mix["clients"]
    bs = proto["batch_size"]
    n_train = splits["client_x"].shape[1]
    nb = n_train // bs
    init_pers, client_round = load("protocols", proto["row"]).make(
        apply, proto, max(1, nb), mix["total_rounds"])
    prec = "highest" if dtype == jnp.float32 else "default"

    def cast(tree):
        return jax.tree.map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, tree)

    with jax.default_matmul_precision(prec):
        jround = jax.jit(client_round)

        @jax.jit
        def accuracy(params, scales, bn, x, y):
            scaled = jax.tree.map(
                lambda w, s: w * (s.reshape((-1,) + (1,) * (w.ndim - 1))
                                  if s.ndim else s), params, scales)
            logits, _ = apply(scaled, bn, x, False)
            return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

        @jax.jit
        def shuffles(kb):
            keys = jax.random.split(kb, k)
            return jax.vmap(lambda kk: jax.random.permutation(
                kk, n_train)[:nb * bs].reshape(nb, bs))(keys)

        k_init, key = jax.random.split(engine_key)
        params, bn = init(k_init)
        scales = load("protocols", proto["row"]).init_scales(params)
        params, scales, bn = cast(params), cast(scales), cast(bn)
        data = cast({n: splits[n] for n in ("client_x", "client_val_x",
                                            "test_x")})
        pers = [init_pers(params, scales) for _ in range(k)]
        out = {"loss": [], "acc": [], "state0": host_state(params, scales,
                                                           bn)}
        for r in range(rounds):
            key, kb = jax.random.split(key)
            bidx = shuffles(kb)
            sent = range(k // 2) if fault == "half" else range(k)
            dp, ds, dbn, losses = [], [], [], []
            for i in range(k):
                res, pers[i] = jround(params, scales, bn, pers[i],
                                      data["client_x"][i],
                                      splits["client_y"][i],
                                      data["client_val_x"][i],
                                      splits["client_val_y"][i], bidx[i])
                losses.append(float(res["loss"]))
                if i not in sent:
                    continue
                rp, rs = wire.decode(res["recon"], res["s_recon"])
                if fault == "double":
                    rp = jax.tree.map(lambda x: 2 * x, rp)
                dp.append(rp)
                ds.append(rs)
                dbn.append(res["bn"])

            def mean(trees):
                return jax.tree.map(lambda *xs: jnp.mean(jnp.stack(xs), 0),
                                    *trees)

            seen = (params, scales, bn)
            params = jax.tree.map(lambda p, d: p + d, params, mean(dp))
            if fault != "scales":
                scales = jax.tree.map(lambda s, d: s + d, scales, mean(ds))
            if fault != "bn":
                bn = mean(dbn)
            if fault != "stale_eval":
                seen = (params, scales, bn)
            out["loss"].append(float(np.mean(losses)))
            out["acc"].append(float(accuracy(*seen, data["test_x"],
                                             splits["test_y"])))
            if r == 0:
                out["state1"] = host_state(params, scales, bn)
        out["state_last"] = host_state(params, scales, bn)
    return out
