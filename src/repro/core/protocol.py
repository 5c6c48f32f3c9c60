"""Federated communication protocols (paper §3-4, Algorithm 1) — jittable core.

One *communication epoch* (round):
  1. clients sync with the server state (clients track the server model;
     local divergence is transient within the round),
  2. local training of W on the client split (scales S frozen),
  3. differential update + optional error feedback (Eq. 5) + sparsification
     (Eqs. 2/3 or fixed-rate / ternary for the STC baseline),
  4. optional filter-scaling sub-epochs on the sparsely-updated model
     (E sub-epochs, frozen W and BN, best-of-subepochs, accept-if-improves),
  5. uniform quantization -> integer levels (the codec input).

Everything here is pure-jittable and vmapped over the client axis; the host
loop in fsfl.py does server aggregation + DeepCABAC byte measurement.

Baseline matrix (Table 2):
  fedavg           no compression
  fedavg_nnc       quantization + DeepCABAC only
  stc              ternary + error feedback   [21]
  eqs23            our sparsification (Eqs. 2+3 or fixed-rate), no scaling
  stc_scaled       STC + filter scaling (STC-dagger)
  fsfl             Eqs. 2+3 / fixed-rate + scaling (+ optional error feedback)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.comms import stages as stages_lib
from repro.core import delta as delta_lib
from repro.core import quant as quant_lib
from repro.core import scaling as scaling_lib
from repro.core import sparsify as sparsify_lib
from repro.models.cnn import CNNModel
from repro.optim import adam, apply_updates, sgd
from repro.optim import schedule as schedule_lib


@dataclasses.dataclass(frozen=True)
class ProtocolConfig:
    name: str = "fsfl"
    # --- compression ---
    method: str = "sparse"            # "none" | "sparse" | "ternary"
    quantize: bool = True
    step_size: float = quant_lib.STEP_SIZE_UNI
    fine_step_size: float = quant_lib.STEP_SIZE_FINE
    delta: float = 1.0                # Eq. 2
    gamma: float = 1.0                # Eq. 3
    fixed_sparsity: float | None = None   # Table 2: 0.96
    structured: bool = True
    unstructured: bool = True
    error_feedback: bool = False      # Eq. 5
    # --- scaling (the paper's contribution) ---
    scaling: bool = False
    scale_subepochs: int = 2          # E
    scale_lr: float = 1e-3
    scale_optimizer: str = "adam"     # "adam" | "sgd"
    scale_schedule: str = "none"      # "none" | "linear" | "cawr"
    scale_predicate: Callable | None = None  # which leaves get S (None=default)
    # --- local training ---
    local_lr: float = 1e-3
    local_optimizer: str = "adam"
    batch_size: int = 64
    # --- partial updates (VGG16_partial) ---
    trainable_predicate: Callable | None = None  # None = everything trainable
    # --- misc ---
    total_rounds: int = 15            # |T|, for schedule horizons


class ClientPersistent(NamedTuple):
    """Per-client state that persists across rounds (stacked on client axis)."""
    residual: Any
    opt_state: Any
    scale_opt_state: Any
    sched_step: jax.Array  # scale-schedule step counter


class ServerState(NamedTuple):
    params: Any
    scales: Any
    bn_state: Any


class RoundOutput(NamedTuple):
    levels_params: Any        # int32 levels per client (codec input)
    levels_scales: Any
    recon_delta_params: Any   # dequantized reconstruction (what server applies)
    recon_delta_scales: Any
    bn_state: Any
    persistent: ClientPersistent
    metrics: Any


# Fine-quantized leaves: biases / norm params (1-D) per paper §5.1.
# (Lives with the other codec stages; kept as an alias for old importers.)
_path_fine_mask = stages_lib.path_fine_mask


def _trainable_mask(params: Any, predicate) -> Any:
    if predicate is None:
        return jax.tree.map(lambda _: True, params)
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: predicate(scaling_lib.path_str(kp), leaf), params)


def _mask_tree(tree: Any, mask: Any) -> Any:
    return jax.tree.map(lambda x, m: x if m else jnp.zeros_like(x), tree, mask)


def make_protocol(model: CNNModel, cfg: ProtocolConfig, steps_per_round: int):
    """Builds (init_fn, client_round_fn, eval_fn).

    client_round_fn is vmappable over the leading client axis of
    (data, persistent state); server state is broadcast.
    """
    w_opt = (adam(cfg.local_lr) if cfg.local_optimizer == "adam"
             else sgd(cfg.local_lr, momentum=0.9))

    sub_steps = steps_per_round  # scale sub-epoch reuses the round's batches
    if cfg.scale_schedule == "none":
        s_sched = schedule_lib.constant(cfg.scale_lr)
    elif cfg.scale_schedule == "linear":
        s_sched = schedule_lib.linear(
            cfg.scale_lr, cfg.total_rounds * cfg.scale_subepochs * max(sub_steps, 1))
    else:  # cawr: warm restart each round, decaying across that round's sub-epochs
        s_sched = schedule_lib.cawr(
            cfg.scale_lr, period=max(cfg.scale_subepochs * sub_steps, 1))
    s_opt = (adam(s_sched) if cfg.scale_optimizer == "adam"
             else sgd(s_sched, momentum=0.9))

    up_stages = stages_lib.UpstreamStages(
        method=cfg.method, quantize=cfg.quantize,
        sparsify=sparsify_lib.SparsifyConfig(
            delta=cfg.delta, gamma=cfg.gamma, step_size=cfg.step_size,
            unstructured=cfg.unstructured, structured=cfg.structured,
            fixed_sparsity=cfg.fixed_sparsity),
        quant=quant_lib.QuantConfig(step_size=cfg.step_size,
                                    fine_step_size=cfg.fine_step_size),
        ternary_sparsity=cfg.fixed_sparsity or 0.96)

    scale_pred = cfg.scale_predicate or scaling_lib.default_predicate

    # ------------------------------------------------------------- losses

    def logits_fn(params, scales, bn_state, x, train):
        scaled = scaling_lib.apply_scales_tree(params, scales)
        return model.apply(scaled, bn_state, x, train=train)

    def loss_fn(params, scales, bn_state, x, y, train):
        logits, new_bn = logits_fn(params, scales, bn_state, x, train)
        loss = jnp.mean(
            -jax.nn.log_softmax(logits)[jnp.arange(y.shape[0]), y])
        return loss, new_bn

    def accuracy(params, scales, bn_state, x, y):
        logits, _ = logits_fn(params, scales, bn_state, x, train=False)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    # ------------------------------------------------------------- init

    def init(key):
        params, bn_state = model.init(key)
        scales = scaling_lib.init_scales(params, scale_pred)
        server = ServerState(params, scales, bn_state)

        def per_client(params):
            return ClientPersistent(
                residual=jax.tree.map(jnp.zeros_like, params),
                opt_state=w_opt.init(params),
                scale_opt_state=s_opt.init(scaling_lib.init_scales(params, scale_pred)),
                sched_step=jnp.zeros((), jnp.int32),
            )

        return server, per_client(params)

    smask_cache = {}

    def _smask(params):
        # key on the treedef itself (hashable, structural equality) — id()
        # of a transient treedef can be recycled after garbage collection
        key = jax.tree.structure(params)
        if key not in smask_cache:
            smask_cache[key] = scaling_lib.scale_mask(params, scale_pred)
        return smask_cache[key]

    # ------------------------------------------------------------- round

    def client_round(server: ServerState, persistent: ClientPersistent,
                     train_x, train_y, val_x, val_y, batch_idx) -> RoundOutput:
        """One communication epoch for ONE client (vmap over clients)."""
        params0, scales0, bn0 = server.params, server.scales, server.bn_state
        t_mask = _trainable_mask(params0, cfg.trainable_predicate)
        s_mask = _smask(params0)
        fine_mask = _path_fine_mask(params0)

        # ---- 2. local training of W (S frozen) --------------------------
        def w_step(carry, idx):
            params, bn, opt_state = carry
            x, y = train_x[idx], train_y[idx]
            (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, scales0, bn, x, y, True)
            grads = _mask_tree(grads, t_mask)
            upd, opt_state = w_opt.update(grads, opt_state, params)
            return (apply_updates(params, upd), new_bn, opt_state), loss

        # stage scopes (HLO metadata only): the device profile attributes
        # each operation to train_weights, compress or train_scales
        with jax.named_scope("train_weights"):
            (params1, bn1, opt_state1), losses = jax.lax.scan(
                w_step, (params0, bn0, persistent.opt_state), batch_idx)

        # ---- 3. codec stages: delta + error feedback + sparsify + quant --
        with jax.named_scope("compress"):
            raw_delta = stages_lib.extract_delta(params1, params0)
            carried = stages_lib.carry_residual(raw_delta, persistent.residual,
                                                cfg.error_feedback)
            levels, recon_delta, sparse_delta = up_stages.compress(carried,
                                                                   fine_mask)
            new_residual = stages_lib.new_residual(carried, recon_delta,
                                                   cfg.error_feedback,
                                                   persistent.residual)

            # the sparsely updated model that S-training sees (Alg. 1 line 11)
            params_hat = delta_lib.tree_add(params0, recon_delta)

        # ---- 4. scaling-factor sub-epochs (Alg. 1 lines 13-19) ----------
        if cfg.scaling:
            def s_loss(scales, x, y):
                # BN frozen (train=False) and W frozen by construction
                loss, _ = loss_fn(params_hat, scales, bn1, x, y, False)
                return loss

            def sub_epoch(carry, _):
                scales, sopt, best_s, best_perf = carry

                def s_step(inner, idx):
                    scales, sopt = inner
                    g = jax.grad(s_loss)(scales, train_x[idx], train_y[idx])
                    g = jax.tree.map(
                        lambda gi, m: gi if m else jnp.zeros_like(gi), g, s_mask)
                    upd, sopt = s_opt.update(g, sopt, scales)
                    return (apply_updates(scales, upd), sopt), 0.0

                (scales, sopt), _ = jax.lax.scan(s_step, (scales, sopt), batch_idx)
                perf = accuracy(params_hat, scales, bn1, val_x, val_y)
                better = perf >= best_perf
                best_s = jax.tree.map(
                    lambda b, s: jnp.where(better, s, b), best_s, scales)
                best_perf = jnp.where(better, perf, best_perf)
                return (scales, sopt, best_s, best_perf), perf

            with jax.named_scope("train_scales"):
                perf0 = accuracy(params_hat, scales0, bn1, val_x, val_y)
                (scales_end, sopt1, best_s, best_perf), _ = jax.lax.scan(
                    sub_epoch, (scales0, persistent.scale_opt_state, scales0, perf0),
                    None, length=cfg.scale_subepochs)
            scales1 = best_s  # == scales0 if no sub-epoch improved (discard rule)
            sopt_state1 = sopt1
        else:
            scales1 = scales0
            sopt_state1 = persistent.scale_opt_state
            perf0 = accuracy(params_hat, scales0, bn1, val_x, val_y)
            best_perf = perf0

        # ---- 5. quantize the S delta (fine step size) --------------------
        with jax.named_scope("compress"):
            s_delta = delta_lib.tree_sub(scales1, scales0)
            s_levels, s_recon = stages_lib.quantize_scales_delta(
                s_delta, cfg.fine_step_size)

        metrics = {
            "train_loss": jnp.mean(losses),
            "val_acc_unscaled": perf0,
            "val_acc": best_perf,
            "update_sparsity": sparsify_lib.tree_sparsity(sparse_delta),
        }
        return RoundOutput(
            levels_params=levels, levels_scales=s_levels,
            recon_delta_params=recon_delta, recon_delta_scales=s_recon,
            bn_state=bn1,
            persistent=ClientPersistent(new_residual, opt_state1, sopt_state1,
                                        persistent.sched_step + cfg.scale_subepochs * sub_steps),
            metrics=metrics)

    def evaluate(server: ServerState, x, y):
        return accuracy(server.params, server.scales, server.bn_state, x, y)

    return init, client_round, evaluate


# --------------------------------------------------------------------------
# Named baseline configurations (Table 2 rows)
# --------------------------------------------------------------------------

def baseline_configs(fixed_sparsity: float = 0.96, **common) -> dict[str, ProtocolConfig]:
    return {
        "fedavg": ProtocolConfig(name="fedavg", method="none", quantize=False, **common),
        "fedavg_nnc": ProtocolConfig(name="fedavg_nnc", method="none", **common),
        # Table 2 uses one constant (unstructured-comparable) 96% rate "for
        # STC and our methods"; error accumulation (Eq. 5) is part of the
        # fixed-rate pipelines — without it a 96%-sparse update at this model
        # scale discards nearly all signal (§5.5).
        "stc": ProtocolConfig(name="stc", method="ternary", error_feedback=True,
                              fixed_sparsity=fixed_sparsity, structured=False,
                              **common),
        "eqs23": ProtocolConfig(name="eqs23", method="sparse",
                                error_feedback=True, structured=False,
                                fixed_sparsity=fixed_sparsity, **common),
        "stc_scaled": ProtocolConfig(name="stc_scaled", method="ternary",
                                     error_feedback=True, scaling=True,
                                     fixed_sparsity=fixed_sparsity,
                                     structured=False, **common),
        "fsfl": ProtocolConfig(name="fsfl", method="sparse", scaling=True,
                               error_feedback=True, structured=False,
                               fixed_sparsity=fixed_sparsity, **common),
    }
