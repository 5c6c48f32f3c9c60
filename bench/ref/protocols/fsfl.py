"""The paper's FSFL client round (arXiv:2204.04424, Algorithm 1), written
out plainly for one client.

1. Local training of the weights W for one epoch of minibatches with Adam,
   filter scales S frozen, batch norm in training mode.
2. The differential update dW = W_after - W_before plus the error-feedback
   residual of the last round (Eq. 5), sparsified per tensor to the
   largest ``1 - fixed_sparsity`` share by magnitude (ties kept), and
   quantized uniformly: round-half-even of x / step, clipped to +-2**23,
   with the fine step for batch-norm and one-dimensional tensors.  The
   residual keeps what the quantized update left out.
3. ``scale_subepochs`` sub-epochs of training S alone on the sparsely
   updated model (Adam under a linear decay to 0 over ``total_rounds``
   rounds of sub-epoch steps, batch norm frozen, the same minibatches),
   keeping the best S by validation accuracy, ties going to the later one,
   and S unchanged if none beats the start.
4. The S update, quantized with the fine step.

Scales exist for every tensor of two or more dimensions, one per output
channel (dim 0); other tensors carry a scalar 1 that never trains.
"""
import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
MAX_LEVEL = 2 ** 23


def _path(kp):
    return "/".join(str(getattr(k, "key", k)) for k in kp)


def adam_init(tree):
    zeros = jax.tree.map(jnp.zeros_like, tree)
    return (jnp.zeros((), jnp.int32), zeros, zeros)


def adam_step(grads, state, lr_at):
    step, mu, nu = state
    lr = lr_at(step)
    t = (step + 1).astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu,
                      grads)
    bc1, bc2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    upd = jax.tree.map(
        lambda m, v: (-lr * (m / bc1) / (jnp.sqrt(v / bc2) + ADAM_EPS)
                      ).astype(m.dtype), mu, nu)
    return upd, (step + 1, mu, nu)


def init_scales(params):
    return jax.tree.map(
        lambda w: jnp.ones((w.shape[0],) if w.ndim >= 2 else (), w.dtype),
        params)


def _scaled(params, scales):
    return jax.tree.map(
        lambda w, s: w * (s.reshape((-1,) + (1,) * (w.ndim - 1))
                          if s.ndim else s), params, scales)


def _topk(x, sparsity):
    flat = jnp.abs(x.reshape(-1))
    k = max(1, int(round(flat.shape[0] * (1.0 - sparsity))))
    thresh = jax.lax.top_k(flat, k)[0][-1]
    return jnp.where(jnp.abs(x) >= thresh, x, 0.0)


def _quantize(x, step):
    lv = jnp.clip(jnp.round(x / step), -MAX_LEVEL, MAX_LEVEL)
    return (lv * step).astype(x.dtype)


def make(apply, proto: dict, steps_per_round: int, total_rounds: int):
    """(init_persistent, client_round) for the protocol settings
    ``proto`` (the configuration file's ``protocol`` object)."""
    sparsity = proto["fixed_sparsity"]
    step, fine = proto["step_size"], proto["fine_step_size"]
    w_lr = proto["local_lr"]
    s_peak = proto["scale_lr"]
    n_sub = proto["scale_subepochs"]
    s_total = max(total_rounds * n_sub * steps_per_round, 1)

    def s_lr(t):
        frac = jnp.clip(t.astype(jnp.float32) / s_total, 0.0, 1.0)
        return s_peak * (1.0 - frac)

    def loss(params, scales, bn, x, y, train):
        logits, new_bn = apply(_scaled(params, scales), bn, x, train)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(logp[jnp.arange(y.shape[0]), y]), new_bn

    def accuracy(params, scales, bn, x, y):
        logits, _ = apply(_scaled(params, scales), bn, x, False)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    def init_persistent(params, scales):
        return {"residual": jax.tree.map(jnp.zeros_like, params),
                "w_opt": adam_init(params), "s_opt": adam_init(scales)}

    def client_round(params0, scales0, bn0, pers, tx, ty, vx, vy, bidx):
        def w_step(carry, idx):
            params, bn, opt = carry
            (l, new_bn), g = jax.value_and_grad(loss, has_aux=True)(
                params, scales0, bn, tx[idx], ty[idx], True)
            upd, opt = adam_step(g, opt, lambda t: w_lr)
            params = jax.tree.map(lambda p, u: p + u, params, upd)
            return (params, new_bn, opt), l

        (params1, bn1, w_opt), losses = jax.lax.scan(
            w_step, (params0, bn0, pers["w_opt"]), bidx)

        carried = jax.tree.map(lambda a, b, r: (a - b) + r, params1, params0,
                               pers["residual"])
        recon = jax.tree_util.tree_map_with_path(
            lambda kp, c: _quantize(
                _topk(c, sparsity),
                fine if ("bn" in _path(kp) or c.ndim < 2) else step),
            carried)
        residual = jax.tree.map(lambda c, r: c - r, carried, recon)
        params_hat = jax.tree.map(lambda p, r: p + r, params0, recon)

        real = jax.tree.map(lambda s: s.ndim > 0, scales0)

        def s_loss(scales, x, y):
            return loss(params_hat, scales, bn1, x, y, False)[0]

        def sub_epoch(carry, _):
            scales, opt, best, best_acc = carry

            def s_step(inner, idx):
                scales, opt = inner
                g = jax.grad(s_loss)(scales, tx[idx], ty[idx])
                g = jax.tree.map(lambda gi, m: gi if m else jnp.zeros_like(gi),
                                 g, real)
                upd, opt = adam_step(g, opt, s_lr)
                return (jax.tree.map(lambda s, u: s + u, scales, upd),
                        opt), None

            (scales, opt), _ = jax.lax.scan(s_step, (scales, opt), bidx)
            acc = accuracy(params_hat, scales, bn1, vx, vy)
            better = acc >= best_acc
            best = jax.tree.map(lambda b, s: jnp.where(better, s, b), best,
                                scales)
            return (scales, opt, best, jnp.where(better, acc, best_acc)), None

        acc0 = accuracy(params_hat, scales0, bn1, vx, vy)
        (_, s_opt, best, _), _ = jax.lax.scan(
            sub_epoch, (scales0, pers["s_opt"], scales0, acc0), None,
            length=n_sub)
        s_recon = jax.tree.map(lambda b, s: _quantize(b - s, fine), best,
                               scales0)
        out = {"recon": recon, "s_recon": s_recon, "bn": bn1,
               "loss": jnp.mean(losses.astype(jnp.float32))}
        return out, {"residual": residual, "w_opt": w_opt, "s_opt": s_opt}

    return init_persistent, client_round
