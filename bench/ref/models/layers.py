"""Layers of the plain reference models: NHWC activations, OIHW filters."""
import jax
import jax.numpy as jnp

BN_MOMENTUM = 0.9
BN_EPS = 1e-5


def conv_init(key, out_c, in_c, k):
    w = jax.random.normal(key, (out_c, in_c, k, k))
    return {"w": (w * jnp.sqrt(2.0 / (in_c * k * k))).astype(jnp.float32)}


def dense_init(key, out_d, in_d):
    w = jax.random.normal(key, (out_d, in_d)) * jnp.sqrt(2.0 / in_d)
    return {"w": w.astype(jnp.float32),
            "b": jnp.zeros((out_d,), jnp.float32)}


def bn_init(c):
    return ({"gamma": jnp.ones((c,), jnp.float32),
             "beta": jnp.zeros((c,), jnp.float32)},
            {"mean": jnp.zeros((c,), jnp.float32),
             "var": jnp.ones((c,), jnp.float32)})


def conv(w, x, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def dense(p, x):
    return x @ p["w"].T + p["b"]


def batch_norm(p, s, x, train):
    """Batch statistics over N, H, W when training (running statistics
    move by 1 - BN_MOMENTUM), the running statistics otherwise."""
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        s = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
             "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    else:
        mean, var = s["mean"], s["var"]
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["gamma"] + p["beta"]
    return y, s


def max_pool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")
