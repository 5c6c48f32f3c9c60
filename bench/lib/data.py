"""The benchmark's own data: seeded synthetic image tasks, split per client.

A copy of the generator the program ships (``repro.data.synthetic`` /
``repro.data.federated``), kept here so that the inputs of every cell, and
what the reference sees, are fixed by this directory and not by the code
under test.  Class-conditional smooth prototypes plus noise and random
horizontal flips, normalised; then an IID 70/15/15 split into per-client
train / per-client validation / a shared test set.

Everything is made on the device in one jitted call from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key for any whole seed below 2**64: ``PRNGKey`` keeps only the low
    32 bits, so the high bits are folded in."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64), got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _prototypes(key, n_classes, per_class, channels, size):
    coarse = jax.random.normal(key, (n_classes * per_class, 8, 8, channels))
    protos = jax.image.resize(
        coarse, (n_classes * per_class, size, size, channels),
        method="bilinear")
    return jnp.tanh(protos * 1.5)


def _images(key, task: dict, n: int):
    kp, kl, kn, ks, kf = jax.random.split(key, 5)
    per = task["prototypes_per_class"]
    protos = _prototypes(kp, task["num_classes"], per, task["channels"],
                         task["size"])
    labels = jax.random.randint(kl, (n,), 0, task["num_classes"])
    which = jax.random.randint(ks, (n,), 0, per)
    base = protos[labels * per + which]
    imgs = base + task["noise"] * jax.random.normal(kn, base.shape)
    flip = jax.random.bernoulli(kf, 0.5, (n,))
    imgs = jnp.where(flip[:, None, None, None], imgs[:, :, ::-1, :], imgs)
    imgs = (imgs - jnp.mean(imgs)) / (jnp.std(imgs) + 1e-6)
    return imgs.astype(jnp.float32), labels.astype(jnp.int32)


def split_sizes(samples: int, clients: int, train_frac: float,
                val_frac: float) -> dict[str, int]:
    """Rows per client (train, val) and test rows of the IID split."""
    n_test = int(samples * (1.0 - train_frac - val_frac))
    per = (samples - n_test) // clients
    n_val = max(1, int(per * val_frac / (train_frac + val_frac)))
    return {"train": per - n_val, "val": n_val, "test": n_test}


@functools.partial(jax.jit, static_argnames=("task_items", "samples",
                                             "clients", "train_frac",
                                             "val_frac"))
def _make(key, task_items, samples, clients, train_frac, val_frac):
    task = dict(task_items)
    k_data, k_split = jax.random.split(key)
    x, y = _images(k_data, task, samples)
    perm = jax.random.permutation(k_split, samples)
    x, y = x[perm], y[perm]
    sz = split_sizes(samples, clients, train_frac, val_frac)
    n_test, n_val = sz["test"], sz["val"]
    per = sz["train"] + n_val
    rest_x = x[n_test:n_test + per * clients]
    rest_y = y[n_test:n_test + per * clients]
    cx = rest_x.reshape((clients, per) + x.shape[1:])
    cy = rest_y.reshape((clients, per))
    return {"client_x": cx[:, n_val:], "client_y": cy[:, n_val:],
            "client_val_x": cx[:, :n_val], "client_val_y": cy[:, :n_val],
            "test_x": x[:n_test], "test_y": y[:n_test]}


def make_splits(key: jax.Array, data: dict, clients: int) -> dict:
    """The cell's client splits as a dict of device arrays."""
    task = data["task"]
    return _make(key, tuple(sorted(task.items())), data["samples"], clients,
                 data["train_frac"], data["val_frac"])
