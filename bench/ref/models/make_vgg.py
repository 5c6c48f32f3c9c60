"""Plain VGG of the paper's thinned VGG-11 (arXiv:2204.04424, sec. 5.1).

3x3 convolutions (no bias) each followed by batch norm and ReLU, 2x2 max
pooling after the layers in ``pool_after``, global average pooling, then a
ReLU dense layer and the classifier.  He-normal initialisation, zero dense
bias, batch-norm scale 1 and shift 0, running mean 0 and variance 1.
Parameters are named as the configuration's builder names them, so the
comparison can pair them leaf by leaf.
"""
import jax
import jax.numpy as jnp

from bench.ref.models import layers


def build(name, widths, num_classes, in_channels=3, dense_width=128,
          pool_after=(0, 1, 3, 5, 7)):
    pool_after = set(pool_after)

    def init(key):
        keys = jax.random.split(key, len(widths) + 2)
        params, state = {}, {}
        in_c = in_channels
        for i, w in enumerate(widths):
            params[f"conv{i}"] = layers.conv_init(keys[i], w, in_c, 3)
            params[f"bn{i}"], state[f"bn{i}"] = layers.bn_init(w)
            in_c = w
        params["fc0"] = layers.dense_init(keys[-2], dense_width, widths[-1])
        params["fc1"] = layers.dense_init(keys[-1], num_classes, dense_width)
        return params, state

    def apply(params, state, x, train):
        new_state = {}
        for i in range(len(widths)):
            x = layers.conv(params[f"conv{i}"]["w"], x, 1)
            x, new_state[f"bn{i}"] = layers.batch_norm(
                params[f"bn{i}"], state[f"bn{i}"], x, train)
            x = jax.nn.relu(x)
            if i in pool_after:
                x = layers.max_pool2(x)
        x = jnp.mean(x, axis=(1, 2))
        x = jax.nn.relu(layers.dense(params["fc0"], x))
        return layers.dense(params["fc1"], x), new_state

    return init, apply
