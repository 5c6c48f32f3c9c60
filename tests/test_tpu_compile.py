"""The main-path Pallas kernels compile for a TPU v5e, at paper width.

Interpret mode accepts block shapes Mosaic refuses (rank-1 blocks that
break the tiling, ``(1, n)`` row blocks, ``(1,)`` scalar blocks), so the
CPU parity suites cannot show that a kernel runs on the chip.  These tests
lower and compile each kernel for a ``v5e:2x2`` topology that is described,
not attached (the TPU compiler ships with jaxlib), at the flattened update
size of the paper's thinned VGG-11, and check that the compiled program
holds the Mosaic kernel.  Nothing runs: this says nothing about results or
times, only that the chip's compiler takes the kernels.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around these
compiles (they cannot be read back without a chip).
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.delta_compress import (delta_apply, delta_compress,
                                          delta_compress_batch)
from repro.kernels.level_assign import level_assign

VGG11_THINNED_PARAMS = 849_834   # models/cnn.py vgg11_thinned(10)
BLOCK = 128                      # the int8-blockscale wire block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_delta_compress_compiles(one_chip):
    n = VGG11_THINNED_PARAMS
    text = _compile(
        lambda d, t: delta_compress(d, t, block=BLOCK, interpret=False),
        _spec(one_chip, (n,)), _spec(one_chip, ()))
    assert "tpu_custom_call" in text


def test_delta_apply_compiles(one_chip):
    n = VGG11_THINNED_PARAMS
    text = _compile(
        lambda w, q, s, c: delta_apply(w, q, s, c, block=BLOCK,
                                       interpret=False),
        _spec(one_chip, (n,)), _spec(one_chip, (n,), jnp.int8),
        _spec(one_chip, (-(-n // BLOCK),)), _spec(one_chip, ()))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k", [1, 8])
def test_delta_compress_batch_compiles(one_chip, k):
    text = _compile(
        lambda d, t: delta_compress_batch(d, t, block=BLOCK, interpret=False),
        _spec(one_chip, (k, VGG11_THINNED_PARAMS)), _spec(one_chip, ()))
    assert "tpu_custom_call" in text


def test_level_assign_compiles(one_chip):
    k, n = 4, VGG11_THINNED_PARAMS
    text = _compile(
        lambda d, r, t, s: level_assign(d, r, t, s, interpret=False),
        _spec(one_chip, (k, n)), _spec(one_chip, (k, n)),
        _spec(one_chip, ()), _spec(one_chip, ()))
    assert "tpu_custom_call" in text
