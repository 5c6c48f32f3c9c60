"""Cohort-axis Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Separate from tests/test_kernels.py because that module needs the
hypothesis dev extra; the device-encode path's correctness must be
asserted in every environment, so this suite uses plain parametrize.

Tolerance contract (see kernels/README.md):
  * int8 codes / integer levels — the wire data — are asserted BITWISE,
  * float scales vs the pure-jnp oracle use rtol=1e-6 (Pallas-interpret
    `amax/127` can differ from eager jnp by 1 ulp),
  * kernel-vs-kernel (batched row vs per-client call) IS bitwise — that
    equivalence is what makes device-encoded payloads byte-identical,
  * the level_assign float carry allows atol=2e-7 (FMA contraction in
    `carried - lv * step`); the levels stay bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.delta_compress import (delta_apply, delta_compress,
                                          delta_compress_batch)
from repro.kernels.level_assign import level_assign


# ------------------------------------------------- ragged delta_compress

@pytest.mark.parametrize("n", [0, 5, 127, 128, 1000, 300_001])
def test_delta_compress_ragged_shapes(n):
    """Non-block-multiple n pads device-side INSIDE the jitted wrapper
    (the (n,) API stays; scales keep the ceil(n/block) layout)."""
    d = (jax.random.normal(jax.random.PRNGKey(n + 1), (n,)) * 0.3
         if n else jnp.zeros((0,)))
    q, scales = delta_compress(d, 0.1, block=128, interpret=True)
    q_ref, s_ref = ref.delta_compress(d, 0.1, 128)
    assert q.shape == (n,) and scales.shape == (-(-n // 128),)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(scales), np.asarray(s_ref),
                               rtol=1e-6)


def _roundtrip_through_apply(n):
    k = jax.random.PRNGKey(21)
    w = jax.random.normal(k, (n,))
    d = jax.random.normal(jax.random.fold_in(k, 1), (n,)) * 0.1
    q, scales = delta_compress(d, 0.0, block=128, interpret=True)
    out = delta_apply(w, q, scales, coef=1.0, block=128, interpret=True)
    deq = np.zeros(-(-n // 128) * 128, np.float32)
    deq[:n] = np.asarray(q, np.float32)
    deq = (deq.reshape(-1, 128) * np.asarray(scales)[:, None]).reshape(-1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w) + deq[:n],
                               rtol=1e-6)


def test_delta_compress_ragged_roundtrips_through_apply():
    """delta_apply accepts the same ragged n (pads, slices back)."""
    _roundtrip_through_apply(777)


def test_delta_apply_spans_tiles():
    """Past one grid program's tile (2048 blocks of 128): the partial
    last tile is clipped, never written out of range."""
    _roundtrip_through_apply(300_001)


# ------------------------------------------------- delta_compress_batch

@pytest.mark.parametrize("k", [1, 4, 8])
def test_delta_compress_batch_bitwise_vs_single(k):
    """The cohort (K, n) kernel must be BIT-identical per row to the
    per-client kernel — this equivalence is what makes the device encode
    payloads byte-equal to the host path."""
    n = 300  # ragged: exercises the in-wrapper pad on both paths
    d = jax.random.normal(jax.random.PRNGKey(k), (k, n)) * 0.3
    qb, sb = delta_compress_batch(d, 0.05, block=128, interpret=True)
    assert qb.shape == (k, n) and sb.shape == (k, -(-n // 128))
    for i in range(k):
        qi, si = delta_compress(d[i], 0.05, block=128, interpret=True)
        np.testing.assert_array_equal(np.asarray(qb[i]), np.asarray(qi))
        np.testing.assert_array_equal(
            np.asarray(sb[i]).view(np.uint32),
            np.asarray(si).view(np.uint32))  # bitwise, not allclose


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("n", [128, 512, 100_000])
def test_delta_compress_batch_matches_ref(k, n):
    # n=100_000, k=4: 3128 blocks span two tiles, the last one partial
    d = jax.random.normal(jax.random.PRNGKey(k * 7 + n), (k, n)) * 0.2
    qb, sb = delta_compress_batch(d, 0.1, block=128, interpret=True)
    q_ref, s_ref = ref.delta_compress_batch(d, 0.1, 128)
    np.testing.assert_array_equal(np.asarray(qb), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(sb), np.asarray(s_ref), rtol=1e-6)


def test_delta_compress_batch_empty():
    qb, sb = delta_compress_batch(jnp.zeros((3, 0)), 0.0, block=128,
                                  interpret=True)
    assert qb.shape == (3, 0) and sb.shape == (3, 0)


# ------------------------------------------------- level_assign

@pytest.mark.parametrize("k", [1, 4, 8, 10])
@pytest.mark.parametrize("n", [64, 257, 1024, 20_000])
def test_level_assign_matches_ref(k, n):
    """Fused carry+sparsify+quantize vs the residual.py/quant.py chain:
    LEVELS (the wire data) are bitwise; the float carry may differ by FMA
    contraction in `carried - lv * step`.  k=10 and n=20_000 leave
    partial edge tiles on both grid axes (8 rows x 16384 columns)."""
    key = jax.random.PRNGKey(k * 31 + n)
    d = jax.random.normal(key, (k, n)) * 1e-2
    r = jax.random.normal(jax.random.fold_in(key, 1), (k, n)) * 1e-3
    step = 4.8828125e-4
    lv, carry = level_assign(d, r, 2e-3, step, interpret=True)
    lv_ref, c_ref = ref.level_assign(d, r, 2e-3, step)
    assert lv.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(lv_ref))
    np.testing.assert_allclose(np.asarray(carry), np.asarray(c_ref),
                               atol=2e-7)


def test_level_assign_matches_core_chain():
    """Against the actual core modules the kernel fuses (Eq. 5 carry →
    threshold sparsify → uniform quantize)."""
    from repro.core import quant as quant_lib
    key = jax.random.PRNGKey(5)
    d = jax.random.normal(key, (3, 500)) * 1e-2
    r = jax.random.normal(jax.random.fold_in(key, 1), (3, 500)) * 1e-3
    theta, step = 2e-3, quant_lib.STEP_SIZE_UNI
    carried = d + r
    kept = jnp.where(jnp.abs(carried) >= theta, carried, 0.0)
    want_lv = quant_lib.quantize(kept, step)
    lv, carry = level_assign(d, r, theta, step, interpret=True)
    np.testing.assert_array_equal(np.asarray(lv), np.asarray(want_lv))
    np.testing.assert_allclose(np.asarray(carry),
                               np.asarray(carried - want_lv * step),
                               atol=2e-7)


def test_level_assign_clips_to_max_level():
    d = jnp.array([[1e6, -1e6, 0.0]])
    r = jnp.zeros((1, 3))
    lv, _ = level_assign(d, r, 0.0, 1e-4, max_level=7, interpret=True)
    np.testing.assert_array_equal(np.asarray(lv), [[7, -7, 0]])


# ------------------------------------------------- platform choice

def test_interpret_follows_the_platform():
    """One place decides interpret mode: off a TPU every kernel whose
    ``interpret`` is left as None runs in the interpreter, an explicit
    flag wins, and a run that asks for the chip fails instead of
    falling back."""
    from repro.kernels import platform

    on_tpu = jax.default_backend() == "tpu"
    assert platform.interpret() is (not on_tpu)
    assert platform.resolve(None) is platform.interpret()
    assert platform.resolve(True) is True and platform.resolve(False) is False
    if not on_tpu:
        with pytest.raises(RuntimeError, match="no TPU"):
            platform.require_tpu()
    d = jax.random.normal(jax.random.PRNGKey(3), (2, 300)) * 0.3
    q, s = delta_compress_batch(d, 0.05, block=128)
    qi, si = delta_compress_batch(d, 0.05, block=128,
                                  interpret=platform.interpret())
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qi))
