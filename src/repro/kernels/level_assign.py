"""Pallas kernel: the level codecs' fused lossy front end on the cohort axis.

One pass over stacked ``(K, n)`` deltas fuses the whole client-side
compression chain the level codecs (golomb / nnc-cabac) transmit:

    carried = delta + residual            # error-feedback carry (Eq. 5)
    kept    = carried · [|carried| ≥ θ]   # threshold sparsify (Eq. 2 style)
    levels  = clip(round(kept / step), ±max_level)   # uniform quantize (§3)
    carry   = carried − levels · step     # next round's residual

The unfused pipeline (``core/residual.py`` + ``core/sparsify.py`` +
``core/quant.py``) materialises ``carried``/``kept``/``recon`` as separate
HBM arrays per stage; this kernel reads delta+residual once and writes only
the int32 levels and the f32 carry.  Semantics are pinned against the
pure-jnp oracle ``ref.level_assign`` (round-to-nearest-even, the repo-wide
quantization convention) in ``tests/test_kernels_cohort.py``.

The chain is elementwise, so the ``(K, n)`` array is tiled in 2-D
``(rows, cols)`` Mosaic tiles — ``rows`` is all of ``K`` up to 8, else 8;
``cols`` is all of ``n`` up to ``_TILE_COLS``, else that multiple of 128 —
and partial edge tiles are clipped by Pallas, so nothing is padded and one
program's working set is bounded at any model width.  θ and the step ride
in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform

_TILE_ROWS = 8          # f32/int32 sublane tiling
_TILE_COLS = 16384      # 8 x 16384 x 4 B = 512 KiB per array per program


def _level_assign_kernel(theta_ref, step_ref, d_ref, r_ref, lv_ref, c_ref,
                         *, max_level):
    carried = d_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    step = step_ref[0]
    kept = jnp.where(jnp.abs(carried) >= theta_ref[0], carried, 0.0)
    lv = jnp.clip(jnp.round(kept / step), -max_level, max_level)
    lv_ref[...] = lv.astype(jnp.int32)
    c_ref[...] = carried - lv * step


@functools.partial(jax.jit, static_argnames=("max_level", "interpret"))
def level_assign(deltas: jax.Array, residuals: jax.Array, theta, step, *,
                 max_level: int = 2**23, interpret: bool | None = None):
    """Fused EF-carry → sparsify → quantize over stacked (K, n) deltas.

    Returns ``(levels int32 (K, n), carry f32 (K, n))`` in ONE dispatch.
    ``theta``/``step`` are scalars shared across the cohort (the engine's
    per-tensor step sizes dispatch one call per step group).
    """
    k, n = deltas.shape
    if residuals.shape != (k, n):
        raise ValueError(f"residuals have shape {residuals.shape}, "
                         f"deltas {deltas.shape}")
    if n == 0 or k == 0:
        return (jnp.zeros((k, n), jnp.int32), jnp.zeros((k, n), jnp.float32))
    rows = k if k <= _TILE_ROWS else _TILE_ROWS
    cols = n if n <= _TILE_COLS else _TILE_COLS
    tile = pl.BlockSpec((rows, cols), lambda i, j: (i, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_level_assign_kernel, max_level=max_level),
        grid=(pl.cdiv(k, rows), pl.cdiv(n, cols)),
        in_specs=[smem, smem, tile, tile],
        out_specs=[tile, tile],
        out_shape=[jax.ShapeDtypeStruct((k, n), jnp.int32),
                   jax.ShapeDtypeStruct((k, n), jnp.float32)],
        interpret=platform.resolve(interpret),
    )(jnp.reshape(jnp.asarray(theta, jnp.float32), (1,)),
      jnp.reshape(jnp.asarray(step, jnp.float32), (1,)),
      deltas, residuals)
