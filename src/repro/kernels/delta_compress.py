"""Pallas TPU kernel: fused differential-update compression (paper §3 wire
format) — threshold sparsify (Eq. 2 style) + per-block symmetric int8
quantization in ONE pass over the delta.

The unfused jnp pipeline reads the delta three times (mask, max, quantize);
this kernel streams the delta through VMEM once and emits the int8 payload
+ per-block scale.  Memory-bound: one HBM read, 1/4 + eps write.

Layout.  A wire block of ``block`` consecutive elements is one ROW of a
2-D ``(nblk, block)`` view, so the per-block amax is a lane reduction and
every Mosaic tile is ``(rows, block)``: ``rows`` is the whole row count or
a multiple of 32 (the int8 sublane tiling), ``block`` the full last dim.
Each grid program takes ``_TILE_BYTES`` of f32 input whatever the model
width; a partial last tile is clipped by Pallas (rows are independent, so
its out-of-range rows are never written back).  Scalars (theta, coef)
ride in SMEM.

  * ``delta_compress`` — the per-client ``(n,)`` variant.  Ragged ``n``
    is zero-padded INSIDE the jitted function to a block multiple; padded
    lanes quantize to 0 and an all-pad block gets the scale-1 sentinel
    the host layout pins.
  * ``delta_compress_batch`` — the cohort variant over stacked ``(K, n)``
    deltas: rows are padded to a block multiple and the whole cohort is
    ONE ``(K·nblk, block)`` view, so row ``i`` of the output is
    bit-identical to ``delta_compress(deltas[i])`` — each block is reduced
    alone, whichever program holds it.  This is the uplink's device fast
    path (``repro.comms.device``).

Companion: `delta_apply` — fused dequant + server-side apply (W += c·q·s).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import platform

# f32 input bytes per grid program: double-buffered input and output plus
# the in-kernel temporaries stay well under a v5e core's 16 MiB scoped VMEM
_TILE_BYTES = 1 << 20
# int8 arrays tile as (32, 128) on TPU: a partial-array row block must be a
# multiple of this
_INT8_ROWS = 32

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _tile_rows(nrows: int, width: int) -> int:
    """Rows per program: all of them if they fit, else the largest
    multiple of 32 under the byte budget."""
    cap = max(_INT8_ROWS,
              _TILE_BYTES // (width * 4) // _INT8_ROWS * _INT8_ROWS)
    return nrows if nrows <= cap else cap


def _scalar(x) -> jax.Array:
    return jnp.reshape(jnp.asarray(x, jnp.float32), (1,))


def _compress_kernel(theta_ref, d_ref, q_ref, s_ref):
    d = d_ref[...].astype(jnp.float32)                  # (rows, block)
    kept = jnp.where(jnp.abs(d) >= theta_ref[0], d, 0.0)
    amax = jnp.max(jnp.abs(kept), axis=1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(kept / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _compress_blocks(blocks: jax.Array, theta, interpret: bool):
    """(nblk, block) -> (q int8 (nblk, block), scales f32 (nblk,))."""
    nblk, block = blocks.shape
    rows = _tile_rows(nblk, block)
    q, scales = pl.pallas_call(
        _compress_kernel,
        grid=(pl.cdiv(nblk, rows),),
        in_specs=[_SMEM, pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nblk, block), jnp.int8),
                   jax.ShapeDtypeStruct((nblk, 1), jnp.float32)],
        interpret=interpret,
    )(_scalar(theta), blocks)
    return q, scales[:, 0]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_compress(delta: jax.Array, theta, *, block: int = 1024,
                   interpret: bool | None = None):
    """delta: (n,) for ANY n (padded device-side); theta: scalar (Eq. 2).

    Returns (q int8 (n,), scales f32 (ceil(n/block),)).  The scale of a
    trailing partial block is computed over the zero-padded block — zeros
    never win the amax, so it equals the unpadded block's scale.
    ``interpret=None`` takes ``platform.interpret()`` when traced.
    """
    n = delta.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int8), jnp.zeros((0,), jnp.float32))
    flat = jnp.pad(delta, (0, (-n) % block))
    q, scales = _compress_blocks(flat.reshape(-1, block), theta,
                                 platform.resolve(interpret))
    return q.reshape(-1)[:n], scales


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_compress_batch(deltas: jax.Array, theta, *, block: int = 128,
                         interpret: bool | None = None):
    """Cohort variant: deltas (K, n) for ANY n, ONE pallas dispatch.

    Returns (q int8 (K, n), scales f32 (K, ceil(n/block))), row i byte-equal
    to ``delta_compress(deltas[i], theta, block=block)``.
    """
    k, n = deltas.shape
    if n == 0 or k == 0:
        return (jnp.zeros((k, 0), jnp.int8), jnp.zeros((k, 0), jnp.float32))
    flat = jnp.pad(deltas, ((0, 0), (0, (-n) % block)))
    p = flat.shape[1]
    q, scales = _compress_blocks(flat.reshape(-1, block), theta,
                                 platform.resolve(interpret))
    return q.reshape(k, p)[:, :n], scales.reshape(k, p // block)


def _apply_kernel(coef_ref, w_ref, q_ref, s_ref, o_ref):
    deq = q_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = (w_ref[...].astype(jnp.float32)
                  + coef_ref[0] * deq).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_apply(w: jax.Array, q: jax.Array, scales: jax.Array,
                coef=1.0, *, block: int = 1024,
                interpret: bool | None = None) -> jax.Array:
    """Fused dequantize + apply: returns w + coef * (q * scale).

    Accepts ANY n (padded device-side); scales has ceil(n/block) entries —
    the layout ``delta_compress`` emits.
    """
    n = w.shape[0]
    if q.shape != (n,):
        raise ValueError(f"q has shape {q.shape}, want ({n},)")
    if n == 0:
        return w
    pad = (-n) % block
    nblk = (n + pad) // block
    if scales.shape != (nblk,):
        raise ValueError(f"scales has shape {scales.shape}, want ({nblk},)")
    rows = _tile_rows(nblk, block)
    tile = pl.BlockSpec((rows, block), lambda i: (i, 0))
    out = pl.pallas_call(
        _apply_kernel,
        grid=(pl.cdiv(nblk, rows),),
        in_specs=[_SMEM, tile, tile,
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nblk, block), w.dtype),
        interpret=platform.resolve(interpret),
    )(_scalar(coef), jnp.pad(w, (0, pad)).reshape(nblk, block),
      jnp.pad(q, (0, pad)).reshape(nblk, block), scales.reshape(nblk, 1))
    return out.reshape(-1)[:n]
