"""Public wrappers for the 2-D Pallas kernels: shape padding + dispatch.

Each call resolves interpret mode through ``repro.kernels.platform``:
compiled by Mosaic where JAX's default backend is a TPU, interpreted
elsewhere.  The wire kernels (``delta_compress``, ``level_assign``) pad
ragged shapes themselves and are called directly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import platform
from repro.kernels import row_stats as rs
from repro.kernels import scaled_matmul as sm


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def scaled_matmul(x, w, s, *, bm=128, bn=128, bk=128):
    """y = x @ (s ⊙ W).T with padding to block multiples."""
    x2, M = _pad_to(x, 0, bm)
    x2, K = _pad_to(x2, 1, bk)
    w2, N = _pad_to(w, 0, bn)
    w2, _ = _pad_to(w2, 1, bk)
    s2, _ = _pad_to(s, 0, bn)
    out = sm.scaled_matmul(x2, w2, s2, bm=bm, bn=bn, bk=bk,
                           interpret=platform.interpret())
    return out[:M, :N]


def row_stats(w, *, bm=128, bn=512):
    w2, M = _pad_to(w, 0, bm)
    w2, N = _pad_to(w2, 1, bn)
    out = rs.row_stats(w2, bm=bm, bn=bn, interpret=platform.interpret())
    # padding zeros dilute the mean; rescale to the true column count
    return out[:M] * (w2.shape[1] / N)
