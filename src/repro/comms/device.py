"""Device-resident cohort encode: the uplink fast path (ROADMAP item #2).

The host batch path slices a stacked ``RoundOutput`` into K pytrees and
runs each through ``Codec.encode`` — for ``int8-blockscale`` that used to
mean one Pallas dispatch per leaf per client with a host round-trip around
every call.  The functions here keep the whole cohort on the accelerator:
ONE fused program over the stacked client axis (the same axis
``fl/executors.py`` vmaps), ONE ``jax.device_get``, then a thin host loop
that only slices rows and frames bytes — for the level codecs only PR-5's
pass-2 range-coder renormalisation remains sequential per client.

Per codec:

  ``int8-blockscale``  per-leaf zero-pad to ``block`` multiples, concat to
                       one ``(K, P)`` buffer, ``delta_compress_batch`` in
                       one grid-(K,) dispatch.  Leaf-aligned padding means
                       every 128-block sits inside one leaf, so the q/scale
                       chunks are bit-identical to the host per-leaf layout.
  ``golomb``           int32 zigzag of the stacked levels on device (exact
                       iff max |level| < 2**30 — levels are clipped to
                       ±2**23 by ``core/quant.py``; a device range check
                       falls back to the host int64 path otherwise), host
                       ``choose_k``/``encode_egk`` per row slice.
  ``nnc-cabac``        CABAC pass-1 row-skip flags (``rows.any(axis=1)``)
                       computed for the whole cohort in one program and
                       handed to ``nnc.encode_leaves_batch`` — exact
                       booleans, so pass 1 emits the identical bins.

Every payload is byte-identical to the host ``encode_batch`` (asserted
across codec × schema in tests/test_comms.py, and the frozen seed pins hold
with ``device_encode=on`` in tests/test_rounds.py).  A function returns
``None`` when a device invariant fails (e.g. the zigzag range guard); the
uplink then hands that cohort to the host path.

Two monotone counters make the path observable: ``dispatch_count()``
(fused device programs launched here; ``fl/rounds.Uplink`` differences it
around each cohort into the ``uplink.kernel_dispatches`` metric) and
``handover_count()`` (cohorts handed back to the host path).  Whether the
Pallas kernel runs compiled or interpreted is
``repro.kernels.platform.interpret()``.

This module is imported lazily (from the codecs' ``encode_cohort``
overrides), so jax loads only when the device path is actually taken.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding import golomb as golomb_lib
from repro.coding import nnc
from repro.coding.bitstream import BitWriter
from repro.comms.codec import (ClientUpdate, WireSpec, _cohort_size,
                               check_batch_clients, sorted_items)
from repro.kernels.delta_compress import delta_compress_batch

_ZIGZAG_SAFE = 2 ** 30   # |level| bound for exact int32 zigzag

_dispatches = 0
_handovers = 0


def dispatch_count() -> int:
    """Total fused device programs launched by this module (monotone)."""
    return _dispatches


def handover_count() -> int:
    """Cohorts the device encoders handed back to the host path (monotone)."""
    return _handovers


def _dispatched() -> None:
    global _dispatches
    _dispatches += 1


def count_handover() -> None:
    """Record one cohort handed to the host path (``fl/rounds.Uplink``
    calls this whenever ``Codec.encode_cohort`` returns ``None``)."""
    global _handovers
    _handovers += 1


def _ordered_stacked(tree: Any, allowed=None) -> list[tuple[str, Any]]:
    """(path, stacked leaf) in sorted-path wire order (send mask applied)."""
    items = sorted_items(tree)
    if allowed is not None:
        items = [(p, l) for p, l in items if p in allowed]
    return items


def _bn_stack(out: Any, spec: WireSpec):
    return out.bn_state if (spec.version != 1 and spec.bn is not None) \
        else None


def _tree_row(tree: Any, i: int) -> Any:
    return jax.tree.map(lambda x: x[i], tree)


def _frame_row(codec, body: bytes, bn_host: Any, i: int,
               spec: WireSpec) -> bytes:
    if spec.version == 1:
        return body
    bn_row = None if bn_host is None else _tree_row(bn_host, i)
    return codec._frame(body, ClientUpdate(None, None, None, None, bn=bn_row),
                        spec)


# ===========================================================================
# int8-blockscale
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("block",))
def _int8_program(leaves, block: int):
    """Pad each stacked leaf to a block multiple, concat, ONE batched
    sparsify+quantize dispatch over the (K, P) cohort buffer."""
    k = leaves[0].shape[0]
    flats = []
    for leaf in leaves:
        f = leaf.reshape(k, -1).astype(jnp.float32)
        pad = (-f.shape[1]) % block
        if pad:
            f = jnp.pad(f, ((0, 0), (0, pad)))
        flats.append(f)
    buf = jnp.concatenate(flats, axis=1)
    return delta_compress_batch(buf, 0.0, block=block)


def int8_encode_cohort(codec, out: Any, spec: WireSpec, *,
                       clients: Sequence[int] | None = None
                       ) -> list[bytes] | None:
    """Device cohort encode for ``Int8BlockScaleCodec``."""
    k = _cohort_size(out)
    check_batch_clients(clients, k, "cohort rows")
    p_items = _ordered_stacked(out.recon_delta_params, spec.sent_paths)
    block = codec.block
    sizes = [int(np.prod(leaf.shape[1:])) for _, leaf in p_items]
    padded = [n + (-n) % block for n in sizes]
    nblks = [p // block for p in padded]
    if sum(padded):
        q, s = _int8_program(tuple(l for _, l in p_items), block=block)
        _dispatched()
    else:
        q = np.zeros((k, 0), np.int8)
        s = np.zeros((k, 0), np.float32)
    s_stack = (tuple(l for _, l in _ordered_stacked(out.recon_delta_scales))
               if spec.scales is not None else ())
    q, s, s_host, bn_host = jax.device_get(
        (q, s, s_stack, _bn_stack(out, spec)))
    payloads = []
    for i in range(k):
        chunks = []
        qo = so = 0
        for j in range(len(p_items)):
            chunks.append(np.ascontiguousarray(q[i, qo:qo + padded[j]])
                          .tobytes())
            chunks.append(np.ascontiguousarray(s[i, so:so + nblks[j]])
                          .astype("<f4").tobytes())
            qo += padded[j]
            so += nblks[j]
        for leaf in s_host:
            chunks.append(np.ascontiguousarray(leaf[i])
                          .astype("<f4").tobytes())
        payloads.append(_frame_row(codec, b"".join(chunks), bn_host, i, spec))
    return payloads


# ===========================================================================
# level codecs: shared cohort views
# ===========================================================================

def _level_stacks(out: Any, spec: WireSpec):
    """Ordered stacked level sections: the cohort twin of
    ``LevelCodec._level_items``.  ``_msg`` nests params under "p" and
    scales under "s", so the combined sorted-path wire order is exactly
    sorted p-paths then sorted s-paths."""
    p_items = _ordered_stacked(out.levels_params, spec.sent_paths)
    s_items = (_ordered_stacked(out.levels_scales)
               if spec.scales is not None else [])
    return p_items + s_items


def _ternary_stack(out: Any, spec: WireSpec):
    """Stacked sent-recon leaves for the per-tensor magnitude tail."""
    if not spec.ternary:
        return ()
    return tuple(
        l for _, l in _ordered_stacked(out.recon_delta_params,
                                       spec.sent_paths))


def _ternary_maxima(recon_leaves):
    """(K, L) per-client max|recon| per sent tensor — exact f32 max."""
    if not recon_leaves:
        return None
    k = recon_leaves[0].shape[0]
    return jnp.stack(
        [jnp.max(jnp.abs(l.reshape(k, -1).astype(jnp.float32)), axis=1)
         for l in recon_leaves], axis=1)


def _ternary_tail_row(tern_host, i: int) -> bytes:
    if tern_host is None:
        return b""
    return np.ascontiguousarray(tern_host[i]).astype("<f4").tobytes()


# ===========================================================================
# golomb
# ===========================================================================

@jax.jit
def _golomb_program(level_leaves, recon_leaves):
    """Zigzag the stacked levels into one (K, P) int32 buffer + range guard
    + ternary maxima, all in ONE fused program."""
    k = level_leaves[0].shape[0]
    flats = [l.reshape(k, -1).astype(jnp.int32) for l in level_leaves]
    buf = jnp.concatenate(flats, axis=1)
    in_range = (jnp.logical_and(buf.max() < _ZIGZAG_SAFE,
                                buf.min() > -_ZIGZAG_SAFE)
                if buf.size else jnp.bool_(True))
    zig = (buf << 1) ^ (buf >> 31)
    return zig, in_range, _ternary_maxima(recon_leaves)


def golomb_encode_cohort(codec, out: Any, spec: WireSpec, *,
                         clients: Sequence[int] | None = None
                         ) -> list[bytes] | None:
    """Device cohort encode for ``GolombCodec``; None → host fallback."""
    k = _cohort_size(out)
    check_batch_clients(clients, k, "cohort rows")
    items = _level_stacks(out, spec)
    if not items:
        return None          # degenerate spec; host path handles it
    zig, in_range, tern = _golomb_program(
        tuple(l for _, l in items), _ternary_stack(out, spec))
    _dispatched()
    zig, in_range, tern_host, bn_host = jax.device_get(
        (zig, in_range, tern, _bn_stack(out, spec)))
    if not bool(in_range):
        return None          # int32 zigzag would wrap; host int64 path
    sizes = [int(np.prod(leaf.shape[1:])) for _, leaf in items]
    zig = zig.astype(np.int64)   # exact: guarded above
    payloads = []
    for i in range(k):
        w = BitWriter()
        off = 0
        for n in sizes:
            vals = zig[i, off:off + n]
            kk = golomb_lib.choose_k(vals)
            w.put_uint(kk, 4)
            golomb_lib.encode_egk(w, vals, kk)
            off += n
        body = w.to_bytes() + _ternary_tail_row(tern_host, i)
        payloads.append(_frame_row(codec, body, bn_host, i, spec))
    return payloads


# ===========================================================================
# nnc-cabac
# ===========================================================================

@jax.jit
def _nnc_program(structured_leaves, recon_leaves):
    """CABAC pass-1 row-skip flags for every structured tensor in the
    cohort + ternary maxima, ONE fused program."""
    flags = tuple(
        (l.reshape(l.shape[0], l.shape[1], -1) != 0).any(axis=2)
        for l in structured_leaves)
    return flags, _ternary_maxima(recon_leaves)


def nnc_encode_cohort(codec, out: Any, spec: WireSpec, *,
                      clients: Sequence[int] | None = None
                      ) -> list[bytes] | None:
    """Device cohort encode for ``NncCabacCodec``."""
    k = _cohort_size(out)
    check_batch_clients(clients, k, "cohort rows")
    items = _level_stacks(out, spec)
    structured = [leaf.ndim >= 3 for _, leaf in items]   # orig ndim >= 2
    flags, tern = _nnc_program(
        tuple(l for (_, l), st in zip(items, structured) if st),
        _ternary_stack(out, spec))
    _dispatched()
    leaves, flags, tern_host, bn_host = jax.device_get(
        (tuple(l for _, l in items), flags, tern, _bn_stack(out, spec)))
    leaf_lists, flag_lists = [], []
    for i in range(k):
        leaf_lists.append([leaf[i] for leaf in leaves])
        row_flags, j = [], 0
        for st in structured:
            row_flags.append(flags[j][i] if st else None)
            j += int(st)
        flag_lists.append(row_flags)
    bodies = nnc.encode_leaves_batch(leaf_lists, row_flags=flag_lists)
    return [
        _frame_row(codec, body + _ternary_tail_row(tern_host, i), bn_host,
                   i, spec)
        for i, body in enumerate(bodies)]
