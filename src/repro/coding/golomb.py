"""Exponential-Golomb codes (order-k), vectorised both directions.

DeepCABAC binarises quantization-level remainders with exp-Golomb codes whose
bins are bypass-coded; STC's position coding is Golomb as well.  Encoding is
fully vectorised (bit matrix assembly in numpy).  :func:`decode_egk` is the
server-decode hot path under the vectorized NNC engine: a large section
finds its codeword boundaries by chunk synchronisation (speculative parses
from every chunk start, advanced in lockstep, then joined where the true
parse lands on one of them), a small one walks them in plain ints; either
way every value is then read from a 64-bit window of the packed bytes in
one pass.  :func:`decode_egk_many` runs the same parse over one section
of each of several independent streams at once (a batch of messages laid
side by side in a :class:`~repro.coding.bitstream.ReaderGroup`), so one
set of array operations advances all of them.  :func:`decode_egk_jump`
resolves the boundaries instead by a pointer-doubling orbit over a
per-position jump table — the bypass half of the ``speculative`` NNC
engine.  :func:`decode_egk_ref` keeps the original
bit-by-bit walk as the reference both fast parsers are differentially
tested against.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.coding.bitstream import BitReader, BitWriter, ReaderGroup
from repro.obs import trace as obs_trace

_MAX_CODE_BITS = 63   # value bits fit int64; longer prefixes prove corruption


def egk_bit_length(values: np.ndarray, k: int) -> np.ndarray:
    """Bits used by order-k exp-Golomb for each unsigned value."""
    v = values.astype(np.int64) + (1 << k)
    nbits = np.floor(np.log2(np.maximum(v, 1))).astype(np.int64) + 1
    # prefix zeros = nbits - k - 1, then nbits bits of value
    return 2 * nbits - k - 1


def choose_k(values: np.ndarray) -> int:
    """Cheap near-optimal order choice: k ~ log2(mean)."""
    if values.size == 0:
        return 0
    mean = float(np.mean(values))
    if mean < 1.0:
        return 0
    return min(15, int(np.floor(np.log2(mean + 1))))


def encode_egk(writer: BitWriter, values: np.ndarray, k: int) -> None:
    """Vectorised order-k exp-Golomb encode of unsigned ints.

    Single-pass bit-matrix assembly: every codeword's value bits are
    extracted with one broadcast shift and scattered with one fancy-indexed
    store (the old per-bit-position loop paid numpy call overhead
    ``nbits.max()`` times over).  Spanned as ``golomb.encode`` with
    ``values=len(values)``."""
    if values.size == 0:
        return
    with obs_trace.span("golomb.encode", values=len(values)):
        _encode_egk(writer, values, k)


def _encode_egk(writer: BitWriter, values: np.ndarray, k: int) -> None:
    v = values.astype(np.int64) + (1 << k)
    nbits = np.floor(np.log2(v)).astype(np.int64) + 1
    total = 2 * nbits - k - 1  # prefix (nbits-k-1 zeros) + nbits value bits
    offsets = np.cumsum(total) - total
    out = np.zeros(int(total.sum()), np.uint8)
    vstart = offsets + total - nbits   # value bits end each codeword
    # group codewords by bit length: every group shares one rectangular
    # (count, nb) layout, so the whole section assembles with ~log(vmax)
    # dense fancy stores and no ragged masking temporaries
    for nb in np.unique(nbits).tolist():
        idx = np.flatnonzero(nbits == nb)
        cols = np.arange(nb)
        bits_mat = (v[idx, None] >> (nb - 1 - cols)[None, :]) & 1
        out[vstart[idx, None] + cols[None, :]] = bits_mat
    writer.put_bits(out)


def decode_egk_ref(reader: BitReader, count: int, k: int) -> np.ndarray:
    """Reference bit-by-bit decode (the fast parser's differential oracle)."""
    out = np.empty(count, np.int64)
    for i in range(count):
        zeros = 0
        while reader.get_bit() == 0:
            zeros += 1
        nbits = zeros + k + 1
        rest = 0
        for _ in range(nbits - 1):
            rest = (rest << 1) | reader.get_bit()
        v = (1 << (nbits - 1)) | rest
        out[i] = v - (1 << k)
    return out


def decode_egk(reader: BitReader, count: int, k: int) -> np.ndarray:
    """Vectorised order-k exp-Golomb decode of ``count`` values.

    Finds the ``count + 1`` codeword boundaries (each start, then the end)
    and extracts every value from the packed bytes in one pass
    (:func:`_values`).  A section of ``_VECTOR_MIN`` codewords or more
    finds its boundaries by chunk synchronisation (:func:`_bounds_vector`);
    a shorter one walks them in plain Python ints (:func:`_bounds_scalar`),
    where the vector set-up would cost more than it saves.  Bit-exact with
    :func:`decode_egk_ref`: the same values and the same cursor afterwards.
    Raises ``EOFError`` when the section runs past the stream and, failing
    that, ``ValueError`` on a codeword of more than 63 value bits.  Spanned
    as ``golomb.decode`` with ``values=count``, ``vector`` (1 where the
    chunk synchronisation ran) and ``resync_steps`` (the codewords it
    walked from chunk entries before they merged).
    """
    if count == 0:
        return np.empty(0, np.int64)
    vector = count >= _VECTOR_MIN
    with obs_trace.span("golomb.decode", values=count, vector=int(vector),
                        streams=1, lockstep_values=0) as sp:
        s = reader.tell()
        if vector:
            bounds, steps = _bounds_vector(reader, s, count, k)
        else:
            bounds = _bounds_scalar(reader.words(), s, count, k + 1,
                                    reader.nbits, reader.last_one())
            steps = 0
        sp.set(resync_steps=steps)
        v = _values(reader.words(), bounds[:-1], bounds[1:], k)
        reader.seek(int(bounds[-1]))
        return v


def decode_egk_many(group: ReaderGroup,
                    asks: Sequence[tuple[int, int, int]]) -> list[np.ndarray]:
    """One exp-Golomb section from each of several streams, parsed in
    lockstep: ``asks`` holds ``(reader, count, k)`` for readers of
    ``group``, each reader at most once, and the result holds each ask's
    values.

    The streams are independent, so one set of array operations advances
    all the sections of one order ``k`` together: the boundary search of
    :func:`decode_egk` runs over the group's address space, where every
    lane walks its own stream alone (:func:`_bounds_many`), and the values
    are read in one pass.  Whether the boundaries are searched by chunk
    synchronisation or walked is decided by the summed count, as
    :func:`decode_egk` decides it by its one count.  Each reader's values
    and cursor afterwards are those of :func:`decode_egk` on that reader
    alone, and so is the error: an ``EOFError`` of any stream is raised
    before a ``ValueError`` of any, and after an error no cursor has
    moved.  An ask with a count of 0 reads nothing; where one stream alone
    is left, this is :func:`decode_egk` on it.  Spanned as
    ``golomb.decode`` with ``values`` (the summed count), ``vector``,
    ``resync_steps``, ``streams`` (the readers advanced) and
    ``lockstep_values`` (``values`` where more than one reader advanced,
    else 0).
    """
    out = [np.empty(0, np.int64)] * len(asks)
    live = [j for j, (_, count, _) in enumerate(asks) if count]
    if len(live) <= 1:
        for j in live:
            i, count, k = asks[j]
            out[j] = decode_egk(group.readers[i], count, k)
        return out
    total = sum(asks[j][1] for j in live)
    vector = total >= _VECTOR_MIN
    with obs_trace.span("golomb.decode", values=total, vector=int(vector),
                        streams=len(live), lockstep_values=total) as sp:
        found, steps = [], 0
        for k in sorted({asks[j][2] for j in live}):
            same = [j for j in live if asks[j][2] == k]
            idx = np.array([asks[j][0] for j in same], np.int64)
            count = np.array([asks[j][1] for j in same], np.int64)
            s0 = group.base[idx] + [group.readers[i].tell()
                                    for i in idx.tolist()]
            if vector:
                bounds, st = _bounds_many(group, s0, count, k + 1, idx)
                steps += st
            else:
                bounds = [_bounds_scalar(group.words, s, c, k + 1,
                                         int(group.ends[i]),
                                         int(group.last_ones[i]))
                          for s, c, i in zip(s0.tolist(), count.tolist(),
                                             idx.tolist())]
            found.append((same, idx, count, k, bounds))
        sp.set(resync_steps=steps)
        for same, idx, count, k, bounds in found:
            v = _values(group.words,
                        np.concatenate([b[:-1] for b in bounds]),
                        np.concatenate([b[1:] for b in bounds]), k)
            for j, part in zip(same, np.split(v, np.cumsum(count)[:-1])):
                out[j] = part
        for same, idx, _, _, bounds in found:
            for i, b, base in zip(idx.tolist(), bounds,
                                  group.base[idx].tolist()):
                group.readers[i].seek(int(b[-1]) - base)
        return out


# sections of at least this many codewords take the chunk synchronisation;
# below it the Python walk is cheaper than the vector set-up (measured on
# realistic gap and remainder sections, see PERF.md)
_VECTOR_MIN = 1024

# chunk width in minimal codewords (k + 1 bits each): a speculative path
# crosses its chunk in at most this many vector steps
_CHUNK_CODES = 16

# resync lanes left to the plain-int walk: below this many, a vector step
# costs more than stepping each lane on its own
_RESYNC_TAIL = 24

# leading zeros of every 16-bit value, doubled (a codeword's prefix and as
# many value bits again); 0 has no set bit and takes the far path
_ZZ16 = 2 * (16 - np.frexp(np.arange(1 << 16, dtype=np.float64))[1]
             ).astype(np.int64)

_M64 = (1 << 64) - 1


def _bounds_scalar(words: np.ndarray, s: int, count: int, k1: int, n: int,
                   last: int) -> np.ndarray:
    """The ``count + 1`` boundaries from ``s``, one codeword at a time, in
    a stream that ends at ``n`` and whose last set bit is at ``last``."""
    item = words.item
    out = [s]
    for _ in range(count):
        if s >= n:
            raise EOFError("bitstream exhausted")
        s = _next_scalar(words, item, s, k1, n, last)
        out.append(s)
    if s > n:
        raise EOFError("bitstream exhausted")
    return np.array(out, np.int64)


def _next_scalar(words: np.ndarray, item, s: int, k1: int, n: int,
                 last: int) -> int:
    """The codeword end after start ``s`` in plain ints: the prefix ends at
    the first set bit of the 64-bit window at ``s`` (``int.bit_length``),
    and the codeword spans twice the prefix plus k + 1."""
    w = (item(s >> 3) << (s & 7)) & _M64
    if w:
        return s + 2 * (64 - w.bit_length()) + k1
    return int(_far_bounds(words, np.array([s]), k1, n, last)[0])   # 57+ zeros


def _step(reader: BitReader, p: np.ndarray, zz: np.ndarray,
          k1: int) -> np.ndarray:
    """Next codeword boundary after each position of ``p``; ``zz`` is
    ``_ZZ16 + k + 1``.  A codeword that runs past the stream ends past
    ``n``, and a start with no set bit after it gets ``n + 1``: either way
    at or past the end of every window."""
    w = reader.words().take(p >> 3)
    w <<= p & 7
    t = w.view(np.uint64)
    t >>= np.uint64(48)
    q = zz.take(t.view(np.int64))
    q += p
    if not t.all():                         # 16+ leading zeros
        far = np.flatnonzero(t == 0)
        q[far] = _far_bounds(reader.words(), p[far], k1, reader.nbits,
                             reader.last_one())
    return q


def _bit_length(w: np.ndarray) -> np.ndarray:
    """Bit length of each uint64 (0 for 0), exact through float64."""
    hi = np.frexp((w >> np.uint64(11)).astype(np.float64))[1]
    lo = np.frexp((w & np.uint64(0x7FF)).astype(np.float64))[1]
    return np.where(hi > 0, hi + 11, lo).astype(np.int64)


def _far_bounds(words: np.ndarray, p: np.ndarray, k1: int, n,
                last) -> np.ndarray:
    """Codeword ends for starts whose first 16 bits are zero: the set bit
    that ends the prefix is found word by word; a start past its stream's
    last set bit ``last`` has none and gets ``n + 1``.  ``n`` and ``last``
    are one stream's or one per start (starts in the streams of a
    :class:`~repro.coding.bitstream.ReaderGroup`)."""
    out = np.zeros(p.size, np.int64)
    out += n + 1
    live = np.flatnonzero(p <= last)
    if not live.size:
        return out
    q = p[live]
    w = (words[q >> 3] << (q & 7)).view(np.uint64)
    one = q + 64 - _bit_length(w)           # valid where w != 0
    byte = (q >> 3) + 8
    rest = np.flatnonzero(w == 0)
    while rest.size:                        # whole zero words: next word
        w = words[byte[rest]].view(np.uint64)
        bl = _bit_length(w)
        got = bl > 0
        one[rest[got]] = 8 * byte[rest[got]] + 64 - bl[got]
        byte[rest] += 8
        rest = rest[~got]
    out[live] = np.minimum(2 * one - q + k1, out[live])
    return out


def _bounds_vector(reader: BitReader, s0: int, count: int,
                   k: int) -> tuple[np.ndarray, int]:
    """The ``count + 1`` boundaries from ``s0`` by chunk synchronisation,
    window by window (:func:`_window`), and the resync steps it took.  The
    first window is sized for ``k + 2.25`` bits a codeword (a little over
    the mean length at the order ``choose_k`` picks); the next, if the
    boundaries ran short, from the bits a codeword the first one read.
    No window reads past the stream; a parse that reaches its end first
    raises ``EOFError``."""
    n = reader.nbits
    k1 = k + 1
    chunk = _CHUNK_CODES * k1
    zz = _ZZ16 + k1
    parts, have, steps = [], 0, 0
    s, per = s0, k1 + 1.25
    while have < count + 1:
        if s > n:
            raise EOFError("bitstream exhausted")
        need = count + 1 - have
        e = min(s + int(need * per) + chunk, n + 1)
        b, s, st = _window(reader, s, e, zz, k1, chunk)
        parts.append(b)
        have += b.size
        steps += st
        per = 1.25 * (s - s0) / have
    bounds = parts[0] if len(parts) == 1 else np.concatenate(parts)
    return bounds[:count + 1], steps


def _window(reader: BitReader, s: int, e: int, zz: np.ndarray, k1: int,
            chunk: int) -> tuple[np.ndarray, int, int]:
    """Boundaries of the true parse from ``s`` inside ``[s, e)``.

    Returns them in order, the true parse's first boundary at or past
    ``e`` (where the next window starts) and the resync steps taken.

    1. Every chunk start (``s``, ``s + chunk``, ...) starts a speculative
       path; all advance in lockstep, one vector step per codeword, until
       each has left its chunk.  A path's positions are its chunk's marks.
    2. Each path then walks on from its exit, in lockstep, until it lands
       on a mark (of whichever chunk: from there on it coincides with that
       chunk's path) or leaves the window.  These are the resync steps.
    3. The true parse starts on path 0.  Each path it follows hands it on
       where it landed, to the path of the chunk it landed in, from that
       mark on; the chain over chunks is resolved by doubling.  Its
       boundaries are each followed path's marks from its entry on plus
       that path's resync steps.
    """
    J = -(-(e - s) // chunk)
    c = s + chunk * np.arange(J, dtype=np.int64)
    end = np.minimum(c + chunk, e)
    rows = [c]
    p = c
    for _ in range(_CHUNK_CODES):           # codewords are k + 1 bits or more
        p = _step(reader, p, zz, k1)
        rows.append(p)
    P = np.stack(rows)                      # (steps + 1, J), rising per column
    own = P < end
    size = J * chunk + 1                    # the last slot stays unmarked
    mark = np.zeros(size, bool)
    mark[P[own] - s] = True

    # resync: every path from its exit until it lands on a mark
    lanes = np.arange(J)
    q = P[own.sum(0), lanes]
    land = np.empty(J, np.int64)
    wl, wq = [], []
    while lanes.size > _RESYNC_TAIL:
        done = mark[np.minimum(q - s, size - 1)]
        done |= q >= e
        land[lanes[done]] = q[done]
        keep = ~done
        lanes, q = lanes[keep], q[keep]
        wl.append(lanes)
        wq.append(q)
        q = _step(reader, q, zz, k1)
    words, n, last = reader.words(), reader.nbits, reader.last_one()
    item, tl, tq = words.item, [], []
    for i, x in zip(lanes.tolist(), q.tolist()):
        while x < e and not mark[x - s]:
            tl.append(i)
            tq.append(x)
            x = _next_scalar(words, item, x, k1, n, last)
        land[i] = x
    wl.append(np.array(tl, np.int64))
    wq.append(np.array(tq, np.int64))

    # the chain over paths: path i hands on to the chunk it landed in
    nxt = np.append(np.where(land < e, (land - s) // chunk, J), J)
    seq, f = np.zeros(1, np.int64), nxt
    while seq[-1] != J:
        seq = np.concatenate([seq, f[seq]])
        f = f[f]
    on = np.zeros(J + 1, bool)
    on[seq] = True
    on = on[:J]
    # entry of each followed path: its marks before the hand-over drop
    entry = np.full(J, s, np.int64)
    src = np.flatnonzero(on & (nxt[:J] < J))
    entry[nxt[src]] = land[src]
    own &= P >= entry
    own &= on
    wl, wq = np.concatenate(wl), np.concatenate(wq)
    wq = wq[on[wl]]
    hit = np.zeros(size, bool)              # in order, without a sort
    hit[P[own] - s] = True
    hit[wq - s] = True
    bounds = np.flatnonzero(hit)
    bounds += s
    last = int(seq[np.searchsorted(seq, J) - 1])
    return bounds, int(land[last]), int(wq.size)


def _step_many(group: ReaderGroup, p: np.ndarray, zz: np.ndarray,
               k1: int) -> np.ndarray:
    """:func:`_step` for positions in a group's streams: a start with no
    set bit after it in its stream gets that stream's end + 1."""
    w = group.words.take(p >> 3)
    w <<= p & 7
    t = w.view(np.uint64)
    t >>= np.uint64(48)
    q = zz.take(t.view(np.int64))
    q += p
    if not t.all():                         # 16+ leading zeros
        far = np.flatnonzero(t == 0)
        i = group.stream_of(p[far])
        q[far] = _far_bounds(group.words, p[far], k1, group.ends[i],
                             group.last_ones[i])
    return q


def _bounds_many(group: ReaderGroup, s0: np.ndarray, count: np.ndarray,
                 k1: int, idx: np.ndarray) -> tuple[list[np.ndarray], int]:
    """:func:`_bounds_vector` for one section of order ``k1 - 1`` in each
    of the streams ``idx`` of ``group``: each stream's ``count + 1``
    boundaries from ``s0`` (group positions), and the resync steps taken.
    Each window (:func:`_window_many`) covers every stream whose
    boundaries are still short, each stream's part sized as
    :func:`_bounds_vector` sizes its window."""
    n = group.ends[idx]
    last = group.last_ones[idx]
    chunk = _CHUNK_CODES * k1
    parts: list[list[np.ndarray]] = [[] for _ in idx]
    have = np.zeros(idx.size, np.int64)
    s = s0.copy()
    per = np.full(idx.size, k1 + 1.25)
    steps = 0
    act = np.arange(idx.size)
    while act.size:
        if (s[act] > n[act]).any():
            raise EOFError("bitstream exhausted")
        sa = s[act]
        need = count[act] + 1 - have[act]
        e = np.minimum(sa + (need * per[act]).astype(np.int64) + chunk,
                       n[act] + 1)
        got, nxt, st = _window_many(group, sa, e, k1, n[act], last[act])
        for j, b in zip(act.tolist(), got):
            parts[j].append(b)
        have[act] += [b.size for b in got]
        s[act] = nxt
        steps += st
        per[act] = 1.25 * (nxt - s0[act]) / have[act]
        act = act[have[act] < count[act] + 1]
    bounds = [(p[0] if len(p) == 1 else np.concatenate(p))[:c + 1]
              for p, c in zip(parts, count.tolist())]
    return bounds, steps


def _window_many(group: ReaderGroup, s: np.ndarray, e: np.ndarray, k1: int,
                 n: np.ndarray, last: np.ndarray
                 ) -> tuple[list[np.ndarray], np.ndarray, int]:
    """:func:`_window` over one window ``[s[j], e[j])`` in each of several
    streams of a group: each window's boundaries, each window's first
    boundary at or past its end, and the resync steps taken.

    The chunks of all windows form one row of paths, and the marks of all
    windows lie side by side in one array (window j's position ``x`` at
    slot ``x + d[j]``).  A path walks its own stream only: it resyncs on
    its own window's marks and stops at its own window's end, so it hands
    the parse on within its stream, and the chain over paths is resolved
    from every window's first path at once.
    """
    A = s.size
    chunk = _CHUNK_CODES * k1
    zz = _ZZ16 + k1
    J = -(-(e - s) // chunk)
    first = np.cumsum(J) - J
    Jt = int(J.sum())
    sid = np.repeat(np.arange(A), J)        # the window of each path
    path = np.arange(Jt)
    size = J * chunk + 1                    # each window's last slot stays
    d = np.cumsum(size) - size - s          # unmarked
    sl, el, dl = s[sid], e[sid], d[sid]
    c = sl + chunk * (path - first[sid])
    end = np.minimum(c + chunk, el)
    rows = [c]
    p = c
    for _ in range(_CHUNK_CODES):           # codewords are k + 1 bits or more
        p = _step_many(group, p, zz, k1)
        rows.append(p)
    P = np.stack(rows)                      # (steps + 1, Jt), rising by column
    own = P < end
    Pm = P + dl                             # the marks' slots
    mark = np.zeros(int(size.sum()), bool)
    mark[Pm[own]] = True

    # resync: every path from its exit until it lands on a mark of its own
    # window or leaves it (past its window's end a slot may be another
    # window's: the end test decides there)
    top = mark.size - 1
    lanes = path
    q = P[own.sum(0), lanes]
    land = np.empty(Jt, np.int64)
    wl, wq = [], []
    while lanes.size > _RESYNC_TAIL:
        done = mark[np.minimum(q + dl[lanes], top)]
        done |= q >= el[lanes]
        land[lanes[done]] = q[done]
        keep = ~done
        lanes, q = lanes[keep], q[keep]
        wl.append(lanes)
        wq.append(q)
        q = _step_many(group, q, zz, k1)
    words = group.words
    item, tl, tq = words.item, [], []
    for i, x in zip(lanes.tolist(), q.tolist()):
        j = int(sid[i])
        ej, dj, nj, lj = int(e[j]), int(d[j]), int(n[j]), int(last[j])
        while x < ej and not mark[x + dj]:
            tl.append(i)
            tq.append(x)
            x = _next_scalar(words, item, x, k1, nj, lj)
        land[i] = x
    wl.append(np.array(tl, np.int64))
    wq.append(np.array(tq, np.int64))

    # the chain over paths, from every window's first path at once
    nxt = np.append(np.where(land < el, first[sid] + (land - sl) // chunk,
                             Jt), Jt)
    seq, f = first[None, :], nxt
    while (seq[-1] != Jt).any():
        seq = np.concatenate([seq, f[seq]])
        f = f[f]
    on = np.zeros(Jt + 1, bool)
    on[seq] = True
    on = on[:Jt]
    entry = c.copy()
    src = np.flatnonzero(on & (nxt[:Jt] < Jt))
    entry[nxt[src]] = land[src]
    # the true parse, in order without a sort: the marks of the followed
    # paths from their entries on (every other mark is cleared; no two
    # paths mark one slot) and their resync steps (never marked)
    followed = P >= entry
    followed &= on
    own &= ~followed
    mark[Pm[own]] = False
    wl, wq = np.concatenate(wl), np.concatenate(wq)
    walked = on[wl]
    mark[wq[walked] + dl[wl[walked]]] = True
    slots = np.flatnonzero(mark)
    cut = np.searchsorted(slots, np.cumsum(size)[:-1]).tolist()
    got = [b - dj for b, dj in zip(np.split(slots, cut), d.tolist())]
    lastp = seq[(seq != Jt).sum(0) - 1, np.arange(A)]
    return got, land[lastp], int(walked.sum())


def _values(words: np.ndarray, starts: np.ndarray, ends: np.ndarray,
            k: int) -> np.ndarray:
    """Every codeword's value from where it starts and ends: codeword i's
    first set bit is ``z = (starts[i] + ends[i] - k - 1) / 2`` and its
    value bits run from ``z`` to ``ends[i]``; they are read from the 64-bit
    window at ``z``'s byte (plus the next byte where they pass its end).
    Raises ``ValueError`` on
    codewords too long to be well-formed."""
    if starts.size == 0:
        return np.zeros(0, np.int64)
    z = (starts + ends - (k + 1)) >> 1
    nb = ends - z
    top = int(nb.max())
    if top > _MAX_CODE_BITS:
        raise ValueError(f"exp-Golomb codeword of {top} bits (corrupt)")
    byte = z >> 3
    off = z & 7
    w = words[byte]
    w <<= off
    w = w.view(np.uint64)
    if top > 57:
        far = np.flatnonzero(off + nb > 64)
        nxt = words[byte[far] + 8].view(np.uint64)
        w[far] |= nxt >> (64 - off[far]).view(np.uint64)
    w >>= (64 - nb).view(np.uint64)
    v = w.view(np.int64)
    v -= 1 << k
    return v


# below this count the jump decoder falls back to the sequential boundary
# walk: the table build + doubling rounds are O(stream) while the walk is
# O(count), so short sections (remainder tails, tiny tensors) lose
_JUMP_MIN = 512

# doubling the jump table costs one full-stream gather per round; past this
# jump width it is cheaper to extend the orbit in fixed-width chunks
_JUMP_CAP = 2048


def decode_egk_jump(reader: BitReader, count: int, k: int) -> np.ndarray:
    """Order-k exp-Golomb decode with a speculative parallel boundary walk.

    The sequential phase-1 recurrence ``s' = 2 * next_one(s) - s + k + 1``
    is a pointer chase through a table that exists for EVERY bit position:
    ``f = clip(reader.jump_base() + k + 1, n + 1)`` (the ``n + 1`` slot is
    an EOF fixed point).  Starts then enumerate by pointer doubling —
    ``f[f]`` jumps two codewords, ``f[f][f[f]]`` four — so the orbit of
    ``count`` boundaries resolves in ``log2(count)`` dense gathers instead
    of ``count`` Python iterations.  Each codeword's first-set-bit position
    falls out of consecutive starts (``z = (s + s' - k - 1) / 2``), so no
    per-codeword index walk remains.  Bit-exact with :func:`decode_egk`
    (same values, same cursor, same EOFError/ValueError surface); used by
    the ``speculative`` NNC engine on large sections.  Spanned as
    ``golomb.decode`` with ``values=count``, like :func:`decode_egk`.
    """
    if count < _JUMP_MIN:
        return decode_egk(reader, count, k)
    with obs_trace.span("golomb.decode", values=count, streams=1,
                        lockstep_values=0):
        return _decode_egk_orbit(reader, count, k)


def _decode_egk_orbit(reader: BitReader, count: int, k: int) -> np.ndarray:
    n = reader.nbits
    base = reader.jump_base()
    cached = reader.jump_pow.get(k)
    if cached is not None:
        # reuse an earlier section's composed table: seed the first `jump`
        # starts with a scalar walk over the base, then extend jump-wide
        jump, f = cached
        s = reader.tell()
        seed = np.empty(min(jump, count + 1), np.int64)
        for i in range(seed.size):
            seed[i] = s
            s = int(base[s]) + (k + 1)
            if s > n:
                s = n + 1
        starts = seed
    else:
        f = base + (k + 1)
        np.minimum(f, n + 1, out=f)
        starts = np.array([reader.tell()], np.int64)
        jump = 1
    while starts.size < count + 1:
        ext = f[starts[-jump:]]
        need = count + 1 - starts.size
        starts = np.concatenate([starts, ext[:need] if ext.size > need
                                 else ext])
        if starts.size < count + 1 and jump < _JUMP_CAP:
            f = f[f]
            jump <<= 1
    if cached is None and jump > 1:
        reader.jump_pow[k] = (jump, f)
    s = int(starts[-1])
    if s > n:
        raise EOFError("bitstream exhausted")
    v = _values(reader.words(), starts[:-1], starts[1:], k)
    reader.seek(s)
    return v
