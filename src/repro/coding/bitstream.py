"""Plain bitstream writer/reader (host-side, numpy-backed).

Used for the *bypass* portion of the NNC-style codec: raw bits whose
probability is ~0.5 and which therefore gain nothing from arithmetic coding.
Keeping them out of the arithmetic engine lets us vectorise them with numpy
(run lengths, signs, exp-Golomb remainders), which makes exact byte
measurement affordable inside the FL benchmarks.
"""
from __future__ import annotations

import numpy as np


# per-width MSB-first shift vectors, cached: put_uint runs several times per
# tensor on the encode hot path and np.arange dominated its cost
_SHIFTS: dict[int, np.ndarray] = {}


def _shifts(width: int) -> np.ndarray:
    s = _SHIFTS.get(width)
    if s is None:
        s = _SHIFTS[width] = np.arange(width - 1, -1, -1, dtype=np.int64)
    return s


class BitWriter:
    def __init__(self) -> None:
        self._chunks: list[np.ndarray] = []  # uint8 arrays of 0/1 bits

    def put_bit(self, bit: int) -> None:
        self._chunks.append(np.array([bit & 1], np.uint8))

    def put_bits(self, bits: np.ndarray) -> None:
        """Append a 1-D array of 0/1 values (any int dtype)."""
        if bits.size:
            self._chunks.append(bits.astype(np.uint8) & 1)

    def put_uint(self, value: int, width: int) -> None:
        """Fixed-width big-endian unsigned integer."""
        bits = (value >> _shifts(width)) & 1
        self._chunks.append(bits.astype(np.uint8))

    @property
    def bit_length(self) -> int:
        return int(sum(c.size for c in self._chunks))

    def to_bytes(self) -> bytes:
        if not self._chunks:
            return b""
        bits = np.concatenate(self._chunks)
        return np.packbits(bits).tobytes()


class BitReader:
    def __init__(self, data: bytes) -> None:
        raw = np.frombuffer(data, np.uint8)
        self._raw = raw
        self._bits = np.unpackbits(raw)
        self._pos = 0
        self._words: np.ndarray | None = None
        self._last_one: int | None = None
        self._jump: np.ndarray | None = None
        # composed exp-Golomb jump tables, keyed by order k: a multi-section
        # message reuses section 1's doubled table for every later section
        # with the same k (see golomb.decode_egk_jump)
        self.jump_pow: dict[int, tuple[int, np.ndarray]] = {}

    def get_bit(self) -> int:
        b = int(self._bits[self._pos])
        self._pos += 1
        return b

    def get_bits(self, n: int) -> np.ndarray:
        out = self._bits[self._pos:self._pos + n]
        if out.size != n:
            raise EOFError("bitstream exhausted")
        self._pos += n
        return out

    def get_uint(self, width: int) -> int:
        bits = self.get_bits(width)
        return int(bits.dot(1 << _shifts(width)))

    @property
    def bits_remaining(self) -> int:
        return int(self._bits.size - self._pos)

    # -- block access (package-internal) ------------------------------------
    # The vectorised exp-Golomb parsers (repro.coding.golomb) read many
    # codewords straight from the packed bytes; they read ``nbits``,
    # ``words``/``last_one`` and ``tell``, and commit their final
    # cursor via ``seek``.

    @property
    def nbits(self) -> int:
        return int(self._bits.size)

    def words(self) -> np.ndarray:
        """The 64 stream bits from every byte on, built once per reader:
        ``words()[i]`` holds bytes ``i .. i + 7`` big-endian as one int64
        (bit 63 is the first bit of byte ``i``; zeros past the end).  One
        gather and one shift put the 57+ bits from any bit position in the
        high bits of a word.  Entries run 8 bytes past the stream, so that
        a codeword start a little past its end still indexes them."""
        if self._words is None:
            nbytes = self._raw.size
            buf = np.zeros(nbytes + 16, np.uint8)
            buf[:nbytes] = self._raw
            self._words = np.ndarray((nbytes + 8,), ">u8", buf, 0,
                                     (1,)).astype(np.int64)
        return self._words

    def last_one(self) -> int:
        """Position of the stream's last set bit (-1 if none)."""
        if self._last_one is None:
            nz = np.flatnonzero(self._raw)
            if nz.size:
                b = int(nz[-1])
                v = int(self._raw[b])       # its lowest set bit is last
                self._last_one = 8 * b + 8 - (v & -v).bit_length()
            else:
                self._last_one = -1
        return self._last_one

    def jump_base(self) -> np.ndarray:
        """k-independent exp-Golomb boundary-jump base, built once per reader.

        ``base[q] = 2 * next_one(q) - q`` for every bit position ``q``: a
        codeword starting at ``q`` ends at ``base[q] + k + 1`` (prefix zeros
        up to the first set bit, then as many value bits again plus ``k``).
        Positions with no remaining set bit — including the two sentinel
        slots ``q in (n, n+1)`` — hold ``n + 2`` so any order-k jump table
        derived from the base clamps them to the ``n + 1`` EOF fixed point.
        Shared by every exp-Golomb section of a message (the base does not
        depend on the section's ``k``); built with array operations only,
        as a reversed running minimum of the set-bit positions."""
        if self._jump is None:
            n = self._bits.size
            pos = np.arange(n + 2, dtype=np.int64)
            nxt = np.full(n + 2, n + 2, np.int64)
            np.copyto(nxt[:n], pos[:n], where=self._bits.view(bool))
            nxt = np.minimum.accumulate(nxt[::-1])[::-1]
            base = nxt + nxt - pos
            base[nxt == n + 2] = n + 2
            self._jump = base
        return self._jump

    def tell(self) -> int:
        return self._pos

    def seek(self, pos: int) -> None:
        if not 0 <= pos <= self._bits.size:
            raise EOFError("bitstream exhausted")
        self._pos = pos


class ReaderGroup:
    """Several readers' streams in one address space, for a parser that
    advances one section of each in lockstep (``golomb.decode_egk_many``).

    Reader i's bit ``p`` is bit ``base[i] + p`` of the group, and
    ``words`` is :meth:`BitReader.words` over all of them: each stream is
    followed by 16 zero bytes, so a 64-bit window taken anywhere up to 64
    bits past a stream's end reads that stream or zeros, never the next
    stream.  ``ends[i]`` and ``last_ones[i]`` are reader i's ``nbits`` and
    ``last_one()`` in group positions.  The readers keep their own cursors.
    """

    def __init__(self, readers) -> None:
        self.readers = list(readers)
        sizes = np.array([r._raw.size + 16 for r in self.readers], np.int64)
        starts = np.cumsum(sizes) - sizes
        buf = np.zeros(int(sizes.sum()) + 8, np.uint8)
        for r, b in zip(self.readers, starts.tolist()):
            buf[b:b + r._raw.size] = r._raw
        self.words = np.ndarray((int(sizes.sum()),), ">u8", buf, 0,
                                (1,)).astype(np.int64)
        self.base = 8 * starts
        self.ends = self.base + [r.nbits for r in self.readers]
        self.last_ones = self.base + [r.last_one() for r in self.readers]

    def stream_of(self, p: np.ndarray) -> np.ndarray:
        """The stream each group position lies in."""
        return np.searchsorted(self.base, p, "right") - 1
