"""The exp-Golomb section parse of ``golomb.decode_egk`` against the
bit-by-bit reference ``golomb.decode_egk_ref``.

Large sections find their codeword boundaries by chunk synchronisation,
small ones by a plain-int walk; both read the values from 64-bit windows of
the packed bytes.  Every case checks the values and the reader's cursor
afterwards, on sections that start mid-byte, end at the stream's end or
are followed by other bypass data, whose first window is too small, whose
zero prefixes run longer than a chunk, and whose value bits run past a
64-bit window.  The error surface is the
walk's: ``EOFError`` on a section that runs past the stream, ``ValueError``
on a codeword of more than 63 value bits, and ``CorruptPayloadError``
through ``nnc.decode_tree``.
"""
import numpy as np
import pytest

from repro.coding import golomb, nnc
from repro.coding.bitstream import BitReader, BitWriter
from repro.coding.errors import CorruptPayloadError
from repro.obs import make_telemetry

VMIN = golomb._VECTOR_MIN


def _values(kind: str, count: int, k: int, seed: int) -> np.ndarray:
    r = np.random.default_rng(seed)
    if kind == "geom":          # around the order's own scale
        return r.geometric(1.0 / (1 << k), count) - 1
    if kind == "zeros":         # every codeword k + 1 bits: chunk-aligned
        return np.zeros(count, np.int64)
    if kind == "wide":          # ~30-bit values at a small order
        return r.integers(0, 1 << 30, count)
    if kind == "huge":          # 57-63 value bits: windows that spill
        return r.integers(1 << 56, 1 << 62, count)
    if kind == "long":          # zero prefixes two past a chunk (k <= 2)
        v = r.geometric(1.0 / (1 << k), count) - 1
        far = r.random(count) < 0.05
        v[far] = (1 << (16 * (k + 1) + k + 2)) + r.integers(0, 99, far.sum())
        return v
    raise ValueError(kind)


def _section(vals, k, pre, tail, seed=0):
    """``pre`` leading bits, the section, then ``tail`` random bits."""
    r = np.random.default_rng(seed + 1)
    w = BitWriter()
    w.put_bits(r.integers(0, 2, pre))
    golomb.encode_egk(w, vals.astype(np.int64), k)
    w.put_bits(r.integers(0, 2, tail))
    return w.to_bytes()


def _check(data, pre, count, k, vals=None):
    fast, ref = BitReader(data), BitReader(data)
    fast.seek(pre)
    ref.seek(pre)
    want = golomb.decode_egk_ref(ref, count, k)
    got = golomb.decode_egk(fast, count, k)
    np.testing.assert_array_equal(got, want)
    if vals is not None:
        np.testing.assert_array_equal(got, vals)
    assert fast.tell() == ref.tell()
    return fast


def _end_exact_pre(vals, k):
    """A lead-in that makes the section end on the stream's last bit."""
    return -int(golomb.egk_bit_length(vals, k).sum()) % 8


CASES = (
    # every order, a large section mid-byte with bypass data after it
    [("geom", 3000, k, 3, 40) for k in range(16)]
    # just under, at and over the small-section threshold
    + [("geom", VMIN + d, 3, 5, 17) for d in (-1, 0, 1)]
    # around the chunk (16 minimal codewords) and its multiples
    + [("zeros", 16 * m + d, k, 0, 9) for k in (0, 4)
       for m in (VMIN // 16, VMIN // 16 + 1, 130) for d in (-1, 0, 1)]
    # the last codeword ends exactly at the stream's end
    + [(kind, 2500, k, None, 0) for kind in ("geom", "zeros")
       for k in (0, 3, 7)]
    # the first window falls short and has to grow
    + [("wide", 4000, k, 6, 13) for k in (0, 2)]
    # zero prefixes longer than a chunk
    + [("long", 2000, k, 1, 21) for k in (0, 2)]
    # value bits that run past their byte's 64-bit window
    + [("huge", 1500, k, pre, 7) for k in (0, 9) for pre in (0, 5)]
)


@pytest.mark.parametrize("kind,count,k,pre,tail", CASES,
                         ids=[f"{c[0]}-n{c[1]}-k{c[2]}"
                              + ("-end" if c[3] is None else "")
                              for c in CASES])
def test_section_parse_matches_reference(kind, count, k, pre, tail):
    vals = _values(kind, count, k, seed=count + k)
    if pre is None:
        pre = _end_exact_pre(vals, k)
    data = _section(vals, k, pre, tail, seed=count)
    fast = _check(data, pre, count, k, vals)
    if tail:
        # what follows the section is read from where the parse left off
        r = BitReader(data)
        r.seek(fast.tell())
        np.testing.assert_array_equal(fast.get_bits(tail), r.get_bits(tail))
    else:
        assert fast.bits_remaining < 8


@pytest.mark.parametrize("count", [1, 2, 15, 16, 17, 31, 32, 33, 47, 48, 49,
                                   100, 257])
@pytest.mark.parametrize("kind", ["geom", "zeros", "long"])
def test_chunk_sync_on_small_sections(kind, count, monkeypatch):
    """The chunk synchronisation forced onto sections far below the
    threshold: one chunk, a few, a partial last one."""
    monkeypatch.setattr(golomb, "_VECTOR_MIN", 1)
    vals = _values(kind, count, 1, seed=count)
    _check(_section(vals, 1, 5, 11, seed=count), 5, count, 1, vals)
    pre = _end_exact_pre(vals, 1)
    _check(_section(vals, 1, pre, 0, seed=count), pre, count, 1, vals)


def test_consecutive_sections_share_one_reader():
    """Sections of different orders and sizes back to back, as a message
    lays them out: each picks up where the last left off."""
    r = np.random.default_rng(5)
    plan = [(3, 4000), (5, 3900), (3, 7), (5, 2), (0, 1500), (9, 60)]
    w = BitWriter()
    secs = []
    for k, n in plan:
        v = r.geometric(1.0 / (1 << k), n) - 1
        golomb.encode_egk(w, v, k)
        w.put_uint(int(r.integers(0, 16)), 4)
        secs.append(v)
    data = w.to_bytes()
    fast, ref = BitReader(data), BitReader(data)
    for (k, n), v in zip(plan, secs):
        np.testing.assert_array_equal(golomb.decode_egk(fast, n, k), v)
        golomb.decode_egk_ref(ref, n, k)
        assert fast.tell() == ref.tell()
        assert fast.get_uint(4) == ref.get_uint(4)


# ------------------------------------------------------------- errors


@pytest.mark.parametrize("count", [40, VMIN + 500])
@pytest.mark.parametrize("cut", ["mid", "last_byte", "one_more",
                                 "overlong_cut"])
def test_truncated_section_raises_eof(count, cut):
    """The section ends on the stream's last bit; cut it in half, drop its
    last byte (the last codeword is 39 bits: it alone loses value bits,
    its prefix stays whole), or ask for one codeword more than it holds."""
    vals = _values("geom", count, 3, seed=1)
    vals[-1] = 1 << 20
    pre = _end_exact_pre(vals, 3)
    data = _section(vals, 3, pre, 0)
    if cut == "mid":
        data = data[:len(data) // 2]
    elif cut == "last_byte":
        data = data[:-1]
    elif cut == "one_more":
        count += 1
    else:
        w = BitWriter()
        w.put_bits(np.zeros(pre, np.uint8))
        golomb.encode_egk(w, vals[:-1], 3)
        w.put_bits(np.zeros(64 - 4, np.uint8))     # 64 value bits due,
        w.put_bits(np.ones(20, np.uint8))          # 20 present
        data = w.to_bytes()
    r = BitReader(data)
    r.seek(pre)
    with pytest.raises(EOFError):
        golomb.decode_egk(r, count, 3)


def _long_codeword_stream(count, k, at, nbits):
    """A section with codeword ``at`` replaced by one of ``nbits`` value
    bits, all ones, with enough codewords after it that the stream does
    not run out; and the values the reference reads from it."""
    r = np.random.default_rng(2)
    vals = r.geometric(1.0 / (1 << k), count + 200) - 1
    w = BitWriter()
    golomb.encode_egk(w, vals[:at], k)
    w.put_bits(np.zeros(nbits - k - 1, np.uint8))  # the prefix
    w.put_bits(np.ones(nbits, np.uint8))           # its value bits
    golomb.encode_egk(w, vals[at:], k)
    want = np.insert(vals, at, (1 << nbits) - 1 - (1 << k)
                     if nbits <= 63 else -1)[:count]
    return w.to_bytes(), want


@pytest.mark.parametrize("nbits", [64, 70])
@pytest.mark.parametrize("count,at", [(30, 7), (VMIN + 600, 900),
                                      (VMIN + 600, 0)])
def test_overlong_codeword_raises_value_error(count, at, nbits):
    data, _ = _long_codeword_stream(count, 2, at, nbits)
    with pytest.raises(ValueError, match="63|bits"):
        golomb.decode_egk(BitReader(data), count, 2)


@pytest.mark.parametrize("count,at", [(30, 7), (VMIN + 600, 900)])
def test_longest_codeword_decodes(count, at):
    """63 value bits is the longest well-formed codeword: it decodes."""
    data, want = _long_codeword_stream(count, 2, at, 63)
    _check(data, 0, count, 2, want)


def _big_message():
    """One tensor whose gap and remainder sections are both large, and one
    whose sections are small."""
    r = np.random.default_rng(11)
    w = np.zeros((40, 256), np.int32)
    pos = r.choice(w.size, 3000, replace=False)
    w.flat[pos] = (3 + r.geometric(1 / 30, 3000)) * r.choice([-1, 1], 3000)
    b = np.array([0, 4, -1, 0, 9, 0, 0, -2], np.int32)
    return {"w": w, "b": b}


def _reframe(msg, byp):
    cab_len = int.from_bytes(msg[:8], "big")
    cab = msg[16:16 + cab_len]
    return (cab_len.to_bytes(8, "big") + len(byp).to_bytes(8, "big")
            + cab + bytes(byp))


@pytest.mark.parametrize("tensor", ["w", "b"])
def test_truncated_section_is_corrupt_payload(tensor):
    """Through the NNC decoder a gap section cut short is a typed error
    whose cause is the parser's EOFError."""
    tree = _big_message()
    one = {tensor: tree[tensor]}
    msg = nnc.encode_tree(one)
    cab_len = int.from_bytes(msg[:8], "big")
    byp = msg[16 + cab_len:]
    with pytest.raises(CorruptPayloadError) as exc:
        nnc.decode_tree(_reframe(msg, byp[:5]), nnc.shapes_of(one))
    assert isinstance(exc.value.__cause__, EOFError)


def test_overlong_codeword_is_corrupt_payload():
    """Zeros over the middle of the large gap section leave a codeword of
    more than 63 value bits: a typed error whose cause is the parser's
    ValueError."""
    tree = {"w": _big_message()["w"]}
    msg = nnc.encode_tree(tree)
    cab_len = int.from_bytes(msg[:8], "big")
    byp = bytearray(msg[16 + cab_len:])
    byp[400:412] = bytes(12)
    with pytest.raises(CorruptPayloadError) as exc:
        nnc.decode_tree(_reframe(msg, byp), nnc.shapes_of(tree))
    assert isinstance(exc.value.__cause__, ValueError)


# ------------------------------------------------------------- engagement


def test_golomb_decode_spans_record_the_parse():
    """Decoding one message under trace telemetry opens one
    ``golomb.decode`` span per non-empty section: the values sum to the
    message's codewords, large sections take the chunk synchronisation
    (``vector=1``, resync steps counted) and small ones the walk."""
    tree = _big_message()
    msg = nnc.encode_tree(tree)
    lv = np.concatenate([np.abs(v).ravel() for v in tree.values()])
    codewords = int((lv > 0).sum() + (lv > 2).sum())    # gaps + remainders
    tel = make_telemetry("trace")
    with tel.activate():
        out = nnc.decode_tree(msg, nnc.shapes_of(tree))
    for key in tree:
        np.testing.assert_array_equal(np.asarray(out[key]), tree[key])
    spans = [s for s in tel.recorder.snapshot() if s.name == "golomb.decode"]
    assert sum(s.attrs["values"] for s in spans) == codewords
    big = [s for s in spans if s.attrs["values"] >= VMIN]
    small = [s for s in spans if s.attrs["values"] < VMIN]
    assert len(big) == 2 and small
    assert all(s.attrs["vector"] == 1 and s.attrs["resync_steps"] > 0
               for s in big)
    assert all(s.attrs["vector"] == 0 and s.attrs["resync_steps"] == 0
               for s in small)

    # telemetry off inside a traced scope: the decode records nothing
    outer = make_telemetry("trace")
    with outer.activate(), make_telemetry("off").activate():
        nnc.decode_tree(msg, nnc.shapes_of(tree))
    assert outer.recorder.snapshot() == []
