"""Exact round-trip tests for the NNC/DeepCABAC-style codec."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # property tests need the dev extra
from hypothesis import given, settings, strategies as st

from repro.coding import nnc
from repro.coding.bitstream import BitReader, BitWriter
from repro.coding.cabac import ContextSet, Decoder, Encoder
from repro.coding import golomb


# ------------------------------------------------------------- bitstream

def test_bitwriter_roundtrip():
    w = BitWriter()
    w.put_uint(12345, 17)
    w.put_bits(np.array([1, 0, 1, 1], np.uint8))
    w.put_bit(1)
    r = BitReader(w.to_bytes())
    assert r.get_uint(17) == 12345
    np.testing.assert_array_equal(r.get_bits(4), [1, 0, 1, 1])
    assert r.get_bit() == 1


@given(st.lists(st.integers(0, 2**31 - 1), min_size=0, max_size=100), st.integers(0, 8))
@settings(max_examples=50, deadline=None)
def test_expgolomb_roundtrip(vals, k):
    w = BitWriter()
    arr = np.array(vals, np.int64)
    golomb.encode_egk(w, arr, k)
    if len(vals):
        r = BitReader(w.to_bytes())
        out = golomb.decode_egk(r, len(vals), k)
        np.testing.assert_array_equal(out, arr)


def test_egk_bit_length_matches_encoder():
    vals = np.array([0, 1, 2, 5, 100, 10000], np.int64)
    for k in (0, 1, 3):
        w = BitWriter()
        golomb.encode_egk(w, vals, k)
        assert w.bit_length == int(golomb.egk_bit_length(vals, k).sum())


# ------------------------------------------------------------- cabac

@given(st.lists(st.integers(0, 1), min_size=1, max_size=500), st.integers(1, 4))
@settings(max_examples=40, deadline=None)
def test_cabac_roundtrip(bits, nctx):
    enc = Encoder()
    cenc = ContextSet(nctx)
    for i, b in enumerate(bits):
        enc.encode_bit(cenc, i % nctx, b)
    data = enc.finish()
    dec = Decoder(data)
    cdec = ContextSet(nctx)
    out = [dec.decode_bit(cdec, i % nctx) for i in range(len(bits))]
    assert out == bits


def test_cabac_compresses_skewed_bits():
    rng = np.random.default_rng(0)
    bits = (rng.random(20000) < 0.02).astype(int)  # 2% ones
    enc = Encoder()
    ctx = ContextSet(1)
    for b in bits:
        enc.encode_bit(ctx, 0, int(b))
    nbytes = len(enc.finish())
    # empirical entropy ~0.14 bits/bin -> ~350 bytes; assert well under raw.
    assert nbytes < 20000 / 8 / 4


# ------------------------------------------------------------- nnc

def _roundtrip(tree):
    data = nnc.encode_tree(tree)
    out = nnc.decode_tree(data, nnc.shapes_of(tree))
    import jax
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return len(data)


def test_nnc_roundtrip_mixed_tree():
    rng = np.random.default_rng(1)
    tree = {
        "conv": {"w": (rng.integers(-5, 6, (16, 8, 3, 3)) *
                       (rng.random((16, 8, 3, 3)) < 0.05)).astype(np.int32),
                 "b": rng.integers(-2, 3, (16,)).astype(np.int32)},
        "dense": {"w": np.zeros((10, 32), np.int32)},
        "scalar": np.array(3, np.int32),
    }
    _roundtrip(tree)


def test_nnc_roundtrip_all_zero():
    tree = {"w": np.zeros((64, 64), np.int32)}
    nbytes = _roundtrip(tree)
    assert nbytes < 64  # 64 row-skip bins + headers, heavily compressed


def test_nnc_roundtrip_dense_values():
    rng = np.random.default_rng(2)
    tree = {"w": rng.integers(-100, 101, (32, 16)).astype(np.int32)}
    _roundtrip(tree)


@given(st.integers(1, 40), st.integers(1, 12), st.floats(0.0, 1.0), st.integers(0, 2**31 - 1))
@settings(max_examples=40, deadline=None)
def test_nnc_roundtrip_property(m, n, density, seed):
    rng = np.random.default_rng(seed)
    mask = rng.random((m, n)) < density
    vals = rng.integers(-(2**20), 2**20, (m, n)) * mask
    _roundtrip({"w": vals.astype(np.int32), "v": vals[0].astype(np.int32)})


def test_sparse_structured_codes_smaller_than_dense():
    rng = np.random.default_rng(3)
    dense = rng.integers(-8, 9, (128, 64)).astype(np.int32)
    sparse = dense.copy()
    sparse[8:] = 0  # 94% of rows skipped
    b_dense = len(nnc.encode_tree({"w": dense}))
    b_sparse = len(nnc.encode_tree({"w": sparse}))
    assert b_sparse < b_dense / 8


# ------------------------------------------------------------- span counts

def _spans_of(rec, name):
    return [s for s in rec.snapshot() if s.name == name]


@pytest.mark.parametrize("engine", ["vectorized", "speculative"])
def test_decode_span_counts_match_the_encoder(engine):
    """The decoder's ``cabac.decode`` spans count every context-coded bin
    the encoder's pass 1 scanned, and ``golomb.decode`` every value
    ``golomb.encode`` wrote — the counts the benchmark's time per bin
    divides by.  The large tensor takes the speculative engine's jump
    parse (over ``golomb._JUMP_MIN`` positions)."""
    import jax

    from repro.obs import trace as obs_trace

    rng = np.random.default_rng(5)
    tree = {"conv": (rng.integers(-40, 41, (64, 8, 3, 3))
                     * (rng.random((64, 8, 3, 3)) < 0.3)).astype(np.int32),
            "bias": rng.integers(-3, 4, (64,)).astype(np.int32),
            "zero": np.zeros((8, 8), np.int32)}
    rec = obs_trace.SpanRecorder()
    with obs_trace.use_recorder(rec):
        data = nnc.encode_tree(tree)
        out = nnc.decode_tree(data, nnc.shapes_of(tree), engine=engine)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a), b)
    (scan,) = _spans_of(rec, "cabac.pass1.state_scan")
    dec = _spans_of(rec, "cabac.decode")
    assert dec and sum(s.attrs["bins"] for s in dec) == scan.attrs["bins"]
    enc_v = [s.attrs["values"] for s in _spans_of(rec, "golomb.encode")]
    dec_v = [s.attrs["values"] for s in _spans_of(rec, "golomb.decode")]
    assert sum(enc_v) == sum(dec_v) > golomb._JUMP_MIN
    assert sorted(enc_v) == sorted(dec_v)
    # every decode span sits inside the message's nnc.decode span
    (msg,) = _spans_of(rec, "nnc.decode")
    for s in dec + _spans_of(rec, "golomb.decode"):
        assert msg.t0_ns <= s.t0_ns
        assert s.t0_ns + s.dur_ns <= msg.t0_ns + msg.dur_ns
