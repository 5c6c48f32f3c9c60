"""The registered wire codecs.

Registry (ordered roughly by fidelity; ratios are typical for a 96%-sparse
quantized VGG update, see ``benchmarks/compression.py --smoke``):

  raw-fp32         little-endian float32 of the reconstruction; lossless.
                   The uncompressed-FedAvg baseline wire format.
  fp16             float16 params section (scales stay float32); ~2x.
  int8-blockscale  per-block symmetric int8 via the fused Pallas kernel
                   ``kernels/delta_compress.py`` (one pass: threshold +
                   quantize); ~4x, tolerance-bounded by amax/254 per block.
  golomb           order-k exp-Golomb over zigzagged quantization levels
                   (k per tensor, 4-bit header); lossless on levels.
  nnc-cabac        the paper's full stack: DeepCABAC context-coded row-skip
                   flags + zero-runs + gt1/gt2 magnitudes (coding/nnc.py);
                   lossless on levels and byte-identical to the seed's
                   ``measure_update_bytes`` accounting.

Level codecs (golomb, nnc-cabac) put integer quantization levels on the wire
and dequantize on decode; ternary messages append one float32 magnitude per
params tensor after the level stream (STC's per-tensor mu).  Float codecs
(raw-fp32, fp16, int8-blockscale) transmit the reconstruction itself, so
they compose with ANY upstream lossy stage chain.
"""
from __future__ import annotations

import numpy as np

from repro.coding import nnc
from repro.coding import golomb as golomb_lib
from repro.obs import trace as obs_trace
from repro.coding.bitstream import BitReader, BitWriter
from repro.comms.codec import (ClientUpdate, Codec, Decoded, WireSpec,
                               check_batch_clients, rebuild_tree,
                               register_codec, sorted_items)
from repro.comms.codec import _decode_bn as decode_bn_tail


def _np32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


def _sent_recon_items(upd: ClientUpdate, spec: WireSpec):
    """Encoder-side (path, recon_leaf) pairs in wire order (mask applied)."""
    return [(p, l) for p, l in sorted_items(upd.recon_params)
            if p in spec.sent_paths]


def _encode_scales_fp32(upd: ClientUpdate, spec: WireSpec) -> list[bytes]:
    """Shared float-codec scales framing: raw little-endian float32."""
    if spec.scales is None:
        return []
    return [np.ascontiguousarray(_np32(leaf).astype("<f4")).tobytes()
            for _, leaf in sorted_items(upd.recon_scales)]


def _decode_scales_fp32(payload: bytes, off: int, spec: WireSpec):
    """Inverse of :func:`_encode_scales_fp32`; returns (scales_tree, off)."""
    if spec.scales is None:
        return None, off
    by_s: dict[str, np.ndarray] = {}
    for path, s in spec.scale_items():
        n = int(np.prod(s.shape)) if s.shape else 1
        by_s[path] = (np.frombuffer(payload, "<f4", n, off)
                      .astype(np.float32).reshape(s.shape))
        off += n * 4
    return rebuild_tree(spec.scales, by_s), off


# ===========================================================================
# float codecs: transmit the reconstruction
# ===========================================================================

class RawFloatCodec(Codec):
    """Raw little-endian floats, params in ``param_dtype``, scales float32."""

    def __init__(self, name: str, param_dtype: str, lossless: bool):
        self.name = name
        self.param_dtype = param_dtype   # numpy dtype str, e.g. "<f4"
        self.lossless = lossless

    def _encode_body(self, upd: ClientUpdate, spec: WireSpec) -> bytes:
        chunks = [np.ascontiguousarray(_np32(leaf).astype(self.param_dtype))
                  .tobytes() for _, leaf in _sent_recon_items(upd, spec)]
        chunks += _encode_scales_fp32(upd, spec)
        return b"".join(chunks)

    def _decode_body(self, payload: bytes, spec: WireSpec) -> Decoded:
        off = 0
        itemsize = np.dtype(self.param_dtype).itemsize
        by_path: dict[str, np.ndarray] = {}
        for path, s in spec.param_items():
            n = int(np.prod(s.shape)) if s.shape else 1
            arr = np.frombuffer(payload, self.param_dtype, n, off)
            by_path[path] = arr.astype(np.float32).reshape(s.shape)
            off += n * itemsize
        params = rebuild_tree(spec.params, by_path)
        scales, off = _decode_scales_fp32(payload, off, spec)
        return Decoded(params, scales)


class Int8BlockScaleCodec(Codec):
    """Per-block symmetric int8 with one float32 scale per block.

    Reuses the fused Pallas sparsify+quantize kernel from
    ``kernels/delta_compress.py`` (threshold 0: sparsification already
    happened in the graph stages); off a TPU the kernel runs in the Pallas
    interpreter (``repro.kernels.platform``).  The scales section stays raw
    float32 — scale deltas are ~1e-6 magnitude and precision-critical.
    Worst-case reconstruction error per block is ``amax/254`` (half a
    quantization step).
    """

    name = "int8-blockscale"
    lossless = False
    # encode dispatches the Pallas kernel through jax, ONE dispatch per
    # message.  The uplink's process pool is forkserver-based and pins each
    # worker's JAX to the CPU before any backend starts (the accelerator
    # belongs to the parent), so a worker's encode is host work: its own
    # CPU runtime, the kernel interpreted, bytes identical
    fork_safe = True
    block = 128

    def _kernel(self):
        from repro.kernels.delta_compress import delta_compress
        return lambda flat: delta_compress(flat, 0.0, block=self.block)

    def _encode_body(self, upd: ClientUpdate, spec: WireSpec) -> bytes:
        # The per-leaf zero pad is wire LAYOUT, not a kernel requirement
        # (the kernel pads ragged n itself): aligning every leaf to
        # a block boundary keeps each 128-block inside one tensor, so the
        # concatenated buffer quantizes to the same q/scale chunks as the
        # historical leaf-at-a-time dispatch — but in ONE kernel call per
        # message instead of one per leaf.
        flats, meta = [], []
        for _, leaf in _sent_recon_items(upd, spec):
            flat = _np32(leaf).reshape(-1)
            pad = (-flat.size) % self.block
            padded = flat.size + pad
            meta.append((padded, padded // self.block))
            flats.append(np.pad(flat, (0, pad)) if pad else flat)
        chunks = []
        if flats:
            q, s = self._kernel()(np.concatenate(flats))
            q = np.asarray(q, np.int8)
            s = np.asarray(s)
            qo = so = 0
            for padded, nblk in meta:
                chunks.append(q[qo:qo + padded].tobytes())
                chunks.append(s[so:so + nblk].astype("<f4").tobytes())
                qo += padded
                so += nblk
        chunks += _encode_scales_fp32(upd, spec)
        return b"".join(chunks)

    def encode_cohort(self, out, spec: WireSpec, *, clients=None):
        from repro.comms import device

        return device.int8_encode_cohort(self, out, spec, clients=clients)

    def _decode_body(self, payload: bytes, spec: WireSpec) -> Decoded:
        off = 0
        by_path: dict[str, np.ndarray] = {}
        for path, s in spec.param_items():
            n = int(np.prod(s.shape)) if s.shape else 1
            padded = n + (-n) % self.block
            nblk = padded // self.block
            q = np.frombuffer(payload, np.int8, padded, off)
            off += padded
            sc = np.frombuffer(payload, "<f4", nblk, off)
            off += nblk * 4
            deq = (q.reshape(nblk, self.block).astype(np.float32)
                   * sc[:, None].astype(np.float32))
            by_path[path] = deq.reshape(-1)[:n].reshape(s.shape)
        params = rebuild_tree(spec.params, by_path)
        scales, off = _decode_scales_fp32(payload, off, spec)
        return Decoded(params, scales)


# ===========================================================================
# level codecs: transmit integer quantization levels, dequantize on decode
# ===========================================================================

class LevelCodec(Codec):
    """Base for codecs that serialise the int32 level pytrees.

    Subclasses implement ``_encode_levels``/``_decode_levels`` over the
    ordered ``(path, int32 array)`` sections.  This base handles the ternary
    magnitude tail (one float32 per sent params tensor, appended after the
    level stream) and the dequantization back to float32 reconstructions —
    bit-identical to the in-graph dequantize (a single float32 multiply).
    """

    lossless = True
    needs = ("levels",)

    def _encode_levels(self, p_items, s_items) -> bytes:
        raise NotImplementedError

    def _decode_levels(self, body: bytes, p_shapes, s_shapes):
        """-> ({path: int32 array}, {path: int32 array})"""
        raise NotImplementedError

    # -- shared assembly pieces (per-message AND batch paths) ---------------

    def _level_items(self, upd: ClientUpdate, spec: WireSpec):
        """-> (p_items, s_items): the ordered int32 sections to code."""
        p_items = [(p, np.asarray(l, np.int32))
                   for p, l in sorted_items(upd.levels_params)
                   if p in spec.sent_paths]
        s_items = ([] if spec.scales is None else
                   [(p, np.asarray(l, np.int32))
                    for p, l in sorted_items(upd.levels_scales)])
        return p_items, s_items

    def _ternary_tail(self, upd: ClientUpdate, spec: WireSpec) -> bytes:
        if not spec.ternary:
            return b""
        return np.array([np.max(np.abs(_np32(l)))
                         for _, l in _sent_recon_items(upd, spec)],
                        "<f4").tobytes()

    @staticmethod
    def _split_ternary(payload: bytes, spec: WireSpec, n_params: int):
        """-> (level body, per-tensor ternary magnitudes or None)."""
        if not (spec.ternary and n_params):
            return payload, None
        tail = 4 * n_params
        return payload[:-tail], np.frombuffer(payload[-tail:], "<f4")

    def _dequantize(self, p_levels, s_levels, mags, spec: WireSpec,
                    p_shapes, s_shapes) -> Decoded:
        """Decoded level sections -> float32 reconstructions."""
        by_path: dict[str, np.ndarray] = {}
        for i, (path, _) in enumerate(p_shapes):
            lv = p_levels[path].astype(np.float32)
            if spec.ternary:
                by_path[path] = np.float32(mags[i]) * np.sign(lv)
            else:
                by_path[path] = lv * np.float32(spec.param_step(path))
        params = rebuild_tree(spec.params, by_path)
        scales = None
        if spec.scales is not None:
            by_s = {path: s_levels[path].astype(np.float32)
                    * np.float32(spec.fine_step_size)
                    for path, _ in s_shapes}
            scales = rebuild_tree(spec.scales, by_s)
        return Decoded(params, scales)

    def _encode_body(self, upd: ClientUpdate, spec: WireSpec) -> bytes:
        p_items, s_items = self._level_items(upd, spec)
        return self._encode_levels(p_items, s_items) + self._ternary_tail(
            upd, spec)

    def _decode_body(self, payload: bytes, spec: WireSpec) -> Decoded:
        p_shapes = [(p, tuple(s.shape)) for p, s in spec.param_items()]
        s_shapes = [(p, tuple(s.shape)) for p, s in spec.scale_items()]
        body, mags = self._split_ternary(payload, spec, len(p_shapes))
        p_levels, s_levels = self._decode_levels(body, p_shapes, s_shapes)
        return self._dequantize(p_levels, s_levels, mags, spec,
                                p_shapes, s_shapes)


class NncCabacCodec(LevelCodec):
    """The paper's DeepCABAC/NNC stack (``repro.coding.nnc``).

    The wire message is ``{"p": <param levels>, "s": <scale levels>}`` —
    exactly the message the seed's ``measure_update_bytes`` accounted, so
    payload lengths reproduce the seed byte totals bit-for-bit (nnc sorts
    leaves by path and never serialises the path strings, so the flattened
    sections code to the identical stream).

    Batch calls route through ``nnc.encode_tree_batch``/
    ``decode_tree_batch``: the cohort's level messages code against ONE
    shared shapes view (paths formatted, sorted and template-flattened
    once), with every payload byte-identical to its per-message call.
    """

    name = "nnc-cabac"
    # decode-side engine (see coding/nnc.py): encode bytes are identical
    # across engines, so variants interoperate freely on the wire
    decode_engine = nnc.DEFAULT_ENGINE

    def with_decode_engine(self, engine: str) -> "NncCabacCodec":
        import copy

        nnc._check_engine(engine)
        if engine == self.decode_engine:
            return self
        dup = copy.copy(self)
        dup.decode_engine = engine
        return dup

    @staticmethod
    def _msg(p_items, s_items) -> dict:
        msg: dict = {"p": dict(p_items)}
        if s_items:
            msg["s"] = dict(s_items)
        return msg

    @staticmethod
    def _msg_shapes(p_shapes, s_shapes) -> dict:
        shapes: dict = {"p": {p: jax_sds(shape) for p, shape in p_shapes}}
        if s_shapes:
            shapes["s"] = {p: jax_sds(shape) for p, shape in s_shapes}
        return shapes

    def _encode_levels(self, p_items, s_items) -> bytes:
        return nnc.encode_tree(self._msg(p_items, s_items))

    def _decode_levels(self, body, p_shapes, s_shapes):
        decoded = nnc.decode_tree(body, self._msg_shapes(p_shapes, s_shapes),
                                  engine=self.decode_engine)
        return decoded["p"], decoded.get("s", {})

    def encode_batch(self, upds, spec, *, clients=None):
        check_batch_clients(clients, len(upds), "updates")
        with obs_trace.span("codec.encode_batch", codec=self.name,
                            n=len(upds)):
            pieces = [self._level_items(u, spec) for u in upds]
            bodies = nnc.encode_tree_batch(
                [self._msg(p, s) for p, s in pieces])
            return [self._frame(body + self._ternary_tail(u, spec), u, spec)
                    for body, u in zip(bodies, upds)]

    def encode_cohort(self, out, spec: WireSpec, *, clients=None):
        from repro.comms import device

        return device.nnc_encode_cohort(self, out, spec, clients=clients)

    def decode_batch(self, payloads, spec, *, clients=None):
        check_batch_clients(clients, len(payloads), "payloads")
        if not payloads:
            return []
        with obs_trace.span("codec.decode_batch", codec=self.name,
                            n=len(payloads)):
            p_shapes = [(p, tuple(s.shape)) for p, s in spec.param_items()]
            s_shapes = [(p, tuple(s.shape)) for p, s in spec.scale_items()]
            frames = [self._deframe(p, spec) for p in payloads]
            split = [self._split_ternary(body, spec, len(p_shapes))
                     for body, _ in frames]
            trees = nnc.decode_tree_batch([body for body, _ in split],
                                          self._msg_shapes(p_shapes,
                                                           s_shapes),
                                          engine=self.decode_engine)
            out = []
            for tree, (_, mags), (_, bn_tail) in zip(trees, split, frames):
                dec = self._dequantize(tree["p"], tree.get("s", {}), mags,
                                       spec, p_shapes, s_shapes)
                if spec.version != 1:
                    dec = dec._replace(bn=decode_bn_tail(bn_tail, spec))
                out.append(dec)
            return out

    def payload_sections(self, payload, spec):
        """Real anatomy of one nnc payload: the 16-byte length header, the
        CABAC and bypass streams, plus (when present) the ternary magnitude
        tail and the schema-v2 frame sections.  Sums to ``len(payload)``."""
        sections: dict[str, int] = {}
        body = payload
        bn_tail = 0
        if spec.version != 1:
            sections["frame.header"] = 1
            bn_tail = spec.bn_nbytes
            body = payload[1:len(payload) - bn_tail]
        n_params = len(spec.param_items())
        mag_tail = 4 * n_params if (spec.ternary and n_params) else 0
        if mag_tail:
            body = body[:len(body) - mag_tail]
        sections["nnc.header"] = 16
        sections["nnc.cabac"] = int.from_bytes(body[:8], "big")
        sections["nnc.bypass"] = int.from_bytes(body[8:16], "big")
        if mag_tail:
            sections["ternary.mags"] = mag_tail
        if spec.version != 1:
            sections["frame.bn"] = bn_tail
        return sections


def jax_sds(shape):
    import jax

    return jax.ShapeDtypeStruct(shape, np.int32)


class GolombCodec(LevelCodec):
    """Order-k exp-Golomb over zigzag-mapped levels, one k per tensor.

    Lighter than CABAC (no context modelling, no row-skip flags) and fully
    vectorised on encode; zeros cost one bit at k=0, so heavily sparse level
    tensors still compress well.  Lossless on levels.
    """

    name = "golomb"
    decode_engine = "vectorized"

    def with_decode_engine(self, engine: str) -> "GolombCodec":
        import copy

        if engine not in ("vectorized", "speculative"):
            raise ValueError(
                f"codec {self.name!r} has no {engine!r} decode engine")
        if engine == self.decode_engine:
            return self
        dup = copy.copy(self)
        dup.decode_engine = engine
        return dup

    @staticmethod
    def _zigzag(x: np.ndarray) -> np.ndarray:
        x = x.astype(np.int64)
        return (x << 1) ^ (x >> 63)

    @staticmethod
    def _unzigzag(v: np.ndarray) -> np.ndarray:
        return (v >> 1) ^ -(v & 1)

    def _encode_levels(self, p_items, s_items) -> bytes:
        w = BitWriter()
        for _, leaf in list(p_items) + list(s_items):
            zig = self._zigzag(leaf.reshape(-1))
            k = golomb_lib.choose_k(zig)
            w.put_uint(k, 4)
            golomb_lib.encode_egk(w, zig, k)
        return w.to_bytes()

    def encode_cohort(self, out, spec: WireSpec, *, clients=None):
        from repro.comms import device

        return device.golomb_encode_cohort(self, out, spec, clients=clients)

    def _decode_levels(self, body, p_shapes, s_shapes):
        r = BitReader(body)
        egk = (golomb_lib.decode_egk_jump
               if self.decode_engine == "speculative"
               else golomb_lib.decode_egk)

        def section(shapes):
            out = {}
            for path, shape in shapes:
                n = int(np.prod(shape)) if shape else 1
                k = r.get_uint(4)
                vals = egk(r, n, k)
                out[path] = (self._unzigzag(vals).astype(np.int32)
                             .reshape(shape))
            return out

        return section(p_shapes), section(s_shapes)


# ---------------------------------------------------------------- registry

register_codec("raw-fp32", lambda: RawFloatCodec("raw-fp32", "<f4",
                                                 lossless=True))
register_codec("fp16", lambda: RawFloatCodec("fp16", "<f2", lossless=False))
register_codec("int8-blockscale", Int8BlockScaleCodec)
register_codec("golomb", GolombCodec)
register_codec("nnc-cabac", NncCabacCodec)
