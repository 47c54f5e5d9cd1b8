"""Pluggable on-the-wire row codecs (ROADMAP: wire-format hot path).

Rows travel the simulated network *encoded*: the storage node spends CPU to
encode (charged to ``SimServerNode.cpu`` on the virtual clock), every wire
stage (node egress FIFO, AIMD transfer, client-ingress NIC) carries the
encoded byte count, and the client spends CPU to decode before delivery
(charged via ``ConnectionPool``'s host-decode resource).  The flow
controller is fed *wire* bytes, so its delivery-rate/BDP estimates — and
the ``SharedIngressLimiter`` / per-tenant egress accounting — see the
route's effective bandwidth gain, while ``LoaderStats`` keeps reporting
decoded (payload) bytes.  That split is what makes compression a real,
measurable CPU-vs-bandwidth knob per route: a 150 ms WAN route buys
throughput with cheap CPU; a local route mostly buys queueing.

Codecs:

* ``none``        — identity.  Zero cost, zero extra simulator events:
  byte accounting stays bit-identical to the pre-codec loader (asserted by
  ``tests/test_wirefmt.py``).
* ``byteshuffle`` — lz4-style lossless filter: a byte transpose (stride
  swept per payload — 4 groups the high bytes of int32/float32 streams
  into long runs, 3 de-interleaves RGB uint8 frames into channel planes)
  followed by run-length encoding, with a store-raw escape when encoding
  would expand.  Mirrors the shuffle+LZ blocks of Blosc/LZ4 at simulator
  speed.
* ``int8``        — lossy block quantization of float32 payloads, the
  numpy mirror of ``train/compression.py``'s ``quantize_int8`` idiom
  (per-block amax scale, round, clip to ±127).  Bounded error:
  ``|x - decode(encode(x))| <= amax_block / 127`` per element.  Non-float
  payloads (length not a multiple of 4) take the store-raw escape.

For *lazy* rows (size-only datasets, no real bytes) each codec also
provides a deterministic ``encoded_size`` model calibrated against its real
encoder on synthetic image-like entropy, so virtual-clock runs bill the same
ratios the real path would.
"""

from __future__ import annotations

import struct
from typing import Dict, Optional

import numpy as np

# Wire frame header: magic, codec id, flags, raw length.
_MAGIC = b"WF"
_HEADER = struct.Struct("<2sBBI")          # magic, codec_id, flags, raw_len
_FLAG_RAW = 0x01                           # store-raw escape (no transform)

# Node-side encode parallelism: a storage node encodes on this many cores
# (Scylla-style shard-per-core, a slice of the node reserved for the codec).
# One request's encode still runs on ONE core — serve() charges the full
# single-core latency but only 1/cores of serialized FIFO time — so encode
# adds latency everywhere but only caps throughput at cores x rate.
NODE_CODEC_CORES = 5
# Client-side decode parallelism (the io-threads double as decode workers).
HOST_CODEC_CORES = 8


class WireCodec:
    """One wire format: real encode/decode + deterministic cost models."""

    name = "abstract"
    codec_id = 0xFF
    lossless = True
    # Modelled compressed fraction for lazy (size-only) rows.
    model_ratio = 1.0
    # Single-core throughputs, bytes of *raw* payload per second.
    encode_Bps: Optional[float] = None     # None = free (codec "none")
    decode_Bps: Optional[float] = None

    # -- real path ---------------------------------------------------------
    def encode(self, raw: bytes) -> bytes:
        raise NotImplementedError

    def decode(self, blob: bytes) -> bytes:
        raise NotImplementedError

    # -- models (lazy rows / virtual clock) --------------------------------
    def encoded_size(self, raw_len: int) -> int:
        """Deterministic wire size for a lazy row of ``raw_len`` bytes."""
        return max(int(raw_len * self.model_ratio), _HEADER.size + 1)

    def encode_seconds(self, raw_len: int) -> float:
        """Single-core node CPU seconds to encode ``raw_len`` raw bytes."""
        return 0.0 if self.encode_Bps is None else raw_len / self.encode_Bps

    def decode_seconds(self, raw_len: int) -> float:
        """Single-core host CPU seconds to decode back ``raw_len`` bytes."""
        return 0.0 if self.decode_Bps is None else raw_len / self.decode_Bps

    # -- frame helpers -----------------------------------------------------
    def _frame(self, flags: int, raw_len: int, body: bytes) -> bytes:
        return _HEADER.pack(_MAGIC, self.codec_id, flags, raw_len) + body

    def _unframe(self, blob: bytes):
        magic, codec_id, flags, raw_len = _HEADER.unpack_from(blob)
        if magic != _MAGIC or codec_id != self.codec_id:
            raise ValueError(f"not a {self.name} wire frame")
        return flags, raw_len, blob[_HEADER.size:]


class NoneCodec(WireCodec):
    """Identity codec: the pre-codec wire format, bit for bit."""

    name = "none"
    codec_id = 0
    model_ratio = 1.0

    def encode(self, raw: bytes) -> bytes:
        return raw

    def decode(self, blob: bytes) -> bytes:
        return blob

    def encoded_size(self, raw_len: int) -> int:
        return raw_len


# -- byteshuffle helpers -----------------------------------------------------

# Candidate shuffle strides: the transpose only creates runs when the stride
# matches the data's element period — 4 for int32/float32 streams, 3 for
# interleaved RGB uint8, 2 for int16, 1 for already-flat byte planes.  The
# encoder sweeps these and records the winner in the frame's flags byte.
_SHUFFLE_STRIDES = (1, 2, 3, 4, 8)


def _shuffle(x: np.ndarray, stride: int) -> np.ndarray:
    pad = (-x.size) % stride
    if pad:
        x = np.concatenate((x, np.zeros(pad, dtype=np.uint8)))
    return x.reshape(-1, stride).T.ravel()


def _rle_encode(x: np.ndarray) -> bytes:
    """Run-length encode a uint8 vector as (len<=255, value) pairs."""
    if x.size == 0:
        return b""
    change = np.flatnonzero(x[1:] != x[:-1])
    starts = np.concatenate(([0], change + 1))
    lengths = np.diff(np.concatenate((starts, [x.size])))
    vals = x[starts]
    reps = (lengths + 254) // 255          # chunks per run (runs may be >255)
    out_vals = np.repeat(vals, reps)
    out_lens = np.full(out_vals.size, 255, dtype=np.int64)
    out_lens[np.cumsum(reps) - 1] = lengths - (reps - 1) * 255
    pairs = np.empty((out_vals.size, 2), dtype=np.uint8)
    pairs[:, 0] = out_lens
    pairs[:, 1] = out_vals
    return pairs.tobytes()


def _rle_decode(blob: bytes, n: int) -> np.ndarray:
    pairs = np.frombuffer(blob, dtype=np.uint8).reshape(-1, 2)
    out = np.repeat(pairs[:, 1], pairs[:, 0])
    if out.size < n:
        raise ValueError("truncated RLE stream")
    return out[:n]


class ByteShuffleCodec(WireCodec):
    """Lossless byte shuffle + RLE (lz4-style, with raw escape).

    The encoder sweeps ``_SHUFFLE_STRIDES`` and keeps the shortest body —
    stride 4 wins on int32/float32 streams, stride 3 on interleaved RGB
    uint8 frames — storing the winning stride in the frame's flags byte
    (``flags >> 1``; bit 0 stays the raw escape).  The sweep is a few extra
    vectorized passes, inside the modelled lz4-class encode rate.
    """

    name = "byteshuffle"
    codec_id = 1
    lossless = True
    # Calibrated on DataRow.materialize()'s uint64-seeded payloads restricted
    # to image-like low-entropy lanes; see tests/test_wirefmt.py.
    model_ratio = 0.55
    encode_Bps = 1.2e9                     # lz4-class compress, one core
    decode_Bps = 2.4e9                     # decompress is ~2x faster

    def encode(self, raw: bytes) -> bytes:
        n = len(raw)
        x = np.frombuffer(raw, dtype=np.uint8)
        best_body, best_stride = None, 0
        for stride in _SHUFFLE_STRIDES:
            body = _rle_encode(_shuffle(x, stride))
            if best_body is None or len(body) < len(best_body):
                best_body, best_stride = body, stride
        if len(best_body) >= n:            # incompressible: store raw
            return self._frame(_FLAG_RAW, n, raw)
        return self._frame(best_stride << 1, n, best_body)

    def decode(self, blob: bytes) -> bytes:
        flags, raw_len, body = self._unframe(blob)
        if flags & _FLAG_RAW:
            return bytes(body[:raw_len])
        stride = flags >> 1
        if stride not in _SHUFFLE_STRIDES:
            raise ValueError(f"corrupt byteshuffle frame: stride {stride}")
        padded = raw_len + ((-raw_len) % stride)
        shuffled = _rle_decode(body, padded)
        x = shuffled.reshape(stride, -1).T.ravel()
        return x[:raw_len].tobytes()


class Int8QuantCodec(WireCodec):
    """Lossy per-block int8 quantization of float32 payloads.

    The numpy mirror of ``train.compression.quantize_int8``: per ``BLOCK``
    floats, ``scale = max(amax, 1e-12)/127``; values round+clip to int8.
    Wire layout: frame header, float count, per-block f32 scales, int8 data
    — ~0.26x the raw bytes.  Payloads whose length is not a multiple of 4
    (not a float stream) are stored raw.
    """

    name = "int8"
    codec_id = 2
    lossless = False
    BLOCK = 1024
    # 1/4 data + 4/BLOCK scales + header slack.
    model_ratio = 0.26
    encode_Bps = 2.0e9                     # one vectorized pass, one core
    decode_Bps = 2.0e9

    def encode(self, raw: bytes) -> bytes:
        n = len(raw)
        if n % 4 != 0 or n == 0:
            return self._frame(_FLAG_RAW, n, raw)
        x = np.frombuffer(raw, dtype="<f4")
        if not np.all(np.isfinite(x)):     # not a float stream after all
            return self._frame(_FLAG_RAW, n, raw)
        nfloat = x.size
        pad = (-nfloat) % self.BLOCK
        xp = np.concatenate((x, np.zeros(pad, dtype="<f4"))) if pad else x
        blocks = xp.reshape(-1, self.BLOCK)
        amax = np.abs(blocks).max(axis=1, keepdims=True)
        scale = np.maximum(amax, 1e-12) / 127.0
        q = np.clip(np.round(blocks / scale), -127, 127).astype(np.int8)
        body = (struct.pack("<I", nfloat)
                + scale.astype("<f4").tobytes()
                + q.tobytes()[:nfloat])    # drop pad-element bytes
        if len(body) >= n:
            return self._frame(_FLAG_RAW, n, raw)
        return self._frame(0, n, body)

    def decode(self, blob: bytes) -> bytes:
        flags, raw_len, body = self._unframe(blob)
        if flags & _FLAG_RAW:
            return bytes(body[:raw_len])
        (nfloat,) = struct.unpack_from("<I", body)
        nblocks = (nfloat + self.BLOCK - 1) // self.BLOCK
        off = 4
        scale = np.frombuffer(body, dtype="<f4", count=nblocks, offset=off)
        off += 4 * nblocks
        q = np.frombuffer(body, dtype=np.int8, count=nfloat, offset=off)
        pad = nblocks * self.BLOCK - nfloat
        qp = (np.concatenate((q, np.zeros(pad, dtype=np.int8))) if pad
              else q)
        x = qp.reshape(-1, self.BLOCK).astype(np.float32) * scale[:, None]
        return x.ravel()[:nfloat].astype("<f4").tobytes()


_CODECS: Dict[str, WireCodec] = {}


def register_codec(codec: WireCodec) -> WireCodec:
    _CODECS[codec.name] = codec
    return codec


NONE = register_codec(NoneCodec())
BYTESHUFFLE = register_codec(ByteShuffleCodec())
INT8 = register_codec(Int8QuantCodec())

WIRE_CODECS = tuple(_CODECS)


def get_codec(name: "str | WireCodec | None") -> WireCodec:
    """Resolve a codec by name (None -> the identity codec)."""
    if name is None:
        return NONE
    if isinstance(name, WireCodec):
        return name
    try:
        return _CODECS[name]
    except KeyError:
        raise ValueError(f"unknown wire codec {name!r} "
                         f"(choose from {WIRE_CODECS})") from None


__all__ = ["WireCodec", "NoneCodec", "ByteShuffleCodec", "Int8QuantCodec",
           "get_codec", "register_codec", "WIRE_CODECS",
           "NODE_CODEC_CORES", "HOST_CODEC_CORES", "NONE", "BYTESHUFFLE",
           "INT8"]
