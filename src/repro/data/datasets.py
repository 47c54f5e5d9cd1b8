"""Synthetic datasets + ingestion into the KV store.

``SyntheticImageDataset`` mirrors ImageNet-1k statistics (Table 1: 1.28 M
images, mean 115 kB, lognormal-ish size spread) without materializing bytes —
used by the simulator's network tests, where only transfer sizes matter.

``SyntheticTokenDataset`` produces *real* payloads: token-sequence records
(features+label serialized together, as the paper requires for OOO assembly)
— used by the JAX training integration and the examples.

``ingest`` is the serial/parallel ingestion path (paper Sec. 4.1): rows are
inserted atomically (data+metadata) with seeded UUIDs.
"""

from __future__ import annotations

import struct
import uuid as _uuid
from dataclasses import dataclass
from typing import Iterator, List, Optional, Tuple

import numpy as np

from repro.core.kvstore import DataRow, KVStore, MetaRow, make_uuid

IMAGENET_MEAN_BYTES = 115_000
IMAGENET_TRAIN_IMAGES = 1_281_167


@dataclass
class SyntheticImageDataset:
    """Size-only image blobs with entity/class metadata (lazy payloads)."""

    n_samples: int = 50_000
    n_classes: int = 1000
    n_entities: int = 2_000            # e.g. patients / photographers
    mean_bytes: int = IMAGENET_MEAN_BYTES
    seed: int = 0

    def rows(self) -> Iterator[Tuple[DataRow, MetaRow]]:
        rng = np.random.default_rng(self.seed)
        # lognormal around the ImageNet mean with a realistic spread
        mu = np.log(self.mean_bytes) - 0.5 * 0.45 ** 2
        for _ in range(self.n_samples):
            u = make_uuid(rng)
            size = int(np.clip(rng.lognormal(mu, 0.45), 5_000, 2_000_000))
            label = int(rng.integers(self.n_classes))
            entity = f"ent{int(rng.integers(self.n_entities)):06d}"
            yield (DataRow(u, label, size, payload=None),
                   MetaRow(u, entity, label, {"size": size}))


TOKEN_RECORD_MAGIC = b"TKRC"


def encode_token_record(tokens: np.ndarray, label: int) -> bytes:
    """features+label in ONE blob — the property OOO assembly relies on."""
    tok = np.ascontiguousarray(tokens, dtype=np.int32)
    header = TOKEN_RECORD_MAGIC + struct.pack("<ii", int(label), tok.size)
    return header + tok.tobytes()


def decode_token_record(blob) -> Tuple[np.ndarray, int]:
    """Accepts any byte buffer — including the zero-copy memoryviews an
    arena-backed batch serves from ``AssembledBatch.payloads()``."""
    if bytes(blob[:4]) != TOKEN_RECORD_MAGIC:
        raise ValueError("not a token record")
    label, n = struct.unpack("<ii", blob[4:12])
    tokens = np.frombuffer(blob, dtype=np.int32, offset=12, count=n)
    return tokens, label


@dataclass
class SyntheticTokenDataset:
    """Real token-sequence payloads for end-to-end JAX training."""

    n_samples: int = 4096
    seq_len: int = 128
    vocab: int = 32000
    n_classes: int = 8
    n_entities: int = 64
    seed: int = 0

    def rows(self) -> Iterator[Tuple[DataRow, MetaRow]]:
        rng = np.random.default_rng(self.seed)
        for _ in range(self.n_samples):
            u = make_uuid(rng)
            # structured "language": a drifting Markov-ish walk is learnable
            start = int(rng.integers(self.vocab))
            steps = rng.integers(-32, 33, size=self.seq_len)
            tokens = (start + np.cumsum(steps)) % self.vocab
            label = int(rng.integers(self.n_classes))
            blob = encode_token_record(tokens.astype(np.int32), label)
            entity = f"ent{int(rng.integers(self.n_entities)):04d}"
            yield (DataRow(u, label, len(blob), payload=blob),
                   MetaRow(u, entity, label, {}))


@dataclass
class SyntheticPixelDataset:
    """Real fixed-size pixel payloads: raw (h, w, c) uint8 frames.

    Every row is exactly ``h*w*c`` bytes with no per-record header — the
    shape IS the codec — so an arena slab sized to ``nbytes`` holds a whole
    batch as one contiguous (B, h, w, c) tensor and the device feed can
    upload it with a single ``device_put`` (see ``data.pipeline.ImageFeed``).

    Frames are piecewise-constant colour fields (smooth sinusoids quantized
    to 16 levels, one phase set per class): realistic-looking *compressible*
    bytes, so the ``byteshuffle`` wire codec gets the long runs real images
    give it — unlike the uniformly random ``DataRow.materialize`` payloads,
    which are incompressible by construction.
    """

    n_samples: int = 1024
    h: int = 32
    w: int = 32
    c: int = 3
    n_classes: int = 10
    n_entities: int = 64
    seed: int = 0

    @property
    def nbytes(self) -> int:
        """Bytes per frame (== the arena slot size for this dataset)."""
        return self.h * self.w * self.c

    def make_frame(self, rng: np.random.Generator, label: int) -> np.ndarray:
        # The sinusoid is sampled on a coarse grid and block-upsampled, so
        # frames are piecewise-constant in >= (h//8, w//8) blocks — real
        # byte runs for the byteshuffle codec's RLE stage, not just a claim.
        by, bx = max(1, self.h // 8), max(1, self.w // 8)
        gh, gw = -(-self.h // by), -(-self.w // bx)
        yy = np.linspace(0.0, 1.0, gh)[:, None]
        xx = np.linspace(0.0, 1.0, gw)[None, :]
        img = np.empty((self.h, self.w, self.c), dtype=np.uint8)
        for ch in range(self.c):
            fy = 1.0 + (label % 3)
            fx = 1.0 + ((label + ch) % 4)
            phase = rng.uniform(0.0, 2.0 * np.pi)
            field = 127.5 + 120.0 * np.sin(
                2.0 * np.pi * (yy * fy + xx * fx) + phase)
            coarse = (np.round(field / 16.0) * 16.0).clip(0, 255)
            full = np.repeat(np.repeat(coarse, by, axis=0), bx, axis=1)
            img[..., ch] = full[:self.h, :self.w]
        return img

    def rows(self) -> Iterator[Tuple[DataRow, MetaRow]]:
        rng = np.random.default_rng(self.seed)
        for _ in range(self.n_samples):
            u = make_uuid(rng)
            label = int(rng.integers(self.n_classes))
            blob = self.make_frame(rng, label).tobytes()
            entity = f"ent{int(rng.integers(self.n_entities)):04d}"
            yield (DataRow(u, label, len(blob), payload=blob),
                   MetaRow(u, entity, label, {"h": self.h, "w": self.w,
                                              "c": self.c}))


def ingest(store: KVStore, dataset, parallel: int = 1) -> List[_uuid.UUID]:
    """Serial or chunked-parallel ingestion; returns inserted UUIDs in order.

    (The paper offers serial or Spark-parallel ingestion; here 'parallel'
    chunks the row stream — insertion is atomic per row either way.)
    """
    uuids: List[_uuid.UUID] = []
    rows = list(dataset.rows())
    if parallel > 1:
        import concurrent.futures as cf

        chunks = [rows[i::parallel] for i in range(parallel)]

        def insert_chunk(chunk):
            for data, meta in chunk:
                store.insert_atomic(data, meta)

        with cf.ThreadPoolExecutor(max_workers=parallel) as ex:
            list(ex.map(insert_chunk, chunks))
    else:
        for data, meta in rows:
            store.insert_atomic(data, meta)
    uuids.extend(r[0].uuid for r in rows)
    return uuids


__all__ = ["SyntheticImageDataset", "SyntheticTokenDataset",
           "SyntheticPixelDataset", "ingest",
           "encode_token_record", "decode_token_record",
           "IMAGENET_MEAN_BYTES", "IMAGENET_TRAIN_IMAGES"]
