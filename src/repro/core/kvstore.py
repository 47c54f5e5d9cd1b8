"""Cassandra-compatible KV store model: tables, rows, atomic batch insert.

Mirrors the paper's data model (Listing 1): a ``metadata`` table queried only
at split-creation time, and a ``data`` table holding ``(uuid, label, blob)``
rows fetched during training.  Features and annotations travel together in one
row — the property that makes out-of-order batch assembly possible (Sec. 3.4).

Blobs may be *lazy* (size-only) so the simulator can model a 147 GB dataset
without materializing it; the device feeds, the examples and the chip
benchmark use real payloads.
"""

from __future__ import annotations

import hashlib
import threading
import uuid as _uuid
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional

import numpy as np


def make_uuid(rng: np.random.Generator) -> _uuid.UUID:
    """Deterministic UUID4 from a seeded generator."""
    return _uuid.UUID(bytes=rng.bytes(16), version=4)


@dataclass
class DataRow:
    """Row of the ``data`` table: features + annotation in a single row."""

    uuid: _uuid.UUID
    label: int
    size: int                       # payload size in bytes
    payload: Optional[bytes] = None  # None => lazy blob (size-only rows)

    def materialize(self) -> bytes:
        """Full-size payload — always ``len() == self.size`` so arena copies
        and ``bytes_received`` accounting line up with ``FetchResult.size``."""
        if self.payload is not None:
            return self.payload
        # Deterministic pseudo-payload derived from the uuid.
        seed = int.from_bytes(self.uuid.bytes[:8], "little")
        return np.random.default_rng(seed).bytes(self.size)


@dataclass
class MetaRow:
    """Row of the ``metadata`` table (entity/class info used for splits)."""

    uuid: _uuid.UUID
    entity_id: str                  # e.g. patient_id — must not leak across splits
    label: int
    extra: Dict = field(default_factory=dict)


class KVStore:
    """The logical database: data + metadata tables with atomic batch insert."""

    def __init__(self, keyspace: str = "patches") -> None:
        self.keyspace = keyspace
        self._data: Dict[_uuid.UUID, DataRow] = {}
        self._meta: Dict[_uuid.UUID, MetaRow] = {}
        self._lock = threading.Lock()

    # -- writes --------------------------------------------------------------
    def insert_atomic(self, data: DataRow, meta: MetaRow) -> None:
        """Cassandra ``BatchStatement`` analogue: both rows or neither."""
        if data.uuid != meta.uuid:
            raise ValueError("data/meta uuid mismatch in atomic batch")
        with self._lock:
            self._data[data.uuid] = data
            self._meta[data.uuid] = meta

    def insert_many(self, rows: Iterable) -> int:
        n = 0
        for data, meta in rows:
            self.insert_atomic(data, meta)
            n += 1
        return n

    # -- reads ---------------------------------------------------------------
    def get_data(self, key: _uuid.UUID) -> DataRow:
        try:
            return self._data[key]
        except KeyError:
            raise KeyError(f"uuid {key} not in {self.keyspace}.data") from None

    def get_meta(self, key: _uuid.UUID) -> MetaRow:
        return self._meta[key]

    def scan_metadata(self) -> List[MetaRow]:
        """Full metadata scan — used only for split creation (cheap table)."""
        with self._lock:
            return list(self._meta.values())

    def uuids(self) -> List[_uuid.UUID]:
        with self._lock:
            return list(self._data.keys())

    def __len__(self) -> int:
        return len(self._data)

    def total_bytes(self) -> int:
        return sum(r.size for r in self._data.values())


def token_of(key: _uuid.UUID) -> int:
    """Cassandra Murmur3-style token (md5 here; distribution is what matters)."""
    return int.from_bytes(hashlib.md5(key.bytes).digest()[:8], "big")


__all__ = ["KVStore", "DataRow", "MetaRow", "make_uuid", "token_of"]
