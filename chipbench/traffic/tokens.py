"""Token records from the seed, in the store's record format.

A copy of the program's ``SyntheticTokenDataset`` rule: each record is a
drifting walk over the vocabulary (a start drawn uniformly, then steps
uniform in -32..32, modulo the vocabulary) with a class label, serialized
with its label in one blob, as out-of-order assembly needs: ``TKRC``, the
label and the token count as little-endian int32, then the int32 tokens.
"""

from __future__ import annotations

import dataclasses
import struct
import uuid as _uuid
from typing import List

import numpy as np

MAGIC = b"TKRC"


def encode(tokens: np.ndarray, label: int) -> bytes:
    tok = np.ascontiguousarray(tokens, dtype=np.int32)
    return MAGIC + struct.pack("<ii", int(label), tok.size) + tok.tobytes()


@dataclasses.dataclass
class TokenRecords:
    keys: List[_uuid.UUID]
    tokens: np.ndarray          # (n, seq_len) int32
    labels: np.ndarray          # (n,) int


def generate(seed: int, n: int, seq_len: int, vocab: int,
             n_classes: int = 8) -> TokenRecords:
    rng = np.random.default_rng([seed, 0x70C5])
    keys = [_uuid.UUID(bytes=rng.bytes(16), version=4) for _ in range(n)]
    if len(set(keys)) != n:
        raise ValueError("two keys drew the same uuid")
    start = rng.integers(0, vocab, size=(n, 1))
    steps = rng.integers(-32, 33, size=(n, seq_len))
    tokens = ((start + np.cumsum(steps, axis=1)) % vocab).astype(np.int32)
    labels = rng.integers(0, n_classes, size=n)
    return TokenRecords(keys, tokens, labels)
