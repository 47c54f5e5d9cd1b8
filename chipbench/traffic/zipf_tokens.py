"""Token records with natural text's unigram skew, from the seed, in the
store's record format (``tokens.encode``).

Each token id is drawn i.i.d. from Zipf(s) over the vocabulary: the id of
rank r (from 1) has weight ``r ** -s``, and which id holds which rank is a
permutation drawn from the seed.  With random embeddings, hot ids route
to the same experts again and again, the imbalance a dropless expert layer
has to take; the walk of ``tokens.generate`` spreads it away.
"""

from __future__ import annotations

import uuid as _uuid

import numpy as np

from chipbench.traffic.tokens import TokenRecords


def generate(seed: int, n: int, seq_len: int, vocab: int,
             n_classes: int = 8, s: float = 1.0) -> TokenRecords:
    rng = np.random.default_rng([seed, 0x21F7])
    keys = [_uuid.UUID(bytes=rng.bytes(16), version=4) for _ in range(n)]
    if len(set(keys)) != n:
        raise ValueError("two keys drew the same uuid")
    weight = np.arange(1, vocab + 1, dtype=np.float64) ** -s
    cdf = np.cumsum(weight / weight.sum())
    rank = np.searchsorted(cdf, rng.random((n, seq_len)), side="right")
    ids = rng.permutation(vocab).astype(np.int32)
    tokens = ids[np.minimum(rank, vocab - 1)]
    labels = rng.integers(0, n_classes, size=n)
    return TokenRecords(keys, tokens, labels)
