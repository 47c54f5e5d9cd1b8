"""Batch assembly (paper Fig. 2): requests fan out, callbacks fan in.

After the last sample of a batch arrives, the output tensor is allocated
contiguously in one shot and samples are copied in by a thread pool; the
batch becomes available when the copy completes.  In virtual-clock mode the
copy is *modelled* (bytes / host-copy bandwidth); in real-clock mode the copy
actually happens into a preallocated numpy arena (shared-memory analogue).
"""

from __future__ import annotations

import functools
import uuid as _uuid
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import numpy as np

from .arena import ArenaSlab, PinnedArena
from .connection import ConnectionPool, FetchResult
from .netsim import Clock
from .stats import span

HOST_COPY_BANDWIDTH = 20.0e9  # bytes/s, multi-threaded memcpy into the arena


@dataclass
class AssembledBatch:
    """One output batch: features+labels, ready for the device feed.

    With an arena-backed assembler the payload bytes live in ``slab`` (one
    reused contiguous buffer; the per-sample ``FetchResult.payload`` refs
    are dropped at assembly) and ``payloads()`` serves zero-copy views.
    ``nbytes`` is *decoded* (host/consumer) bytes; ``wire_nbytes`` is what
    actually crossed the network — they differ under a wire codec, and
    egress/tenant accounting must use the wire figure.
    """

    seq: int
    samples: List[FetchResult]
    t_first_issue: float
    t_last_arrival: float
    t_ready: float
    epoch: int = 0
    slab: Optional[ArenaSlab] = None

    @property
    def nbytes(self) -> int:
        """Decoded payload bytes (what the host/device consume)."""
        return sum(s.size for s in self.samples)

    @property
    def wire_nbytes(self) -> int:
        """Encoded bytes billed on the wire (== nbytes without a codec)."""
        return sum(s.wire_size for s in self.samples)

    @property
    def labels(self) -> np.ndarray:
        return np.asarray([s.label for s in self.samples], dtype=np.int32)

    def payloads(self) -> "List[Optional[bytes] | memoryview]":
        if self.slab is not None:
            return [self.slab.view(i, s.size)
                    for i, s in enumerate(self.samples)]
        return [s.payload for s in self.samples]

    def pixels(self, h: int, w: int, c: int) -> np.ndarray:
        """Zero-copy ``(B, h, w, c)`` uint8 view (arena batches only)."""
        if self.slab is None:
            raise ValueError("pixels() needs an arena-backed batch "
                             "(LoaderConfig.use_arena=True)")
        return self.slab.pixels(h, w, c)

    def release(self) -> None:
        """Recycle the arena slab (no-op otherwise).  Call after the batch
        content has been uploaded/consumed; views from ``payloads()`` /
        ``pixels()`` must not be read afterwards."""
        if self.slab is not None:
            self.slab.release()

    @property
    def uuids(self) -> List[_uuid.UUID]:
        return [s.uuid for s in self.samples]


class BatchAssembler:
    """Models (or performs) the contiguous-allocation + parallel-copy stage."""

    def __init__(self, clock: Clock, copy_bandwidth: float = HOST_COPY_BANDWIDTH,
                 real_copy: bool = False,
                 arena: Optional[PinnedArena] = None) -> None:
        self._clock = clock
        self._copy_bw = copy_bandwidth
        self._real_copy = real_copy
        # Pinned arena (core/arena.py): real copies land in a reused
        # contiguous slab instead of a fresh bytearray per batch, and the
        # per-sample payload refs are dropped — the slab is the only copy.
        self._arena = arena
        self.bytes_assembled = 0

    def assemble(self, seq: int, epoch: int, samples: List[FetchResult],
                 on_ready: Callable[[AssembledBatch], None]) -> None:
        t_arr = max(s.t_done for s in samples)
        nbytes = sum(s.size for s in samples)
        self.bytes_assembled += nbytes
        slab = None
        if self._real_copy and self._arena is not None:
            with span("loader.assemble", batch=seq):
                slab = self._arena.acquire()
                for i, s in enumerate(samples):
                    slab.write(i, s.payload, s.size)
                    s.payload = None       # the slab owns the bytes now
        elif self._real_copy:
            # Legacy one-shot bytearray; copies are cheap at test scale.
            # Each sample owns exactly ``size`` bytes (payloads are
            # full-size since DataRow.materialize stopped truncating — clip
            # defensively so a short payload can never smear into its
            # neighbour's slot).
            with span("loader.assemble", batch=seq):
                arena = bytearray(nbytes)
                off = 0
                for s in samples:
                    if s.payload is not None:
                        n = min(len(s.payload), s.size)
                        arena[off:off + n] = s.payload[:n]
                    off += s.size
        delay = nbytes / self._copy_bw
        batch = AssembledBatch(seq=seq, samples=list(samples),
                               t_first_issue=min(s.t_issued for s in samples),
                               t_last_arrival=t_arr,
                               t_ready=self._clock.now() + delay,
                               epoch=epoch, slab=slab)
        self._clock.schedule(delay, on_ready, batch)


class BatchRequest:
    """In-order unit of work: all UUIDs of one batch requested at once.

    Results are tracked per *slot*, not per uuid: a batch that spans an epoch
    boundary can legitimately contain the same uuid twice (tail of one
    permutation + head of the next), and keying a dict by uuid would then
    wait forever on a count that can never be reached.
    """

    def __init__(self, seq: int, epoch: int, uuids: List[_uuid.UUID],
                 pool: ConnectionPool, assembler: BatchAssembler,
                 on_ready: Callable[[AssembledBatch], None]) -> None:
        self.seq = seq
        self.epoch = epoch
        self._results: List[Optional[FetchResult]] = [None] * len(uuids)
        self._got = 0
        self._want = len(uuids)
        self._assembler = assembler
        self._on_ready = on_ready
        for i, key in enumerate(uuids):  # all requests posted to the driver at once
            pool.fetch(key, functools.partial(self._one_done, i))

    def _one_done(self, slot: int, res: FetchResult) -> None:
        if self._results[slot] is not None:
            return
        self._results[slot] = res
        self._got += 1
        if self._got == self._want:
            self._assembler.assemble(self.seq, self.epoch,
                                     list(self._results), self._on_ready)


__all__ = ["AssembledBatch", "BatchAssembler", "BatchRequest",
           "HOST_COPY_BANDWIDTH"]
