"""One-call stack construction: config object -> running data stack.

The chain ``Cluster``/``FederatedCluster`` -> ``ConnectionPool`` ->
``CassandraLoader`` -> ``DeviceFeed``/``ImageFeed`` is built in one place,
so every caller (``run_training``, the examples, the chip benchmark's
drivers, the tests) gets the same defaults and the same validation.
:func:`build_stack` is the one spelling:

    from repro.core import LoaderConfig, build_stack

    stack = build_stack(store=store, uuids=uuids,
                        config=LoaderConfig(route="high", materialize=True),
                        feed="device", seq_len=64)
    batch, meta = next(stack.feed)
    ...
    stack.close()

The config object decides the shape of the stack:

* a :class:`~repro.core.loader.LoaderConfig` builds the single-host chain
  (clock -> cluster -> pool -> loader, plus an optional feed); the loader's
  own defaulting is reused, so a ``build_stack`` stack is bit-identical to
  the equivalent hand-wired one;
* a :class:`~repro.core.multihost.MultiHostConfig` builds a
  :class:`~repro.core.multihost.MultiHostRun` — N sharded loaders against
  one shared cluster or a federation (``clusters=`` gives a
  ``FederatedCluster`` with per-member routes/rings/RF).

Everything is keyword-only and validated up front: unknown feed kinds,
missing feed parameters, or feed requests that the config cannot serve
(feeds need ``materialize=True``, the image feed ``use_arena=True`` too;
per-host feeds over a ``MultiHostConfig`` are not built here) raise
``ValueError``/``TypeError`` at construction, not deep inside the first
``next_batch``.

Old hand-wiring keeps working — this module only composes public
constructors and adds no behaviour of its own.
"""

from __future__ import annotations

import uuid as _uuid
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

from .kvstore import KVStore
from .loader import CassandraLoader, LoaderConfig
from .multihost import MultiHostConfig, MultiHostRun
from .netsim import Clock

FEED_KINDS = (None, "device", "image")


@dataclass
class Stack:
    """What :func:`build_stack` returns — every layer, individually usable.

    ``loader``/``feed`` are populated for a ``LoaderConfig`` stack, ``run``
    for a ``MultiHostConfig`` stack; the rest are always present (for a
    multi-host stack, ``loaders`` lists every per-host loader and
    ``cluster``/``pool`` refer to host 0's view).
    """

    config: "LoaderConfig | MultiHostConfig"
    clock: Clock
    cluster: object
    pool: object
    loader: Optional[CassandraLoader] = None
    feed: Optional[object] = None
    run: Optional[MultiHostRun] = None
    loaders: List[CassandraLoader] = field(default_factory=list)

    def next_batch(self, timeout: float = 600.0):
        """Single-host convenience passthrough to the loader."""
        if self.loader is None:
            raise RuntimeError("next_batch() is a single-host convenience; "
                               "use stack.run for a MultiHostConfig stack")
        return self.loader.next_batch(timeout=timeout)

    def close(self) -> None:
        for ld in (self.loaders or
                   ([self.loader] if self.loader is not None else [])):
            ld.close()


def _build_feed(kind: str, loader: CassandraLoader, *,
                seq_len: Optional[int],
                image_shape: Optional[Tuple[int, int, int]],
                out_shape: Optional[Tuple[int, int]],
                feed_prefetch: int, step_stats, mean, std, feed_seed: int,
                interpret: bool):
    from repro.data.pipeline import DeviceFeed, ImageFeed
    if kind == "device":
        if seq_len is None:
            raise ValueError("feed='device' needs seq_len=")
        return DeviceFeed(loader, seq_len, prefetch=feed_prefetch,
                          step_stats=step_stats)
    if seq_len is not None:
        raise ValueError("seq_len= only applies to feed='device'")
    if image_shape is None or out_shape is None:
        raise ValueError("feed='image' needs image_shape=(h, w, c) and "
                         "out_shape=(out_h, out_w)")
    if loader.arena is None:
        raise ValueError("feed='image' uploads whole arena slabs — set "
                         "use_arena=True on the LoaderConfig")
    h, w, c = image_shape
    out_h, out_w = out_shape
    return ImageFeed(loader, h, w, c, out_h, out_w, mean=mean, std=std,
                     seed=feed_seed, prefetch=feed_prefetch,
                     step_stats=step_stats, interpret=interpret)


def build_stack(*, store: KVStore, uuids: Sequence[_uuid.UUID],
                config: "LoaderConfig | MultiHostConfig",
                clock: Optional[Clock] = None,
                cluster: Optional[object] = None,
                ingress: Optional[object] = None,
                start: bool = False,
                feed: Optional[str] = None,
                seq_len: Optional[int] = None,
                image_shape: Optional[Tuple[int, int, int]] = None,
                out_shape: Optional[Tuple[int, int]] = None,
                feed_prefetch: int = 2,
                step_stats=None,
                mean=None, std=None, feed_seed: int = 0,
                interpret: bool = False) -> Stack:
    """Assemble the full data stack from one config object.

    Parameters
    ----------
    store, uuids
        The KV store and the sample keys to load (as everywhere else).
    config
        ``LoaderConfig`` for the single-host chain, ``MultiHostConfig`` for
        an N-host run (federated when ``config.clusters`` is set).
    clock, cluster, ingress
        Optional externally-owned pieces for co-located loaders (single-host
        only; multi-host runs own theirs so checkpoints stay self-contained):
        several ``build_stack`` calls sharing one clock + cluster + client
        ``RateResource`` model N GPUs on one machine contending for the NIC.
    start
        Start the prefetchers (``loader.start()`` / ``run.start()``) before
        returning.  Feeds start their loader on first ``next()`` anyway.
    feed
        ``None`` (default), ``"device"`` (token batches; needs ``seq_len``
        and ``config.materialize=True``) or ``"image"`` (uint8 image rows;
        needs ``image_shape``/``out_shape``, ``materialize=True`` and
        ``use_arena=True``).
    feed_prefetch, step_stats, mean, std, feed_seed, interpret
        Passed through to the feed constructor (``interpret=True`` runs the
        image feed's Pallas kernel in the interpreter, for CPU callers).
    """
    if feed not in FEED_KINDS:
        raise ValueError(f"unknown feed kind {feed!r} "
                         f"(choose from {FEED_KINDS})")

    if isinstance(config, MultiHostConfig):
        if feed is not None:
            raise ValueError("per-host feeds over a MultiHostConfig are not "
                             "built here — build the MultiHostRun stack and "
                             "wrap stack.loaders[i] yourself")
        if clock is not None or cluster is not None or ingress is not None:
            raise ValueError("MultiHostRun owns its clock/cluster/ingress; "
                             "clock=/cluster=/ingress= are single-host only")
        run = MultiHostRun(store, list(uuids), config)
        if start:
            run.start()
        host0 = run.loaders[0]
        return Stack(config=config, clock=run.clock, cluster=run.cluster,
                     pool=host0.pool, run=run, loaders=list(run.loaders))

    if not isinstance(config, LoaderConfig):
        raise TypeError(f"config must be a LoaderConfig or MultiHostConfig, "
                        f"got {type(config).__name__}")
    if feed is not None and not config.materialize:
        raise ValueError(f"feed={feed!r} consumes real payload bytes — set "
                         "materialize=True on the LoaderConfig")

    loader = CassandraLoader(store, list(uuids), config, clock=clock,
                             cluster=cluster, ingress=ingress)
    feed_obj = None
    if feed is not None:
        feed_obj = _build_feed(feed, loader, seq_len=seq_len,
                               image_shape=image_shape, out_shape=out_shape,
                               feed_prefetch=feed_prefetch,
                               step_stats=step_stats, mean=mean, std=std,
                               feed_seed=feed_seed, interpret=interpret)
    if start:
        loader.start()
    return Stack(config=config, clock=loader.clock, cluster=loader.cluster,
                 pool=loader.pool, loader=loader, feed=feed_obj,
                 loaders=[loader])


__all__ = ["FEED_KINDS", "Stack", "build_stack"]
