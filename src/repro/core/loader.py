"""Top-level loader — the ``fn.crs4.cassandra(...)`` analogue (Listing 3).

One object wires together: a cluster (or a handle to a shared one), the
client connection pool, the epoch plan, and a prefetching strategy.  It is
the single public entry point used by the data pipeline, ``build_stack`` and
the examples.
"""

from __future__ import annotations

import uuid as _uuid
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

from .arena import PinnedArena
from .batch_loader import BatchAssembler
from .cluster import Cluster
from .connection import ConnectionPool
from .flowctl import FlowControlConfig
from .kvstore import KVStore
from .netsim import Clock, RealClock, VirtualClock
from .prefetcher import EpochPlan, PrefetchConfig, make_prefetcher


@dataclass
class LoaderConfig:
    """Mirrors the plugin arguments of the paper's Listing 3 (+ sim knobs)."""

    batch_size: int = 512
    prefetch_buffers: int = 8
    io_threads: int = 8
    conns_per_thread: int = 2
    out_of_order: bool = True
    incremental_ramp: bool = True
    ramp_every: int = 4
    # route tier name (local | low | med | high) or a RouteProfile — e.g. a
    # schedule-carrying dynamic route from core/scenarios.py
    route: "str | object" = "high"
    backend: str = "scylla"         # scylla | cassandra
    n_nodes: int = 1
    replication_factor: int = 1
    # seconds, None, or "auto" (delay = controller min-RTT x
    # hedge_rtt_multiple; needs flow_control="adaptive")
    hedge_after: "Optional[float | str]" = None
    seed: int = 0
    shard_id: int = 0               # per-host / per-GPU shard of the UUID list
    num_shards: int = 1
    materialize: bool = False       # deliver real payload bytes
    virtual_clock: bool = True
    # Token-aware placement: bias routing toward these storage nodes (the
    # subset this host's shard keys were replica-skewed toward).  None keeps
    # the unbiased least-loaded-replica routing.
    preferred_nodes: Optional[Tuple[str, ...]] = None
    # "static" keeps the paper's fixed prefetch depth (default, bit-identical
    # to pre-flow-control behaviour); "adaptive" wires a BDP-tracking
    # FlowController (core/flowctl.py) between the pool and the prefetcher.
    flow_control: str = "static"
    flow: Optional[FlowControlConfig] = None
    # Per-key route admission in the prefetcher (see PrefetchConfig):
    # defer keys whose serving route is at its measured budget.
    route_admission: bool = False
    # Wire codec (core/wirefmt.py): rows travel encoded — the node pays
    # encode CPU, the wire carries fewer bytes, the client pays decode CPU.
    # "none" (default) is bit-identical to the pre-codec loader.
    wire_codec: str = "none"
    # Controller-driven issue parallelism (needs flow_control="adaptive"):
    # routing concentrates on a budget-sized active prefix of connections.
    io_scaling: bool = False
    # Pinned-arena batch assembly (materialize mode): decoded rows land in
    # reused contiguous slabs (core/arena.py) instead of per-sample bytes +
    # a fresh buffer per batch; the device feed uploads whole slabs.
    use_arena: bool = False
    arena_slot_bytes: Optional[int] = None   # None = max row size in shard


class CassandraLoader:
    """Iterable over AssembledBatch with checkpointable position."""

    def __init__(self, store: KVStore, uuids: List[_uuid.UUID],
                 cfg: LoaderConfig, clock: Optional[Clock] = None,
                 cluster: Optional[Cluster] = None,
                 plan: Optional[EpochPlan] = None,
                 pool=None, ingress=None, flow_limiter=None) -> None:
        self.cfg = cfg
        self.clock = clock or (VirtualClock() if cfg.virtual_clock else RealClock())
        self.cluster = cluster or Cluster(
            self.clock, store, backend=cfg.backend, n_nodes=cfg.n_nodes,
            rf=cfg.replication_factor, seed=cfg.seed + 5)
        # Pool randomness is decorrelated per shard (each host sees its own
        # network weather); the *plan* seed must stay shared across shards so
        # every host computes the same global shuffle.  An externally-built
        # pool (e.g. a FederatedConnectionPool spanning several clusters,
        # each with its own route) replaces the single-route default.
        # ``ingress`` shares one client NIC across co-located loaders
        # (multi-host shared_client_ingress); None keeps a private NIC.
        self.pool = pool or ConnectionPool(
            self.clock, self.cluster, cfg.route,
            io_threads=cfg.io_threads, conns_per_thread=cfg.conns_per_thread,
            seed=cfg.seed + 11 + 7919 * cfg.shard_id,
            hedge_after=cfg.hedge_after,
            materialize=cfg.materialize,
            preferred_nodes=cfg.preferred_nodes,
            ingress=ingress,
            wire_codec=cfg.wire_codec,
            io_scaling=cfg.io_scaling)
        # An externally-built plan (placement policies, elastic reflow)
        # overrides the default contiguous-strip sharding.
        self.plan = plan or EpochPlan(uuids, seed=cfg.seed,
                                      shard_id=cfg.shard_id,
                                      num_shards=cfg.num_shards)
        pcfg = PrefetchConfig(batch_size=cfg.batch_size,
                              num_buffers=cfg.prefetch_buffers,
                              out_of_order=cfg.out_of_order,
                              incremental_ramp=cfg.incremental_ramp,
                              ramp_every=cfg.ramp_every,
                              flow_control=cfg.flow_control,
                              flow=cfg.flow,
                              route_admission=cfg.route_admission)
        # Adaptive flow control: the pool measures (RTT + delivery rate per
        # completion), the controller budgets, the prefetcher obeys.  A pool
        # that already carries a controller (MultiHostRun's shared-ingress
        # fairness cap attaches one before building the loader) is reused.
        self.flow_controller = None
        if cfg.flow_control == "adaptive":
            self.flow_controller = (
                self.pool.controller
                or self.pool.attach_flow_control(cfg.flow or FlowControlConfig(),
                                                 cfg.batch_size,
                                                 limiter=flow_limiter))
        # Pinned-arena assembly: real copies land in reused contiguous slabs
        # sized for the largest row this shard can see; the device feed
        # uploads whole slabs (see data/pipeline.ImageFeed).
        self.arena = None
        assembler = None
        if cfg.use_arena and cfg.materialize:
            slot = cfg.arena_slot_bytes or max(
                (store.get_data(u).size for u in uuids), default=1)
            self.arena = PinnedArena(cfg.batch_size, slot, initial_slabs=2)
            assembler = BatchAssembler(self.clock, real_copy=True,
                                       arena=self.arena)
        self.prefetcher = make_prefetcher(self.clock, self.pool, self.plan, pcfg,
                                          real_copy=cfg.materialize,
                                          controller=self.flow_controller,
                                          assembler=assembler)

    # -- iteration ---------------------------------------------------------
    def start(self, epoch: int = 0, cursor: int = 0) -> "CassandraLoader":
        self.prefetcher.start(epoch, cursor)
        return self

    def next_batch(self, timeout: float = 600.0):
        return self.prefetcher.next_batch(timeout=timeout)

    def __iter__(self):
        while True:
            yield self.next_batch()

    @property
    def started(self) -> bool:
        """True once the prefetcher is running (public — consumers such as
        ``DeviceFeed`` must not reach into ``prefetcher._started``)."""
        return self.prefetcher.started

    @property
    def ready_batches(self) -> int:
        """Assembled batches ``next_batch`` would return without blocking."""
        return self.prefetcher.ready_batches

    # -- checkpointing ------------------------------------------------------
    def state(self, rewind_batches: int = 0) -> dict:
        """Checkpointable position; ``rewind_batches`` backs off batches a
        downstream buffer already pulled but the consumer never saw."""
        return self.prefetcher.state(rewind_batches=rewind_batches)

    def flow_snapshot(self) -> Optional[dict]:
        """Flow-controller state to ride a checkpoint (None in static mode) —
        a restore passes it back through :meth:`restore_flow` so adaptive
        runs resume at the measured operating point instead of
        re-slow-starting."""
        if self.flow_controller is None:
            return None
        return self.flow_controller.snapshot()

    def restore_flow(self, state: Optional[dict]) -> None:
        """Re-seed the flow controller from a checkpoint snapshot (no-op in
        static mode or when the checkpoint predates flow control)."""
        if self.flow_controller is not None and state:
            self.flow_controller.restore(state)

    @property
    def stats(self):
        return self.prefetcher.stats

    def batches_per_epoch(self) -> int:
        return len(self.plan) // self.cfg.batch_size

    def close(self) -> None:
        if isinstance(self.clock, RealClock):
            self.clock.close()


def tight_loop(loader: CassandraLoader, n_batches: int,
               timeout: float = 600.0) -> dict:
    """Paper Sec. 4.2.1: consume as fast as possible, no decode/GPU work."""
    loader.start()
    for _ in range(n_batches):
        loader.next_batch(timeout=timeout)
    st = loader.stats
    skip = max(0, min(2, n_batches - 2))   # short runs: never a negative slice
    return {
        "throughput_Bps": st.throughput(skip=skip),
        "batches": n_batches,
        "batch_times": st.batch_times(skip=1),
        "disk_bytes": loader.cluster.total_disk_bytes(),
        "net_bytes": loader.pool.bytes_received,          # wire (encoded)
        "payload_bytes": loader.pool.payload_bytes_received,
    }


__all__ = ["LoaderConfig", "CassandraLoader", "tight_loop"]
