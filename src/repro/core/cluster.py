"""Cluster topology: consistent-hash token ring, replication, routing.

Models the server side of a Cassandra/ScyllaDB deployment: each node owns
token ranges (with virtual nodes for balance), rows are replicated RF-ways,
and a token-aware client can route any request directly to a replica —
the property the paper's driver relies on for low latency.
"""

from __future__ import annotations

import bisect
import uuid as _uuid
from typing import Dict, List, Optional

import numpy as np

from .kvstore import KVStore, token_of
from .netsim import (BACKENDS, DISK_BANDWIDTH, NIC_BANDWIDTH, BackendModel,
                     Clock, RouteProfile, SimServerNode, TIERS)


class TokenRing:
    """Consistent-hash ring with virtual nodes."""

    def __init__(self, node_names: List[str], vnodes: int = 64, seed: int = 7) -> None:
        rng = np.random.default_rng(seed)
        self._points: List[int] = []
        self._owners: List[str] = []
        entries = []
        for name in node_names:
            for tok in rng.integers(0, 2 ** 64, size=vnodes, dtype=np.uint64):
                entries.append((int(tok), name))
        entries.sort()
        self._points = [e[0] for e in entries]
        self._owners = [e[1] for e in entries]
        self._names = list(node_names)

    def replicas_for_token(self, token: int, rf: int) -> List[str]:
        """Walk the ring clockwise collecting rf distinct owners."""
        if not self._points:
            return []
        idx = bisect.bisect_right(self._points, token) % len(self._points)
        out: List[str] = []
        i = idx
        while len(out) < min(rf, len(self._names)):
            owner = self._owners[i % len(self._points)]
            if owner not in out:
                out.append(owner)
            i += 1
        return out

    def replicas(self, key: _uuid.UUID, rf: int) -> List[str]:
        return self.replicas_for_token(token_of(key), rf)


class Cluster:
    """A set of simulated storage nodes fronted by a token ring.

    The *store* (logical contents) is shared; per-node simulation state
    (disk, egress, GC) is separate, so routing decisions have performance
    consequences just as they do against a real cluster.
    """

    def __init__(self, clock: Clock, store: KVStore, backend: str = "scylla",
                 n_nodes: int = 1, rf: int = 1, seed: int = 1234,
                 disk_bandwidth: float = DISK_BANDWIDTH,
                 egress_bandwidth: float = NIC_BANDWIDTH,
                 node_prefix: str = "", cpu_cores: int = 0) -> None:
        if isinstance(backend, str):
            backend_model = BACKENDS[backend]
        else:
            backend_model = backend
        self.clock = clock
        self.store = store
        self.backend = backend_model
        self.rf = min(rf, n_nodes)
        self.ring_seed = seed     # recorded so checkpoints can rebuild the ring
        # A federation member qualifies its node names ("eu/node0") so the
        # merged node namespace stays collision-free across clusters.
        self.node_prefix = node_prefix
        names = [f"{node_prefix}node{i}" for i in range(n_nodes)]
        self.nodes: Dict[str, SimServerNode] = {
            name: SimServerNode(name, backend_model,
                                np.random.default_rng(seed + 17 * i),
                                disk_bandwidth=disk_bandwidth,
                                egress_bandwidth=egress_bandwidth,
                                cpu_cores=cpu_cores)
            for i, name in enumerate(names)
        }
        self.ring = TokenRing(names, seed=seed)

    def replica_nodes(self, key: _uuid.UUID) -> List[SimServerNode]:
        return [self.nodes[n] for n in self.ring.replicas(key, self.rf)]

    def total_disk_bytes(self) -> int:
        return sum(n.disk_bytes for n in self.nodes.values())

    def node_names(self) -> List[str]:
        return list(self.nodes.keys())

    # -- failure injection --------------------------------------------------
    def fail_node(self, name: str) -> None:
        """Take a node dark immediately (see schedule_failure for mid-run)."""
        self.nodes[name].fail()

    def recover_node(self, name: str) -> None:
        self.nodes[name].recover()

    def schedule_failure(self, name: str, after: float,
                         recover_after: Optional[float] = None) -> None:
        """Node ``name`` goes dark ``after`` seconds from now; optionally
        comes back ``recover_after`` seconds later."""
        node = self.nodes[name]
        self.clock.schedule(after, node.fail)
        if recover_after is not None:
            self.clock.schedule(after + recover_after, node.recover)

    def alive_nodes(self) -> List[str]:
        return [n for n, node in self.nodes.items() if not node.down]

    # -- load reporting -----------------------------------------------------
    def load_report(self) -> Dict[str, Dict[str, float]]:
        """Per-node served-load snapshot (replica-aware routing makes these
        diverge under contention; ``MultiHostRun``'s report carries them).
        ``egress_share`` is each node's fraction of total cluster egress —
        the imbalance signal the placement policies compete on."""
        now = self.clock.now()
        total_egress = sum(n.egress_bytes for n in self.nodes.values())
        report: Dict[str, Dict[str, float]] = {}
        for name, node in self.nodes.items():
            report[name] = {
                "requests": node.requests_served,
                "egress_bytes": node.egress_bytes,
                "egress_share": node.egress_bytes / max(total_egress, 1),
                "disk_bytes": node.disk_bytes,
                "egress_busy_frac": (node.egress.fifo.busy_seconds
                                     / max(now, 1e-9)),
                # Single-core seconds spent encoding wire-codec frames
                # (zero without a codec) — the CPU the node trades for
                # wire bandwidth.
                "encode_cpu_s": node.encode_cpu_seconds,
                "down": float(node.down),
            }
        return report


__all__ = ["TokenRing", "Cluster"]
