"""Hot-key replication + bandwidth-aware ownership rebalancing.

Property coverage pins the two contracts the runtime-placement layer adds:

* ``FederatedRing.rebalance`` is a pure function of (weights, spare, step) —
  deterministic, total-weight-conserving, ownership stays disjoint and
  complete (every key has exactly one owner, replicas stay inside it);
* replica invalidation never yields a stale read: the version check at
  serve time holds under arbitrary interleavings of promotion, write and
  invalidation, and end-to-end across cluster-outage injection.

Delivery audits use the in-order/low-latency configuration so exact uuid
streams can be asserted; outage tests use hedging + OOO to cover the
failover machinery under realistic conditions (same split as
``tests/test_federation.py``).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (ClusterSpec, KVStore, MultiHostConfig, MultiHostRun,
                        ReplicationConfig, ZipfPlan)
from repro.core.federation import FederatedCluster, FederatedRing
from repro.core.kvstore import DataRow, MetaRow, make_uuid
from repro.core.netsim import RouteProfile, RouteSchedule, VirtualClock
from repro.core.replication import HotKeyTracker, ReplicaCache
from repro.data.datasets import SyntheticImageDataset, ingest

SPECS = (ClusterSpec("onprem", route="local", n_nodes=4,
                     replication_factor=2),
         ClusterSpec("overseas", route="high", n_nodes=4,
                     replication_factor=2))


@pytest.fixture(scope="module")
def store_uuids():
    return _shared_store()


_STORE_CACHE = None


def _shared_store():
    """Fixture-equivalent the @given property tests can call directly."""
    global _STORE_CACHE
    if _STORE_CACHE is None:
        store = KVStore()
        uuids = ingest(store, SyntheticImageDataset(n_samples=6_000, seed=5))
        _STORE_CACHE = (store, uuids)
    return _STORE_CACHE


def _cfg(n_hosts=2, **kw):
    defaults = dict(n_hosts=n_hosts, batch_size=100, prefetch_buffers=4,
                    io_threads=4, hedge_after=1.0, seed=13,
                    placement="replication_aware", clusters=SPECS)
    defaults.update(kw)
    return MultiHostConfig(**defaults)


def _uuids(n, seed=7):
    rng = np.random.default_rng(seed)
    return [make_uuid(rng) for _ in range(n)]


def _meta(w_a, w_b):
    return [{"name": "a", "n_nodes": 4, "ring_seed": 1, "rf": 2,
             "weight": w_a},
            {"name": "b", "n_nodes": 4, "ring_seed": 2, "rf": 2,
             "weight": w_b}]


# ---------------------------------------------------------------------------
# FederatedRing.rebalance: deterministic, conserving, disjoint + complete
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(w_a=st.integers(min_value=1, max_value=8),
       w_b=st.integers(min_value=1, max_value=8),
       spare_a=st.integers(min_value=0, max_value=1000),
       spare_b=st.integers(min_value=0, max_value=1000),
       step_pct=st.integers(min_value=0, max_value=100))
def test_rebalance_deterministic_and_conserving(w_a, w_b, spare_a, spare_b,
                                                step_pct):
    ring = FederatedRing.from_metadata(_meta(w_a, w_b))
    spare = {"a": float(spare_a), "b": float(spare_b)}
    step = step_pct / 100.0
    r1 = ring.rebalance(spare, step=step)
    r2 = ring.rebalance(spare, step=step)
    assert r1.weights == r2.weights                 # pure function
    assert all(w >= 1 for w in r1.weights.values())
    if r1 is not ring:                              # an actual shift
        grain = FederatedRing.REBALANCE_GRAIN
        assert sum(r1.weights.values()) == (w_a + w_b) * grain
    # metadata() -> from_metadata() roundtrips the emitted map exactly
    rebuilt = FederatedRing.from_metadata(r1.metadata())
    assert rebuilt.weights == r1.weights


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       spare_a=st.integers(min_value=0, max_value=1000))
def test_rebalance_ownership_disjoint_complete(seed, spare_a):
    ring = FederatedRing.from_metadata(_meta(2, 2))
    shifted = ring.rebalance({"a": float(spare_a), "b": 0.0}, step=0.5)
    keys = _uuids(300, seed=seed)
    counts = {"a": 0, "b": 0}
    for u in keys:
        owner = shifted.owner_of(u)
        assert owner in ("a", "b")                  # complete
        counts[owner] += 1
        reps = shifted.replicas(u)
        assert reps and all(r.startswith(f"{owner}/") for r in reps)
    if spare_a > 0:
        # all the spare sits on "a": ownership must not shift *away* from it
        base = sum(1 for u in keys if ring.owner_of(u) == "a")
        assert counts["a"] >= base


def test_rebalance_validates_inputs():
    ring = FederatedRing.from_metadata(_meta(1, 1))
    with pytest.raises(ValueError, match="step must be in"):
        ring.rebalance({"a": 1.0}, step=1.5)
    assert ring.rebalance({"a": 0.0, "b": 0.0}, step=0.5) is ring
    assert ring.rebalance({"a": 5.0}, step=0.0) is ring


def test_rebalance_needs_adaptive_flow(store_uuids):
    store, uuids = store_uuids
    run = MultiHostRun(store, uuids, _cfg()).start()
    with pytest.raises(ValueError, match="adaptive"):
        run.rebalance()


# ---------------------------------------------------------------------------
# HotKeyTracker: O(k) memory, windowed hotness
# ---------------------------------------------------------------------------

def test_tracker_space_saving_bound_and_hotness():
    clock = VirtualClock()
    cfg = ReplicationConfig(track_k=8, window=2.0, hot_rate=4.0, min_count=8)
    tr = HotKeyTracker(cfg, clock)
    cold = _uuids(100, seed=1)
    for u in cold:
        tr.record(u)
    assert len(tr) <= 8                     # space-saving memory bound
    hot = cold[0]
    for _ in range(50):
        tr.record(hot)
    assert tr.is_hot(hot)
    assert not tr.is_hot(cold[50])
    # hotness is windowed: once the accesses age out, the key cools off
    clock.schedule(10.0, lambda: None)
    clock.drain()
    tr.record(_uuids(1, seed=2)[0])         # roll the buckets forward
    assert tr.rate(hot) == 0.0
    assert not tr.is_hot(hot)
    # ...but the space-saving count survives (top-k is lifetime state)
    assert dict((str(k), c) for k, c, _ in tr.top(3))[str(hot)] >= 50


def test_tracker_snapshot_roundtrip():
    clock = VirtualClock()
    tr = HotKeyTracker(ReplicationConfig(track_k=4), clock)
    keys = _uuids(3, seed=9)
    for u in keys:
        for _ in range(5):
            tr.record(u)
    tr2 = HotKeyTracker(ReplicationConfig(track_k=4), clock)
    tr2.restore(tr.snapshot())
    assert tr2.snapshot() == tr.snapshot()


# ---------------------------------------------------------------------------
# ReplicaCache: promotion lifecycle, version guard, capacity
# ---------------------------------------------------------------------------

def test_cache_promotion_commit_and_version_guard():
    cache = ReplicaCache(capacity=2)
    k = _uuids(1)[0]
    tok = cache.begin_promotion(k, "onprem", version=0, now=0.0)
    assert tok is not None
    assert cache.serving_cluster(k, 0, now=0.1) is None     # not live yet
    cache.commit_promotion(k, tok)
    assert cache.serving_cluster(k, 0, now=0.2) == "onprem"
    # a write bumped the version: the entry must not serve, and is dropped
    assert cache.serving_cluster(k, 1, now=0.3) is None
    assert cache.stale_blocked == 1
    assert cache.get(k) is None


def test_cache_reservation_token_guards_races():
    cache = ReplicaCache(capacity=4)
    k = _uuids(1)[0]
    t1 = cache.begin_promotion(k, "onprem", version=0, now=0.0)
    cache.invalidate(k)                     # write-through won the race
    t2 = cache.begin_promotion(k, "onprem", version=1, now=0.1)
    cache.commit_promotion(k, t1)           # stale copy lands: must no-op
    assert cache.serving_cluster(k, 1, now=0.2) is None
    cache.commit_promotion(k, t2)
    assert cache.serving_cluster(k, 1, now=0.3) == "onprem"
    cache.release(k, t1)                    # stale abort: must no-op too
    assert cache.serving_cluster(k, 1, now=0.4) == "onprem"


def test_cache_capacity_evicts_coldest_live():
    cache = ReplicaCache(capacity=2)
    a, b, c = _uuids(3, seed=3)
    for key, t in ((a, 0.0), (b, 1.0)):
        cache.commit_promotion(key, cache.begin_promotion(key, "onprem", 0,
                                                          now=t))
    cache.serving_cluster(b, 0, now=2.0)    # b is warm, a is coldest
    assert cache.begin_promotion(c, "onprem", 0, now=3.0) is not None
    assert cache.get(a) is None and cache.get(b) is not None
    assert cache.evictions == 1


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=100_000))
def test_cache_never_serves_stale_version_under_random_ops(seed):
    """Model-checked invariant: whatever the interleaving of promotions,
    writes (version bumps + invalidation) and serves, a serve only ever
    succeeds at the key's current version."""
    rng = np.random.default_rng(seed)
    cache = ReplicaCache(capacity=4)
    keys = _uuids(6, seed=11)
    version = {k: 0 for k in keys}          # the model's source of truth
    pending = {}                            # key -> (token, version at begin)
    for t in range(120):
        k = keys[rng.integers(len(keys))]
        op = rng.integers(4)
        if op == 0:
            tok = cache.begin_promotion(k, "onprem", version[k], now=float(t))
            if tok is not None:
                pending[k] = (tok, version[k])
        elif op == 1 and k in pending:
            cache.commit_promotion(k, pending.pop(k)[0])
        elif op == 2:                       # write-through: bump + invalidate
            version[k] += 1
            cache.invalidate(k)
        else:
            got = cache.serving_cluster(k, version[k], now=float(t))
            if got is not None:
                e = cache.get(k)
                assert e is not None and e.version == version[k]


# ---------------------------------------------------------------------------
# ZipfPlan: the skewed workload class
# ---------------------------------------------------------------------------

def test_zipf_plan_deterministic_and_skewed():
    uuids = _uuids(500, seed=21)
    plan = ZipfPlan(uuids, seed=3, shard_id=0, num_shards=2, s=1.3)
    assert len(plan) == 250                 # uniform strip size
    assert plan.permutation(0) == plan.permutation(0)
    assert plan.permutation(0) != plan.permutation(1)
    # shards draw distinct streams over the SAME rank->key map
    other = ZipfPlan(uuids, seed=3, shard_id=1, num_shards=2, s=1.3)
    assert other.permutation(0) != plan.permutation(0)
    assert other._uuids == plan._uuids
    # skew: the top-ranked key dominates any mid-ranked one
    sample = plan.permutation(0) + plan.permutation(1) + other.permutation(0)
    top = plan._uuids[0]
    mid = plan._uuids[250]
    assert sample.count(top) > 10 * max(sample.count(mid), 1) \
        or sample.count(mid) == 0


def test_zipf_plan_advance_and_overrides():
    plan = ZipfPlan(_uuids(100, seed=2), seed=0, shard_id=0, num_shards=4)
    assert plan.advance(1, 20, 30) == (3, 0)        # 25-sample epochs
    with pytest.raises(ValueError, match="negative cursor"):
        plan.advance(0, -1)
    with pytest.raises(ValueError, match="overrides"):
        plan.install_overrides({0: []})
    assert plan.pending_overrides(0) == {}


def test_zipf_checkpoint_resumes_exactly(store_uuids):
    store, uuids = store_uuids
    fast = dict(out_of_order=False, hedge_after=None, sampling="zipf",
                zipf_s=1.2, placement="cluster_aware")
    a = MultiHostRun(store, uuids, _cfg(**fast)).start()
    a.run(4)
    ck = a.checkpoint()
    tail_a, tail_b = [], []
    a.run(3, on_batch=lambda h, b: tail_a.extend(str(u) for u in b.uuids))
    b = MultiHostRun(store, uuids, _cfg(**fast)).start(ck)
    b.run(3, on_batch=lambda h, b: tail_b.extend(str(u) for u in b.uuids))
    assert tail_a == tail_b                 # bit-identical resume


def test_zipf_elastic_restore_restarts_at_epoch_boundary(store_uuids):
    store, uuids = store_uuids
    fast = dict(out_of_order=False, hedge_after=None, sampling="zipf",
                zipf_s=1.2, placement="cluster_aware")
    a = MultiHostRun(store, uuids, _cfg(n_hosts=2, **fast)).start()
    a.run(4)
    ck = a.checkpoint()
    b = MultiHostRun(store, uuids, _cfg(n_hosts=3, **fast)).start(ck)
    rep = b.run(4)
    assert rep["rounds"] == 4               # all batches delivered on 3 hosts


# ---------------------------------------------------------------------------
# End to end: serving, promotion, reports, checkpoints, outages
# ---------------------------------------------------------------------------

def test_replication_serves_hot_keys_and_reports(store_uuids):
    store, uuids = store_uuids
    run = MultiHostRun(store, uuids, _cfg(sampling="zipf", zipf_s=1.3))
    rep = run.run(10)
    assert rep["replica_hit_frac"] > 0.1
    assert rep["wan_bytes_saved"] > 0
    assert rep["replication"]["promotions"] > 0
    assert rep["replication"]["cached_keys"] > 0
    # promotion has a real WAN cost, visible in the accounting
    assert rep["replication"]["promotion_wan_bytes"] > 0


def test_replication_requires_federation(store_uuids):
    store, uuids = store_uuids
    with pytest.raises(ValueError, match="needs a federation"):
        MultiHostRun(store, uuids,
                     MultiHostConfig(n_hosts=2, placement="replication_aware"))
    with pytest.raises(ValueError, match="needs a federation"):
        MultiHostRun(store, uuids,
                     MultiHostConfig(n_hosts=2,
                                     replication=ReplicationConfig()))


def test_exactly_once_preserved_with_replication_and_outage(store_uuids):
    """Uniform sampling + replica serving: epoch 0 still delivers every
    uuid exactly once while the region cluster (the one holding the
    replicas) goes dark mid-run — replica-served fetches fail over to the
    home cluster under the same once-guard as everything else.  Replicas
    are pre-promoted so the uniform (once-per-epoch) access pattern
    actually serves through the cache from the first round."""
    store, uuids = store_uuids
    subset = uuids[:1200]
    # in-order assembly: batch.epoch labels are exact, so the audit can
    # assert set equality (the once-guard under test is in the pools and
    # identical for both prefetchers)
    run = MultiHostRun(store, subset, _cfg(
        replication=ReplicationConfig(capacity=2000),
        placement="cluster_aware", out_of_order=False))
    fed = run.federation
    promoted = 0
    for u in subset:
        if fed.owner_of(u) == "overseas":
            tok = fed.replication.cache.begin_promotion(
                u, "onprem", fed.version_of(u), now=0.0)
            fed.replication.cache.commit_promotion(u, tok)
            promoted += 1
    assert promoted > 300                   # ~half the keyspace is cached
    run.start()
    delivered = {}

    def on_batch(host_id, batch):
        delivered.setdefault(batch.epoch, []).extend(
            str(u) for u in batch.uuids)

    run.run(1, on_batch=on_batch)
    run.inject_cluster_outage("onprem", after=0.0, recover_after=1.5)
    run.run(5, on_batch=on_batch)           # finishes epoch 0 (6x2x100)
    assert fed.replication.cache.hits > 0   # replica serving participated
    assert len(delivered[0]) == len(set(delivered[0])) == 1200


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(min_value=0, max_value=1_000))
def test_no_stale_read_across_outage_and_writes(seed):
    """The satellite property: replica invalidation never yields a stale
    read, across cluster-outage injection.  Writes bump key versions
    mid-run while the home cluster flaps; at every point any cache entry
    that serves must hold the key's current version."""
    store, uuids = _shared_store()
    run = MultiHostRun(store, uuids, _cfg(
        seed=seed, sampling="zipf", zipf_s=1.3,
        replication=ReplicationConfig(hot_rate=1.0, min_count=2))).start()
    fed = run.federation
    run.run(4)
    run.inject_cluster_outage("overseas", after=0.0, recover_after=2.0)
    # write through every currently-replicated key (and a few cold ones):
    # versions bump, replicas must drop
    targets = fed.replication.cache.keys()[:8] + uuids[:2]
    for u in targets:
        row = store.get_data(u)
        fed.write_through(DataRow(u, row.label, row.size),
                          MetaRow(u, entity_id="w", label=row.label))
        assert fed.replication.cache.get(u) is None
    run.run(4)
    # whatever got (re-)promoted since serves the *current* version
    for u in fed.replication.cache.keys():
        entry = fed.replication.cache.get(u)
        if entry.live:
            assert entry.version == fed.version_of(u)
    rep = run.run(2)
    assert rep["rounds"] == 2               # still delivering after all that


def test_replication_snapshot_rides_elastic_checkpoint(store_uuids):
    store, uuids = store_uuids
    a = MultiHostRun(store, uuids, _cfg(sampling="zipf", zipf_s=1.3))
    a.run(10)
    ck = a.checkpoint()
    assert ck["replication"]["cache"]       # something was promoted
    b = MultiHostRun(store, uuids, _cfg(n_hosts=3, sampling="zipf",
                                        zipf_s=1.3)).start(ck)
    restored = b.federation.replication.cache
    assert sorted(restored.snapshot()) == sorted(ck["replication"]["cache"])
    rep = b.run(4)
    assert rep["replica_hit_frac"] > 0.0    # restored replicas serve at once


def test_rebalanced_ownership_rides_checkpoint(store_uuids):
    store, uuids = store_uuids
    cfg = _cfg(placement="cluster_aware", flow_control="adaptive",
               hedge_after=None)
    a = MultiHostRun(store, uuids, cfg).start()
    a.run(6)
    weights = a.rebalance(step=0.3)
    assert a.federation.routing_ring.weights == weights
    ck = a.checkpoint()
    assert ck["ownership"]
    b = MultiHostRun(store, uuids, cfg).start(ck)
    assert b.federation.routing_ring.weights == weights
    # the declared ring (strip metadata) is untouched by the rebalance
    assert ck["federation"] == b.federation.ring.metadata()
    rep = b.run(2)
    assert rep["ownership_weights"] == weights


# ---------------------------------------------------------------------------
# Everything moving at once: a WAN member whose latency creeps x6 while a
# Zipf hotset rotates, with replication, demotion and cadenced rebalancing
# ---------------------------------------------------------------------------

TRACKING_ROUNDS = 30


@pytest.fixture(scope="module")
def tracking(store_uuids):
    store, uuids = store_uuids
    far = RouteProfile(
        "wan/creep", rtt=0.08, conn_capacity=0.5e9, loss_per_byte=1e-11,
        schedules=(RouteSchedule("latency", "ramp", factor=6.0,
                                 at=1.0, until=5.0),))
    cfg = MultiHostConfig(
        n_hosts=2, batch_size=128, prefetch_buffers=8, io_threads=4,
        ramp_every=1, hedge_after="auto", seed=23,
        placement="replication_aware",
        clusters=(ClusterSpec("near", route="low", n_nodes=4,
                              replication_factor=2, weight=1),
                  ClusterSpec("far", route=far, n_nodes=4,
                              replication_factor=2, weight=2)),
        flow_control="adaptive", route_admission=True,
        sampling="zipf", zipf_s=1.3, zipf_shift_every=2,
        rebalance_every=5,
        replication=ReplicationConfig(window=1.0, demote_after=0.5,
                                      min_count=6))
    # a small key universe cycles Zipf epochs, and so hotset rotations,
    # fast enough that the run sees several
    run = MultiHostRun(store, uuids[:2400], cfg).start()
    return cfg, run.run(TRACKING_ROUNDS, step_time=0.05)


def test_tracking_hotset_shift_demotes_replicas(tracking):
    _, rep = tracking
    assert rep["replication"]["demotions"] >= 1


def test_tracking_rebalance_fires_on_cadence(tracking):
    cfg, rep = tracking
    assert rep["rebalances"] == TRACKING_ROUNDS // cfg.rebalance_every


def test_tracking_replica_cache_serves_hits(tracking):
    _, rep = tracking
    assert rep["replica_hit_frac"] > 0.0


def test_rebalance_shifts_weight_toward_spare_member(store_uuids):
    """The keyspace is declared WAN-heavy (overseas weight 3); the local
    member's controllers measure spare BDP, so a rebalance moves serving
    weight toward it, and the WAN then carries a smaller share of bytes."""
    store, uuids = store_uuids
    specs = (ClusterSpec("onprem", route="local", n_nodes=6,
                         replication_factor=2, weight=1,
                         node_egress_bandwidth=1.25e9),
             ClusterSpec("overseas", route="high", n_nodes=4,
                         replication_factor=2, weight=3,
                         node_egress_bandwidth=1.25e9))
    run = MultiHostRun(store, uuids, MultiHostConfig(
        n_hosts=4, batch_size=256, prefetch_buffers=24, io_threads=8,
        ramp_every=1, hedge_after=1.0, seed=19, placement="cluster_aware",
        clusters=specs, flow_control="adaptive")).start()
    before = run.run(8)
    w0 = before["ownership_weights"]
    w1 = run.rebalance(step=0.3)
    after = run.run(8)

    def local_share(w):
        return w["onprem"] / sum(w.values())

    assert local_share(w1) > local_share(w0)
    assert after["wan_bytes_share"] < before["wan_bytes_share"]
