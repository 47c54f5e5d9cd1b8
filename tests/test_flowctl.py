"""Adaptive flow control: config validation, the static ramp's transient
bound (paper Sec. 3.4), BDP convergence, fairness, and checkpoint re-seeding.
"""

import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CassandraLoader, Cluster, ConnectionPool,
                        FlowControlConfig, FlowController, KVStore,
                        LoaderConfig, MultiHostConfig, MultiHostRun,
                        merge_snapshots)
from repro.core.flowctl import FlowControllerGroup
from repro.core.netsim import RouteProfile, VirtualClock, route_bdp_samples
from repro.core.prefetcher import EpochPlan, PrefetchConfig, make_prefetcher
from repro.core.stats import windowed_series
from repro.data.datasets import SyntheticImageDataset, ingest

SAMPLE_BYTES = 115_621          # SyntheticImageDataset mean row size


@pytest.fixture(scope="module")
def store_uuids():
    return _shared_store()


_STORE_CACHE = None


def _shared_store():
    """Fixture-equivalent the @given property tests can call directly (the
    hypothesis shim's wrappers take no named params, so pytest cannot inject
    fixtures into them)."""
    global _STORE_CACHE
    if _STORE_CACHE is None:
        store = KVStore()
        uuids = ingest(store, SyntheticImageDataset(n_samples=30_000,
                                                    seed=11))
        _STORE_CACHE = (store, uuids)
    return _STORE_CACHE


# ---------------------------------------------------------------------------
# Shared windowed-throughput helper (the dedup target)
# ---------------------------------------------------------------------------

def test_windowed_series_buckets_and_gaps():
    events = [(0.1, 10.0), (0.4, 20.0), (1.6, 40.0)]
    out = windowed_series(events, window=0.5)
    # bucket 0: 30 units / 0.5 s; bucket [0.5, 1.5): empty; bucket 3: 40
    assert out == [(0.0, 60.0), (0.5, 0.0), (1.0, 0.0), (1.5, 80.0)]


def test_windowed_series_empty_and_bad_window():
    assert windowed_series([], window=0.5) == []
    with pytest.raises(ValueError, match="window must be positive"):
        windowed_series([(0.0, 1.0)], window=0.0)


def test_loader_and_connection_series_share_the_helper():
    """The three former copies now all route through windowed_series."""
    from repro.core.netsim import SimConnection
    from repro.core.stats import LoaderStats
    import inspect
    for obj in (SimConnection.throughput_series,
                LoaderStats.throughput_windows):
        assert "windowed_series" in inspect.getsource(obj)


# ---------------------------------------------------------------------------
# Config validation (fail at construction, not deep in the loop)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,msg", [
    (dict(num_buffers=0), r"num_buffers must be >= 1, got 0"),
    (dict(num_buffers=-3), r"num_buffers must be >= 1, got -3"),
    (dict(ramp_every=0), r"ramp_every must be >= 1, got 0"),
    (dict(batch_size=0), r"batch_size must be >= 1, got 0"),
    (dict(flow_control="auto"), r"unknown flow_control mode 'auto'"),
])
def test_prefetch_config_validates_on_construction(kw, msg):
    with pytest.raises(ValueError, match=msg):
        PrefetchConfig(**kw)


@pytest.mark.parametrize("kw,msg", [
    (dict(floor_batches=0), r"floor_batches must be >= 1"),
    (dict(ceiling_batches=2, floor_batches=4),
     r"ceiling_batches \(2\) must be >= floor_batches \(4\)"),
    (dict(gain=0.0), r"gain must be positive"),
    (dict(beta=1.0), r"beta must be in \(0, 1\)"),
    (dict(rtt_inflation=1.0), r"rtt_inflation must be > 1"),
    (dict(rate_window=0.0), r"rate_window and rtt_window must be positive"),
    (dict(rate_buckets=1), r"rate_buckets must be >= 2"),
])
def test_flow_config_validates_on_construction(kw, msg):
    with pytest.raises(ValueError, match=msg):
        FlowControlConfig(**kw)


def test_loader_config_surfaces_prefetch_validation(store_uuids):
    store, uuids = store_uuids
    with pytest.raises(ValueError, match="num_buffers must be >= 1"):
        CassandraLoader(store, uuids[:100],
                        LoaderConfig(prefetch_buffers=0, route="low"))


# ---------------------------------------------------------------------------
# Static ramp: the paper's +1/ramp_every transient bound (Sec. 3.4)
# ---------------------------------------------------------------------------

def test_static_ramp_transient_bounded(store_uuids):
    """The static ramp's burst above steady state is never more than one
    extra batch per ``ramp_every`` consumed: depth == min(k, 1 + c//r), one
    batch of requests at t=0, and per-consume request bursts of at most 2B
    (1B replacement + 1B ramp step)."""
    store, uuids = store_uuids
    B, k, r = 64, 8, 4
    cfg = LoaderConfig(batch_size=B, prefetch_buffers=k, ramp_every=r,
                       io_threads=4, route="low", seed=7,
                       incremental_ramp=True)
    ld = CassandraLoader(store, uuids[:8000], cfg)
    ld.start()
    assert ld.prefetcher._target_depth() == 1
    assert ld.pool.requests_sent == B          # one batch at t=0, not k
    prev_depth, prev_sent = 1, ld.pool.requests_sent
    for c in range(1, 4 * r * k):
        ld.next_batch()
        depth = ld.prefetcher._target_depth()
        assert depth == min(k, 1 + c // r)     # the exact ramp law
        assert depth - prev_depth <= 1         # never jumps
        burst = ld.pool.requests_sent - prev_sent
        assert burst <= 2 * B                  # replacement + one ramp step
        if depth == prev_depth and depth == k:
            assert burst <= B                  # steady state: replacement only
        prev_depth, prev_sent = depth, ld.pool.requests_sent


# ---------------------------------------------------------------------------
# BDP convergence (property): arbitrary latency/bandwidth pairs
# ---------------------------------------------------------------------------

def _adaptive_prefetcher(store, uuids, profile, B, flow, seed=7):
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=1, rf=1,
                      seed=seed)
    pool = ConnectionPool(clock, cluster, profile, io_threads=2, seed=seed)
    ctl = pool.attach_flow_control(flow, B)
    plan = EpochPlan(list(uuids), seed=3)
    pf = make_prefetcher(clock, pool, plan,
                         PrefetchConfig(batch_size=B, flow_control="adaptive",
                                        flow=flow))
    pf.controller = ctl
    return pf, ctl


@given(rtt_ms=st.integers(1, 300), conn_mbps=st.integers(20, 500))
@settings(max_examples=10, deadline=None)
def test_budget_converges_to_route_bdp(rtt_ms, conn_mbps):
    """For arbitrary (latency, bandwidth) routes the steady-state budget
    lands within 2x of the true route BDP (clamped to floor/ceiling) and
    never exceeds the configured ceiling."""
    store, uuids = _shared_store()
    B = 64
    flow = FlowControlConfig(floor_batches=1, ceiling_batches=64)
    profile = RouteProfile(f"p{rtt_ms}_{conn_mbps}", rtt=rtt_ms / 1e3,
                           conn_capacity=conn_mbps * 1e6, loss_per_byte=0.0,
                           jitter=0.02)
    pf, ctl = _adaptive_prefetcher(store, uuids[:20_000], profile, B, flow)
    for _ in range(100):
        pf.next_batch(timeout=5000.0)
    # the analytic yardstick (io_threads=2 -> 4 connections)
    bdp = route_bdp_samples(profile, 4, SAMPLE_BYTES)
    expected = min(max(bdp, flow.floor_batches * B),
                   flow.ceiling_batches * B)
    budget = ctl.operating_budget()
    assert budget <= flow.ceiling_batches * B               # hard ceiling
    assert max(b for _, b in ctl.budget_trace) <= flow.ceiling_batches * B
    assert expected / 2 <= budget <= 2 * expected


# ---------------------------------------------------------------------------
# The headline invariants of adaptive flow control: it matches the best
# static depth on the WAN route and does not over-buffer a local one
# ---------------------------------------------------------------------------

def _tput(store, uuids, route, mode, k, n_batches=70, B=256):
    cfg = LoaderConfig(batch_size=B, prefetch_buffers=k, io_threads=8,
                       route=route, seed=2, flow_control=mode)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    for _ in range(n_batches):
        ld.next_batch(timeout=3000.0)
    return ld.stats.throughput(skip=15), ld.flow_controller


def test_adaptive_matches_best_static_on_wan_route(store_uuids):
    """On the simulated 150 ms route the controller reaches >= 90% of the
    best static num_buffers from a sweep — with zero tuning."""
    store, uuids = store_uuids
    static = {k: _tput(store, uuids, "high", "static", k)[0]
              for k in (2, 8, 16, 32)}
    adaptive, ctl = _tput(store, uuids, "high", "adaptive", 8)
    best = max(static.values())
    assert adaptive >= 0.9 * best
    # ...while the shallow static depths are far off the mark (the knob the
    # controller removes really was load-bearing)
    assert static[2] < 0.5 * best


def test_adaptive_does_not_overbuffer_local_route(store_uuids):
    """On the ~0.05 ms local route the steady-state budget stays within 2x
    of the route's true BDP (in batches, floored at the one-batch minimum
    the assembler needs) instead of the static default's 8-16 buffers."""
    store, uuids = store_uuids
    B = 256
    adaptive, ctl = _tput(store, uuids, "local", "adaptive", 8, B=B)
    # the analytic yardstick (io_threads=8 -> 16 connections, NIC-bound)
    bdp_batches = max(1, math.ceil(route_bdp_samples("local", 16,
                                                     SAMPLE_BYTES) / B))
    assert ctl.depth() <= 2 * bdp_batches
    # and the shallow budget still delivers (>= 80% of an eager static-16)
    static, _ = _tput(store, uuids, "local", "static", 16, B=B)
    assert adaptive >= 0.8 * static


# ---------------------------------------------------------------------------
# Checkpoint: controller state rides the multi-host checkpoint
# ---------------------------------------------------------------------------

def _mh_cfg(n_hosts, **kw):
    defaults = dict(n_hosts=n_hosts, batch_size=128, io_threads=4,
                    route="med", n_nodes=4, replication_factor=2,
                    hedge_after=None, seed=9, flow_control="adaptive")
    defaults.update(kw)
    return MultiHostConfig(**defaults)


def test_flow_state_roundtrips_same_n(store_uuids):
    store, uuids = store_uuids
    cfg = _mh_cfg(2)
    run = MultiHostRun(store, uuids[:8000], cfg).start()
    rep = run.run(8)
    assert [f["depth_batches"] for f in rep["flow"]]      # reported
    ck = run.checkpoint()
    budgets = [ld.flow_controller.operating_budget()
               for ld in run.loaders]
    assert all("flow" in s for s in ck["shards"])
    assert all(s["flow"]["min_rtt"] > 0 for s in ck["shards"])

    res = MultiHostRun(store, uuids[:8000], cfg).start(ck)
    restored = [ld.flow_controller.operating_budget()
                for ld in res.loaders]
    assert restored == budgets                  # exact re-seed, no slow start
    res.run(2)                                  # and it keeps loading


def test_flow_state_reseeds_across_elastic_resize(store_uuids):
    """N -> M restore conserves the cluster-wide in-flight total: the N
    budgets merge and split M ways, so no host re-slow-starts from the
    floor against a warm cluster."""
    store, uuids = store_uuids
    run = MultiHostRun(store, uuids[:8000], _mh_cfg(2)).start()
    run.run(8)
    ck = run.checkpoint()
    old = [ld.flow_controller.operating_budget() for ld in run.loaders]
    floor = run.loaders[0].flow_controller.cfg.floor_batches * 128

    run3 = MultiHostRun(store, uuids[:8000], _mh_cfg(3)).start(ck)
    new = [ld.flow_controller.operating_budget() for ld in run3.loaders]
    assert len(set(new)) == 1                   # all seeded from one merge
    assert new[0] > floor                       # not re-slow-starting
    assert abs(sum(new) - sum(old)) <= 3 * 128  # total conserved (+-rounding)
    run3.run(2)


def test_cross_shape_restore_federated_to_plain(store_uuids):
    """A federated checkpoint restored onto a non-federated adaptive run
    collapses the member snapshots (budgets sum, min-RTT mins) instead of
    silently re-starting from the floor."""
    from repro.core import ClusterSpec
    store, uuids = store_uuids
    fed = MultiHostConfig(
        n_hosts=2, batch_size=128, io_threads=4, hedge_after=None, seed=9,
        flow_control="adaptive", placement="cluster_aware",
        clusters=(ClusterSpec("near", route="local", n_nodes=2),
                  ClusterSpec("far", route="high", n_nodes=2)))
    run = MultiHostRun(store, uuids[:8000], fed).start()
    run.run(8)
    ck = run.checkpoint()
    assert "members" in ck["shards"][0]["flow"]

    plain = MultiHostRun(store, uuids[:8000], _mh_cfg(2)).start(ck)
    floor = plain.loaders[0].flow_controller.cfg.floor_batches * 128
    for ld in plain.loaders:
        assert ld.flow_controller.operating_budget() > floor
        assert ld.flow_controller.min_rtt() is not None
    plain.run(2)


def test_cross_shape_restore_plain_to_federated(store_uuids):
    """A single-cluster checkpoint restored onto a federated adaptive run
    splits the budget across the member controllers."""
    from repro.core import ClusterSpec
    store, uuids = store_uuids
    run = MultiHostRun(store, uuids[:8000], _mh_cfg(2)).start()
    run.run(8)
    ck = run.checkpoint()
    total = sum(ld.flow_controller.operating_budget() for ld in run.loaders)

    fed = MultiHostConfig(
        n_hosts=2, batch_size=128, io_threads=4, hedge_after=None, seed=9,
        flow_control="adaptive", placement="cluster_aware",
        clusters=(ClusterSpec("near", route="local", n_nodes=2),
                  ClusterSpec("far", route="high", n_nodes=2)))
    frun = MultiHostRun(store, uuids[:8000], fed).start(ck)
    seeded = sum(ctl.operating_budget()
                 for ld in frun.loaders
                 for ctl in ld.flow_controller.members.values())
    # extensive quantities split across members; floors may round up
    assert seeded >= total * 0.5
    frun.run(2)


def test_retry_counters_are_per_window(store_uuids):
    """failovers / cluster_failovers report the run() window's delta, so a
    recovered outage stops showing up in later windows (matches the
    window-delta egress accounting)."""
    from repro.core import ClusterSpec
    store, uuids = store_uuids
    cfg = MultiHostConfig(
        n_hosts=2, batch_size=100, io_threads=4, hedge_after=1.0, seed=9,
        out_of_order=False, placement="cluster_aware",
        clusters=(ClusterSpec("us", route="low", n_nodes=2),
                  ClusterSpec("eu", route="med", n_nodes=2)))
    run = MultiHostRun(store, uuids[:4000], cfg).start()
    run.run(1)
    run.inject_cluster_outage("eu", after=0.0, recover_after=3.0)
    dark = run.run(4)
    assert dark["cluster_failovers"] > 0
    run.clock.sleep(4.0)                        # let eu recover
    warm = run.run(4)
    assert warm["cluster_failovers"] == 0       # window delta, not cumulative
    assert warm["failovers"] <= dark["failovers"]


def test_merge_snapshots_handles_federation_members():
    merged = merge_snapshots(
        [{"members": {"a": {"budget": 600.0, "probe_cap": 600.0,
                            "min_rtt": 0.1, "rate": 100.0,
                            "avg_bytes": 1e5}},
          },
         {"members": {"a": {"budget": 300.0, "probe_cap": 300.0,
                            "min_rtt": 0.2, "rate": 50.0,
                            "avg_bytes": 1e5}}}], new_count=3)
    a = merged["members"]["a"]
    assert a["budget"] == pytest.approx(450.0 * 2 / 3)
    assert a["min_rtt"] == pytest.approx(0.1)   # min over shards


def test_static_checkpoint_has_no_flow_state(store_uuids):
    """Static mode stays bit-identical to pre-flow-control checkpoints, and
    an adaptive run restores a static (flow-less) checkpoint gracefully."""
    store, uuids = store_uuids
    cfg = _mh_cfg(2, flow_control="static")
    run = MultiHostRun(store, uuids[:8000], cfg).start()
    run.run(2)
    ck = run.checkpoint()
    assert all("flow" not in s for s in ck["shards"])
    assert all(ld.flow_controller is None for ld in run.loaders)

    adaptive = MultiHostRun(store, uuids[:8000], _mh_cfg(2)).start(ck)
    adaptive.run(2)                             # fresh slow start, no crash


# ---------------------------------------------------------------------------
# Federation: one controller per member; shared ingress: fairness cap
# ---------------------------------------------------------------------------

def test_federation_wan_member_ramps_deep_local_stays_shallow(store_uuids):
    from repro.core import ClusterSpec
    store, uuids = store_uuids
    cfg = MultiHostConfig(
        n_hosts=1, batch_size=128, io_threads=4, hedge_after=None, seed=9,
        flow_control="adaptive", placement="cluster_aware",
        clusters=(ClusterSpec("near", route="local", n_nodes=2),
                  ClusterSpec("far", route="high", n_nodes=2)))
    run = MultiHostRun(store, uuids[:20_000], cfg).start()
    rep = run.run(60)
    members = rep["flow"][0]["members"]
    assert isinstance(run.loaders[0].flow_controller, FlowControllerGroup)
    # the 150 ms member needs a deep window; the local member must not copy it
    assert members["far"]["budget_samples"] > 4 * members["near"]["budget_samples"]
    assert members["near"]["depth_batches"] <= 2
    assert members["far"]["min_rtt_s"] > 0.1 > members["near"]["min_rtt_s"]


def test_shared_ingress_fairness_cap(store_uuids):
    """N adaptive hosts behind ONE client NIC converge to ~1/N shares: the
    limiter caps every budget at its fair-share BDP of the shared link."""
    store, uuids = store_uuids
    cfg = _mh_cfg(2, shared_client_ingress=True,
                  client_ingress_bandwidth=2e9, node_egress_bandwidth=6.25e9)
    run = MultiHostRun(store, uuids[:20_000], cfg).start()
    rep = run.run(20)
    assert run.limiter is not None
    assert rep["fairness"] > 0.8                # ~1/N shares
    budgets = [f["budget_samples"] for f in rep["flow"]]
    # every budget obeys the fair-share cap (gain x (bw/N) x min_rtt)
    for ld, b in zip(run.loaders, budgets):
        cap = run.limiter.fair_cap_samples(ld.flow_controller)
        floor = ld.flow_controller.cfg.floor_batches * 128
        assert b <= max(cap, floor) + 1


def test_shared_ingress_drained_host_share_redistributed(store_uuids):
    """Work conservation on the shared NIC: when one host stops pulling
    (drained / blocked on compute), it drops out of the active set after
    ``activity_window`` and the remaining host's fair cap grows to the full
    NIC instead of half — the equal-split blind spot this fixes."""
    store, uuids = store_uuids
    cfg = _mh_cfg(2, shared_client_ingress=True,
                  client_ingress_bandwidth=1e9)
    run = MultiHostRun(store, uuids[:20_000], cfg).start()
    run.run(8)                                   # both hosts loading
    lim = run.limiter
    ctl0 = run.loaders[0].flow_controller
    assert len(lim.active_members()) == 2
    contended_cap = lim.fair_cap_samples(ctl0)

    # host 1 goes idle: wait out the activity window, then only host 0 pulls
    run.clock.sleep(1.5 * lim.activity_window)
    t0, b0 = run.clock.now(), run.loaders[0].pool.bytes_received
    for _ in range(12):
        run.loaders[0].next_batch()
    solo_rate = ((run.loaders[0].pool.bytes_received - b0)
                 / (run.clock.now() - t0))
    assert lim.active_members() == [ctl0]        # host 1 aged out
    # full-NIC cap, exactly the single-member formula
    cap = lim.fair_cap_samples(ctl0)
    assert cap == pytest.approx(
        ctl0.cfg.gain * (lim.bandwidth / ctl0.avg_sample_bytes())
        * ctl0.min_rtt())
    assert cap > 1.6 * contended_cap
    # ...and the surviving host actually uses the freed share: its solo
    # rate clearly beats its half-NIC contended share
    assert solo_rate > 0.7 * lim.bandwidth


def test_shared_ingress_rejected_with_federation(store_uuids):
    from repro.core import ClusterSpec
    store, uuids = store_uuids
    cfg = MultiHostConfig(n_hosts=2, shared_client_ingress=True,
                          clusters=(ClusterSpec("a"),))
    with pytest.raises(ValueError, match="shared_client_ingress"):
        MultiHostRun(store, uuids[:500], cfg)


def test_budget_respects_tiny_ceiling(store_uuids):
    """A ceiling below the route BDP pins the budget at the ceiling."""
    store, uuids = store_uuids
    B = 64
    flow = FlowControlConfig(floor_batches=1, ceiling_batches=3)
    profile = RouteProfile("fat", rtt=0.100, conn_capacity=5e8,
                           loss_per_byte=0.0, jitter=0.02)
    pf, ctl = _adaptive_prefetcher(store, uuids[:20_000], profile, B, flow)
    for _ in range(40):
        pf.next_batch(timeout=5000.0)
    assert ctl.operating_budget() == 3 * B
    assert ctl.depth() == 3
    assert max(b for _, b in ctl.budget_trace) <= 3 * B


# ---------------------------------------------------------------------------
# Hedge accounting: on_hedge only when a duplicate request is actually sent
# ---------------------------------------------------------------------------

def _one_fetch_pool(conns_per_thread: int):
    store = KVStore()
    uuids = ingest(store, SyntheticImageDataset(n_samples=4, seed=2))
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=1, rf=1, seed=3)
    pool = ConnectionPool(clock, cluster, "high", io_threads=1,
                          conns_per_thread=conns_per_thread, seed=5,
                          hedge_after=0.01)
    ctl = pool.attach_flow_control(FlowControlConfig(), batch_size=8)
    done = []
    pool.fetch(uuids[0], done.append)
    assert clock.run_until(lambda: len(done) == 1, timeout=60.0)
    return pool, ctl


def test_hedge_suppressed_without_backup_connection_is_not_counted():
    """Regression: the hedge timer used to feed on_hedge *before* checking
    whether a duplicate could actually be sent, so a pool with no distinct
    backup connection (everything else excluded/dark) AIMD-backed-off the
    budget for a hedge that never happened."""
    pool, ctl = _one_fetch_pool(conns_per_thread=1)
    assert pool.requests_sent == 1          # nothing was duplicated...
    assert ctl.loss_signals == 0            # ...so no congestion signal


def test_hedge_that_fires_is_counted():
    pool, ctl = _one_fetch_pool(conns_per_thread=2)
    assert pool.requests_sent == 2          # duplicate actually sent
    assert ctl.loss_signals == 1
