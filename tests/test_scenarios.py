"""Time-varying routes and the declarative scenario matrix.

Covers the schedule sampler (``netsim.RouteSchedule``/``RouteProfile``),
the flow controller's re-convergence machinery (min-RTT anchor, dead-band
ratchet, regime shifts, load-aware backoff), replica demotion consistency,
and the ``core/scenarios.py`` declarative layer and its scenario matrix.
"""

import json
import math
import uuid as _uuid

import pytest

from repro.core import (CassandraLoader, Cluster, ConnectionPool,
                        FlowControlConfig, FlowController, KVStore,
                        LoaderConfig, OracleDepthController, QUICK_MATRIX,
                        Scenario, SCENARIOS, matrix, run_cell)
from repro.core.netsim import TIERS, RouteProfile, RouteSchedule, VirtualClock
from repro.core.replication import ReplicaCache
from repro.data.datasets import SyntheticImageDataset, ingest

from dataclasses import replace


@pytest.fixture(scope="module")
def store_uuids():
    store = KVStore()
    uuids = ingest(store, SyntheticImageDataset(n_samples=12_000, seed=11))
    return store, uuids


# ---------------------------------------------------------------------------
# Schedule sampling (netsim)
# ---------------------------------------------------------------------------

def test_schedule_step_ramp_sinusoid_values():
    step = RouteSchedule("latency", "step", factor=4.0, at=2.0)
    assert step.multiplier(1.9) == 1.0
    assert step.multiplier(2.0) == 4.0
    assert step.multiplier(100.0) == 4.0        # until defaults to forever

    ramp = RouteSchedule("latency", "ramp", factor=9.0, at=2.0, until=4.0)
    assert ramp.multiplier(2.0) == 1.0
    assert ramp.multiplier(3.0) == pytest.approx(5.0)   # halfway
    assert ramp.multiplier(4.0) == 9.0
    assert ramp.multiplier(50.0) == 9.0                 # holds after

    sine = RouteSchedule("bandwidth", "sinusoid", amplitude=0.5, period=4.0)
    vals = [sine.multiplier(t) for t in (0.0, 1.0, 2.0, 3.0, 4.0)]
    assert vals[0] == pytest.approx(1.0)
    assert max(vals) == pytest.approx(1.5)
    assert min(vals) == pytest.approx(0.5)
    assert sine.multiplier(6.0) == pytest.approx(sine.multiplier(2.0))


def test_schedules_compose_and_random_walk_is_clamped_and_deterministic():
    prof = RouteProfile(
        "combo", rtt=0.1, conn_capacity=1e8, loss_per_byte=0.0,
        schedules=(RouteSchedule("latency", "step", factor=3.0, at=1.0),
                   RouteSchedule("latency", "step", factor=2.0, at=2.0)))
    assert prof.latency_multiplier(0.5) == 1.0
    assert prof.latency_multiplier(1.5) == 3.0
    assert prof.latency_multiplier(2.5) == 6.0          # multiplicative

    rw = RouteSchedule("bandwidth", "random_walk", sigma=1.5, interval=0.25,
                       seed=3)
    series = [rw.multiplier(t * 0.25) for t in range(200)]
    assert series == [rw.multiplier(t * 0.25) for t in range(200)]  # pure fn
    assert all(RouteSchedule.MIN_MULT <= m <= RouteSchedule.MAX_MULT
               for m in series)
    assert len(set(series)) > 10                        # actually wanders


def test_outage_windows():
    prof = RouteProfile("flaky", rtt=0.01, conn_capacity=1e8,
                        loss_per_byte=0.0,
                        outages=((2.0, 0.5), (5.0, 1.0)))
    assert not prof.is_static
    for t, down in ((1.99, False), (2.0, True), (2.49, True), (2.5, False),
                    (5.5, True), (6.0, False)):
        assert prof.down_at(t) is down


def test_neutral_schedule_is_bit_identical_to_static(store_uuids):
    """A schedule whose multiplier is identically 1.0 must not perturb a
    single event time: the dynamic sampling path multiplies the same
    floats by 1.0, so the virtual clocks agree exactly."""
    store, uuids = store_uuids

    def end_time(route):
        cfg = LoaderConfig(batch_size=64, prefetch_buffers=4, io_threads=2,
                           route=route, seed=5)
        ld = CassandraLoader(store, uuids[:4000], cfg)
        ld.start()
        for _ in range(20):
            ld.next_batch(timeout=1000.0)
        return ld.clock.now()

    static = TIERS["med"]
    neutral = replace(static, schedules=(
        RouteSchedule("latency", "step", factor=1.0, at=0.0),
        RouteSchedule("bandwidth", "step", factor=1.0, at=0.0)))
    assert not neutral.is_static
    assert end_time(neutral) == end_time(static)


# ---------------------------------------------------------------------------
# FlowController re-convergence (unit level, stub clock)
# ---------------------------------------------------------------------------

class _StubClock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t


def _controller(**kw):
    cfg = FlowControlConfig(rtt_window=4.0, regime_buckets=1,
                            probe_rtt_interval=1e9, **kw)
    clock = _StubClock()
    return FlowController(cfg, batch_size=64, clock=clock), clock


def _feed(ctl, clock, rtt, duration, dt=0.1, nbytes=100_000):
    end = clock.t + duration
    while clock.t < end:
        clock.t += dt
        ctl.on_complete(clock.t - rtt, clock.t, nbytes)


def test_min_rtt_anchor_immune_to_queue_drift():
    """Samples inflated by less than the budget gain are self-queueing by
    definition; the windowed filter alone would let the 0.10 s floor expire
    and re-anchor at the queued 0.15 s, feeding the queue back into the BDP
    estimate.  The anchor must hold."""
    ctl, clock = _controller()
    _feed(ctl, clock, rtt=0.10, duration=2.0)
    _feed(ctl, clock, rtt=0.15, duration=20.0)   # 5x the rtt_window
    assert ctl.min_rtt() == pytest.approx(0.10)
    assert ctl.regime_shifts == 0


def test_dead_band_ratchet_tracks_slow_creep():
    """A bucket floor above gain x anchor cannot be our own queue — the
    route moved, but not regime_factor-far.  The anchor must ratchet up
    (to at least done_min / gain) without a re-slow-start, or the budget
    would spiral down on slow ramps."""
    ctl, clock = _controller()
    _feed(ctl, clock, rtt=0.10, duration=2.0)
    _feed(ctl, clock, rtt=0.25, duration=10.0)   # 2.5x: gain < 2.5 < 3.0
    gain = ctl.cfg.gain
    assert 0.25 / gain <= ctl.min_rtt() <= 0.25 + 1e-9
    assert ctl.regime_shifts == 0                # no full shift declared


def test_regime_shift_reanchors_and_reslowstarts():
    ctl, clock = _controller()
    _feed(ctl, clock, rtt=0.10, duration=2.0)
    ctl._slow_start = False
    _feed(ctl, clock, rtt=0.50, duration=3.0)    # 5x > regime_factor 3.0
    assert ctl.regime_shifts == 1
    assert ctl.min_rtt() == pytest.approx(0.50)
    assert ctl._slow_start                        # re-probing the new BDP


def test_load_aware_backoff_ignores_self_serialization():
    """Constant-RTT operation — however slow — explains itself via
    budget/delivery_rate; only RTTs far beyond propagation + own-load
    serialization may back the budget off."""
    ctl, clock = _controller()
    _feed(ctl, clock, rtt=0.30, duration=8.0, dt=0.01)
    assert ctl.backoffs == 0
    # now genuine congestion: RTT 30x with the same delivery cadence
    _feed(ctl, clock, rtt=9.0, duration=2.0, dt=0.01)
    assert ctl.backoffs >= 1


# ---------------------------------------------------------------------------
# Re-convergence, end to end (loader on a scheduled route)
# ---------------------------------------------------------------------------

def _adaptive_run(store, uuids, route, n_batches, B=64):
    flow = FlowControlConfig(rtt_window=4.0, regime_buckets=1,
                             probe_rtt_interval=6.0, ceiling_batches=64)
    cfg = LoaderConfig(batch_size=B, io_threads=2, route=route, seed=7,
                       flow_control="adaptive", flow=flow)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    for _ in range(n_batches):
        ld.next_batch(timeout=3000.0)
    return ld


def test_controller_reconverges_after_latency_step(store_uuids):
    """After a x8 latency step the controller must declare a regime shift,
    re-anchor its min-RTT to the new propagation delay, and grow the
    budget toward the multiplied BDP instead of staying pinned."""
    store, uuids = store_uuids
    r1 = 0.02 * 8
    route = RouteProfile(
        "step8", rtt=0.02, conn_capacity=8e7, loss_per_byte=0.0,
        schedules=(RouteSchedule("latency", "step", factor=8.0, at=1.0),))
    ld = _adaptive_run(store, uuids[:12_000], route, n_batches=90)
    ctl = ld.flow_controller
    assert ctl.regime_shifts >= 1
    assert r1 * 0.9 <= ctl.min_rtt() <= r1 * 2.0
    # the budget rebuilt: well above one batch, tracking the new BDP
    assert ctl.depth(64) >= 3


def test_controller_tracks_latency_ramp_without_collapse(store_uuids):
    """A slow x2.5 ramp never crosses the regime factor; the dead-band
    ratchet alone must keep the budget alive and the pipe full."""
    store, uuids = store_uuids
    route = RouteProfile(
        "creep", rtt=0.03, conn_capacity=8e7, loss_per_byte=0.0,
        schedules=(RouteSchedule("latency", "ramp", factor=2.5, at=1.0,
                                 until=3.0),))
    ld = _adaptive_run(store, uuids[:12_000], route, n_batches=80)
    ctl = ld.flow_controller
    assert ctl.min_rtt() > 0.03                  # anchor ratcheted up
    assert ctl.depth(64) >= 2                    # no spiral to the floor


# ---------------------------------------------------------------------------
# Replica demotion: cold entries go, stale reads stay impossible
# ---------------------------------------------------------------------------

def test_demotion_drops_cold_never_serves_stale():
    cache = ReplicaCache(capacity=8)
    keys = [_uuid.uuid4() for _ in range(4)]
    for k in keys:
        tok = cache.begin_promotion(k, "edge", version=1, now=0.0)
        cache.commit_promotion(k, tok)
    assert all(cache.serving_cluster(k, 1, now=1.0) == "edge" for k in keys)

    # hotset rotates away from keys[2:]; they go cold past demote_after
    hot = set(keys[:2])
    n = cache.demote_cold(now=3.0, is_hot=lambda k: k in hot,
                          demote_after=1.5)
    assert n == 2 and cache.demotions == 2
    for k in keys[2:]:
        assert cache.get(k) is None
        assert cache.serving_cluster(k, 1, now=3.0) is None
    # survivors still serve...
    assert cache.serving_cluster(keys[0], 1, now=3.0) == "edge"
    # ...but never at a stale version, demoted or not
    assert cache.serving_cluster(keys[0], 2, now=3.0) is None
    assert cache.stale_blocked == 1 and cache.get(keys[0]) is None


def test_demotion_over_rotating_hotsets_serves_only_live_current():
    """Property over three hotset rotations: every successful serve is for
    a key that is currently promoted and at the current version."""
    cache = ReplicaCache(capacity=16)
    keys = [_uuid.uuid4() for _ in range(12)]
    version, now = 1, 0.0
    for rotation in range(3):
        hot = set(keys[rotation * 4:(rotation + 1) * 4])
        for k in hot:
            tok = cache.begin_promotion(k, "edge", version, now)
            if tok is not None:
                cache.commit_promotion(k, tok)
        now += 2.0
        cache.demote_cold(now, is_hot=lambda k: k in hot, demote_after=1.0)
        for k in keys:
            got = cache.serving_cluster(k, version, now)
            if got is not None:
                e = cache.get(k)
                assert e is not None and e.live and e.version == version
    assert cache.demotions >= 4                  # rotations actually demoted


# ---------------------------------------------------------------------------
# Per-route admission (satellite: prefetcher consults the pool's budget)
# ---------------------------------------------------------------------------

def test_pool_admit_tracks_controller_budget(store_uuids):
    store, uuids = store_uuids
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=1, rf=1,
                      seed=3)
    pool = ConnectionPool(clock, cluster, TIERS["med"], io_threads=2, seed=3)
    assert pool.admit(uuids[0])                  # static: always admissible
    ctl = pool.attach_flow_control(FlowControlConfig(), batch_size=64)
    budget = ctl.budget()
    assert budget >= 64
    for u in uuids[:budget]:                     # fill to the budget...
        pool.fetch(u, lambda res: None)
    assert not pool.admit(uuids[budget])         # ...and admission closes
    clock.run_until(lambda: pool.inflight == 0, timeout=60.0)
    assert pool.admit(uuids[budget])             # drained: open again


# ---------------------------------------------------------------------------
# Declarative scenarios + oracle
# ---------------------------------------------------------------------------

def test_scenarios_roundtrip_through_json():
    for sc in SCENARIOS.values():
        back = Scenario.from_dict(json.loads(json.dumps(sc.to_dict())))
        assert back == sc


def test_registry_shapes():
    assert set(s.name for s in matrix(quick=True)) <= set(SCENARIOS)
    quick = {s.name: s for s in matrix(quick=True)}
    full = {s.name: s for s in matrix(quick=False)}
    assert "rwalk" in full and "rwalk" not in quick
    for name, sc in quick.items():
        assert full[name].n_batches == 2 * sc.n_batches
    assert not SCENARIOS["steady"].dynamic
    assert all(SCENARIOS[n].dynamic for n in SCENARIOS if n != "steady")


def test_oracle_depth_follows_schedule_and_outages():
    clock = _StubClock()
    route = RouteProfile(
        "orc", rtt=0.15, conn_capacity=30e6, loss_per_byte=0.0,
        schedules=(RouteSchedule("latency", "step", factor=16.0, at=5.0),),
        outages=((20.0, 1.0),))
    oc = OracleDepthController(clock, route, n_conns=8,
                               sample_bytes=115_000, batch_size=128)
    clock.t = 1.0
    before = oc.depth()
    clock.t = 6.0
    after = oc.depth()
    assert after > before                        # BDP multiplied with RTT
    assert after >= 8 * before * 0.5             # roughly tracks the x16
    clock.t = 20.5
    assert oc.depth() == 1                       # down link: nothing to buffer
    clock.t = 22.0
    assert oc.depth() == after


def test_run_cell_modes_smoke(store_uuids):
    store, uuids = store_uuids
    sc = Scenario("tiny", rtt=0.01, n_batches=4, batch_size=32,
                  io_threads=2,
                  schedules=(RouteSchedule("latency", "step", factor=2.0,
                                           at=0.5),))
    out = {m: run_cell(store, uuids[:2000], sc, m)
           for m in ("static-2", "adaptive", "oracle")}
    for m, r in out.items():
        assert r["MBps"] > 0.0 and r["t_end_s"] > 0.0, m
    assert "steady_depth" in out["adaptive"]
    with pytest.raises(ValueError, match="unknown mode"):
        run_cell(store, uuids[:2000], sc, "psychic")


# The oracle knows every schedule in advance; the adaptive controller only
# measures.  Holding >= 1/1.5 of the oracle's virtual-clock throughput on
# every cell, with no per-scenario tuning, is what re-convergence buys.
ORACLE_SLACK = 1.5


@pytest.mark.parametrize("name", QUICK_MATRIX)
def test_adaptive_within_oracle_slack_on_every_quick_cell(store_uuids, name):
    store, uuids = store_uuids
    sc = SCENARIOS[name]
    adaptive = run_cell(store, uuids, sc, "adaptive")["MBps"]
    oracle = run_cell(store, uuids, sc, "oracle")["MBps"]
    assert adaptive >= oracle / ORACLE_SLACK
