"""Unit tests for the network/storage simulator."""

import heapq
import math
import random

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.netsim import (AIMDBandwidth, FifoResource, RateResource,
                               RouteProfile, SCYLLA, CASSANDRA, SimServerNode,
                               TIERS, VirtualClock)


def test_virtual_clock_orders_events():
    clk = VirtualClock()
    seen = []
    clk.schedule(2.0, seen.append, "b")
    clk.schedule(1.0, seen.append, "a")
    clk.schedule(3.0, seen.append, "c")
    clk.drain()
    assert seen == ["a", "b", "c"]
    assert clk.now() == pytest.approx(3.0)


def test_virtual_clock_run_until():
    clk = VirtualClock()
    box = []
    clk.schedule(5.0, box.append, 1)
    assert clk.run_until(lambda: len(box) == 1, timeout=10.0)
    assert clk.now() == pytest.approx(5.0)


def test_fifo_resource_serializes():
    f = FifoResource("x")
    assert f.acquire(0.0, 1.0) == pytest.approx(1.0)
    assert f.acquire(0.5, 1.0) == pytest.approx(2.0)   # queues behind job 1
    assert f.acquire(10.0, 1.0) == pytest.approx(11.0)  # idle gap respected


def test_rate_resource_tracks_bytes():
    r = RateResource("pipe", 100.0)
    t = r.acquire(0.0, 200)
    assert t == pytest.approx(2.0)
    assert r.bytes_total == 200


def test_aimd_decreases_on_loss_and_recovers():
    route = RouteProfile("t", rtt=0.1, conn_capacity=1e8, loss_per_byte=1e-6,
                         loss_spread=1.0)
    bw = AIMDBandwidth(np.random.default_rng(0), route)
    r0 = bw.rate
    # force events: huge transfer => Poisson mean >> 1
    bw.transfer_seconds(10_000_000, now=0.0)
    assert bw.rate < r0
    # loss-free route ramps toward capacity
    route2 = RouteProfile("t2", rtt=0.1, conn_capacity=1e8, loss_per_byte=0.0)
    bw2 = AIMDBandwidth(np.random.default_rng(0), route2)
    assert bw2.rate == pytest.approx(bw2.capacity)


def test_aimd_burst_state_transitions():
    route = RouteProfile("t", rtt=0.1, conn_capacity=1e8, loss_per_byte=1e-9,
                         burst_factor=100.0, burst_on_mean=1.0, burst_off_mean=1.0)
    bw = AIMDBandwidth(np.random.default_rng(3), route)
    states = set()
    for k in range(200):
        bw._advance_state(k * 0.5)
        states.add(bw._congested)
    assert states == {True, False}


def test_cassandra_model_reads_more_disk_than_scylla():
    rng = np.random.default_rng(0)
    sc = SimServerNode("s", SCYLLA, rng)
    ca = SimServerNode("c", np.random.default_rng(0) and CASSANDRA,
                       np.random.default_rng(1))
    sc.serve(0.0, 1_000_000)
    ca.serve(0.0, 1_000_000)
    assert ca.disk_bytes == pytest.approx(2.25e6, rel=0.01)
    assert sc.disk_bytes == 1_000_000


def test_tier_table_is_monotone_in_latency():
    assert TIERS["low"].rtt < TIERS["med"].rtt < TIERS["high"].rtt


def test_multihost_sim_determinism():
    """Two ``MultiHostRun`` sims with the same seed produce byte-identical
    reports — the whole simulation runs on the ``VirtualClock``, so any
    wall-clock leakage (time.time() creeping into scheduling or stats)
    would show up as float drift here."""
    from repro.core import KVStore, MultiHostConfig, MultiHostRun
    from repro.data.datasets import SyntheticImageDataset, ingest

    def go():
        store = KVStore()
        uuids = ingest(store, SyntheticImageDataset(n_samples=3000, seed=3))
        cfg = MultiHostConfig(n_hosts=2, batch_size=64, prefetch_buffers=2,
                              io_threads=2, route="low", n_nodes=4,
                              replication_factor=2, hedge_after=0.5, seed=9,
                              node_egress_bandwidth=2e8,
                              placement="token_aware")
        run = MultiHostRun(store, uuids, cfg)
        rep = run.run(4)
        rep["checkpoint"] = run.checkpoint()
        return rep

    r1, r2 = go(), go()
    assert r1 == r2                    # every float, exactly
    assert repr(r1) == repr(r2)        # and byte-identical serialized


def test_deterministic_replay():
    """Same seed => byte-identical event trace (virtual-clock runs reproduce)."""

    def run():
        clk = VirtualClock()
        rng = np.random.default_rng(42)
        node = SimServerNode("n", SCYLLA, np.random.default_rng(7))
        from repro.core.netsim import RateResource, SimConnection
        ingress = RateResource("i", 1e9)
        conn = SimConnection(0, clk, node, TIERS["high"], rng, ingress)
        done = []
        for _ in range(50):
            conn.request(115_000, done.append)
        clk.drain()
        return done

    assert run() == run()


# -- event-core ordering property (calendar queue vs reference heap) --------

class _ReferenceClock:
    """The pre-calendar event core: one binary heap of (time, seq) records.

    This is the ordering oracle the calendar-queue ``VirtualClock`` must
    match bit-identically — same ``delay <= 0`` clamp, same tie-break, same
    cancellation semantics (records are skipped at pop time, not removed).
    """

    class _Handle:
        def __init__(self, rec):
            self._rec = rec

        def cancel(self):
            if self._rec is None or self._rec[2] is None:
                return False
            self._rec[2] = None
            self._rec = None
            return True

    def __init__(self):
        self._t = 0.0
        self._seq = 0
        self._heap = []

    def now(self):
        return self._t

    def schedule_cancellable(self, delay, fn, *args):
        t = self._t + delay if delay > 0.0 else self._t
        rec = [t, self._seq, fn, args]
        self._seq += 1
        heapq.heappush(self._heap, rec)
        return self._Handle(rec)

    def drain(self):
        while self._heap:
            t, _, fn, args = heapq.heappop(self._heap)
            if fn is None:
                continue
            if t > self._t:
                self._t = t
            fn(*args)


# Delay menu stresses every placement path: 0.0 (same-time tie-break on
# seq), sub-slot values, the exact slot width and its boundary, mid-ring,
# the ring horizon (1.024 s) and beyond it (far-heap spill + jump-to-head).
_DELAYS = (0.0, 0.0, 3e-4, 1e-3, 0.002, 0.0021, 0.0155, 0.25,
           1.023, 1.024, 1.5, 4.2)


def _event_program(clock, seed, n_initial):
    """Randomized interleaved schedule/cancel workload; returns fire log.

    The same (seed, n_initial) drives the same rng draw sequence on both
    clocks *as long as the fire order matches* — any ordering divergence
    desynchronizes the draws and shows up as a log mismatch."""
    rng = random.Random(seed)
    log = []
    pending = []
    counter = iter(range(10 ** 9))

    def add(depth):
        label = next(counter)
        h = clock.schedule_cancellable(rng.choice(_DELAYS), fire, label, depth)
        pending.append(h)

    def fire(label, depth):
        log.append((label, clock.now()))
        if depth < 3 and rng.random() < 0.6:
            for _ in range(rng.randint(1, 2)):
                add(depth + 1)
        if pending and rng.random() < 0.35:
            # may already have fired — cancel must be a safe no-op then
            pending.pop(rng.randrange(len(pending))).cancel()

    for _ in range(n_initial):
        add(0)
    clock.drain()
    return log


@given(seed=st.integers(0, 2 ** 31 - 1), n_initial=st.integers(1, 40))
@settings(max_examples=25, deadline=None)
def test_calendar_queue_matches_reference_heap(seed, n_initial):
    """Pop order under arbitrary interleaved schedule/cancel sequences is
    bit-identical to the reference (time, seq) heap — the invariant every
    committed determinism baseline rests on."""
    real = VirtualClock()
    ref = _ReferenceClock()
    log_real = _event_program(real, seed, n_initial)
    log_ref = _event_program(ref, seed, n_initial)
    assert log_real == log_ref
    assert real.now() == ref.now()
    assert real.events_processed == len(log_real)


def test_event_handle_cancel_semantics():
    clk = VirtualClock()
    fired = []
    h1 = clk.schedule_cancellable(1.0, fired.append, "a")
    h2 = clk.schedule_cancellable(2.0, fired.append, "b")
    assert h1.cancel() is True          # this call killed it
    assert h1.cancel() is False         # double-cancel is a no-op
    clk.drain()
    assert fired == ["b"]
    assert h2.cancel() is False         # already fired
    assert h2.cancelled


def test_cancelled_inf_timer_never_fires():
    clk = VirtualClock()
    fired = []
    h = clk.schedule_cancellable(math.inf, fired.append, "never")
    clk.schedule(1.0, fired.append, "a")
    assert h.cancel()
    clk.drain()
    assert fired == ["a"]
    assert clk.now() == pytest.approx(1.0)


def test_events_processed_counts_fired_only():
    clk = VirtualClock()
    for i in range(5):
        clk.schedule(0.001 * i, lambda: None)
    h = clk.schedule_cancellable(0.5, lambda: None)
    h.cancel()
    clk.drain()
    assert clk.events_processed == 5
