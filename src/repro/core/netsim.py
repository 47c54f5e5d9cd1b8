"""Deterministic network / storage simulator.

The paper's phenomena are produced by real WAN links (heterogeneous TCP
throughput, congestion, high RTT) and real database nodes (service latency,
GC pauses, disk read amplification).  This container has neither a WAN nor a
database cluster, so we model them explicitly with a discrete-event simulator
that the *actual loader code* runs against: the loader is callback-driven
(as the paper's C++ loader is), and the simulator fires those callbacks either
in virtual time (fast, perfectly reproducible behavioural tests) or in real
time (threaded timers; used by the device feeds on the chip, the
JAX-integration tests and the examples).

Key modelled effects, each traceable to a paper observation:
  * per-connection AIMD (CUBIC-like) bandwidth processes with Poisson
    congestion events  -> Fig. 5/6 heterogeneous per-connection throughput;
  * FIFO wire occupancy per connection + shared NIC egress  -> burst overload
    when prefetch buffers are filled eagerly (Sec. 3.4);
  * backend service models (Scylla: shard-per-core, low variance;
    Cassandra: JVM GC pauses + block-read disk amplification)  -> Fig. 7.
"""

from __future__ import annotations

import heapq
import itertools
import math
import threading
import time as _time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import numpy as np

from . import stats as _stats
from .stats import windowed_series

# ---------------------------------------------------------------------------
# Clocks
# ---------------------------------------------------------------------------


class Clock:
    """Abstract clock: schedule callbacks, advance time, block on predicates."""

    def now(self) -> float:
        raise NotImplementedError

    def schedule(self, delay: float, fn: Callable, *args) -> None:
        raise NotImplementedError

    def run_until(self, predicate: Callable[[], bool], timeout: float = 120.0) -> bool:
        """Advance/wait until ``predicate()`` is true. Returns success."""
        raise NotImplementedError

    def sleep(self, duration: float) -> None:
        deadline = self.now() + duration
        self.schedule(duration, lambda: None)   # wake event: a virtual clock
        # only advances through events, so the deadline must be one.
        self.run_until(lambda: self.now() >= deadline, timeout=duration + 60.0)


class EventHandle:
    """Cancellation handle returned by ``schedule_cancellable``.

    Holds the event record plus the sequence number it was issued under —
    records are recycled through a freelist, so the seq check is what keeps
    a stale handle from cancelling whoever inherited the record."""

    __slots__ = ("_rec", "_seq")

    def __init__(self, rec: list, seq: int) -> None:
        self._rec = rec
        self._seq = seq

    def cancel(self) -> bool:
        """Cancel the event if it has not fired; True if this call killed it.
        A cancelled record stays in its bucket (removing it would cost a
        heap rebuild) and is skipped + recycled when its time comes."""
        rec = self._rec
        if rec is None:
            return False
        self._rec = None
        if rec[1] != self._seq or rec[2] is None:
            return False                      # already fired / recycled
        rec[2] = None
        rec[3] = None
        return True

    @property
    def cancelled(self) -> bool:
        rec = self._rec
        return rec is None or rec[1] != self._seq or rec[2] is None


class VirtualClock(Clock):
    """Single-threaded discrete-event clock. Deterministic and fast.

    Calendar-queue / heap hybrid.  Pop order is exactly ``(time, seq)`` —
    bit-identical to a single binary heap of ``(time, seq, fn, args)``
    tuples (the pre-calendar implementation, still what ``RealClock``
    uses) — but the hot path does O(1)-ish amortized work per event and
    allocates nothing per event in steady state:

    * **Event records are reusable lists** ``[time, seq, fn, args]`` drawn
      from a freelist — ``heapq`` compares them elementwise and ``seq`` is
      unique, so ``fn`` is never reached by a comparison, and unlike tuples
      they can be recycled after firing.
    * **Near-horizon slotted buckets**: a power-of-two ring of
      ``_N_SLOTS`` lists, each covering ``_SLOT_WIDTH`` seconds.  An insert
      into a future bucket is a plain ``list.append``; only inserts into
      the *current* bucket pay a ``heappush``.  A bucket is ``heapify``-ed
      (one C call) when it becomes current, which restores the exact
      ``(time, seq)`` order — equal times always map to the same bucket,
      so cross-bucket order is time order and within-bucket order is the
      heap's.
    * **Lazy far-future heap**: events beyond the ring horizon
      (``_N_SLOTS * _SLOT_WIDTH`` ahead) sit in one overflow heap and
      spill into the ring as the horizon advances past them.  When the
      ring drains empty the clock jumps straight to the overflow head's
      bucket instead of walking empty slots.

    ``events_processed`` counts fired events.
    """

    # 512 buckets x 2 ms = a 1.024 s horizon: covers every RTT tier and
    # transfer time the simulator produces; multi-second timers (hedge
    # delays, scheduled failures, training step sleeps) take the far heap.
    _N_SLOTS = 512
    _SLOT_WIDTH = 0.002

    def __init__(self) -> None:
        self._t = 0.0
        self._seq = 0
        self._width = self._SLOT_WIDTH
        self._inv_width = 1.0 / self._SLOT_WIDTH
        self._mask = self._N_SLOTS - 1
        self._slots: List[list] = [[] for _ in range(self._N_SLOTS)]
        self._bucket0 = 0                      # bucket index of _cur
        self._bucket_hi = self._N_SLOTS        # first bucket beyond the ring
        self._horizon_t = self._N_SLOTS * self._SLOT_WIDTH
        self._cur: list = self._slots[0]       # current bucket, heap-ordered
        self._ring_count = 0                   # events resident in the ring
        self._far: list = []                   # overflow heap, (time, seq) order
        self._free: list = []                  # recycled event records
        self.events_processed = 0
        self._lock = threading.RLock()  # loader code may touch from one thread only,
        # but keep it safe for accidental cross-thread use in tests.

    def now(self) -> float:
        return self._t

    # -- scheduling ---------------------------------------------------------
    def _new_record(self, delay: float, fn: Callable, args: tuple) -> list:
        t = self._t + delay if delay > 0.0 else self._t
        seq = self._seq
        self._seq = seq + 1
        if self._free:
            rec = self._free.pop()
            rec[0] = t
            rec[1] = seq
            rec[2] = fn
            rec[3] = args
        else:
            rec = [t, seq, fn, args]
        if t >= self._horizon_t:               # also catches inf timers
            heapq.heappush(self._far, rec)
        else:
            self._place(rec)
        return rec

    def _place(self, rec: list) -> None:
        """Insert a record with time < horizon into the ring."""
        b = int(rec[0] * self._inv_width)
        if b <= self._bucket0:
            heapq.heappush(self._cur, rec)
        else:
            if b >= self._bucket_hi:           # float boundary: clamp into
                b = self._bucket_hi - 1        # the last ring slot
            self._slots[b & self._mask].append(rec)
        self._ring_count += 1

    def schedule(self, delay: float, fn: Callable, *args) -> None:
        with self._lock:
            self._new_record(delay, fn, args)

    def schedule_cancellable(self, delay: float, fn: Callable,
                             *args) -> EventHandle:
        """Like ``schedule`` but returns a cancellation handle.  Separate
        entry point so the plain hot path never allocates a handle."""
        with self._lock:
            rec = self._new_record(delay, fn, args)
            return EventHandle(rec, rec[1])

    # -- popping ------------------------------------------------------------
    def _pop_live(self):
        """Next live record in exact (time, seq) order, or None.  Cancelled
        records are skipped and recycled without advancing time."""
        free = self._free
        while True:
            cur = self._cur
            while not cur:
                if self._ring_count:
                    # advance one bucket; the horizon gains one bucket too,
                    # so overdue far-heap events spill into the ring
                    b = self._bucket0 + 1
                    self._bucket0 = b
                    self._bucket_hi += 1
                    self._horizon_t += self._width
                    cur = self._cur = self._slots[b & self._mask]
                    heapq.heapify(cur)
                    far = self._far
                    while far and far[0][0] < self._horizon_t:
                        self._place(heapq.heappop(far))
                else:
                    far = self._far
                    if not far:
                        return None
                    t0 = far[0][0]
                    if t0 == math.inf:         # never-firing timers only
                        rec = heapq.heappop(far)
                        if rec[2] is not None:
                            return rec
                        free.append(rec)       # cancelled inf timer
                        continue
                    # ring is empty: jump straight to the far head's bucket
                    b = int(t0 * self._inv_width)
                    self._bucket0 = b
                    self._bucket_hi = b + self._N_SLOTS
                    self._horizon_t = self._bucket_hi * self._width
                    cur = self._cur = self._slots[b & self._mask]
                    while far and far[0][0] < self._horizon_t:
                        self._place(heapq.heappop(far))
            rec = heapq.heappop(cur)
            self._ring_count -= 1
            if rec[2] is not None:
                return rec
            free.append(rec)                   # cancelled: recycle, no fire

    def step(self) -> bool:
        """Fire the next event. Returns False if none pending."""
        with self._lock:
            rec = self._pop_live()
            if rec is None:
                return False
            t = rec[0]
            if t > self._t:
                self._t = t
            fn = rec[2]
            args = rec[3]
            rec[2] = None
            rec[3] = None
            self._free.append(rec)
            self.events_processed += 1
        fn(*args)
        return True

    def run_until(self, predicate: Callable[[], bool], timeout: float = 120.0) -> bool:
        # timeout is in *virtual* seconds to keep simulated runs deterministic.
        deadline = self._t + timeout
        while not predicate():
            if self._t > deadline or not self.step():
                return predicate()
        return True

    def drain(self, max_events: int = 100_000_000) -> None:
        n = 0
        while self.step():
            n += 1
            if n >= max_events:
                raise RuntimeError("virtual clock drain exceeded event budget")


def _fire(fn: Callable, args: tuple) -> None:
    try:
        fn(*args)
    except Exception:  # pragma: no cover - surfaced via stats in tests
        import traceback

        traceback.print_exc()


class RealClock(Clock):
    """Wall-clock implementation backed by a timer thread.

    While a span recorder is installed (``core.stats.enable``) the thread
    keeps three running sums in it: ``clock.events`` fired,
    ``clock.busy_s`` spent inside them and ``clock.lag_s``, each event's
    fire time less its due time.
    """

    def __init__(self) -> None:
        self._heap: list = []
        self._seq = itertools.count()
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._epoch = _time.monotonic()
        self._thread.start()

    def now(self) -> float:
        return _time.monotonic() - self._epoch

    def schedule(self, delay: float, fn: Callable, *args) -> None:
        with self._cv:
            heapq.heappush(self._heap, (self.now() + max(delay, 0.0), next(self._seq), fn, args))
            self._cv.notify_all()

    def _loop(self) -> None:
        while True:
            with self._cv:
                if self._stop:
                    return
                if not self._heap:
                    self._cv.wait(timeout=0.05)
                    continue
                t, _, fn, args = self._heap[0]
                dt = t - self.now()
                if dt > 0:
                    self._cv.wait(timeout=min(dt, 0.05))
                    continue
                heapq.heappop(self._heap)
            rec = _stats.active
            if rec is None:
                _fire(fn, args)
            else:
                lag = self.now() - t
                t0 = _time.perf_counter()
                _fire(fn, args)
                rec.count("clock.busy_s", _time.perf_counter() - t0)
                rec.count("clock.lag_s", lag)
                rec.count("clock.events")
            with self._cv:
                self._cv.notify_all()

    def run_until(self, predicate: Callable[[], bool], timeout: float = 120.0) -> bool:
        deadline = _time.monotonic() + timeout
        with self._cv:
            while not predicate():
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    return predicate()
                self._cv.wait(timeout=min(remaining, 0.05))
        return True

    def notify(self) -> None:
        with self._cv:
            self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout=1.0)


# ---------------------------------------------------------------------------
# Latency tiers (paper Sec. 4.2) + time-varying route schedules
# ---------------------------------------------------------------------------


# Deterministic random-walk streams for RouteSchedule(kind="random_walk"):
# cumulative standard-normal walks, generated in blocks and cached per seed so
# a frozen schedule can be sampled at arbitrary times in O(1) without carrying
# mutable state.  Block RNGs are seeded by (salt, seed, block) so extending
# the cache never changes earlier values.
_WALK_SALT = 0x52575357  # "RWSW"
_WALK_BLOCK = 1024
_WALK_CACHE: dict = {}


def _walk_level(seed: int, k: int) -> float:
    """Value of walk ``seed`` after ``k`` unit steps (k=0 -> 0.0)."""
    if k <= 0:
        return 0.0
    cum = _WALK_CACHE.get(seed)
    if cum is None:
        cum = [0.0]
        _WALK_CACHE[seed] = cum
    while len(cum) <= k:
        block = len(cum) // _WALK_BLOCK
        rng = np.random.default_rng((_WALK_SALT, seed, block))
        for step in rng.standard_normal(_WALK_BLOCK):
            cum.append(cum[-1] + float(step))
    return cum[k]


SCHEDULE_PARAMS = ("bandwidth", "latency", "loss")
SCHEDULE_KINDS = ("step", "ramp", "sinusoid", "random_walk")


@dataclass(frozen=True)
class RouteSchedule:
    """One time-varying term of a route parameter.

    A schedule is a pure function of time returning a multiplier applied to
    the route's static ``param`` ("bandwidth" scales the per-connection
    capacity ceiling, "latency" scales the RTT, "loss" scales the congestion
    event rate).  Multiple schedules on the same parameter compose by
    multiplication.  Kinds:

    * ``step``     — ``factor`` on ``[at, until)``, 1.0 outside (link
      degradation with a known end, e.g. a maintenance window);
    * ``ramp``     — linear from 1.0 at ``at`` to ``factor`` at ``until``,
      holding ``factor`` afterwards (slow congestion onset);
    * ``sinusoid`` — ``1 + amplitude * sin(2*pi*(t - phase)/period)``
      (diurnal-style oscillation);
    * ``random_walk`` — ``exp(sigma * W(t / interval))`` for a standard
      normal walk ``W`` seeded by ``seed`` (deterministic; same seed + time
      always gives the same multiplier).

    Multipliers are clamped to ``[MIN_MULT, MAX_MULT]`` so no schedule can
    drive a parameter to zero or infinity — outages are modelled separately
    as ``RouteProfile.outages`` windows, not as zero bandwidth.
    """

    param: str                       # "bandwidth" | "latency" | "loss"
    kind: str                        # "step" | "ramp" | "sinusoid" | "random_walk"
    factor: float = 1.0              # step/ramp target multiplier
    at: float = 0.0                  # step/ramp start time, s
    until: float = math.inf          # step end / ramp completion time, s
    period: float = 60.0             # sinusoid period, s
    amplitude: float = 0.0           # sinusoid relative swing, |a| < 1
    phase: float = 0.0               # sinusoid time offset, s
    sigma: float = 0.25              # random-walk per-step log deviation
    interval: float = 1.0            # random-walk step duration, s
    seed: int = 0                    # random-walk stream seed

    MIN_MULT = 0.02
    MAX_MULT = 50.0

    def __post_init__(self) -> None:
        if self.param not in SCHEDULE_PARAMS:
            raise ValueError(f"param must be one of {SCHEDULE_PARAMS}")
        if self.kind not in SCHEDULE_KINDS:
            raise ValueError(f"kind must be one of {SCHEDULE_KINDS}")
        if self.factor <= 0:
            raise ValueError("factor must be > 0")
        if self.kind == "ramp" and not (math.isfinite(self.until)
                                        and self.until > self.at):
            raise ValueError("ramp needs a finite until > at")
        if self.until <= self.at and self.kind == "step":
            raise ValueError("step needs until > at")
        if self.period <= 0:
            raise ValueError("period must be > 0")
        if abs(self.amplitude) >= 1.0:
            raise ValueError("|amplitude| must be < 1")
        if self.sigma < 0 or self.interval <= 0:
            raise ValueError("sigma must be >= 0 and interval > 0")

    def multiplier(self, t: float) -> float:
        if self.kind == "step":
            m = self.factor if self.at <= t < self.until else 1.0
        elif self.kind == "ramp":
            if t <= self.at:
                m = 1.0
            elif t >= self.until:
                m = self.factor
            else:
                frac = (t - self.at) / (self.until - self.at)
                m = 1.0 + (self.factor - 1.0) * frac
        elif self.kind == "sinusoid":
            m = 1.0 + self.amplitude * math.sin(
                2.0 * math.pi * (t - self.phase) / self.period)
        else:  # random_walk
            m = math.exp(self.sigma * _walk_level(self.seed,
                                                  int(t / self.interval)))
        return min(max(m, self.MIN_MULT), self.MAX_MULT)


@dataclass(frozen=True)
class RouteProfile:
    """One client<->server route, mirroring the paper's experimental tiers.

    Static by default; attach ``schedules`` / ``outages`` to make the route
    time-varying.  ``SimConnection`` and ``AIMDBandwidth`` sample the
    multipliers at *event time* (never at connection setup), so a route's
    behaviour under a schedule is a property of the clock, not of when the
    connection happened to be created.  Routes with no schedules and no
    outages take exactly the pre-schedule code paths (bit-identical runs).
    """

    name: str
    rtt: float                      # round-trip time, seconds
    conn_capacity: float            # per-TCP-stream ceiling, bytes/s
    loss_per_byte: float            # Poisson congestion-event rate, events/byte
    loss_spread: float = 4.0        # log-uniform spread of per-connection loss
    jitter: float = 0.05            # relative latency jitter
    # Time-correlated congestion (paper Fig. 5: some routes congested for
    # sustained periods): Markov on/off bursts multiplying the loss rate.
    burst_factor: float = 1.0       # loss multiplier while congested
    burst_on_mean: float = 0.0      # mean congested duration, s
    burst_off_mean: float = float("inf")  # mean clear duration, s
    # Time-varying dynamics (empty = static route).
    schedules: Tuple[RouteSchedule, ...] = ()
    outages: Tuple[Tuple[float, float], ...] = ()  # (start, duration), s

    def __post_init__(self) -> None:
        # Tolerate lists from declarative configs; store as hashable tuples.
        if not isinstance(self.schedules, tuple):
            object.__setattr__(self, "schedules", tuple(self.schedules))
        if not isinstance(self.outages, tuple):
            object.__setattr__(self, "outages",
                               tuple((float(s), float(d))
                                     for s, d in self.outages))
        for start, duration in self.outages:
            if duration <= 0:
                raise ValueError("outage duration must be > 0")

    @property
    def is_static(self) -> bool:
        return not self.schedules and not self.outages

    def multiplier(self, param: str, t: float) -> float:
        m = 1.0
        for s in self.schedules:
            if s.param == param:
                m *= s.multiplier(t)
        return m

    def bandwidth_multiplier(self, t: float) -> float:
        return self.multiplier("bandwidth", t)

    def latency_multiplier(self, t: float) -> float:
        return self.multiplier("latency", t)

    def loss_multiplier(self, t: float) -> float:
        return self.multiplier("loss", t)

    def down_at(self, t: float) -> bool:
        return any(start <= t < start + duration
                   for start, duration in self.outages)


# Paper: Oregon / N.California / Stockholm from an Oregon p4d.24xlarge
# (public NIC 50 Gb/s = 6.25e9 B/s).  Per-stream ceilings and loss rates are
# chosen so a tight loop over the simulator reproduces the paper's measured
# aggregates (Fig. 7).
TIERS = {
    "local": RouteProfile("local", rtt=0.00005, conn_capacity=2.0e9, loss_per_byte=0.0),
    "low": RouteProfile("low", rtt=0.0008, conn_capacity=1.0e9, loss_per_byte=1e-11),
    "med": RouteProfile("med", rtt=0.020, conn_capacity=0.7e9, loss_per_byte=5e-11,
                        burst_factor=10.0, burst_on_mean=2.0, burst_off_mean=60.0),
    # Clear-state AIMD equilibrium ~= sqrt(incr / (0.3*lpb*rtt)) ~= 370 MB/s
    # per stream; Markov congestion bursts (~20% duty) drop a stream to
    # ~40 MB/s (random-walking toward the 5 MB/s floor) for seconds at a
    # time — the sustained stragglers of Fig. 5 that gate in-order assembly.
    "high": RouteProfile("high", rtt=0.150, conn_capacity=0.5e9,
                         loss_per_byte=4e-10, loss_spread=6.0,
                         burst_factor=100.0, burst_on_mean=5.0,
                         burst_off_mean=20.0),
}

NIC_BANDWIDTH = 6.25e9  # 50 Gb/s public interface, bytes/s


# ---------------------------------------------------------------------------
# AIMD per-connection bandwidth process
# ---------------------------------------------------------------------------


class AIMDBandwidth:
    """CUBIC-flavoured AIMD rate process, advanced per transfer.

    Congestion events arrive as a Poisson process in bytes sent; each event
    multiplies the rate by ``beta``; otherwise the rate grows additively per
    RTT (so high-RTT routes recover slowly, as the paper observes citing
    [13, 8]).
    """

    def __init__(self, rng: np.random.Generator, route: RouteProfile,
                 congestion_scale: float = 1.0) -> None:
        self._rng = rng
        self._route = route
        # Heterogeneous routes: some connections traverse congested paths.
        spread = route.loss_spread
        self._loss_per_byte = route.loss_per_byte * congestion_scale * float(
            np.exp(rng.uniform(-np.log(spread), np.log(spread))))
        self.capacity = route.conn_capacity * float(rng.uniform(0.85, 1.0))
        self.rate = self.capacity * (0.5 if route.loss_per_byte > 0 else 1.0)
        self._beta = 0.7
        # additive increase per RTT: reach capacity in ~200 RTTs from half.
        self._incr_per_rtt = self.capacity / 200.0
        self._dynamic = not route.is_static
        # Markov congestion state
        self._congested = False
        self._t_switch = (rng.exponential(route.burst_off_mean)
                          if np.isfinite(route.burst_off_mean) else float("inf"))

    def _advance_state(self, now: float) -> None:
        route = self._route
        while now >= self._t_switch:
            self._congested = not self._congested
            mean = route.burst_on_mean if self._congested else route.burst_off_mean
            self._t_switch += float(self._rng.exponential(max(mean, 1e-9)))

    def transfer_seconds(self, nbytes: int, now: float = 0.0,
                         backlog_rtts: float = 0.0) -> float:
        """Advance the process by one transfer of ``nbytes``; return duration.

        ``backlog_rtts``: queueing delay ahead of this transfer in RTT units.
        Deep queues (bufferbloat from request bursts) raise the drop
        probability — the paper's Sec. 3.4 burst-overload effect that the
        incremental prefetch ramp avoids."""
        if nbytes <= 0:
            return 0.0
        self._advance_state(now)
        if self._dynamic:
            # Sample the route state at event time: a bandwidth schedule caps
            # the usable rate for this transfer (the AIMD state itself is
            # untouched, so the link recovers instantly when the cap lifts),
            # a loss schedule scales the congestion-event rate, and a latency
            # schedule stretches the RTT the additive increase is paced by.
            cap_t = self.capacity * self._route.bandwidth_multiplier(now)
            rate_eff = min(self.rate, cap_t)
            rtt_eff = self._route.rtt * self._route.latency_multiplier(now)
            loss_mult = self._route.loss_multiplier(now)
        else:
            rate_eff = self.rate
            rtt_eff = self._route.rtt
            loss_mult = 1.0
        t = nbytes / rate_eff
        lpb = self._loss_per_byte * (self._route.burst_factor if self._congested
                                     else 1.0) * loss_mult
        if backlog_rtts > 2.0:
            lpb *= 1.0 + 0.4 * (backlog_rtts - 2.0)
        if lpb > 0.0:
            events = self._rng.poisson(lpb * nbytes)
            if events > 0:
                self.rate = max(self.rate * (self._beta ** min(events, 8)),
                                self.capacity * 0.01)
            else:
                rtts = t / max(rtt_eff, 1e-6)
                self.rate = min(self.rate + self._incr_per_rtt * rtts, self.capacity)
        return t


# ---------------------------------------------------------------------------
# Shared FIFO resources (NIC egress, disks, node CPU)
# ---------------------------------------------------------------------------


class FifoResource:
    """A serial resource: work items occupy it back-to-back.

    ``acquire(t, seconds)`` returns the completion time of a job arriving at
    ``t`` that needs the resource for ``seconds``.

    Pure float bookkeeping — no clock events, no allocation — and slotted:
    at 1000-host scale a run holds tens of thousands of these (one wire
    FIFO per connection), so the per-instance dict is worth dropping.
    """

    __slots__ = ("name", "_busy_until", "busy_seconds")

    def __init__(self, name: str) -> None:
        self.name = name
        self._busy_until = 0.0
        self.busy_seconds = 0.0

    def acquire(self, t: float, seconds: float) -> float:
        start = max(t, self._busy_until)
        self._busy_until = start + seconds
        self.busy_seconds += seconds
        return self._busy_until

    @property
    def busy_until(self) -> float:
        return self._busy_until


class RateResource:
    """A shared bandwidth pipe approximated as FIFO at a fixed rate."""

    __slots__ = ("fifo", "rate", "bytes_total")

    def __init__(self, name: str, rate: float) -> None:
        self.fifo = FifoResource(name)
        self.rate = rate
        self.bytes_total = 0

    def acquire(self, t: float, nbytes: int) -> float:
        self.bytes_total += nbytes
        return self.fifo.acquire(t, nbytes / self.rate)


# ---------------------------------------------------------------------------
# Backend service models (paper Sec. 2.3 / Fig. 7)
# ---------------------------------------------------------------------------


@dataclass
class BackendModel:
    """Performance model of a Cassandra-compatible storage node."""

    name: str
    base_service: float            # median per-request service time, s
    service_sigma: float           # lognormal sigma of service time
    read_amplification: float      # disk bytes read per payload byte
    gc_rate: float                 # GC pauses per second (0 = none)
    gc_pause: float                # mean GC pause duration, s
    disk_efficiency: float = 1.0   # fraction of raw NVMe bw its access pattern gets

    def service_seconds(self, rng: np.random.Generator) -> float:
        return float(self.base_service * rng.lognormal(0.0, self.service_sigma))


# Calibrated so a simulated tight loop reproduces the paper's Fig. 7:
# ScyllaDB ~4.0 GB/s vs Cassandra ~1.6 GB/s at the high-latency tier, with
# Cassandra's disk I/O ~2.25x its network throughput (block-read strategy) and
# its small-chunk access pattern extracting less of the striped NVMe bandwidth.
SCYLLA = BackendModel("scylla", base_service=0.0004, service_sigma=0.3,
                      read_amplification=1.0, gc_rate=0.0, gc_pause=0.0,
                      disk_efficiency=1.0)
CASSANDRA = BackendModel("cassandra", base_service=0.0011, service_sigma=0.8,
                         read_amplification=2.25, gc_rate=2.0, gc_pause=0.060,
                         disk_efficiency=0.45)

BACKENDS = {"scylla": SCYLLA, "cassandra": CASSANDRA}

DISK_BANDWIDTH = 8.0e9  # 4x NVMe striped volume, bytes/s (paper: 7.4 GB/s observed)

# Mean of AIMDBandwidth's per-connection capacity draw (uniform 0.85-1.0) —
# what an analytic "expected bottleneck rate" should multiply capacities by.
EXPECTED_CONN_CAPACITY_DRAW = 0.925


def route_bdp_samples(route: "RouteProfile | str", n_conns: int,
                      sample_bytes: float,
                      backend: "BackendModel" = None,
                      t: Optional[float] = None) -> float:
    """True route BDP in *samples*, from first principles (the analytic
    yardstick the flow-control tests measure the controller
    against — not the controller's own estimate): expected bottleneck rate
    (connections, client NIC, node disk) times the effective round trip
    (propagation + median service + one transfer).

    With ``t`` given, any route schedules are applied at that instant — the
    schedule-aware *oracle* BDP that ``core/scenarios.py`` compares the
    adaptive controller against.  Callers should treat outage windows
    (``prof.down_at(t)``) separately: the BDP of a down link is moot."""
    prof = TIERS[route] if isinstance(route, str) else route
    bw_mult = lat_mult = 1.0
    if t is not None and not prof.is_static:
        bw_mult = prof.bandwidth_multiplier(t)
        lat_mult = prof.latency_multiplier(t)
    backend = backend or SCYLLA
    conn_cap = prof.conn_capacity * bw_mult
    rate_Bps = min(n_conns * conn_cap * EXPECTED_CONN_CAPACITY_DRAW,
                   NIC_BANDWIDTH, DISK_BANDWIDTH)
    rtt_eff = (prof.rtt * lat_mult + backend.base_service
               + sample_bytes / conn_cap)
    return rate_Bps / sample_bytes * rtt_eff


# ---------------------------------------------------------------------------
# Simulated server node + TCP connection
# ---------------------------------------------------------------------------


class SimServerNode:
    """One storage node: CPU service + striped disk + NIC egress.

    A node can be taken *down* (failure injection for multi-host runs): while
    down it serves nothing — in-flight requests that reach it fail, and the
    client side is expected to fail over to another replica.
    """

    def __init__(self, name: str, backend: BackendModel, rng: np.random.Generator,
                 disk_bandwidth: float = DISK_BANDWIDTH,
                 egress_bandwidth: float = NIC_BANDWIDTH,
                 cpu_cores: int = 0) -> None:
        self.name = name
        self.backend = backend
        self._rng = rng
        self.disk = RateResource(f"{name}/disk",
                                 disk_bandwidth * backend.disk_efficiency)
        self.egress = RateResource(f"{name}/egress", egress_bandwidth)
        # Wire-codec encode pool (core/wirefmt.py): ``cpu_cores`` parallel
        # encode workers modelled as one FIFO carrying 1/cores of each job's
        # single-core seconds (aggregate throughput = cores x codec rate)
        # while serve() adds the full single-core seconds as latency.  0
        # cores defers to the caller's default at serve time.
        self.cpu = FifoResource(f"{name}/cpu")
        self.cpu_cores = cpu_cores
        self.encode_cpu_seconds = 0.0      # true core-seconds spent encoding
        self._gc_until = 0.0
        self._next_gc = (self._rng.exponential(1.0 / backend.gc_rate)
                         if backend.gc_rate > 0 else float("inf"))
        self.down = False
        self.requests_served = 0

    def fail(self) -> None:
        self.down = True

    def recover(self) -> None:
        self.down = False

    def serve(self, t: float, nbytes: int, wire_bytes: Optional[int] = None,
              encode_seconds: float = 0.0) -> float:
        """Return the time at which the response starts leaving the node.

        With a wire codec active the disk still reads *raw* bytes (storage
        holds rows uncompressed; encoding happens at send time), the encode
        burns ``encode_seconds`` of one CPU core (serialized through the
        node's encode pool at ``1/cpu_cores`` weight, so aggregate encode
        throughput caps at ``cores x codec rate``), and the egress NIC
        carries the *encoded* ``wire_bytes``.  The default arguments take
        exactly the pre-codec path — zero extra resource touches.
        """
        # JVM GC model: periodic stop-the-world pauses that delay everything.
        if self.backend.gc_rate > 0 and t >= self._next_gc:
            pause = self._rng.exponential(self.backend.gc_pause)
            self._gc_until = max(self._gc_until, self._next_gc + pause)
            self._next_gc += self._rng.exponential(1.0 / self.backend.gc_rate)
        t = max(t, self._gc_until)
        t += self.backend.service_seconds(self._rng)
        disk_bytes = int(nbytes * self.backend.read_amplification)
        t = self.disk.acquire(t, disk_bytes)
        if encode_seconds > 0.0:
            from .wirefmt import NODE_CODEC_CORES
            cores = self.cpu_cores or NODE_CODEC_CORES
            self.encode_cpu_seconds += encode_seconds
            t = max(self.cpu.acquire(t, encode_seconds / cores),
                    t + encode_seconds)
        self.requests_served += 1
        return self.egress.acquire(t, wire_bytes if wire_bytes is not None
                                   else nbytes)

    @property
    def disk_bytes(self) -> int:
        return self.disk.bytes_total

    @property
    def egress_bytes(self) -> int:
        return self.egress.bytes_total


class SimConnection:
    """One TCP connection: request fan-out, FIFO wire, AIMD bandwidth.

    A request dispatched at ``t`` completes at
        max(t + rtt/2 + server service/disk/egress, wire free) + payload/bw + rtt/2
    The per-connection wire FIFO is what makes slow connections *straggle*
    (their queue grows), which is precisely the effect OOO prefetching hides.
    """

    MAX_INFLIGHT = 1024  # paper Sec. 3.3

    def __init__(self, conn_id: int, clock: Clock, node: SimServerNode,
                 route: RouteProfile, rng: np.random.Generator,
                 client_ingress: RateResource) -> None:
        self.conn_id = conn_id
        self._clock = clock
        self._node = node
        self._route = route
        self._dynamic = not route.is_static
        self._rng = rng
        self._bw = AIMDBandwidth(rng, route)
        self._wire = FifoResource(f"conn{conn_id}/wire")
        self._client_ingress = client_ingress
        self.inflight = 0
        self.bytes_done = 0
        self.failed_requests = 0
        self._pending: list = []  # queued beyond MAX_INFLIGHT
        self.trace: List = []  # (t_done, nbytes) for Fig. 5/6 style traces

    @property
    def node_name(self) -> str:
        return self._node.name

    @property
    def node_down(self) -> bool:
        return self._node.down

    def request(self, nbytes: int, on_done: Callable[[float], None],
                on_fail: Optional[Callable[[float], None]] = None,
                wire_bytes: Optional[int] = None,
                encode_seconds: float = 0.0) -> None:
        """Fetch ``nbytes`` of payload.  With a wire codec active the caller
        passes the *encoded* ``wire_bytes`` (what egress/wire/ingress carry
        and ``bytes_done`` counts) plus the node-side ``encode_seconds``;
        the defaults are the exact pre-codec path."""
        if self.inflight >= self.MAX_INFLIGHT:
            self._pending.append((nbytes, on_done, on_fail,
                                  wire_bytes, encode_seconds))
            return
        self._dispatch(nbytes, on_done, on_fail, wire_bytes, encode_seconds)

    def _dispatch(self, nbytes: int, on_done: Callable[[float], None],
                  on_fail: Optional[Callable[[float], None]] = None,
                  wire_bytes: Optional[int] = None,
                  encode_seconds: float = 0.0) -> None:
        # Staged events so every shared resource (disk, NIC egress, wire,
        # client ingress) is acquired in true arrival order — a FIFO advanced
        # with out-of-order timestamps would inflate queue waits.
        self.inflight += 1
        jitter = 1.0 + self._route.jitter * float(self._rng.uniform(-1.0, 1.0))
        self._clock.schedule(self._half_rtt(jitter),
                             self._at_server, nbytes, on_done, on_fail, jitter,
                             wire_bytes, encode_seconds)

    def _half_rtt(self, jitter: float) -> float:
        """Half-RTT flight time, sampling any latency schedule at event time."""
        rtt = self._route.rtt
        if self._dynamic:
            rtt *= self._route.latency_multiplier(self._clock.now())
        return 0.5 * rtt * jitter

    def _at_server(self, nbytes: int, on_done, on_fail, jitter: float,
                   wire_bytes: Optional[int] = None,
                   encode_seconds: float = 0.0) -> None:
        if self._node.down or (self._dynamic
                               and self._route.down_at(self._clock.now())):
            # Connection reset (node down, or the route is inside a scheduled
            # outage window): the error travels back one half-RTT; the caller
            # (ConnectionPool) is responsible for failing over / retrying.
            self._clock.schedule(self._half_rtt(jitter),
                                 self._fail, on_fail)
            return
        t = self._clock.now()
        # service + disk (+ codec encode CPU) + NIC egress; downstream stages
        # (wire FIFO, AIMD transfer, client ingress) carry the encoded bytes.
        t_out = self._node.serve(t, nbytes, wire_bytes, encode_seconds)
        w = wire_bytes if wire_bytes is not None else nbytes
        self._clock.schedule(t_out - t, self._at_wire, w, on_done, jitter)

    def _fail(self, on_fail: Optional[Callable[[float], None]]) -> None:
        self.inflight -= 1
        self.failed_requests += 1
        self._drain_pending()
        if on_fail is not None:
            on_fail(self._clock.now())

    def _at_wire(self, nbytes: int, on_done, jitter: float) -> None:
        t = self._clock.now()
        backlog = (max(self._wire.busy_until - t, 0.0)
                   + max(self._client_ingress.fifo.busy_until - t, 0.0))
        dt = self._bw.transfer_seconds(
            nbytes, t, backlog_rtts=backlog / max(self._route.rtt, 1e-6))
        t_sent = self._wire.acquire(t, dt)
        self._clock.schedule(t_sent - t, self._at_ingress, nbytes, on_done, jitter)

    def _at_ingress(self, nbytes: int, on_done, jitter: float) -> None:
        t = self._clock.now()
        t_recv = self._client_ingress.acquire(t, nbytes)
        t_done = t_recv + self._half_rtt(jitter)   # response flight tail
        self._clock.schedule(t_done - t, self._complete, nbytes, on_done)

    def _complete(self, nbytes: int, on_done: Callable[[float], None]) -> None:
        self.inflight -= 1
        self.bytes_done += nbytes
        now = self._clock.now()
        self.trace.append((now, nbytes))
        self._drain_pending()
        on_done(now)

    def _drain_pending(self) -> None:
        if self._pending and self.inflight < self.MAX_INFLIGHT:
            nb, cb, fb, wb, enc = self._pending.pop(0)
            self._dispatch(nb, cb, fb, wb, enc)

    def throughput_series(self, window: float = 0.5):
        """Windowed throughput trace (t, bytes/s) — reproduces Fig. 5/6."""
        return windowed_series(self.trace, window)


__all__ = [
    "Clock", "VirtualClock", "RealClock", "EventHandle",
    "RouteProfile", "RouteSchedule",
    "SCHEDULE_PARAMS", "SCHEDULE_KINDS", "TIERS",
    "AIMDBandwidth", "FifoResource", "RateResource", "BackendModel",
    "SCYLLA", "CASSANDRA", "BACKENDS", "SimServerNode", "SimConnection",
    "NIC_BANDWIDTH", "DISK_BANDWIDTH", "EXPECTED_CONN_CAPACITY_DRAW",
    "route_bdp_samples",
]
