"""The span recorder of ``core/stats.py``: nothing while it is off; spans
with their thread, parent and request; counters; JAX compiles as spans; and
the event thread's running sums on ``RealClock``."""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring

from repro.core import stats
from repro.core.netsim import RealClock


@pytest.fixture
def recorder():
    rec = stats.enable()
    try:
        yield rec
    finally:
        stats.disable()


def test_off_records_nothing_and_shares_one_null_context():
    assert stats.active is None
    a, b = stats.span("feed.next"), stats.span("feed.upload", batch=3)
    assert a is stats.NULL_SPAN and b is stats.NULL_SPAN
    with a as sp:
        sp.tag(batch=1)
    assert stats.disable() is None


def test_nested_spans_get_their_parents_id(recorder):
    with stats.span("feed.next") as outer:
        with stats.span("feed.loader_wait") as inner:
            inner.tag(batch=7)
        with stats.span("feed.upload", batch=7):
            pass
        outer.tag(batch=5)
    by = {s.name: s for s in recorder.spans}
    assert by["feed.next"].parent is None
    assert by["feed.loader_wait"].parent == by["feed.next"].id
    assert by["feed.upload"].parent == by["feed.next"].id
    assert by["feed.loader_wait"].request == "batch=7"
    assert by["feed.upload"].request == "batch=7"
    assert by["feed.next"].request == "batch=5"
    assert len({s.id for s in recorder.spans}) == 3
    nxt = by["feed.next"]
    for s in recorder.spans:
        assert nxt.start <= s.start <= s.end <= nxt.end


def test_spans_from_two_threads_keep_their_threads(recorder):
    started = threading.Event()
    release = threading.Event()

    def other():
        with stats.span("loader.assemble", batch=1):
            started.set()
            release.wait(5)

    t = threading.Thread(target=other)
    with stats.span("feed.next"):
        t.start()
        started.wait(5)
        # opened inside this thread's span, but on another thread: no parent
        with stats.span("feed.kernel_wait", batch=1):
            release.set()
            t.join(5)
    by = {s.name: s for s in recorder.spans}
    assert by["loader.assemble"].thread == t.ident
    assert by["feed.next"].thread == threading.get_ident()
    assert by["loader.assemble"].parent is None
    assert by["feed.kernel_wait"].parent == by["feed.next"].id


def test_counters_add_up(recorder):
    def add():
        for _ in range(1000):
            recorder.count("clock.events")
            recorder.count("clock.busy_s", 0.5)

    threads = [threading.Thread(target=add) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert recorder.counters == {"clock.events": 4000.0,
                                 "clock.busy_s": 2000.0}


def test_totals_count_and_sum_each_name(recorder):
    for _ in range(3):
        with stats.span("train.step"):
            time.sleep(0.001)
    n, secs = recorder.totals()["train.step"]
    assert n == 3 and secs >= 0.003


def test_a_fresh_jit_gives_one_compile_span_in_the_enabled_interval():
    x = jnp.arange(5.0)
    x.block_until_ready()

    def fresh_function_to_compile(v):
        return v * 3.0 + 1.0

    rec = stats.enable()
    try:
        jax.jit(fresh_function_to_compile)(x).block_until_ready()
    finally:
        stats.disable()
    compiles = [s for s in rec.spans if s.name == "jax.compile"]
    assert len(compiles) == 1
    (c,) = compiles
    assert "fresh_function_to_compile" in c.request
    assert c.thread == threading.get_ident()
    assert rec.enabled_at <= c.start <= c.end <= rec.disabled_at


def test_disable_removes_the_listener_and_enable_refuses_a_second():
    before = len(monitoring.get_event_time_span_listeners())
    rec = stats.enable()
    try:
        assert len(monitoring.get_event_time_span_listeners()) == before + 1
        with pytest.raises(RuntimeError):
            stats.enable()
    finally:
        assert stats.disable() is rec
    assert len(monitoring.get_event_time_span_listeners()) == before
    assert stats.active is None
    jax.jit(lambda v: v - 2.0)(jnp.ones(3)).block_until_ready()
    assert not [s for s in rec.spans if s.name == "jax.compile"]


def test_real_clock_counts_its_events_busy_time_and_lag(recorder):
    clock = RealClock()
    done = []
    try:
        for i in range(5):
            clock.schedule(0.002 * i, lambda: (time.sleep(0.002),
                                               done.append(1)))
        assert clock.run_until(lambda: len(done) == 5, timeout=10.0)
    finally:
        clock.close()
    c = recorder.counters
    assert c["clock.events"] == 5
    assert 0.010 <= c["clock.busy_s"] < 1.0
    assert 0.0 <= c["clock.lag_s"] < 1.0


def test_real_clock_stops_counting_once_disabled():
    rec = stats.enable()
    stats.disable()
    clock = RealClock()
    done = []
    try:
        clock.schedule(0.0, done.append, 1)
        assert clock.run_until(lambda: done, timeout=10.0)
    finally:
        clock.close()
    assert rec.counters == {} and rec.spans == []
