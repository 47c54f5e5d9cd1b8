"""Multi-tenant QoS: spec/scheduler validation, the weighted-fair water-fill
invariants (hypothesis-property-tested: conservation, weighted fairness,
work conservation, no starvation), admission control, the single-tenant
bit-identity regression, and tenancy through the multi-host checkpoint.
"""

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (FlowControlConfig, KVStore, MultiHostConfig,
                        MultiHostRun, QOS_CLASSES, TenantScheduler,
                        TenantSpec)
from repro.core.flowctl import SharedIngressLimiter
from repro.core.prefetcher import EpochPlan
from repro.core.replication import ZipfPlan
from repro.data.datasets import (SyntheticImageDataset, SyntheticTokenDataset,
                                 ingest)
from repro.data.pipeline import DeviceFeed

BW = 2.0e9                      # NIC bandwidth the unit tests schedule


@pytest.fixture(scope="module")
def store_uuids():
    return _shared_store()


_STORE_CACHE = None


def _shared_store():
    """Fixture-equivalent the @given property tests could call directly (the
    hypothesis shim's wrappers take no named params, so pytest cannot inject
    fixtures into them)."""
    global _STORE_CACHE
    if _STORE_CACHE is None:
        store = KVStore()
        uuids = ingest(store, SyntheticImageDataset(n_samples=20_000,
                                                    seed=11))
        _STORE_CACHE = (store, uuids)
    return _STORE_CACHE


class _Ctl:
    """The controller surface TenantScheduler consumes, without the BDP
    machinery: fixed measurements instead of filters."""

    def __init__(self, min_rtt=0.02, avg_bytes=100_000.0, rate=None,
                 inflight=0.0):
        self.cfg = FlowControlConfig()          # gain et al. at defaults
        self._min_rtt = min_rtt
        self._avg = avg_bytes
        self._rate = rate
        self._inflight = inflight

    def min_rtt(self):
        return self._min_rtt

    def avg_sample_bytes(self):
        return self._avg

    def delivery_rate(self):
        return self._rate

    def inflight_samples(self):
        return self._inflight


def _sched(specs, **kw):
    s = TenantScheduler(BW, specs, **kw)
    ctls = {}
    for spec in specs:
        c = _Ctl()
        s.assign(c, spec.name)
        ctls[spec.name] = c
    return s, ctls


# ---------------------------------------------------------------------------
# Spec / scheduler validation
# ---------------------------------------------------------------------------

def test_tenant_spec_defaults():
    t = TenantSpec("serve", qos="latency", weight=2.0)
    assert t.qos in QOS_CLASSES
    assert t.sampling == "uniform" and t.rate_floor is None


@pytest.mark.parametrize("kw", [
    dict(name=""),
    dict(name="t", qos="gold"),
    dict(name="t", weight=0.0),
    dict(name="t", weight=-1.0),
    dict(name="t", rate_floor=0.0),
    dict(name="t", rate_ceiling=-5.0),
    dict(name="t", rate_floor=2e9, rate_ceiling=1e9),
    dict(name="t", sampling="pareto"),
    dict(name="t", zipf_s=0.0),
])
def test_tenant_spec_rejects_bad(kw):
    with pytest.raises(ValueError):
        TenantSpec(**kw)


@pytest.mark.parametrize("specs,kw", [
    ((), {}),
    ((TenantSpec("a"), TenantSpec("a")), {}),
    ((TenantSpec("a", rate_floor=BW), TenantSpec("b", rate_floor=1.0)), {}),
    ((TenantSpec("a"),), dict(latency_burst=0.5)),
    ((TenantSpec("a"),), dict(demand_headroom=1.0)),
])
def test_scheduler_rejects_bad_config(specs, kw):
    with pytest.raises(ValueError):
        TenantScheduler(BW, specs, **kw)


# ---------------------------------------------------------------------------
# The water-fill, unit-level
# ---------------------------------------------------------------------------

def test_single_tenant_fair_cap_bit_identical_to_untenanted():
    """One default tenant degenerates to the equal-split limiter: same cap
    floats, so budgets (and therefore runs) cannot diverge."""
    base = SharedIngressLimiter(BW)
    sched = TenantScheduler(BW, (TenantSpec("solo"),))
    for lim in (base, sched):
        a, b = _Ctl(), _Ctl(min_rtt=0.04, avg_bytes=90_000.0)
        lim.register(a)
        lim.register(b)
        lim.on_complete(a, 0.02, 1.0, 100_000)
        lim.on_complete(b, 0.04, 1.0, 90_000)
        caps = (lim.fair_cap_samples(a), lim.fair_cap_samples(b))
        if lim is base:
            want = caps
    assert caps == want                          # exact ==, not approx


def test_weighted_shares_proportional():
    sched, _ = _sched((TenantSpec("a", weight=1.0),
                       TenantSpec("b", weight=3.0)))
    shares = sched.tenant_shares(now=0.0)
    assert shares["b"] == pytest.approx(3.0 * shares["a"], rel=1e-12)
    assert sum(shares.values()) == pytest.approx(BW, rel=1e-12)


def test_idle_tenant_share_redistributed():
    sched, ctls = _sched((TenantSpec("a"), TenantSpec("b")))
    sched.on_complete(ctls["a"], 0.02, 0.0, 100_000)    # a last seen at t=0
    sched.on_complete(ctls["b"], 0.02, 5.0, 100_000)    # b active at t=5
    shares = sched.tenant_shares()                      # now = 5.0
    assert shares.get("a", 0.0) == 0.0                  # idle: no share
    assert shares["b"] == pytest.approx(BW, rel=1e-12)  # ...redistributed


def test_floor_reserved_under_adversarial_weight():
    floor = 0.25 * BW
    sched, _ = _sched((TenantSpec("f", weight=1.0, rate_floor=floor),
                       TenantSpec("adv", weight=1000.0)))
    shares = sched.tenant_shares(now=0.0)
    assert shares["f"] >= floor * (1.0 - 1e-12)
    assert sum(shares.values()) == pytest.approx(BW, rel=1e-12)


def test_ceiling_closes_out_and_redistributes():
    ceil = 0.1 * BW
    sched, _ = _sched((TenantSpec("capped", rate_ceiling=ceil),
                       TenantSpec("open")))
    shares = sched.tenant_shares(now=0.0)
    assert shares["capped"] == pytest.approx(ceil, rel=1e-12)
    assert shares["open"] == pytest.approx(BW - ceil, rel=1e-12)


def test_demand_cap_redistributes_unused_share():
    """A tenant delivering well below its weight-share is closed out at
    measured demand x headroom; the surplus goes to the tenant that can use
    it (work conservation for low-demand, not just idle, tenants)."""
    sched = TenantScheduler(BW, (TenantSpec("slow"), TenantSpec("hungry")))
    slow = _Ctl(rate=100.0, avg_bytes=100_000.0)        # 1e7 B/s measured
    hungry = _Ctl()                                     # unmeasured: probing
    sched.assign(slow, "slow")
    sched.assign(hungry, "hungry")
    shares = sched.tenant_shares(now=0.0)
    want = 100.0 * 100_000.0 * sched.demand_headroom
    assert shares["slow"] == pytest.approx(want, rel=1e-12)
    assert shares["hungry"] == pytest.approx(BW - want, rel=1e-12)


def test_admit_batch_defers_at_share_latency_rides_burst():
    """At identical load just above the share BDP, the batch tenant defers
    and the latency tenant's burst headroom still admits."""
    specs = (TenantSpec("lat", qos="latency"), TenantSpec("bat", qos="batch"))
    sched = TenantScheduler(BW, specs)
    gain = FlowControlConfig().gain
    cap = gain * ((BW / 2) / 100_000.0) * 0.02          # share BDP, samples
    lat = _Ctl(inflight=1.05 * cap)
    bat = _Ctl(inflight=1.05 * cap)
    sched.assign(lat, "lat")
    sched.assign(bat, "bat")
    assert sched.admit(lat) is True                     # inside 1.25x burst
    assert sched.admit(bat) is False                    # strict at share
    assert sched.admit_denials["bat"] == 1
    assert sched.admit_denials["lat"] == 0
    assert sched.admit_checks["lat"] == sched.admit_checks["bat"] == 1


def test_admit_unmeasured_or_unassigned_always_passes():
    sched = TenantScheduler(BW, (TenantSpec("t"),))
    fresh = _Ctl(min_rtt=None, avg_bytes=None, inflight=1e9)
    sched.assign(fresh, "t")
    assert sched.admit(fresh) is True                   # still ramping
    outsider = _Ctl()
    assert sched.admit(outsider) is True                # not a tenant member


def test_scheduler_snapshot_restore_roundtrip():
    specs = (TenantSpec("a"), TenantSpec("b", weight=2.0, rate_floor=1e8))
    sched, ctls = _sched(specs)
    for i in range(5):
        sched.on_complete(ctls["a"], 0.02, 0.1 * i, 1000)
    sched.admit(ctls["a"])
    snap = sched.snapshot()
    assert snap["tenants"]["b"]["weight"] == 2.0
    assert snap["tenants"]["a"]["egress_bytes"] == 5000

    fresh, _ = _sched(specs)
    fresh.restore(snap)
    assert fresh.snapshot() == snap
    fresh.restore(None)                                 # no-op
    fresh.restore({"tenants": {"ghost": {"egress_bytes": 7}}})  # dropped
    assert fresh.snapshot() == snap


def test_report_sections_per_tenant():
    sched, ctls = _sched((TenantSpec("a", qos="latency"), TenantSpec("b")))
    sched.on_complete(ctls["a"], 0.03, 0.5, 2000)
    rep = sched.report()
    assert set(rep) == {"a", "b"}
    a = rep["a"]
    assert a["qos"] == "latency" and a["completions"] == 1
    assert a["egress_bytes"] == 2000
    assert a["request_latency_s"]["p50"] == pytest.approx(0.03)
    assert a["share_Bps"] > 0.0
    assert {"weight", "rate_floor", "rate_ceiling", "active_members",
            "admit_checks", "admit_denials"} <= set(a)


# ---------------------------------------------------------------------------
# Scheduling invariants, property-tested
# ---------------------------------------------------------------------------

@given(w1=st.integers(1, 100), w2=st.integers(1, 100), w3=st.integers(1, 100),
       f1=st.integers(0, 4), f2=st.integers(0, 4),
       measured=st.integers(0, 7))
@settings(max_examples=25, deadline=None)
def test_prop_shares_conserved_never_exceed_nic(w1, w2, w3, f1, f2,
                                                measured):
    """Conservation: whatever the weights, floors and measured demands,
    granted shares never sum above the NIC bandwidth."""
    specs = (TenantSpec("a", weight=float(w1),
                        rate_floor=f1 * BW / 10 or None),
             TenantSpec("b", weight=float(w2),
                        rate_floor=f2 * BW / 10 or None),
             TenantSpec("c", weight=float(w3)))
    sched = TenantScheduler(BW, specs)
    for i, spec in enumerate(specs):
        rate = 500.0 * (i + 1) if measured & (1 << i) else None
        sched.assign(_Ctl(rate=rate), spec.name)
    shares = sched.tenant_shares(now=0.0)
    assert sum(shares.values()) <= BW * (1 + 1e-9)
    assert all(v >= 0.0 for v in shares.values())


@given(w1=st.integers(1, 64), w2=st.integers(1, 64))
@settings(max_examples=25, deadline=None)
def test_prop_backlogged_tenants_split_by_weight(w1, w2):
    """Weighted fairness: two backlogged tenants with no floors/ceilings
    split the NIC exactly in proportion to their weights."""
    sched, _ = _sched((TenantSpec("a", weight=float(w1)),
                       TenantSpec("b", weight=float(w2))))
    shares = sched.tenant_shares(now=0.0)
    assert shares["a"] * w2 == pytest.approx(shares["b"] * w1, rel=1e-9)
    assert sum(shares.values()) == pytest.approx(BW, rel=1e-9)


@given(n=st.integers(2, 4), mask=st.integers(0, 15),
       w=st.integers(1, 20))
@settings(max_examples=25, deadline=None)
def test_prop_work_conserving_idle_shares_redistributed(n, mask, w):
    """Work conservation: idle tenants get nothing, and the active tenants'
    shares still sum to the whole NIC — no slice is stranded."""
    active_mask = (mask % (1 << n)) | 1         # tenant 0 always active
    specs = tuple(TenantSpec(f"t{i}", weight=float(w if i else 1))
                  for i in range(n))
    sched, ctls = _sched(specs)
    for i, spec in enumerate(specs):
        t = 10.0 if active_mask & (1 << i) else 0.0
        sched.on_complete(ctls[spec.name], 0.02, t, 1000)
    shares = sched.tenant_shares()              # now = 10.0 > window
    active = [s.name for i, s in enumerate(specs) if active_mask & (1 << i)]
    idle = [s.name for i, s in enumerate(specs)
            if not active_mask & (1 << i)]
    assert all(shares.get(nm, 0.0) == 0.0 for nm in idle)
    assert sum(shares[nm] for nm in active) == pytest.approx(BW, rel=1e-9)


@given(adv_w=st.integers(1, 10**6), floor_tenths=st.integers(1, 9))
@settings(max_examples=25, deadline=None)
def test_prop_floor_tenant_never_starved(adv_w, floor_tenths):
    """No starvation: a floor-holding tenant with demand is granted at
    least its floor, however heavy the adversary's weight."""
    floor = floor_tenths * BW / 10
    sched, _ = _sched((TenantSpec("f", weight=1.0, rate_floor=floor),
                       TenantSpec("adv", weight=float(adv_w))))
    shares = sched.tenant_shares(now=0.0)
    assert shares["f"] >= floor * (1.0 - 1e-12)
    assert shares["adv"] <= (BW - floor) * (1 + 1e-9)


# ---------------------------------------------------------------------------
# Tenancy through MultiHostRun
# ---------------------------------------------------------------------------

def _mh_cfg(n_hosts, **kw):
    defaults = dict(n_hosts=n_hosts, batch_size=128, io_threads=4,
                    route="med", n_nodes=4, replication_factor=2,
                    hedge_after=None, seed=9, flow_control="adaptive",
                    shared_client_ingress=True,
                    client_ingress_bandwidth=2.0e9)
    defaults.update(kw)
    return MultiHostConfig(**defaults)


SERVE = TenantSpec("serve", qos="latency", weight=3.0)
TRAIN = TenantSpec("train", qos="batch", weight=1.0,
                   sampling="zipf", zipf_s=1.2)


@pytest.mark.parametrize("kw", [
    dict(tenant_of_host=("a", "b")),                          # no tenants
    dict(tenants=(SERVE,), flow_control="static"),
    dict(tenants=(SERVE,), tenant_of_host=("serve",)),        # wrong length
    dict(tenants=(SERVE,), tenant_of_host=("serve", "ghost")),
    dict(tenants=(SERVE,), shared_client_ingress=False),
    dict(host_sampling=("uniform",)),                         # wrong length
    dict(host_sampling=("uniform", "pareto")),
])
def test_multihost_tenancy_config_rejected(store_uuids, kw):
    store, uuids = store_uuids
    with pytest.raises(ValueError):
        MultiHostRun(store, uuids[:4000], _mh_cfg(2, **kw))


def test_single_tenant_run_bit_identical_to_untenanted(store_uuids):
    """The QoS machinery on a one-tenant config must not move a single
    event: same virtual end time and same bytes as the untenanted run."""
    store, uuids = store_uuids
    small = uuids[:4000]

    def end_state(tenants):
        cfg = _mh_cfg(2, tenants=tenants)
        run = MultiHostRun(store, small, cfg).start()
        rep = run.run(6)
        return run.clock.now(), rep["aggregate_Bps"], rep["per_client_Bps"]

    assert end_state(None) == end_state((TenantSpec("solo"),))


def test_mixed_sampling_plans_and_tenant_report(store_uuids):
    store, uuids = store_uuids
    cfg = _mh_cfg(3, tenants=(SERVE, TRAIN),
                  tenant_of_host=("serve", "train", "train"))
    run = MultiHostRun(store, uuids[:6000], cfg).start()
    assert isinstance(run.loaders[0].plan, EpochPlan)    # uniform tenant
    assert isinstance(run.loaders[1].plan, ZipfPlan)     # zipf tenant
    assert isinstance(run.loaders[2].plan, ZipfPlan)
    rep = run.run(4)
    tenants = rep["tenants"]
    assert tenants["serve"]["hosts"] == [0]
    assert tenants["train"]["hosts"] == [1, 2]
    for entry in tenants.values():
        assert entry["egress_Bps"] > 0.0
        assert 0.0 <= entry["hit_frac"] <= 1.0
        assert entry["request_latency_s"]["p99"] > 0.0
    assert len(rep["request_latency_s"]) == 3
    assert rep["request_latency_s"][0]["p99"] > 0.0
    assert "tenants:" in run.describe()


def test_admission_wired_through_pool_and_never_drops(store_uuids):
    """route_admission consults the tenant scheduler: checks are counted,
    over-share tenants defer, and delivery still completes (advisory)."""
    store, uuids = store_uuids
    cfg = _mh_cfg(3, tenants=(SERVE, TRAIN),
                  tenant_of_host=("serve", "train", "train"),
                  route_admission=True)
    run = MultiHostRun(store, uuids[:6000], cfg).start()
    rep = run.run(4)
    assert rep["rounds"] == 4
    assert sum(run.limiter.admit_checks.values()) > 0
    assert all(b > 0 for b in rep["per_client_Bps"])     # nobody starved


def test_floor_tenant_share_honored_against_zipf_adversary(store_uuids):
    """Integration starvation check: a weight-1 floor tenant against a
    weight-8 zipf adversary still gets granted at least its floor."""
    store, uuids = store_uuids
    floor = 3.0e8
    specs = (TenantSpec("floor", qos="latency", weight=1.0,
                        rate_floor=floor),
             TenantSpec("adv", qos="batch", weight=8.0,
                        sampling="zipf", zipf_s=1.3))
    cfg = _mh_cfg(3, tenants=specs,
                  tenant_of_host=("floor", "adv", "adv"))
    run = MultiHostRun(store, uuids[:6000], cfg).start()
    rep = run.run(6)
    entry = rep["tenants"]["floor"]
    assert entry["share_Bps"] >= floor * (1.0 - 1e-9)
    assert entry["egress_Bps"] > 0.0


def test_tenanted_zipf_checkpoint_resumes_exactly(store_uuids):
    """Mixed uniform+zipf tenant checkpoint restored onto the same config
    continues the exact per-host sample streams (the per-host sampling map
    in the checkpoint decides exactness)."""
    store, uuids = store_uuids
    small = uuids[:3000]
    cfg = _mh_cfg(2, tenants=(SERVE, TRAIN), route="low",
                  out_of_order=False, batch_size=100)

    def collector(dst):
        def on_batch(host_id, batch):
            dst.setdefault(host_id, []).extend(str(u) for u in batch.uuids)
        return on_batch

    unbroken: dict = {}
    run = MultiHostRun(store, small, cfg).start()
    run.run(3, on_batch=collector(unbroken))
    ck = run.checkpoint()
    assert ck["host_sampling"] == ["uniform", "zipf"]
    assert ck["tenant_of_host"] == ["serve", "train"]
    assert ck["tenants"]["tenants"]["train"]["completions"] > 0
    continued: dict = {}
    run.run(4, on_batch=collector(continued))

    resumed: dict = {}
    restore = MultiHostRun(store, small, cfg).start(ck)
    restore.run(4, on_batch=collector(resumed))
    assert resumed == continued                  # same streams, same order


def test_elastic_restore_conserves_tenant_weights_and_counters(store_uuids):
    """N->M restore with the same tenant set: weights ride the checkpoint
    unchanged and the cumulative per-tenant counters re-seed exactly."""
    store, uuids = store_uuids
    specs = (TenantSpec("a", weight=2.0), TenantSpec("b", weight=5.0))
    run = MultiHostRun(store, uuids[:4000],
                       _mh_cfg(2, tenants=specs)).start()
    run.run(4)
    ck = run.checkpoint()
    for spec in specs:
        assert ck["tenants"]["tenants"][spec.name]["weight"] == spec.weight

    restore = MultiHostRun(store, uuids[:4000],
                           _mh_cfg(4, tenants=specs)).start(ck)
    assert restore.limiter.snapshot()["tenants"] == ck["tenants"]["tenants"]
    assert {n: t.weight for n, t in restore.limiter.tenants.items()} == \
        {"a": 2.0, "b": 5.0}
    rep = restore.run(2)                         # and it keeps loading
    assert rep["tenants"]["a"]["egress_Bps"] > 0.0


def test_device_feed_restore_exactly_once_tenanted():
    """The PR-7 consumer-facing checkpoint position composes with tenancy:
    patching a tenanted multi-host checkpoint with ``feed.state()`` makes
    the restore exactly-once (no sample skipped or duplicated)."""
    B, SEQ = 16, 24
    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=256, seq_len=SEQ,
                                                vocab=512, seed=7))
    cfg = _mh_cfg(1, tenants=(TenantSpec("solo"),), route="low",
                  out_of_order=False, batch_size=B, materialize=True)
    n_total, k = len(uuids) // B, 5
    seen = []

    run = MultiHostRun(store, uuids, cfg).start()
    feed = DeviceFeed(run.loaders[0], SEQ)
    for _ in range(k):
        _, meta = next(feed)
        seen.extend(str(s.uuid) for s in meta.samples)
    ck = run.checkpoint()
    assert ck["shards"][0]["cursor"] - feed.state()["cursor"] == 2 * B
    ck["shards"][0].update(feed.state())         # rewind the device queue

    restore = MultiHostRun(store, uuids, cfg).start(ck)
    feed2 = DeviceFeed(restore.loaders[0], SEQ)
    for _ in range(n_total - k):
        _, meta = next(feed2)
        seen.extend(str(s.uuid) for s in meta.samples)
    want = [str(u) for u in restore.loaders[0].plan.permutation(0)]
    assert len(seen) == len(set(seen))           # no duplicates
    assert sorted(seen) == sorted(want)          # nothing skipped


def test_capped_batch_tenant_still_served(store_uuids):
    """A zipf batch tenant capped below its demand beside a latency tenant,
    with tenant admission deferring its over-share requests, is throttled,
    not starved: its hosts keep delivering."""
    store, uuids = store_uuids
    specs = (TenantSpec("serve", qos="latency", weight=3.0,
                        rate_ceiling=0.8e9),
             TenantSpec("train", qos="batch", weight=1.0,
                        rate_ceiling=2.6e9, sampling="zipf", zipf_s=1.3))
    cfg = _mh_cfg(4, tenants=specs,
                  tenant_of_host=("serve", "train", "train", "train"),
                  route_admission=True, batch_size=256, io_threads=8,
                  prefetch_buffers=8, route="high", seed=11,
                  node_egress_bandwidth=1.25e9,
                  client_ingress_bandwidth=5.0e9)
    run = MultiHostRun(store, uuids[:6000], cfg).start()
    rep = run.run(6)
    train = rep["tenants"]["train"]
    assert train["admit_denials"] > 0            # admission did defer it
    assert train["egress_Bps"] > 0.0
    assert all(b > 0 for b in rep["per_client_Bps"][1:])
