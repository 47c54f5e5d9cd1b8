"""Loader -> training loop closure: per-step data-stall accounting
(``core.stats.StepStats``), exactly-once checkpointing through
``DeviceFeed``, and the goodput-facing ``run_training`` surface."""

import json
import os

import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import CassandraLoader, KVStore, LoaderConfig
from repro.core.stats import StepStats
from repro.data.datasets import SyntheticTokenDataset, ingest
from repro.data.pipeline import DeviceFeed
from repro.models import build_model
from repro.train.loop import TrainLoopConfig, run_training
from repro.train.optimizer import OptimizerConfig

SEQ = 24
B = 8


class StubClock:
    """now()-only clock for StepStats units."""

    def __init__(self) -> None:
        self.t = 0.0

    def now(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# StepStats units (stub clock)
# ---------------------------------------------------------------------------

def test_step_stats_stall_fraction_and_goodput():
    clk = StubClock()
    ss = StepStats(clk)
    # 4 steps: waits 1,0,3,0 against computes of 4 -> stall 4/20
    for wait, compute in [(1.0, 4.0), (0.0, 4.0), (3.0, 4.0), (0.0, 4.0)]:
        ss.on_wait(wait, blocked=wait > 0)
        clk.t += wait + compute
        ss.on_compute(compute)
    assert ss.steps == 4
    assert ss.stall_frac() == pytest.approx(4.0 / 20.0)
    assert ss.goodput_sps(batch_size=32) == pytest.approx(4 * 32 / 20.0)
    assert ss.blocked == 2 and ss.buffer_hits == 2
    # skip drops leading steps from both series
    assert ss.stall_frac(skip=2) == pytest.approx(3.0 / 11.0)


def test_step_stats_pairs_only_closed_steps():
    ss = StepStats(StubClock())
    ss.on_wait(5.0)            # open step: wait recorded, no compute yet
    assert ss.steps == 0
    assert ss.stall_frac() == 0.0
    assert ss.goodput_sps(32) == 0.0
    ss.on_compute(5.0)
    assert ss.steps == 1
    assert ss.stall_frac() == pytest.approx(0.5)


def test_step_stats_stall_windows_reuses_windowed_series():
    clk = StubClock()
    ss = StepStats(clk)
    # one stalled step ending at t=1, one clean step ending at t=3
    ss.on_wait(0.8)
    clk.t = 1.0
    ss.on_compute(0.2)
    ss.on_wait(0.0, blocked=False)
    clk.t = 3.0
    ss.on_compute(2.0)
    win = ss.stall_windows(window=1.0)
    assert [t for t, _ in win] == [0.0, 1.0, 2.0, 3.0]
    # 0.8 stalled seconds land in the window containing t_end=1.0
    assert win[1][1] == pytest.approx(0.8)
    assert win[2][1] == 0.0


def test_step_stats_summary_schema():
    ss = StepStats(StubClock())
    ss.on_wait(1.0)
    ss.on_compute(3.0)
    s = ss.summary(batch_size=16)
    assert {"steps", "stall_frac", "goodput_sps", "buffer_hits", "blocked",
            "wait_s", "compute_s"} <= set(s)
    assert s["stall_frac"] == pytest.approx(0.25)
    assert s["wait_s"]["max"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# DeviceFeed accounting + consumer-facing checkpoint position
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def token_store():
    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=512, seq_len=SEQ,
                                                vocab=512, seed=7))
    return store, uuids


def _loader(token_store, **kw):
    store, uuids = token_store
    base = dict(batch_size=B, prefetch_buffers=2, io_threads=2, route="low",
                materialize=True, seed=11)
    base.update(kw)
    return CassandraLoader(store, uuids, LoaderConfig(**base))


def test_device_feed_reports_waits(token_store):
    loader = _loader(token_store)
    feed = DeviceFeed(loader, SEQ)
    for _ in range(6):
        next(feed)
    ss = feed.step_stats
    assert len(ss.wait_s) == 6
    assert ss.buffer_hits + ss.blocked == 6
    # waits are on the loader's (virtual) clock and can't be negative
    assert all(w >= 0.0 for w in ss.wait_s)
    # the first __next__ fills the double buffer cold -> it must block
    assert ss.wait_s[0] > 0.0


def test_device_feed_stall_slow_route_exceeds_fast(token_store):
    """A 150 ms route stalls a tight consumer more than a local one."""

    def stall_for(route):
        # depth-1 in-order loading: every refill waits on the network
        loader = _loader(token_store, route=route, prefetch_buffers=1,
                         out_of_order=False, incremental_ramp=False)
        feed = DeviceFeed(loader, SEQ, prefetch=1)
        ss = feed.step_stats
        for _ in range(8):
            next(feed)
            loader.clock.sleep(0.001)            # near-zero compute
            ss.on_compute(0.001, t_end=loader.clock.now())
        return ss.stall_frac(skip=1)

    slow, fast = stall_for("high"), stall_for("local")
    assert slow > fast
    assert slow > 0.5          # RTT-bound: almost all wall time is stall


def test_device_feed_state_rewinds_queued_batches(token_store):
    loader = _loader(token_store, out_of_order=False)
    feed = DeviceFeed(loader, SEQ, prefetch=2)
    for _ in range(3):
        next(feed)
    # loader has pulled 3 + prefetch batches; the trainer saw only 3
    assert loader.state()["consumed"] == 3 + 2
    pos = feed.state()
    assert pos["consumed"] == 3
    assert pos["cursor"] == 3 * B
    assert len(feed._queue) == 2


def test_loader_public_started_and_ready(token_store):
    loader = _loader(token_store)
    assert not loader.started
    feed = DeviceFeed(loader, SEQ)
    next(feed)                     # feed starts the loader itself
    assert loader.started
    assert loader.ready_batches >= 0


def test_device_feed_restore_exactly_once(token_store):
    """checkpoint->restore through feed.state(): the epoch-0 prefix is
    delivered with no sample skipped or duplicated."""
    store, uuids = token_store
    n_total = len(uuids) // B
    k = 7
    seen = []
    loader = _loader(token_store, out_of_order=False)
    feed = DeviceFeed(loader, SEQ)
    for _ in range(k):
        _, meta = next(feed)
        seen.extend(str(s.uuid) for s in meta.samples)
    pos = feed.state()
    loader.close()

    loader2 = _loader(token_store, out_of_order=False)
    loader2.start(epoch=pos["epoch"], cursor=pos["cursor"])
    feed2 = DeviceFeed(loader2, SEQ)
    for _ in range(n_total - k):
        _, meta = next(feed2)
        seen.extend(str(s.uuid) for s in meta.samples)
    loader2.close()

    want = [str(u) for u in loader2.plan.permutation(0)[:n_total * B]]
    assert len(seen) == len(set(seen))          # no duplicates
    assert sorted(seen) == sorted(want)         # nothing skipped


def test_loader_state_would_skip_queued_batches(token_store):
    """The regression the feed-side checkpoint fixes: restoring from
    loader.state() (cursor past the queued batches) skips samples."""
    loader = _loader(token_store, out_of_order=False)
    feed = DeviceFeed(loader, SEQ, prefetch=2)
    next(feed)
    skewed, exact = loader.state(), feed.state()
    assert skewed["cursor"] - exact["cursor"] == 2 * B


# ---------------------------------------------------------------------------
# run_training end to end (jitted tiny model)
# ---------------------------------------------------------------------------

def _tiny_model():
    return build_model(ArchConfig(
        name="loop-test-lm", family="dense", n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=1, d_ff=64, vocab=512, head_dim=16,
        dtype="float32", remat=False))


@pytest.mark.slow
def test_history_schema_and_stats(token_store):
    store, uuids = token_store
    res = run_training(
        _tiny_model(), store, uuids,
        LoaderConfig(batch_size=B, prefetch_buffers=2, io_threads=2,
                     route="low", materialize=True, seed=3),
        TrainLoopConfig(total_steps=6, seq_len=SEQ, log_every=2,
                        charge_step_time=0.01),
        OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=6))
    for rec in res["history"]:
        # static-mode schema: the pre-existing keys survive unchanged...
        assert {"step", "loss", "sps"} <= set(rec)
        # ...and the stall accounting rides along
        assert 0.0 <= rec["stall_frac"] <= 1.0
        assert rec["goodput_sps"] >= 0.0
    s = res["stats"]
    assert s["steps"] == 6
    assert 0.0 <= s["stall_frac"] <= 1.0
    # pinned compute: goodput can't exceed the compute bound
    assert s["goodput_sps"] <= B / 0.01 * 1.001
    assert res["step_stats"].steps == 6


@pytest.mark.slow
def test_checkpoint_restore_bit_exact_loss_curve(token_store, tmp_path):
    """Interrupting at a checkpoint and restoring replays the identical
    sample stream through DeviceFeed: the loss curve is bit-exact."""
    store, uuids = token_store
    loader_cfg = LoaderConfig(batch_size=B, prefetch_buffers=2, io_threads=2,
                              route="low", out_of_order=False,
                              materialize=True, seed=5)
    opt = OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=8)

    losses_a = []
    run_training(_tiny_model(), store, uuids, loader_cfg,
                 TrainLoopConfig(total_steps=8, seq_len=SEQ, log_every=1,
                                 charge_step_time=0.01),
                 opt, on_metrics=lambda m: losses_a.append(m["loss"]))

    ckpt = str(tmp_path / "ckpt")
    losses_b = []
    run_training(_tiny_model(), store, uuids, loader_cfg,
                 TrainLoopConfig(total_steps=4, seq_len=SEQ, log_every=1,
                                 checkpoint_every=4, checkpoint_dir=ckpt,
                                 charge_step_time=0.01),
                 opt, on_metrics=lambda m: losses_b.append(m["loss"]))
    run_training(_tiny_model(), store, uuids, loader_cfg,
                 TrainLoopConfig(total_steps=8, seq_len=SEQ, log_every=1,
                                 checkpoint_every=4, checkpoint_dir=ckpt,
                                 charge_step_time=0.01),
                 opt, on_metrics=lambda m: losses_b.append(m["loss"]))
    assert losses_b == losses_a    # no skipped/duplicated samples anywhere


@pytest.mark.slow
def test_checkpoint_carries_flow_snapshot(token_store, tmp_path):
    store, uuids = token_store
    ckpt = str(tmp_path / "flow_ckpt")
    run_training(
        _tiny_model(), store, uuids,
        LoaderConfig(batch_size=B, prefetch_buffers=2, io_threads=2,
                     route="med", materialize=True, flow_control="adaptive",
                     seed=9),
        TrainLoopConfig(total_steps=4, seq_len=SEQ, checkpoint_every=4,
                        checkpoint_dir=ckpt, charge_step_time=0.01),
        OptimizerConfig(peak_lr=1e-3, warmup_steps=2, total_steps=4))
    latest = sorted(os.listdir(ckpt))[-1]
    with open(os.path.join(ckpt, latest, "manifest.json")) as f:
        manifest = json.load(f)
    flow = manifest["extra"]["loader"]["flow"]
    assert flow["budget"] > 0            # measured operating point rides along
    # restoring it re-seeds a fresh adaptive loader past slow start
    loader = _loader(token_store, flow_control="adaptive")
    loader.restore_flow(flow)
    assert loader.flow_controller._slow_start is False


def test_flow_snapshot_none_in_static_mode(token_store):
    loader = _loader(token_store)
    assert loader.flow_snapshot() is None
    loader_a = _loader(token_store, flow_control="adaptive")
    snap = loader_a.flow_snapshot()
    assert isinstance(snap, dict) and "budget" in snap


def test_launcher_resumes_from_its_checkpoint(tmp_path, capsys,
                                              monkeypatch):
    """``repro.launch.train.main(argv)`` in-process: a run to step 2 leaves
    a checkpoint, and a run to step 4 resumes from it at step 2."""
    from repro.launch.train import main

    # a set variable makes the launcher leave JAX's cache settings alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))

    argv = ["--arch", "stablelm_1_6b", "--smoke", "--batch-size", "2",
            "--seq-len", "32", "--opt-state-dtype", "int8_factored",
            "--log-every", "1", "--checkpoint-dir", str(tmp_path / "ckpt")]
    first = main(argv + ["--steps", "2"])
    second = main(argv + ["--steps", "4"])
    assert [r["step"] for r in first["history"]] == [1, 2]
    assert [r["step"] for r in second["history"]] == [3, 4]
    assert all(np.isfinite(r["loss"]) and r["step_s"] > 0
               for r in first["history"] + second["history"])
    assert "stablelm-1.6b: loss" in capsys.readouterr().out


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_dir_is_fixed(env_dir, tmp_path, monkeypatch):
    """The entry points' cache is $JAX_COMPILATION_CACHE_DIR when set (JAX
    reads it itself; nothing else is set), else <checkout>/.jax_cache."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.enable_compile_cache() == want
        assert os.path.isfile(os.path.join(compile_cache.CHECKOUT, "ROADMAP.md"))
        assert jax.config.jax_compilation_cache_dir == (
            want if env_dir is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
