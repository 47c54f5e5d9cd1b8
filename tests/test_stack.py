"""build_stack facade + the normalized kwarg surface (PR 10).

Two properties matter:

* **equivalence** — a ``build_stack`` stack is bit-identical to the
  hand-wired chain it replaces (same constructors, same seeds, nothing
  added), so porting the benches/examples to the facade moved no numbers;
* **validation up front** — bad feed kinds, missing feed parameters and
  config/kwarg combinations the stack cannot serve raise at construction,
  not deep inside the first ``next_batch``.
"""

import warnings

import pytest

from repro.core import (CassandraLoader, ConnectionPool, Cluster, KVStore,
                        LoaderConfig, MultiHostConfig, MultiHostRun, Stack,
                        VirtualClock, build_stack)
from repro.core import connection as _connection
from repro.core.wirefmt import get_codec
from repro.data.datasets import SyntheticImageDataset, ingest


@pytest.fixture(scope="module")
def small_store():
    store = KVStore()
    uuids = ingest(store, SyntheticImageDataset(n_samples=2500, seed=0))
    return store, uuids


def _cfg(**kw):
    defaults = dict(batch_size=64, prefetch_buffers=4, io_threads=4,
                    route="low", seed=3)
    defaults.update(kw)
    return LoaderConfig(**defaults)


# -- equivalence ------------------------------------------------------------

def test_single_host_stack_is_bit_identical_to_hand_wiring(small_store):
    store, uuids = small_store

    def consume(loader):
        loader.start()
        for _ in range(8):
            loader.next_batch()
        return list(loader.stats.batch_times(skip=0)), loader.clock.now()

    hand = consume(CassandraLoader(store, uuids, _cfg()))
    stacked = consume(build_stack(store=store, uuids=uuids,
                                  config=_cfg()).loader)
    assert hand == stacked              # every float, exactly


def test_stack_exposes_every_layer(small_store):
    store, uuids = small_store
    stack = build_stack(store=store, uuids=uuids, config=_cfg(), start=True)
    assert isinstance(stack, Stack)
    assert stack.loader is stack.loaders[0]
    assert stack.pool is stack.loader.pool
    assert stack.cluster is stack.loader.cluster
    assert stack.run is None and stack.feed is None
    batch = stack.next_batch()
    assert len(batch.samples) == 64
    stack.close()


def test_shared_clock_cluster_ingress_passthrough(small_store):
    store, uuids = small_store
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=2, seed=8)
    s1 = build_stack(store=store, uuids=uuids, config=_cfg(shard_id=0,
                                                           num_shards=2),
                     clock=clock, cluster=cluster)
    s2 = build_stack(store=store, uuids=uuids, config=_cfg(shard_id=1,
                                                           num_shards=2),
                     clock=clock, cluster=cluster)
    assert s1.clock is clock and s2.clock is clock
    assert s1.cluster is cluster and s2.cluster is cluster
    s1.loader.start(), s2.loader.start()
    b1, b2 = s1.next_batch(), s2.next_batch()
    assert not set(b1.uuids) & set(b2.uuids)      # disjoint shards


def test_multihost_stack_builds_run(small_store):
    store, uuids = small_store
    cfg = MultiHostConfig(n_hosts=2, batch_size=64, prefetch_buffers=2,
                          io_threads=2, route="low", n_nodes=2, seed=4)
    stack = build_stack(store=store, uuids=uuids, config=cfg, start=True)
    assert isinstance(stack.run, MultiHostRun)
    assert len(stack.loaders) == 2
    rep = stack.run.run(2)
    assert rep["aggregate_Bps"] > 0
    with pytest.raises(RuntimeError, match="single-host convenience"):
        stack.next_batch()


# -- validation up front ----------------------------------------------------

def test_unknown_feed_kind_rejected(small_store):
    store, uuids = small_store
    with pytest.raises(ValueError, match="unknown feed kind"):
        build_stack(store=store, uuids=uuids, config=_cfg(), feed="tfrecord")


def test_feed_needs_materialize(small_store):
    store, uuids = small_store
    with pytest.raises(ValueError, match="materialize=True"):
        build_stack(store=store, uuids=uuids, config=_cfg(), feed="device",
                    seq_len=16)


def test_device_feed_needs_seq_len(small_store):
    store, uuids = small_store
    with pytest.raises(ValueError, match="seq_len"):
        build_stack(store=store, uuids=uuids,
                    config=_cfg(materialize=True), feed="device")


def test_image_feed_needs_shapes(small_store):
    store, uuids = small_store
    with pytest.raises(ValueError, match="image_shape"):
        build_stack(store=store, uuids=uuids,
                    config=_cfg(materialize=True), feed="image")


def test_image_feed_needs_arena(small_store):
    store, uuids = small_store
    with pytest.raises(ValueError, match="use_arena"):
        build_stack(store=store, uuids=uuids,
                    config=_cfg(materialize=True), feed="image",
                    image_shape=(32, 32, 3), out_shape=(24, 24))


def test_run_training_rejects_unmaterialized_config_at_construction():
    """``run_training`` builds its feed through ``build_stack``, so a config
    the token feed cannot serve fails there, before any batch is pulled."""
    from repro.configs.base import ArchConfig
    from repro.data.datasets import SyntheticTokenDataset
    from repro.models import build_model
    from repro.train.loop import TrainLoopConfig, run_training

    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=64, seq_len=8,
                                                vocab=64, seed=1))
    model = build_model(ArchConfig(
        name="stack-test-lm", family="dense", n_layers=1, d_model=16,
        n_heads=2, n_kv_heads=1, d_ff=32, vocab=64, head_dim=8,
        dtype="float32", remat=False))
    with pytest.raises(ValueError, match="consumes real payload bytes"):
        run_training(model, store, uuids,
                     LoaderConfig(batch_size=8, route="low", seed=2),
                     TrainLoopConfig(total_steps=1, seq_len=8))


def _token_feed_stack():
    from repro.data.datasets import SyntheticTokenDataset
    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=96, seq_len=8,
                                                vocab=64, seed=4))
    return build_stack(store=store, uuids=uuids,
                       config=LoaderConfig(batch_size=8, route="low",
                                           materialize=True,
                                           out_of_order=False, seed=5),
                       feed="device", seq_len=8)


def _image_feed_stack():
    from repro.data.datasets import SyntheticPixelDataset
    ds = SyntheticPixelDataset(n_samples=96, h=32, w=32, c=3, seed=2)
    store = KVStore()
    uuids = ingest(store, ds)
    return build_stack(store=store, uuids=uuids,
                       config=LoaderConfig(batch_size=8, route="low",
                                           materialize=True,
                                           out_of_order=False,
                                           use_arena=True,
                                           arena_slot_bytes=ds.nbytes,
                                           seed=5),
                       feed="image", image_shape=(ds.h, ds.w, ds.c),
                       out_shape=(24, 24), interpret=True)


@pytest.mark.parametrize("make", [_token_feed_stack, _image_feed_stack],
                         ids=["device", "image"])
def test_both_feeds_deliver_through_the_shared_pull_loop(make):
    """Both feeds keep ``prefetch`` formed batches queued past what the
    consumer saw, and ``state()`` rewinds the loader by exactly that queue,
    so a checkpoint of either feed resumes at the consumer's position."""
    from repro.data.pipeline import _Feed
    stack = make()
    feed = stack.feed
    assert type(feed)._pull_one is _Feed._pull_one
    assert type(feed).__next__ is _Feed.__next__
    for _ in range(3):
        batch, meta = next(feed)
        assert len(meta.samples) == 8
    assert len(feed._queue) == feed.prefetch == 2
    assert stack.loader.state()["consumed"] == 3 + 2
    pos = feed.state()
    assert pos["consumed"] == 3 and pos["cursor"] == 3 * 8
    assert len(feed.step_stats.wait_s) == 3
    stack.close()


def test_multihost_rejects_feed_and_external_pieces(small_store):
    store, uuids = small_store
    cfg = MultiHostConfig(n_hosts=2, batch_size=64, route="low", n_nodes=2)
    with pytest.raises(ValueError, match="MultiHostConfig"):
        build_stack(store=store, uuids=uuids, config=cfg, feed="device",
                    seq_len=16)
    with pytest.raises(ValueError, match="single-host only"):
        build_stack(store=store, uuids=uuids, config=cfg,
                    clock=VirtualClock())


def test_unknown_config_type_rejected(small_store):
    store, uuids = small_store
    with pytest.raises(TypeError, match="LoaderConfig or MultiHostConfig"):
        build_stack(store=store, uuids=uuids, config={"route": "high"})


# -- normalized kwarg surface ----------------------------------------------

def test_connection_pool_codec_alias_warns_once(small_store):
    store, _ = small_store
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=1, seed=1)
    _connection._codec_alias_warned = False       # isolate from test order
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        pool = ConnectionPool(clock, cluster, "low", io_threads=1,
                              codec="byteshuffle")
        ConnectionPool(clock, cluster, "low", io_threads=1, codec="int8")
    deprecations = [x for x in w if issubclass(x.category,
                                               DeprecationWarning)]
    assert len(deprecations) == 1                 # warn-once per process
    assert "wire_codec" in str(deprecations[0].message)
    assert pool.codec.name == get_codec("byteshuffle").name


def test_connection_pool_rejects_both_codec_spellings(small_store):
    store, _ = small_store
    clock = VirtualClock()
    cluster = Cluster(clock, store, backend="scylla", n_nodes=1, seed=1)
    with pytest.raises(TypeError, match="deprecated alias"):
        ConnectionPool(clock, cluster, "low", io_threads=1,
                       wire_codec="byteshuffle", codec="byteshuffle")


def test_multihost_kwarg_validation(small_store):
    store, uuids = small_store

    def mh(**kw):
        defaults = dict(n_hosts=2, batch_size=64, route="low", n_nodes=2)
        defaults.update(kw)
        return MultiHostRun(store, uuids, MultiHostConfig(**defaults))

    with pytest.raises(ValueError, match="wire_codec="):
        mh(wire_codec="auto")                     # needs a federation
    with pytest.raises(ValueError, match="io_scaling"):
        mh(io_scaling=True)                       # needs adaptive flow
    with pytest.raises(ValueError, match="use_arena"):
        mh(use_arena=True)                        # needs materialize
