"""Data pipeline: token codec, DeviceFeed, per-host sharding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CassandraLoader, KVStore, LoaderConfig
from repro.data.datasets import (SyntheticTokenDataset, decode_token_record,
                                 encode_token_record, ingest)
from repro.data.pipeline import DeviceFeed, batch_to_numpy


@given(n=st.integers(1, 300), label=st.integers(-2**31, 2**31 - 1),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_token_record_roundtrip(n, label, seed):
    toks = np.random.default_rng(seed).integers(0, 2**31 - 1, size=n,
                                                dtype=np.int32)
    blob = encode_token_record(toks, label)
    toks2, label2 = decode_token_record(blob)
    assert label2 == label
    np.testing.assert_array_equal(toks, toks2)


def test_token_record_rejects_garbage():
    with pytest.raises(ValueError):
        decode_token_record(b"NOPE" + b"\x00" * 16)


@pytest.fixture(scope="module")
def token_store():
    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=512, seq_len=24,
                                                vocab=1000, seed=3))
    return store, uuids


def test_batch_to_numpy_shapes(token_store):
    store, uuids = token_store
    ld = CassandraLoader(store, uuids, LoaderConfig(
        batch_size=8, prefetch_buffers=2, io_threads=2, route="low",
        materialize=True, seed=4)).start()
    batch = ld.next_batch()
    arrs = batch_to_numpy(batch, seq_len=24)
    assert arrs["tokens"].shape == (8, 24)
    assert arrs["loss_mask"].shape == (8, 24)
    assert (arrs["loss_mask"] == 1.0).all()      # full-length sequences
    assert arrs["tokens"].dtype == np.int32


def test_device_feed_yields_device_arrays(token_store):
    store, uuids = token_store
    ld = CassandraLoader(store, uuids, LoaderConfig(
        batch_size=4, prefetch_buffers=2, io_threads=2, route="low",
        materialize=True, seed=5))
    feed = DeviceFeed(ld, seq_len=24)
    dev_batch, meta = next(feed)
    assert isinstance(dev_batch["tokens"], jax.Array)
    assert dev_batch["tokens"].shape == (4, 24)
    # payload contents survive the trip
    from repro.data.datasets import decode_token_record
    toks0, _ = decode_token_record(meta.samples[0].payload)
    np.testing.assert_array_equal(np.asarray(dev_batch["tokens"][0]),
                                  toks0[:24])


def test_per_host_sharding_is_partition(token_store):
    store, uuids = token_store
    seen = []
    for shard in range(4):
        ld = CassandraLoader(store, uuids, LoaderConfig(
            batch_size=4, prefetch_buffers=2, io_threads=2, route="low",
            materialize=True, seed=6, shard_id=shard, num_shards=4))
        seen.extend(str(u) for u in ld.plan._uuids)
    assert len(seen) == len(uuids)
    assert set(seen) == {str(u) for u in uuids}


def test_image_feed_arena_matches_numpy_reference():
    """build_stack's image feed on the arena path: every batch equals the
    NumPy transform of the store's own bytes under replayed augmentation
    draws, bit for bit, while arena slabs are recycled between batches."""
    from repro.core import build_stack
    from repro.data.datasets import SyntheticPixelDataset
    from repro.data.pipeline import augment_draws
    from repro.kernels.ref import crop_mirror_normalize_np

    ds = SyntheticPixelDataset(n_samples=96, h=32, w=32, c=3, seed=2)
    store = KVStore()
    uuids = ingest(store, ds)
    B, out = 8, 24
    stack = build_stack(
        store=store, uuids=uuids,
        config=LoaderConfig(batch_size=B, route="high", materialize=True,
                            use_arena=True, arena_slot_bytes=ds.nbytes,
                            seed=1),
        feed="image", image_shape=(ds.h, ds.w, ds.c), out_shape=(out, out),
        mean=[123.7, 116.3, 103.5], std=[58.4, 57.1, 57.4], feed_seed=9,
        interpret=True)
    feed = stack.feed
    rng = np.random.default_rng(9)
    for _ in range(5):
        dev, meta = next(feed)
        oy, ox, mirror = augment_draws(rng, B, ds.h, ds.w, out, out)
        pixels = np.stack([
            np.frombuffer(store.get_data(u).payload, dtype=np.uint8
                          ).reshape(ds.h, ds.w, ds.c) for u in meta.uuids])
        want = crop_mirror_normalize_np(pixels, oy, ox, mirror, feed.mean,
                                        feed.inv_std, out, out)
        np.testing.assert_array_equal(np.asarray(dev["images"]), want)
        np.testing.assert_array_equal(np.asarray(dev["labels"]), meta.labels)
    assert stack.loader.arena.stats()["reuses"] > 0
    stack.close()
