"""Wire codecs: roundtrips, bounded loss, byte-accounting identities."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CassandraLoader, Cluster, ConnectionPool, KVStore,
                        LoaderConfig, VirtualClock, get_codec, tight_loop)
from repro.core.wirefmt import (BYTESHUFFLE, INT8, NONE, _rle_decode,
                                _rle_encode)
from repro.data.datasets import SyntheticImageDataset, SyntheticTokenDataset, ingest


@pytest.fixture(scope="module")
def store_uuids():
    store = KVStore()
    uuids = ingest(store, SyntheticImageDataset(n_samples=6_000, seed=9))
    return store, uuids


# -- roundtrips --------------------------------------------------------------


@pytest.mark.parametrize("codec", ["none", "byteshuffle"])
@pytest.mark.parametrize("payload", [
    b"",
    b"\x00" * 10_000,                            # one giant run (RLE > 255)
    bytes(range(256)) * 40,                      # structured, low-entropy
    np.arange(3000, dtype="<f4").tobytes(),      # float ramp: shuffle shines
    b"xyz",                                      # shorter than the stride
    bytes(np.random.default_rng(3).integers(0, 256, 5001, dtype=np.uint8)),
])
def test_lossless_roundtrip(codec, payload):
    c = get_codec(codec)
    assert c.decode(c.encode(payload)) == payload


def test_byteshuffle_compresses_structured_data():
    ramp = np.arange(50_000, dtype="<u4").tobytes()   # high bytes ~constant
    blob = BYTESHUFFLE.encode(ramp)
    assert len(blob) < 0.6 * len(ramp)
    assert BYTESHUFFLE.decode(blob) == ramp


def test_byteshuffle_stride_sweep_picks_channel_period():
    """Interleaved RGB uint8 frames need stride 3, not the float-stream 4:
    the sweep must find it (a fixed stride-4 shuffle raw-escapes here)."""
    from repro.data.datasets import SyntheticPixelDataset

    ds = SyntheticPixelDataset(n_samples=4, h=64, w=64, c=3, seed=9)
    rng = np.random.default_rng(9)
    raw = ds.make_frame(rng, 1).tobytes()
    blob = BYTESHUFFLE.encode(raw)
    assert BYTESHUFFLE.decode(blob) == raw
    assert len(blob) < 0.5 * len(raw)                 # really compressed
    assert blob[3] >> 1 == 3                          # stride in the header
    ramp = np.arange(10_000, dtype="<u4").tobytes()
    assert BYTESHUFFLE.encode(ramp)[3] >> 1 == 4      # floats still pick 4


def test_byteshuffle_raw_escape_on_incompressible():
    raw = bytes(np.random.default_rng(0).integers(0, 256, 8192,
                                                  dtype=np.uint8))
    blob = BYTESHUFFLE.encode(raw)
    assert len(blob) <= len(raw) + 8              # header only, never blowup
    assert BYTESHUFFLE.decode(blob) == raw


@given(n=st.integers(0, 2000), seed=st.integers(0, 1000))
@settings(max_examples=25, deadline=None)
def test_rle_roundtrip_property(n, seed):
    rng = np.random.default_rng(seed)
    # long runs mixed with noise — exercises the >255-run chunking
    x = np.repeat(rng.integers(0, 4, size=max(n // 100, 1), dtype=np.uint8),
                  rng.integers(1, 700, size=max(n // 100, 1)))[:max(n, 1)]
    out = _rle_decode(_rle_encode(x), x.size)
    np.testing.assert_array_equal(out, x)


def test_int8_bounded_error():
    rng = np.random.default_rng(5)
    x = (rng.standard_normal(10_000) * np.exp(rng.uniform(-4, 4, 10_000))
         ).astype("<f4")
    raw = x.tobytes()
    blob = INT8.encode(raw)
    assert len(blob) < 0.3 * len(raw)
    y = np.frombuffer(INT8.decode(blob), dtype="<f4")
    # per-block bound: |x - y| <= amax_block / 127
    pad = (-x.size) % INT8.BLOCK
    xp = np.pad(x, (0, pad))
    bound = np.repeat(np.abs(xp.reshape(-1, INT8.BLOCK)).max(axis=1),
                      INT8.BLOCK)[:x.size] / 127.0
    assert np.all(np.abs(x - y) <= bound + 1e-7)


def test_int8_raw_escape_paths():
    assert INT8.decode(INT8.encode(b"abc")) == b"abc"          # n % 4 != 0
    nan = np.array([1.0, np.nan], "<f4").tobytes()             # not floats
    assert INT8.decode(INT8.encode(nan)) == nan
    assert INT8.decode(INT8.encode(b"")) == b""


def test_frame_guards_and_registry():
    with pytest.raises(ValueError):
        get_codec("zstd-o-matic")
    assert get_codec(None) is NONE
    assert get_codec(BYTESHUFFLE) is BYTESHUFFLE
    with pytest.raises(ValueError):
        INT8.decode(BYTESHUFFLE.encode(b"hello world!"))       # codec mismatch


def test_encoded_size_model_deterministic():
    for c in (NONE, BYTESHUFFLE, INT8):
        assert c.encoded_size(115_000) == c.encoded_size(115_000)
        assert c.encoded_size(115_000) > 0
    assert NONE.encoded_size(115_000) == 115_000
    assert BYTESHUFFLE.encoded_size(115_000) < 115_000
    assert INT8.encoded_size(115_000) < BYTESHUFFLE.encoded_size(115_000)


# -- billing identities ------------------------------------------------------


def test_lazy_billing_matches_size_model(store_uuids):
    """Lazy rows: the pool bills exactly the codec's size model per sample —
    what SimConnection charged egress/wire/ingress with."""
    store, uuids = store_uuids
    codec = get_codec("byteshuffle")
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=4, route="low",
                       wire_codec="byteshuffle", seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    samples = []
    for _ in range(8):
        samples.extend(ld.next_batch().samples)
    assert ld.pool.bytes_received == sum(codec.encoded_size(s.size)
                                         for s in samples)
    assert ld.pool.payload_bytes_received == sum(s.size for s in samples)
    for s in samples:
        assert s.wire_size == codec.encoded_size(s.size)
        assert s.wire_size < s.size


def test_materialized_billing_matches_real_encode(store_uuids):
    """Materialized rows get *really* encoded: the wire bill is exactly
    ``len(encode(payload))`` per row."""
    store, uuids = store_uuids
    codec = get_codec("byteshuffle")
    cfg = LoaderConfig(batch_size=32, prefetch_buffers=2, route="local",
                       wire_codec="byteshuffle", materialize=True, seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    samples = []
    for _ in range(4):
        samples.extend(ld.next_batch().samples)
    expect = sum(len(codec.encode(store.get_data(s.uuid).materialize()))
                 for s in samples)
    assert ld.pool.bytes_received == expect
    assert all(s.payload == store.get_data(s.uuid).materialize()
               for s in samples)                     # decode is lossless


def test_batch_wire_vs_decoded_nbytes(store_uuids):
    store, uuids = store_uuids
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=2, route="low",
                       wire_codec="byteshuffle", seed=7)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    batch = ld.next_batch()
    assert batch.wire_nbytes == sum(s.wire_size for s in batch.samples)
    assert batch.wire_nbytes < batch.nbytes          # codec active
    cfg2 = LoaderConfig(batch_size=64, prefetch_buffers=2, route="low",
                        seed=7)
    ld2 = CassandraLoader(store, uuids, cfg2)
    ld2.start()
    b2 = ld2.next_batch()
    assert b2.wire_nbytes == b2.nbytes               # none: identical


def test_codec_cpu_charged(store_uuids):
    store, uuids = store_uuids
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=4, route="low",
                       wire_codec="byteshuffle", seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    tight_loop(ld, 6)
    node_cpu = sum(n.encode_cpu_seconds for n in ld.cluster.nodes.values())
    assert node_cpu > 0
    assert ld.pool.decode_cpu_seconds > 0
    # the load report surfaces the encode burn
    assert sum(r["encode_cpu_s"]
               for r in ld.cluster.load_report().values()) == node_cpu


def test_codec_none_bit_identical_to_default_pool(store_uuids):
    """wire_codec="none" == a pool built with no codec argument at all:
    same batch timeline, same bytes, zero codec CPU."""
    store, uuids = store_uuids

    def run(build_default: bool):
        cfg = LoaderConfig(batch_size=64, prefetch_buffers=4, route="med",
                           flow_control="adaptive", seed=6, n_nodes=2,
                           replication_factor=2, wire_codec="none")
        if build_default:
            clock = VirtualClock()
            cluster = Cluster(clock, store, backend=cfg.backend,
                              n_nodes=cfg.n_nodes,
                              rf=cfg.replication_factor, seed=cfg.seed + 5)
            pool = ConnectionPool(clock, cluster, cfg.route,
                                  io_threads=cfg.io_threads,
                                  conns_per_thread=cfg.conns_per_thread,
                                  seed=cfg.seed + 11)
            ld = CassandraLoader(store, uuids, cfg, clock=clock,
                                 cluster=cluster, pool=pool)
        else:
            ld = CassandraLoader(store, uuids, cfg)
        ld.start()
        for _ in range(10):
            ld.next_batch()
        return ld

    a, b = run(False), run(True)
    assert a.stats.batch_ready_t == b.stats.batch_ready_t
    assert a.pool.bytes_received == b.pool.bytes_received
    assert a.pool.bytes_received == a.pool.payload_bytes_received
    assert a.pool.decode_cpu_seconds == 0.0 == b.pool.decode_cpu_seconds
    assert sum(n.encode_cpu_seconds for n in a.cluster.nodes.values()) == 0.0


def test_flow_snapshot_roundtrips_with_codec(store_uuids):
    """An adaptive run under a codec checkpoints and restores at the same
    measured operating point (satellite: snapshot must survive the codec)."""
    store, uuids = store_uuids
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=8, route="med",
                       wire_codec="byteshuffle", flow_control="adaptive",
                       seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    tight_loop(ld, 20)
    snap = ld.flow_snapshot()
    assert snap is not None and snap["budget"] > 0

    ld2 = CassandraLoader(store, uuids, cfg)
    ld2.restore_flow(snap)
    snap2 = ld2.flow_snapshot()
    for key in ("budget", "min_rtt", "rate", "avg_bytes"):
        assert snap2[key] == pytest.approx(snap[key])


def test_token_records_survive_byteshuffle(store_uuids):
    """End to end through the codec: real token payloads decode identically
    after the encode->wire->decode trip."""
    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(n_samples=256, seq_len=64,
                                                seed=2))
    from repro.data.datasets import decode_token_record
    cfg = LoaderConfig(batch_size=32, prefetch_buffers=2, route="low",
                       wire_codec="byteshuffle", materialize=True, seed=3)
    ld = CassandraLoader(store, uuids, cfg)
    ld.start()
    batch = ld.next_batch()
    for s, payload in zip(batch.samples, batch.payloads()):
        toks, label = decode_token_record(payload)
        assert label == s.label
        assert toks.size == 64


# -- controller-driven io scaling --------------------------------------------


def test_io_parallelism_tracks_budget(store_uuids):
    store, uuids = store_uuids
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=8, route="local",
                       flow_control="adaptive", io_scaling=True, seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    tight_loop(ld, 15)
    n_conns = len(ld.pool.connections)
    par = ld.flow_controller.io_parallelism(n_conns)
    assert 1 <= par <= n_conns
    # shallow local budget -> far fewer active streams than the full pool
    assert par < n_conns
    assert ld.pool.active_conns_per_node() is not None
    # traffic actually concentrated: the active prefix carries ~everything
    ranks = ld.pool._conn_rank
    m = ld.pool.active_conns_per_node()
    done = [(ranks[c], c.bytes_done) for c in ld.pool.connections]
    total = sum(b for _, b in done)
    active = sum(b for r, b in done if r < max(m, 1))
    assert active > 0.5 * total


def test_io_scaling_off_keeps_full_rotation(store_uuids):
    store, uuids = store_uuids
    cfg = LoaderConfig(batch_size=64, prefetch_buffers=8, route="local",
                       flow_control="adaptive", seed=4)
    ld = CassandraLoader(store, uuids, cfg)
    tight_loop(ld, 6)
    assert ld.pool.active_conns_per_node() is None


def test_io_scaling_throughput_not_much_worse(store_uuids):
    store, uuids = store_uuids

    def run(io_scaling: bool) -> float:
        cfg = LoaderConfig(batch_size=128, prefetch_buffers=8, route="med",
                           flow_control="adaptive", io_scaling=io_scaling,
                           seed=4)
        ld = CassandraLoader(store, uuids, cfg)
        return tight_loop(ld, 25)["throughput_Bps"]

    assert run(True) > 0.7 * run(False)


def test_codec_deepens_adaptive_budget_on_wan_route(store_uuids):
    """On the 150 ms route the wire is the bottleneck: byteshuffle moves
    more samples per wire byte, the controller measures the higher sample
    rate, and its budget converges at least 1.1x deeper than without it."""
    store, uuids = store_uuids

    def budget(codec):
        cfg = LoaderConfig(batch_size=256, route="high", wire_codec=codec,
                           flow_control="adaptive", seed=13, n_nodes=4,
                           replication_factor=2)
        ld = CassandraLoader(store, uuids, cfg)
        tight_loop(ld, 150)
        return ld.flow_controller.budget()

    assert budget("byteshuffle") >= 1.1 * budget("none")
