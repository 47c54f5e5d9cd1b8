"""Wire-codec + zero-copy arena bench: the row hot path, end to end.

Two sections, both deterministic where it matters (virtual clock + seeded
RNGs; only the host-CPU ratio is wall-clock and is gated as a boolean with a
2x margin, not as a ±15% metric):

**A. codec x route** — the CPU-vs-bandwidth trade per route tier.  Lazy
rows (the codecs' calibrated ``encoded_size`` model) stream through the
adaptive flow controller on the 150 ms ``high`` route and the ``local``
route at equal NIC bandwidth.  Steady-state (post-ramp window) payload
throughput is the headline.  Checks:

* ``high_codec_gain``     — byteshuffle effective MB/s on the high route
  >= 1.3x the no-codec run: the wire carries ~0.55x the bytes, so the
  loss-limited AIMD streams deliver proportionally more payload;
* ``codec_deepens_budget`` — the flow controller *measures* the gain: its
  converged budget (BDP in samples) under the codec is >= 1.1x no-codec;
* ``local_codec_no_gain`` — on the local route the single node's encode
  pool (``NODE_CODEC_CORES`` x codec rate < NIC rate) caps the run: the
  codec buys <= 10% — WAN: compress, local: don't;
* ``none_bit_identical``  — ``wire_codec="none"`` bills wire == payload
  bytes, burns zero encode/decode CPU, and produces *exactly* the batch
  timeline of a pool constructed with no codec argument at all.

**B. arena + fused device decode** — real pixel rows
(``SyntheticPixelDataset``) through ``materialize=True`` loaders.  The
arena path uploads each batch as ONE contiguous uint8 slab and runs the
Pallas fused crop/mirror/normalize on device; the materialize path is the
classic CPU pipeline (per-sample frombuffer -> f32 -> crop/mirror ->
normalize -> transpose -> upload).  The kernel runs in the Pallas
interpreter: this section measures host CPU, not the device.  Checks:

* ``arena_matches_materialize`` — both paths produce identical tensors
  (same seeded augmentation draws);
* ``arena_halves_host_cpu``     — per-batch host prep time on the arena
  path <= 0.5x the materialize path (wall clock, after JAX warmup);
* ``arena_reuses_slabs``        — the pinned pool stays at its steady-state
  size (2 slabs) instead of allocating per batch.

Results land in ``results/wirefmt.json`` (quick runs gated against
``benchmarks/baselines/wirefmt.json`` by ``tools/bench_check.py``).
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import (CassandraLoader, ConnectionPool, KVStore,
                        LoaderConfig)
from repro.data.datasets import (SyntheticImageDataset, SyntheticPixelDataset,
                                 ingest)

from .common import RESULTS_DIR, make_store

SEED = 13
BATCH = 256


# ---------------------------------------------------------------------------
# Section A: codec x route
# ---------------------------------------------------------------------------


def _codec_cfg(route: str, codec: str, n_nodes: int) -> LoaderConfig:
    return LoaderConfig(batch_size=BATCH, route=route, wire_codec=codec,
                        flow_control="adaptive", seed=SEED, n_nodes=n_nodes,
                        replication_factor=min(2, n_nodes))


def _run_cell(store, uuids, cfg: LoaderConfig, n_batches: int,
              skip: int) -> dict:
    loader = CassandraLoader(store, uuids, cfg)
    loader.start()
    for _ in range(n_batches):
        loader.next_batch()
    st = loader.stats
    pool = loader.pool
    return {
        "MBps": st.throughput(skip=skip) / 1e6,
        "wire_MB": pool.bytes_received / 1e6,
        "payload_MB": pool.payload_bytes_received / 1e6,
        "budget_samples": loader.flow_controller.budget(),
        "encode_cpu_s": sum(n.encode_cpu_seconds
                            for n in loader.cluster.nodes.values()),
        "decode_cpu_s": pool.decode_cpu_seconds,
        "batch_ready_t": list(st.batch_ready_t),
    }


def _identity_cell(store, uuids, n_batches: int) -> dict:
    """wire_codec="none" vs a pool constructed with NO codec argument:
    identical batch timeline, wire == payload, zero codec CPU."""
    runs = {}
    for tag in ("explicit_none", "default"):
        cfg = LoaderConfig(batch_size=BATCH, route="high",
                           flow_control="adaptive", seed=SEED, n_nodes=2,
                           replication_factor=2)
        if tag == "explicit_none":
            cfg.wire_codec = "none"
            loader = CassandraLoader(store, uuids, cfg)
        else:
            # Bypass LoaderConfig's codec plumbing entirely: the pool is
            # built exactly as pre-codec callers build it.
            from repro.core.netsim import VirtualClock

            from repro.core import Cluster

            clock = VirtualClock()
            cluster = Cluster(clock, store, backend=cfg.backend,
                              n_nodes=cfg.n_nodes, rf=cfg.replication_factor,
                              seed=cfg.seed + 5)
            pool = ConnectionPool(clock, cluster, cfg.route,
                                  io_threads=cfg.io_threads,
                                  conns_per_thread=cfg.conns_per_thread,
                                  seed=cfg.seed + 11)
            loader = CassandraLoader(store, uuids, cfg, clock=clock,
                                     cluster=cluster, pool=pool)
        loader.start()
        for _ in range(n_batches):
            loader.next_batch()
        runs[tag] = {
            "ready_t": list(loader.stats.batch_ready_t),
            "wire": loader.pool.bytes_received,
            "payload": loader.pool.payload_bytes_received,
            "encode_cpu_s": sum(n.encode_cpu_seconds
                                for n in loader.cluster.nodes.values()),
            "decode_cpu_s": loader.pool.decode_cpu_seconds,
        }
    a, b = runs["explicit_none"], runs["default"]
    return {
        "timeline_equal": a["ready_t"] == b["ready_t"],
        "wire_eq_payload": (a["wire"] == a["payload"]
                            and b["wire"] == b["payload"]),
        "zero_codec_cpu": (a["encode_cpu_s"] == 0.0 == a["decode_cpu_s"]
                           and b["encode_cpu_s"] == 0.0 == b["decode_cpu_s"]),
    }


def run_codec_section(quick: bool) -> dict:
    n_samples = 20_000 if quick else 50_000
    n_batches = 150 if quick else 300
    skip = 100 if quick else 200
    store, uuids = make_store(n_samples=n_samples, seed=3)

    cells = {"high": {}, "local": {}}
    codecs = ["none", "byteshuffle"] if quick else ["none", "byteshuffle",
                                                    "int8"]
    for codec in codecs:
        # high: 4 nodes — the AIMD wire is the only bottleneck, compression
        # converts straight to payload throughput.
        cells["high"][codec] = _run_cell(
            store, uuids, _codec_cfg("high", codec, n_nodes=4),
            n_batches, skip)
    for codec in ("none", "byteshuffle"):
        # local: ONE node — its encode pool (cores x codec rate) sits just
        # below the NIC rate, so compression cannot pay here by design.
        cells["local"][codec] = _run_cell(
            store, uuids, _codec_cfg("local", codec, n_nodes=1),
            max(40, n_batches // 3), 2)

    identity = _identity_cell(store, uuids, n_batches=40)

    gain_high = (cells["high"]["byteshuffle"]["MBps"]
                 / cells["high"]["none"]["MBps"])
    gain_local = (cells["local"]["byteshuffle"]["MBps"]
                  / cells["local"]["none"]["MBps"])
    budget_ratio = (cells["high"]["byteshuffle"]["budget_samples"]
                    / cells["high"]["none"]["budget_samples"])
    for route in cells:
        for codec in cells[route]:
            cells[route][codec].pop("batch_ready_t")
    return {
        "cells": cells,
        "gain_high": gain_high,
        "gain_local": gain_local,
        "budget_ratio": budget_ratio,
        "identity": identity,
        "checks": {
            "high_codec_gain": gain_high >= 1.3,
            "codec_deepens_budget": budget_ratio >= 1.1,
            "local_codec_no_gain": gain_local <= 1.1,
            "none_bit_identical": all(identity.values()),
        },
    }


# ---------------------------------------------------------------------------
# Section B: pinned arena + fused on-device decode
# ---------------------------------------------------------------------------


def _pixel_feed(store, uuids, ds, use_arena: bool, batch_size: int,
                out_hw: int):
    from repro.data.pipeline import ImageFeed

    cfg = LoaderConfig(batch_size=batch_size, route="local",
                       materialize=True, use_arena=use_arena,
                       arena_slot_bytes=ds.nbytes, seed=SEED)
    loader = CassandraLoader(store, uuids, cfg)
    feed = ImageFeed(loader, ds.h, ds.w, ds.c, out_h=out_hw, out_w=out_hw,
                     seed=SEED + 1, interpret=True)
    return loader, feed


def run_arena_section(quick: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kernel_ops

    batch_size = 32 if quick else 64
    n_batches = 8 if quick else 24
    hw = 64
    out_hw = 56
    ds = SyntheticPixelDataset(n_samples=1024 if quick else 4096,
                               h=hw, w=hw, c=3, seed=5)
    store = KVStore()
    uuids = ingest(store, ds)

    # Warm up JAX (backend init + kernel compile) so neither path's timed
    # window pays first-call costs.
    warm = jnp.zeros((batch_size, hw, hw, 3), jnp.uint8)
    zero = jnp.zeros((batch_size,), jnp.int32)
    kernel_ops.crop_mirror_normalize(
        warm, zero, zero, zero, jnp.zeros(3), jnp.ones(3),
        out_h=out_hw, out_w=out_hw, interpret=True).block_until_ready()
    jax.device_put(np.zeros((batch_size, 3, out_hw, out_hw),
                            np.float32)).block_until_ready()

    out = {}
    first_images = {}
    for mode, use_arena in (("materialize", False), ("arena", True)):
        loader, feed = _pixel_feed(store, uuids, ds, use_arena, batch_size,
                                   out_hw)
        t0 = time.perf_counter()
        for i in range(n_batches):
            dev, _meta = next(feed)
            if i == 0:
                first_images[mode] = np.asarray(dev["images"])
        wall = time.perf_counter() - t0
        out[mode] = {
            "host_prep_s": feed.host_prep_s,
            "host_prep_ms_per_batch": feed.host_prep_s / feed.batches * 1e3,
            "wall_s": wall,
            "loader_MBps": loader.stats.throughput(skip=2) / 1e6,
        }
        if use_arena:
            out[mode]["arena"] = loader.arena.stats()

    ratio = out["arena"]["host_prep_s"] / out["materialize"]["host_prep_s"]
    max_diff = float(np.abs(first_images["arena"]
                            - first_images["materialize"]).max())
    stats = out["arena"]["arena"]
    return {
        "modes": out,
        "host_cpu_ratio": ratio,
        "max_abs_diff": max_diff,
        "checks": {
            "arena_matches_materialize": max_diff <= 1e-5,
            "arena_halves_host_cpu": ratio <= 0.5,
            "arena_reuses_slabs": (stats["slabs_created"] <= 3
                                   and stats["reuses"] > 0),
        },
    }


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true",
                    help="CI sizing (smaller dataset, fewer batches)")
    args = ap.parse_args(argv)

    print(f"== bench_wirefmt ({'quick' if args.quick else 'full'}) ==")
    t0 = time.time()
    codec = run_codec_section(args.quick)
    print(f"  codec: high gain {codec['gain_high']:.2f}x "
          f"(budget {codec['budget_ratio']:.2f}x deeper), "
          f"local gain {codec['gain_local']:.2f}x "
          f"[{time.time() - t0:.1f}s]")
    t1 = time.time()
    arena = run_arena_section(args.quick)
    print(f"  arena: host CPU {arena['host_cpu_ratio']:.2f}x materialize, "
          f"max|diff| {arena['max_abs_diff']:.1e} "
          f"[{time.time() - t1:.1f}s]")

    results = {
        "quick": args.quick,
        "batch_size": BATCH,
        "n_samples": 20_000 if args.quick else 50_000,
        "n_batches": 150 if args.quick else 300,
        "seed": SEED,
        "codec": codec,
        "arena": arena,
        "checks": {**{f"codec.{k}": v for k, v in codec["checks"].items()},
                   **{f"arena.{k}": v for k, v in arena["checks"].items()}},
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "wirefmt.json")
    with open(path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
    print(f"  wrote {os.path.relpath(path)}")

    # Assert the acceptance criteria from the *written* results file, so a
    # hand-edited file can't diverge from what the gate saw.
    written = json.load(open(path))
    failed = [k for k, ok in written["checks"].items() if not ok]
    if failed:
        print(f"bench_wirefmt FAILED checks: {failed}")
        return 1
    print("bench_wirefmt: all checks passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
