"""Image cells: the paper's tight loop (Sec. 4.2.1) on the program's image
feed, over a route that elapses in wall time.

The window calls ``next()`` on the feed of
``build_stack(config=LoaderConfig(..., virtual_clock=False, materialize=True,
use_arena=True), feed="image")`` as fast as it returns, with nothing else on
the host.  Each call hands back a batch that the crop kernel has finished.

Correct means, against the benchmark's own generator and NumPy transform:

* every delivered key is one of the dataset's keys (``unknown_keys``);
* no key comes back twice within an epoch, less the reordering that the
  in-flight window allows at its ends (``epoch_repeats``);
* every delivered label is its key's label, in the loader's metadata and,
  for the sampled batches, on the device (``label_mismatches``);
* the device batch of each sampled batch equals the reference transform of
  the key's frame under the replayed augmentation draw
  (``pixel_max_abs_diff``, bit for bit).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from chipbench import timing
from chipbench.harness import Run
from chipbench.ref import crop as ref
from chipbench.traffic import pixels


def build(run: Run, rows: pixels.PixelRows):
    """The store and the image stack, from the generated rows alone."""
    from repro.core import KVStore, LoaderConfig, build_stack
    from repro.core.kvstore import DataRow, MetaRow

    cfg, wl = run.config, run.workload
    r, ld, crop = cfg["rows"], cfg["loader"], cfg["crop"]
    nbytes = r["h"] * r["w"] * r["c"]
    blobs = [f.tobytes() for f in rows.frames]
    labels = rows.key_labels
    store = KVStore()
    for i, key in enumerate(rows.keys):
        lab = int(labels[i])
        store.insert_atomic(
            DataRow(key, lab, nbytes, payload=blobs[rows.frame_of_key[i]]),
            MetaRow(key, "", lab, {}))
    loader_cfg = LoaderConfig(
        batch_size=ld["batch_size"], prefetch_buffers=ld["prefetch_buffers"],
        io_threads=ld["io_threads"], conns_per_thread=ld["conns_per_thread"],
        out_of_order=ld["out_of_order"],
        incremental_ramp=ld["incremental_ramp"], ramp_every=ld["ramp_every"],
        route=wl["route"], backend=ld["backend"], n_nodes=ld["n_nodes"],
        flow_control=ld["flow_control"], wire_codec=ld["wire_codec"],
        seed=wl["route_seed"], materialize=True, virtual_clock=False,
        use_arena=ld["use_arena"], arena_slot_bytes=nbytes)
    return build_stack(
        store=store, uuids=rows.keys, config=loader_cfg, feed="image",
        image_shape=(r["h"], r["w"], r["c"]),
        out_shape=(crop["out_h"], crop["out_w"]),
        feed_prefetch=ld["feed_prefetch"], mean=crop["mean"], std=crop["std"],
        feed_seed=feed_seed(run.seed), interpret=run.interpret)


def feed_seed(seed: int) -> int:
    return int(np.random.default_rng([seed, 0xFEED]).integers(2 ** 62))


class Sample:
    """A seeded reservoir of ``k`` batches out of a stream of unknown
    length, each kept with its index in pull order."""

    def __init__(self, k: int, seed: int) -> None:
        self.k = k
        self.rng = np.random.default_rng([seed, 0x5A4D])
        self.kept: list = []
        self.seen = 0

    def offer(self, item) -> None:
        if len(self.kept) < self.k:
            self.kept.append(item)
        else:
            j = int(self.rng.integers(0, self.seen + 1))
            if j < self.k:
                self.kept[j] = item
        self.seen += 1


def window_report(call_s, wait_s, host_prep_s: float) -> str:
    """Where the window's batches went on the host clock, per batch."""
    n = len(call_s)
    return (f"window: {n} batches, next() p50 "
            f"{1e3 * timing.percentile(call_s, 50):.2f} ms, p90 "
            f"{1e3 * timing.percentile(call_s, 90):.2f} ms, max "
            f"{1e3 * max(call_s):.2f} ms; loader wait "
            f"{1e3 * float(np.mean(wait_s)):.2f} ms, feed prep "
            f"{1e3 * host_prep_s / n:.2f} ms")


def epoch_repeats(key_index: np.ndarray, epoch_len: int, slack: int) -> int:
    """Keys delivered twice inside one epoch.  The loader delivers every
    key once per epoch, out of order by at most ``slack`` deliveries, so
    each epoch's stretch of the stream, less ``slack`` at either end, holds
    no key twice."""
    bad = 0
    for lo in range(0, len(key_index), epoch_len):
        inner = key_index[lo + slack:lo + epoch_len - slack]
        bad += len(inner) - len(np.unique(inner))
    return bad


def run(run: Run) -> None:
    cfg, wl = run.config, run.workload
    r, ld, crop = cfg["rows"], cfg["loader"], cfg["crop"]
    B = ld["batch_size"]
    rows = pixels.generate(run.seed, r["n_keys"], r["n_frames"], r["h"],
                           r["w"], r["c"], r["n_classes"])
    index_of = {k: i for i, k in enumerate(rows.keys)}
    stack = build(run, rows)
    feed = stack.feed
    delivered: list = []             # key index per delivered row, in order
    meta_labels: list = []
    sample = Sample(wl["sample_batches"], run.seed)

    def pull():
        with run.span("bench.next_batch"):
            batch, meta = next(feed)
        delivered.extend(index_of.get(u, -1) for u in meta.uuids)
        meta_labels.extend(meta.labels.tolist())
        return batch

    try:
        for _ in range(wl["warmup_batches"]):
            pull()
        run.setup_done()
        waits0, prep0, pos = len(feed.step_stats.wait_s), feed.host_prep_s, \
            wl["warmup_batches"]
        call_s = []
        with run.window():
            start = time.perf_counter()
            deadline = start + run.seconds
            while True:
                t0 = time.perf_counter()
                batch = pull()
                end = time.perf_counter()
                call_s.append(end - t0)
                sample.offer((pos, batch))
                pos += 1
                if end >= deadline:
                    break
        n = len(call_s)
        run.attempted = n
        run.e2e["images_per_s"] = timing.rate(n * B, start, end)
        run.e2e["next_batch_p90_ms"] = 1e3 * timing.percentile(call_s, 90)
        run.counters.update(
            batches=n,
            wait_s=list(feed.step_stats.wait_s[waits0:waits0 + n]),
            host_prep_s=feed.host_prep_s - prep0,
            crop_shape=(B, crop["out_h"], crop["out_w"], r["c"]))
        run.read_memory_peak()
        print(window_report(call_s, run.counters["wait_s"],
                            run.counters["host_prep_s"]),
              file=sys.stderr, flush=True)
    finally:
        stack.close()

    # -- the comparison with the plain reference, after the window ----------
    check(run, rows, np.asarray(delivered), np.asarray(meta_labels),
              sorted(sample.kept, key=lambda kv: kv[0]))


def check(run: Run, rows: pixels.PixelRows, delivered: np.ndarray,
          meta_labels: np.ndarray, kept: list) -> None:
    cfg = run.config
    r, ld, crop = cfg["rows"], cfg["loader"], cfg["crop"]
    B = ld["batch_size"]
    key_labels = rows.key_labels
    known = delivered >= 0
    run.check("unknown_keys", int((~known).sum()), 0)
    # a key may come back early by as much as the loader holds in flight
    # and assembled: its buffers, one batch assembling, the feed's queue
    slack = (ld["prefetch_buffers"] + 2 + ld["feed_prefetch"]) * B
    run.check("epoch_repeats",
              epoch_repeats(delivered, r["n_keys"], slack), 0)
    mismatched = int((meta_labels[known] != key_labels[delivered[known]]).sum())
    draws = ref.replay_draws(feed_seed(run.seed), kept[-1][0] + 1, B, r["h"],
                             r["w"], crop["out_h"], crop["out_w"])
    worst = 0.0
    for pos, batch in kept:
        keys = delivered[pos * B:(pos + 1) * B]
        if (keys < 0).any():
            worst = float("inf")
            continue
        oy, ox, mirror = draws[pos]
        pix = rows.frames[rows.frame_of_key[keys]]
        want = ref.crop_mirror_normalize(pix, oy, ox, mirror, crop["mean"],
                                         crop["std"], crop["out_h"],
                                         crop["out_w"])
        if run.substitute is not None:
            got = run.substitute("images", batch, pix, (oy, ox, mirror))
        else:
            got = np.asarray(batch["images"])
        labels = np.asarray(batch["labels"])
        mismatched += int((labels != key_labels[keys]).sum())
        if got.shape != want.shape:
            worst = float("inf")
            continue
        diff = np.abs(got.astype(np.float32) - want)
        worst = max(worst, float(np.nan_to_num(diff, nan=np.inf).max()))
    run.check("label_mismatches", mismatched, 0)
    run.check("pixel_max_abs_diff", worst, 0.0)
