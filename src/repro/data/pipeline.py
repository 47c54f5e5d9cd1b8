"""Loader -> JAX device feed.

Bridges the paper's loader (AssembledBatch of token-record blobs) to jitted
train steps:
  * decodes token records on host (numpy),
  * assembles the per-host shard of the global batch,
  * forms jax.Arrays laid out for the mesh
    (``jax.make_array_from_process_local_data`` on multi-host,
    plain device_put on single-host),
  * keeps a device-side prefetch queue of depth 2 (double buffering) so
    H2D copy overlaps the train step — the on-device mirror of the paper's
    host-side prefetching.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.loader import CassandraLoader
from repro.core.stats import StepStats, span
from repro.data.datasets import decode_token_record


def batch_to_numpy(batch, seq_len: int, pad_id: int = 0) -> Dict[str, np.ndarray]:
    """Decode an AssembledBatch of token records into dense arrays.

    Reads through ``batch.payloads()`` so arena-backed batches (whose
    per-sample ``payload`` refs were dropped at assembly) decode from
    zero-copy slab views, and legacy batches keep decoding their bytes.
    """
    B = len(batch.samples)
    tokens = np.full((B, seq_len), pad_id, dtype=np.int32)
    mask = np.zeros((B, seq_len), dtype=np.float32)
    labels = np.zeros((B,), dtype=np.int32)
    for i, payload in enumerate(batch.payloads()):
        if payload is None:
            raise ValueError("pipeline requires materialized payloads "
                             "(LoaderConfig.materialize=True)")
        toks, label = decode_token_record(payload)
        n = min(len(toks), seq_len)
        tokens[i, :n] = toks[:n]
        mask[i, :n] = 1.0
        labels[i] = label
    return {"tokens": tokens, "loss_mask": mask, "labels": labels}


class DeviceFeed:
    """Iterator of device-resident batches with double buffering.

    Beyond forming device arrays, the feed is the measurement point for
    per-step data-stall accounting: every ``__next__`` reports to
    ``step_stats`` (a ``core.stats.StepStats``) how long it blocked on the
    loader — on the *loader's* clock, so virtual-clock sims and wall-clock
    runs are both internally consistent — and whether the batch was served
    straight from an already-assembled buffer.  The training loop closes
    each step with ``step_stats.on_compute``.

    The feed also owns the *consumer-facing* checkpoint position:
    ``state()`` is the loader position rewound by the batches sitting in
    the device queue (pulled past the loader cursor but never handed to the
    trainer).  Checkpointing ``loader.state()`` directly would skip those
    in-flight batches on restore; checkpointing ``feed.state()`` makes
    restore exactly-once.
    """

    def __init__(self, loader: CassandraLoader, seq_len: int,
                 shardings: Optional[Dict] = None, mesh=None,
                 prefetch: int = 2,
                 step_stats: Optional[StepStats] = None) -> None:
        self.loader = loader
        self.seq_len = seq_len
        self.shardings = shardings
        self.mesh = mesh
        self.prefetch = prefetch
        self.step_stats = step_stats or StepStats(loader.clock)
        self._queue: collections.deque = collections.deque()
        self._started = False

    def _put(self, host_batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        out = {}
        for k, v in host_batch.items():
            sh = (self.shardings or {}).get(k)
            if sh is not None and jax.process_count() > 1:  # pragma: no cover
                out[k] = jax.make_array_from_process_local_data(sh, v)
            elif sh is not None:
                out[k] = jax.device_put(v, sh)
            else:
                out[k] = jax.device_put(v)
        return out

    def _pull_one(self) -> tuple:
        """Pull one batch from the loader onto the device queue.  Returns
        ``(wait_seconds, buffer_hit)`` on the loader's clock."""
        hit = self.loader.ready_batches > 0
        clk = self.loader.clock
        with span("feed.loader_wait") as sp:
            t0 = clk.now()
            batch = self.loader.next_batch()
            wait = clk.now() - t0
            sp.tag(batch=batch.seq)
        with span("feed.decode", batch=batch.seq):
            host = batch_to_numpy(batch, self.seq_len)
        # Host copy is complete: recycle the arena slab (no-op without one).
        batch.release()
        with span("feed.upload", batch=batch.seq):
            dev = self._put(host)
        self._queue.append((dev, batch))
        return wait, hit

    # -- checkpointing ------------------------------------------------------
    def state(self) -> dict:
        """Consumer-facing loader position: the loader cursor rewound by the
        device-queue batches the trainer has not consumed yet."""
        return self.loader.state(rewind_batches=len(self._queue))

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        with span("feed.next") as sp:
            wait, hit = 0.0, True
            if not self._started:
                if not self.loader.started:
                    self.loader.start()
                self._started = True
                for _ in range(self.prefetch):
                    w, h = self._pull_one()
                    wait += w
                    hit = hit and h
            dev_batch, meta = self._queue.popleft()
            sp.tag(batch=meta.seq)
            w, h = self._pull_one()              # refill behind the consumer
            self.step_stats.on_wait(wait + w, blocked=not (hit and h))
            return dev_batch, meta


def augment_draws(rng: np.random.Generator, B: int, h: int, w: int,
                  out_h: int, out_w: int):
    """One batch's crop offsets and mirror flags, ``(oy, ox, mirror)`` int32
    of shape ``(B,)``.  ``ImageFeed`` makes one such draw per batch in pull
    order; a checker that replays the same seed gets the same draws."""
    oy = rng.integers(0, h - out_h + 1, size=B)
    ox = rng.integers(0, w - out_w + 1, size=B)
    mirror = rng.integers(0, 2, size=B)
    return oy.astype(np.int32), ox.astype(np.int32), mirror.astype(np.int32)


class ImageFeed:
    """Loader -> device feed for fixed-size pixel rows (e.g.
    ``SyntheticPixelDataset``) with fused on-device crop/mirror/normalize.

    Two host paths, selected by whether the loader carries a pinned arena
    (``LoaderConfig.use_arena=True``):

    * **arena** (zero-copy): ``batch.pixels()`` views the slab as one
      contiguous ``(B, h, w, c)`` uint8 tensor, a *single* ``device_put``
      uploads it, and the Pallas ``crop_mirror_normalize`` kernel does the
      crop + mirror + uint8->f32 + normalize + HWC->CHW fused on device.
      The host never materializes a float batch.
    * **materialize** (baseline): per-sample ``np.frombuffer`` -> stack ->
      the NumPy reference transform (four passes over f32 data) ->
      ``device_put`` of the float output.  This is the classic CPU pipeline
      the paper's DALI path replaces.

    Both paths draw crop offsets / mirror flags from the same seeded RNG
    stream (one draw per batch, in pull order), so a pair of runs that
    differs only in the path produces identical augmentations — the
    property ``bench_wirefmt``'s equivalence check and the host-CPU ratio
    comparison rely on.  Per-batch host prep wall time (everything up to
    and including the H2D hand-off, *not* device compute) accumulates in
    ``host_prep_s``.
    """

    def __init__(self, loader: CassandraLoader, h: int, w: int, c: int,
                 out_h: int, out_w: int,
                 mean=None, std=None, seed: int = 0, prefetch: int = 2,
                 step_stats: Optional[StepStats] = None,
                 interpret: bool = False) -> None:
        self.loader = loader
        self.h, self.w, self.c = h, w, c
        self.out_h, self.out_w = out_h, out_w
        self.mean = np.asarray(
            mean if mean is not None else [127.5] * c, dtype=np.float32)
        # DALI's form: one host reciprocal, then (x - mean) * inv_std on
        # both paths, so the kernel and the NumPy transform agree bit for bit
        self.inv_std = np.float32(1.0) / np.asarray(
            std if std is not None else [64.0] * c, dtype=np.float32)
        self.interpret = interpret
        self.prefetch = prefetch
        self.step_stats = step_stats or StepStats(loader.clock)
        self.mode = "arena" if getattr(loader, "arena", None) else "materialize"
        self.host_prep_s = 0.0
        self.batches = 0
        self._rng = np.random.default_rng(seed)
        self._queue: collections.deque = collections.deque()
        self._started = False

    def _form(self, batch) -> Dict[str, jax.Array]:
        # Kernel imports stay lazy: token-path users of this module never
        # pay for building the Pallas kernels.
        from repro.kernels import ops as kernel_ops
        from repro.kernels.ref import crop_mirror_normalize_np

        B = len(batch.samples)
        oy, ox, mirror = augment_draws(self._rng, B, self.h, self.w,
                                       self.out_h, self.out_w)
        labels = batch.labels
        seq = batch.seq
        if self.mode == "arena":
            with span("feed.upload", batch=seq):
                t0 = time.perf_counter()
                pix = batch.pixels(self.h, self.w, self.c)   # zero-copy view
                img_dev = jax.device_put(pix)                # ONE uint8 upload
                self.host_prep_s += time.perf_counter() - t0
            with span("feed.kernel", batch=seq):
                images = kernel_ops.crop_mirror_normalize(
                    img_dev, jnp.asarray(oy), jnp.asarray(ox),
                    jnp.asarray(mirror), jnp.asarray(self.mean),
                    jnp.asarray(self.inv_std), out_h=self.out_h,
                    out_w=self.out_w, interpret=self.interpret)
            # The loader refills a released slab with the next batch.  The
            # upload is asynchronous (and on the CPU backend img_dev may be
            # the slab itself), so recycle it only once the kernel that
            # reads it is done.
            with span("feed.kernel_wait", batch=seq):
                images.block_until_ready()
            with span("feed.release", batch=seq):
                batch.release()
                labels_dev = jax.device_put(labels)
        else:
            t0 = time.perf_counter()
            n = self.h * self.w * self.c
            imgs = np.stack([
                np.frombuffer(p, dtype=np.uint8,
                              count=n).reshape(self.h, self.w, self.c)
                for p in batch.payloads()])
            host = crop_mirror_normalize_np(
                imgs, oy, ox, mirror, self.mean, self.inv_std,
                self.out_h, self.out_w)
            images = jax.device_put(host)
            self.host_prep_s += time.perf_counter() - t0
            labels_dev = jax.device_put(labels)
        self.batches += 1
        return {"images": images, "labels": labels_dev}

    def _pull_one(self) -> tuple:
        hit = self.loader.ready_batches > 0
        clk = self.loader.clock
        with span("feed.loader_wait") as sp:
            t0 = clk.now()
            batch = self.loader.next_batch()
            wait = clk.now() - t0
            sp.tag(batch=batch.seq)
        self._queue.append((self._form(batch), batch))
        return wait, hit

    def state(self) -> dict:
        """Consumer-facing loader position (see ``DeviceFeed.state``)."""
        return self.loader.state(rewind_batches=len(self._queue))

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        with span("feed.next") as sp:
            wait, hit = 0.0, True
            if not self._started:
                if not self.loader.started:
                    self.loader.start()
                self._started = True
                for _ in range(self.prefetch):
                    w, h = self._pull_one()
                    wait += w
                    hit = hit and h
            dev_batch, meta = self._queue.popleft()
            sp.tag(batch=meta.seq)
            w, h = self._pull_one()              # refill behind the consumer
            self.step_stats.on_wait(wait + w, blocked=not (hit and h))
            return dev_batch, meta


__all__ = ["DeviceFeed", "ImageFeed", "augment_draws", "batch_to_numpy"]
