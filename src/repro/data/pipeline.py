"""Loader -> JAX device feed.

Bridges the paper's loader (``AssembledBatch``) to jitted consumers.  One
pull loop keeps a queue of ``prefetch`` (default 2) formed batches ahead of
the consumer, refilled behind each ``next()``; two feeds plug into it:

  * ``DeviceFeed`` decodes token records on the host (numpy) and
    ``device_put``s them without waiting for the copy;
  * ``ImageFeed`` uploads an arena slab of uint8 frames and runs the fused
    crop/mirror/normalize kernel.  It blocks on the upload and the kernel
    before it recycles the slab, so there the copy does not overlap.
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


class _Feed:
    """The pull loop both device feeds share; a subclass supplies only
    ``_form(batch) -> dict of device arrays``.

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

    def __init__(self, loader: CassandraLoader, prefetch: int,
                 step_stats: Optional[StepStats]) -> None:
        self.loader = loader
        self.prefetch = prefetch
        self.step_stats = step_stats or StepStats(loader.clock)
        self._queue: collections.deque = collections.deque()
        self._started = False

    def _form(self, batch) -> Dict[str, jax.Array]:
        raise NotImplementedError

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
        self._queue.append((self._form(batch), batch))
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


class DeviceFeed(_Feed):
    """Token records -> ``{"tokens", "loss_mask", "labels"}`` device arrays,
    ``prefetch`` batches ahead of the consumer."""

    def __init__(self, loader: CassandraLoader, seq_len: int,
                 prefetch: int = 2,
                 step_stats: Optional[StepStats] = None) -> None:
        super().__init__(loader, prefetch, step_stats)
        self.seq_len = seq_len

    def _form(self, batch) -> Dict[str, jax.Array]:
        with span("feed.decode", batch=batch.seq):
            host = batch_to_numpy(batch, self.seq_len)
        # Host copy is complete: recycle the arena slab (no-op without one).
        batch.release()
        with span("feed.upload", batch=batch.seq):
            return {k: jax.device_put(v) for k, v in host.items()}


def augment_draws(rng: np.random.Generator, B: int, h: int, w: int,
                  out_h: int, out_w: int):
    """One batch's crop offsets and mirror flags, ``(oy, ox, mirror)`` int32
    of shape ``(B,)``.  ``ImageFeed`` makes one such draw per batch in pull
    order; a checker that replays the same seed gets the same draws."""
    oy = rng.integers(0, h - out_h + 1, size=B)
    ox = rng.integers(0, w - out_w + 1, size=B)
    mirror = rng.integers(0, 2, size=B)
    return oy.astype(np.int32), ox.astype(np.int32), mirror.astype(np.int32)


class ImageFeed(_Feed):
    """Fixed-size pixel rows (e.g. ``SyntheticPixelDataset``) ->
    ``{"images", "labels"}`` device arrays, with fused on-device
    crop/mirror/normalize.

    The loader must carry an arena (``LoaderConfig.use_arena=True``):
    ``batch.pixels()`` views the slab as one contiguous ``(B, h, w, c)``
    uint8 tensor, a *single* ``device_put`` uploads it, and the Pallas
    ``crop_mirror_normalize`` kernel does the crop + mirror + uint8->f32 +
    normalize + HWC->CHW fused on device.  The host never materializes a
    float batch.

    Crop offsets and mirror flags come from one seeded RNG stream, one
    draw per batch in pull order (``augment_draws``), so a checker that
    replays the seed reproduces every batch with the NumPy reference.
    Per-batch host prep wall time (the slab view and the upload's
    hand-off, *not* device compute) accumulates in ``host_prep_s``.
    """

    def __init__(self, loader: CassandraLoader, h: int, w: int, c: int,
                 out_h: int, out_w: int,
                 mean=None, std=None, seed: int = 0, prefetch: int = 2,
                 step_stats: Optional[StepStats] = None,
                 interpret: bool = False) -> None:
        super().__init__(loader, prefetch, step_stats)
        self.h, self.w, self.c = h, w, c
        self.out_h, self.out_w = out_h, out_w
        self.mean = np.asarray(
            mean if mean is not None else [127.5] * c, dtype=np.float32)
        # DALI's form: one host reciprocal, then (x - mean) * inv_std, so
        # the kernel and the NumPy reference agree bit for bit
        self.inv_std = np.float32(1.0) / np.asarray(
            std if std is not None else [64.0] * c, dtype=np.float32)
        self.interpret = interpret
        self.host_prep_s = 0.0
        self._rng = np.random.default_rng(seed)

    def _form(self, batch) -> Dict[str, jax.Array]:
        # The kernel import stays lazy: token-path users of this module
        # never pay for building the Pallas kernels.
        from repro.kernels import ops as kernel_ops

        oy, ox, mirror = augment_draws(self._rng, len(batch.samples), self.h,
                                       self.w, self.out_h, self.out_w)
        labels = batch.labels
        seq = batch.seq
        with span("feed.upload", batch=seq):
            t0 = time.perf_counter()
            pix = batch.pixels(self.h, self.w, self.c)       # zero-copy view
            img_dev = jax.device_put(pix)                    # ONE uint8 upload
            self.host_prep_s += time.perf_counter() - t0
        with span("feed.kernel", batch=seq):
            images = kernel_ops.crop_mirror_normalize(
                img_dev, jnp.asarray(oy), jnp.asarray(ox),
                jnp.asarray(mirror), jnp.asarray(self.mean),
                jnp.asarray(self.inv_std), out_h=self.out_h,
                out_w=self.out_w, interpret=self.interpret)
        # The loader refills a released slab with the next batch.  The
        # upload is asynchronous (and on the CPU backend img_dev may be the
        # slab itself), so recycle it only once the kernel that reads it is
        # done.
        with span("feed.kernel_wait", batch=seq):
            images.block_until_ready()
        with span("feed.release", batch=seq):
            batch.release()
            labels_dev = jax.device_put(labels)
        return {"images": images, "labels": labels_dev}


__all__ = ["DeviceFeed", "ImageFeed", "augment_draws", "batch_to_numpy"]
