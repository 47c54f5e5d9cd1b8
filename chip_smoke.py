"""Smoke test of the main path on one TPU chip, through the user entry points.

    python3 chip_smoke.py [--seed N]

Phase A — the paper's image path: 256x256x3 uint8 rows in the KV store,
``build_stack(feed="image")`` with the pinned arena over the 150 ms route,
batches of 128 cropped to 224x224 by the Pallas kernel on the device.  Every
batch must equal the NumPy transform of the store's own bytes under the same
augmentation draws, bit for bit.

Phase B — training at full width: ``repro.launch.train`` runs stablelm-1.6b
(24 layers, d=2048) at batch 2 x seq 2048 with int8/factored optimizer
state for 4 steps and checkpoints, then resumes from that bf16 checkpoint
to step 8.  Every loss must be finite.

Each phase prints its shapes, compile seconds, host-clock step times and the
device's ``peak_bytes_in_use``.  The last line is one JSON object naming the
device.  Without a TPU the script exits non-zero before doing anything.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

IMAGENET_MEAN = [123.675, 116.28, 103.53]     # 0.485, 0.456, 0.406 x 255
IMAGENET_STD = [58.395, 57.12, 57.375]        # 0.229, 0.224, 0.225 x 255
ROWS, SIDE, CROP, BATCH, N_BATCHES = 1024, 256, 224, 128, 8
TRAIN_ARGS = ["--arch", "stablelm_1_6b", "--batch-size", "2",
              "--seq-len", "2048", "--opt-state-dtype", "int8_factored"]


def check(ok: bool, what: str) -> None:
    """Fail the run (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def check_kernel(feed, B: int, h: int, w: int, out: int) -> None:
    """Lower and compile the feed's jitted kernel call on its own: it must
    be a Pallas TPU custom call.  Prints compile and call seconds."""
    import jax.numpy as jnp

    from repro.kernels import ops

    img = jnp.zeros((B, h, w, 3), jnp.uint8)
    idx = jnp.zeros((B,), jnp.int32)
    args = (img, idx, idx, idx, jnp.asarray(feed.mean),
            jnp.asarray(feed.inv_std))
    lowered = ops.crop_mirror_normalize.lower(*args, out_h=out, out_w=out)
    check("tpu_custom_call" in lowered.as_text(),
          "the crop kernel is not a Pallas TPU custom call")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    print(f"A: kernel crop_mirror_normalize {tuple(img.shape)} uint8 -> "
          f"({B}, 3, {out}, {out}) float32, tpu_custom_call present, "
          f"compile_s {time.perf_counter() - t0:.3f}", flush=True)
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    print(f"A: kernel call_s {min(times):.6f} (min of 5, host clock)",
          flush=True)


def phase_a(dev, seed: int) -> None:
    import numpy as np

    from repro.core import KVStore, LoaderConfig, build_stack
    from repro.data.datasets import SyntheticPixelDataset, ingest
    from repro.data.pipeline import augment_draws
    from repro.kernels.ref import crop_mirror_normalize_np

    B, out, n_batches = BATCH, CROP, N_BATCHES
    ds = SyntheticPixelDataset(n_samples=ROWS, h=SIDE, w=SIDE, c=3,
                               seed=seed)
    store = KVStore()
    t0 = time.perf_counter()
    uuids = ingest(store, ds)
    print(f"A: ingested {len(uuids)} rows of {ds.nbytes} B "
          f"in {time.perf_counter() - t0:.2f} s", flush=True)

    stack = build_stack(
        store=store, uuids=uuids,
        config=LoaderConfig(batch_size=B, route="high", materialize=True,
                            use_arena=True, arena_slot_bytes=ds.nbytes,
                            seed=seed),
        feed="image", image_shape=(ds.h, ds.w, ds.c), out_shape=(out, out),
        mean=IMAGENET_MEAN, std=IMAGENET_STD, feed_seed=seed + 1)
    feed = stack.feed
    check_kernel(feed, B, ds.h, ds.w, out)

    rng = np.random.default_rng(seed + 1)      # replays the feed's draws
    for i in range(n_batches):
        t0 = time.perf_counter()
        batch, meta = next(feed)
        images = np.asarray(batch["images"])
        next_s = time.perf_counter() - t0
        oy, ox, mirror = augment_draws(rng, B, ds.h, ds.w, out, out)
        pixels = np.stack([
            np.frombuffer(store.get_data(u).payload, dtype=np.uint8
                          ).reshape(ds.h, ds.w, ds.c) for u in meta.uuids])
        want = crop_mirror_normalize_np(pixels, oy, ox, mirror, feed.mean,
                                        feed.inv_std, out, out)
        check(images.shape == want.shape and images.dtype == want.dtype,
              f"batch {i} is {images.shape} {images.dtype}")
        diff = float(np.abs(images - want).max())
        print(f"A: batch {i} images {images.shape} next_s {next_s:.4f} "
              f"max_abs_diff {diff}", flush=True)
        check(diff == 0.0, f"batch {i} differs from the NumPy reference")
        check((np.asarray(batch["labels"]) == meta.labels).all(),
              f"batch {i} labels differ from the store's")
    arena = stack.loader.arena.stats()
    print(f"A: arena slabs_created {arena['slabs_created']} "
          f"reuses {arena['reuses']}, host_prep_s {feed.host_prep_s:.4f}, "
          f"peak_bytes_in_use {peak_bytes(dev)}", flush=True)
    check(arena["reuses"] > 0, "arena slabs were never reused")
    stack.close()


def phase_b(dev, seed: int) -> None:
    from repro.launch.train import main as train_main

    ckpt = os.path.join(HERE, ".chip_smoke", "ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    common = TRAIN_ARGS + ["--route", "high", "--log-every", "1",
                           "--checkpoint-dir", ckpt, "--seed", str(seed)]
    try:
        losses = []
        for steps, first in ((4, 1), (8, 5)):
            print(f"B: train {' '.join(TRAIN_ARGS)} to step {steps}",
                  flush=True)
            result = train_main(common + ["--steps", str(steps)])
            hist = result.pop("history")
            del result                         # frees the state on the chip
            check([h["step"] for h in hist] == list(range(first, steps + 1)),
                  f"run to step {steps} logged steps "
                  f"{[h['step'] for h in hist]}")
            print(f"B: first step (compile included) "
                  f"{hist[0]['step_s']:.3f} s, later steps "
                  f"{[round(h['step_s'], 4) for h in hist[1:]]}", flush=True)
            losses += [h["loss"] for h in hist]
        print(f"B: losses {losses}, peak_bytes_in_use {peak_bytes(dev)}",
              flush=True)
        check(len(losses) == 8 and all(math.isfinite(x) for x in losses),
              "a loss is missing or not finite")
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r})")
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device {dev.device_kind} x{len(jax.devices())}, "
          f"compile cache {enable_compile_cache()}", flush=True)
    phase_a(dev, args.seed)
    phase_b(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
