"""Plain NumPy reference of the image feed's device transform.

DALI's ``crop_mirror_normalize`` as the image feed states it: for each
image a crop window at ``(oy, ox)``, mirrored left-right where the draw
says so, then ``(x - mean) * inv_std`` per channel in float32 (the
reciprocal taken once, in float32), laid out channels first.

The augmentation draws are replayed from the feed's seed: one draw per
batch, in the order the feed pulls batches, of ``oy`` then ``ox`` then the
mirror flags, each uniform over its range.
"""

from __future__ import annotations

import numpy as np


def augment_draws(rng: np.random.Generator, batch: int, h: int, w: int,
                  out_h: int, out_w: int):
    oy = rng.integers(0, h - out_h + 1, size=batch)
    ox = rng.integers(0, w - out_w + 1, size=batch)
    mirror = rng.integers(0, 2, size=batch)
    return oy.astype(np.int32), ox.astype(np.int32), mirror.astype(np.int32)


def replay_draws(seed: int, n_batches: int, batch: int, h: int, w: int,
                 out_h: int, out_w: int) -> list:
    """The draws of the first ``n_batches`` batches the feed pulls."""
    rng = np.random.default_rng(seed)
    return [augment_draws(rng, batch, h, w, out_h, out_w)
            for _ in range(n_batches)]


def inv_std(std) -> np.ndarray:
    return np.float32(1.0) / np.asarray(std, dtype=np.float32)


def crop_mirror_normalize(img: np.ndarray, oy, ox, mirror, mean, std,
                          out_h: int, out_w: int,
                          dtype=np.float32) -> np.ndarray:
    """(B, H, W, C) uint8 -> (B, C, out_h, out_w) in ``dtype``."""
    B, H, W, C = img.shape
    mean = np.asarray(mean, dtype=np.float32)
    scale = inv_std(std)
    out = np.empty((B, C, out_h, out_w), dtype=dtype)
    for i in range(B):
        y, x = int(oy[i]), int(ox[i])
        if not (0 <= y <= H - out_h and 0 <= x <= W - out_w):
            raise ValueError(f"crop offset ({y}, {x}) outside the image")
        crop = img[i, y:y + out_h, x:x + out_w, :]
        if mirror[i]:
            crop = crop[:, ::-1, :]
        norm = (crop.astype(np.float32) - mean) * scale
        out[i] = norm.transpose(2, 0, 1).astype(dtype)
    return out
