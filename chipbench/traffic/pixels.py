"""Image rows from the seed: pre-decoded uint8 frames under many keys.

A copy of the frame rule of the program's ``SyntheticPixelDataset``:
piecewise-constant colour fields (sinusoids quantized to 16 levels, block
upsampled from an 8 x 8 grid, one frequency set per class), which are the
long byte runs a real photo gives a wire codec.  ``n_keys`` keys share
``n_frames`` distinct frames, each key drawing its frame from the seed, so
the store holds a dataset of ImageNet's row size without holding
``n_keys`` distinct frames in host memory.
"""

from __future__ import annotations

import dataclasses
import uuid as _uuid
from typing import List

import numpy as np


def make_frame(rng: np.random.Generator, label: int, h: int, w: int,
               c: int) -> np.ndarray:
    by, bx = max(1, h // 8), max(1, w // 8)
    gh, gw = -(-h // by), -(-w // bx)
    yy = np.linspace(0.0, 1.0, gh)[:, None]
    xx = np.linspace(0.0, 1.0, gw)[None, :]
    img = np.empty((h, w, c), dtype=np.uint8)
    for ch in range(c):
        fy = 1.0 + (label % 3)
        fx = 1.0 + ((label + ch) % 4)
        phase = rng.uniform(0.0, 2.0 * np.pi)
        field = 127.5 + 120.0 * np.sin(
            2.0 * np.pi * (yy * fy + xx * fx) + phase)
        coarse = (np.round(field / 16.0) * 16.0).clip(0, 255)
        full = np.repeat(np.repeat(coarse, by, axis=0), bx, axis=1)
        img[..., ch] = full[:h, :w]
    return img


@dataclasses.dataclass
class PixelRows:
    """Every key's frame and label, and the frames themselves."""

    keys: List[_uuid.UUID]
    frame_of_key: np.ndarray        # (n_keys,) index into frames
    frames: np.ndarray              # (n_frames, h, w, c) uint8
    frame_labels: np.ndarray        # (n_frames,) int

    @property
    def key_labels(self) -> np.ndarray:
        return self.frame_labels[self.frame_of_key]


def make_frames(rng: np.random.Generator, labels: np.ndarray, h: int, w: int,
                c: int) -> np.ndarray:
    """``make_frame`` for every label at once, drawing the same phases in
    the same order, so the frames are equal to the loop's."""
    by, bx = max(1, h // 8), max(1, w // 8)
    gh, gw = -(-h // by), -(-w // bx)
    yy = np.linspace(0.0, 1.0, gh)[None, :, None, None]
    xx = np.linspace(0.0, 1.0, gw)[None, None, :, None]
    phase = rng.uniform(0.0, 2.0 * np.pi, size=(len(labels), c))
    fy = (1.0 + labels % 3).astype(np.float64)[:, None, None, None]
    fx = (1.0 + (labels[:, None] + np.arange(c)) % 4).astype(np.float64)
    field = 127.5 + 120.0 * np.sin(
        2.0 * np.pi * (yy * fy + xx * fx[:, None, None, :])
        + phase[:, None, None, :])
    coarse = (np.round(field / 16.0) * 16.0).clip(0, 255).astype(np.uint8)
    full = np.repeat(np.repeat(coarse, by, axis=1), bx, axis=2)
    return np.ascontiguousarray(full[:, :h, :w, :])


def generate(seed: int, n_keys: int, n_frames: int, h: int, w: int, c: int,
             n_classes: int) -> PixelRows:
    rng = np.random.default_rng([seed, 0x1A6E])
    labels = rng.integers(0, n_classes, size=n_frames)
    frames = make_frames(rng, labels, h, w, c)
    raw = rng.bytes(16 * n_keys)
    keys = [_uuid.UUID(bytes=raw[16 * i:16 * i + 16], version=4)
            for i in range(n_keys)]
    if len(set(keys)) != n_keys:
        raise ValueError("two keys drew the same uuid")
    frame_of_key = rng.integers(0, n_frames, size=n_keys)
    return PixelRows(keys, frame_of_key, frames, labels)
