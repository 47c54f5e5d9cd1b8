"""Pallas TPU fused crop + mirror + normalize (+HWC->CHW) — the on-device
half of DALI's ``crop_mirror_normalize`` stage (paper Listings 2/3).

One grid step processes one image.  The uploaded ``(B, H, W, C)`` uint8
batch is viewed as ``(B, H, W*C)`` (a free reshape), so a block is one
image with the interleaved ``W*C`` row on the lanes instead of ``C=3``.
The crop, the mirror and the channel de-interleave are all *selections*,
done as two one-hot matmuls on the MXU:

    crop_c = R @ X @ S_c       R: (out_h, H)    rows oy .. oy+out_h-1
                               S_c: (W*C, out_w) lane (ox+j)*C + c, or
                                    (ox+out_w-1-j)*C + c when mirrored

with the offsets arriving via scalar prefetch.  Pixels are integers in
0..255, exact in bf16, and each output sums one product with zeros in f32,
so the selection is exact.  The kernel then applies ``(x - mean) * inv_std``
per channel in f32 (DALI's form: the reciprocal is taken once on the host)
and writes the CHW output — one HBM round trip for what a CPU pipeline does
in four passes, bit-identical to ``ref.crop_mirror_normalize_np``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _crop_kernel(scalars_ref, img_ref, norm_ref, o_ref, *,
                 out_h: int, out_w: int, c: int):
    b = pl.program_id(0)
    oy = scalars_ref[b, 0]
    ox = scalars_ref[b, 1]
    mirror = scalars_ref[b, 2]
    h, wc = img_ref.shape[1], img_ref.shape[2]

    x = img_ref[0].astype(jnp.int32).astype(jnp.bfloat16)       # (H, W*C)
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_h, h), 1)
    want_row = jax.lax.broadcasted_iota(jnp.int32, (out_h, h), 0) + oy
    r = (rows == want_row).astype(jnp.bfloat16)                  # (oh, H)
    x = jnp.dot(r, x, preferred_element_type=jnp.float32)        # (oh, W*C)
    x = x.astype(jnp.bfloat16)                                   # exact

    lane = jax.lax.broadcasted_iota(jnp.int32, (wc, out_w), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (wc, out_w), 1)
    src_col = ox + jnp.where(mirror > 0, out_w - 1 - j, j)
    for ch in range(c):
        s = (lane == src_col * c + ch).astype(jnp.bfloat16)      # (W*C, ow)
        crop = jnp.dot(x, s, preferred_element_type=jnp.float32)  # (oh, ow)
        o_ref[0, ch] = ((crop - norm_ref[0, ch]) * norm_ref[1, ch]
                        ).astype(o_ref.dtype)


def crop_mirror_normalize(img: jax.Array, oy: jax.Array, ox: jax.Array,
                          mirror: jax.Array, mean: jax.Array,
                          inv_std: jax.Array, out_h: int, out_w: int,
                          dtype=jnp.float32, *,
                          interpret: bool = False) -> jax.Array:
    """img (B,H,W,C) uint8 -> (B,C,out_h,out_w) ``(crop - mean) * inv_std``.

    Crop offsets are clamped to the valid window so an out-of-range offset
    degrades to an edge crop (keeps kernel and NumPy reference bit-aligned).
    """
    B, H, W, C = img.shape
    oy = jnp.clip(oy.astype(jnp.int32), 0, H - out_h)
    ox = jnp.clip(ox.astype(jnp.int32), 0, W - out_w)
    scalars = jnp.stack([oy, ox, mirror.astype(jnp.int32)], axis=1)  # (B, 3)
    norm = jnp.stack([mean.astype(jnp.float32),
                      inv_std.astype(jnp.float32)])                  # (2, C)
    kernel = functools.partial(_crop_kernel, out_h=out_h, out_w=out_w, c=C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W * C), lambda b, s_ref: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, C, out_h, out_w),
                               lambda b, s_ref: (b, 0, 0, 0)),
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, out_h, out_w), dtype),
        interpret=interpret,
    )(scalars, img.reshape(B, H, W * C), norm)


__all__ = ["crop_mirror_normalize"]
