"""Pure-jnp oracles for every Pallas kernel (the ground truth in tests) —
plus a pure-NumPy ``crop_mirror_normalize_np``, the reference that
``data.pipeline.ImageFeed``'s batches are checked against."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int = 0) -> jax.Array:
    """q (B,H,S,D); k,v (B,K,T,D) -> (B,H,S,D). GQA by head folding."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, S, D)
    s = jnp.einsum("bkgsd,bktd->bkgst", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    ok = jnp.ones((S, T), bool)
    if causal:
        ok &= qpos >= kpos
    if window > 0:
        ok &= (qpos - kpos) < window
    s = jnp.where(ok, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", w, v.astype(jnp.float32))
    return o.reshape(B, H, S, D).astype(q.dtype)


def decode_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array) -> jax.Array:
    """Single-token decode. q (B,H,D); k,v (B,K,T,D); lengths (B,) valid
    prefix lengths. -> (B,H,D)."""
    B, H, D = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, D)
    s = jnp.einsum("bkgd,bktd->bkgt", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    valid = jnp.arange(T)[None, :] < lengths[:, None]          # (B,T)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,bktd->bkgd", w, v.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def crop_mirror_normalize_reference(img: jax.Array, oy: jax.Array,
                                    ox: jax.Array, mirror: jax.Array,
                                    mean: jax.Array, inv_std: jax.Array,
                                    out_h: int, out_w: int,
                                    dtype=jnp.float32) -> jax.Array:
    """img (B,H,W,C) uint8 -> (B,C,out_h,out_w), DALI crop_mirror_normalize.

    oy/ox (B,) crop offsets, mirror (B,) bool, mean/inv_std (C,) in 0..255
    scale: ``(crop - mean) * inv_std``.
    """
    def one(im, y, x, m):
        crop = jax.lax.dynamic_slice(im, (y, x, 0),
                                     (out_h, out_w, im.shape[2]))
        crop = jnp.where(m, crop[:, ::-1, :], crop)
        out = (crop.astype(jnp.float32) - mean) * inv_std
        return out.transpose(2, 0, 1).astype(dtype)

    return jax.vmap(one)(img, oy, ox, mirror)


def crop_mirror_normalize_np(img: np.ndarray, oy, ox, mirror,
                             mean: np.ndarray, inv_std: np.ndarray,
                             out_h: int, out_w: int,
                             dtype=np.float32) -> np.ndarray:
    """NumPy twin of the Pallas kernel: (B,H,W,C) uint8 -> (B,C,oh,ow).

    Same clamping semantics as the kernel entry point (offsets clip to the
    valid window) and the same float32 arithmetic, ``(crop - mean) *
    inv_std``, so the two agree bit for bit.
    """
    B, H, W, C = img.shape
    oy = np.clip(np.asarray(oy, dtype=np.int64), 0, H - out_h)
    ox = np.clip(np.asarray(ox, dtype=np.int64), 0, W - out_w)
    mean = np.asarray(mean, dtype=np.float32)
    inv_std = np.asarray(inv_std, dtype=np.float32)
    out = np.empty((B, C, out_h, out_w), dtype=dtype)
    for i in range(B):
        crop = img[i, oy[i]:oy[i] + out_h, ox[i]:ox[i] + out_w, :]
        if mirror[i]:
            crop = crop[:, ::-1, :]
        x = (crop.astype(np.float32) - mean) * inv_std
        out[i] = x.transpose(2, 0, 1).astype(dtype)
    return out


__all__ = ["mha_reference", "decode_reference",
           "crop_mirror_normalize_reference", "crop_mirror_normalize_np"]
