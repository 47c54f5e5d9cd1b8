"""Jitted public wrappers for the Pallas kernels.

The kernels compile for the TPU.  ``interpret=True`` runs them through the
Pallas interpreter instead; only a caller that wants that (the CPU tests,
CPU callers of ``build_stack(feed="image")``) passes it — the wrappers never
pick a mode on their own.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .crop_norm import crop_mirror_normalize as _cmn
from .decode_attention import flash_decode as _flash_decode
from .flash_attention import flash_attention as _flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret: bool = False):
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k, v, lengths, *, block_k=512, interpret: bool = False):
    return _flash_decode(q, k, v, lengths, block_k=block_k,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("out_h", "out_w", "dtype",
                                             "interpret"))
def crop_mirror_normalize(img, oy, ox, mirror, mean, inv_std, *, out_h, out_w,
                          dtype=jnp.float32, interpret: bool = False):
    return _cmn(img, oy, ox, mirror, mean, inv_std, out_h, out_w, dtype,
                interpret=interpret)


__all__ = ["flash_attention", "flash_decode", "crop_mirror_normalize"]
