"""Pallas TPU grouped matmul (per-expert GEMM) for the MoE dispatch path.

x (E, C, d) @ w (E, d, f) -> (E, C, f): grid (E, C/bc, f/bf, d/bd) with an
f32 VMEM accumulator carried across the (sequential, minor-most) d axis.
Block sizes are MXU-aligned (128); this is the megablox-style building block
the sort-based MoE dispatch feeds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr):
    kd = pl.program_id(3)
    nd = pl.num_programs(3)

    @pl.when(kd == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[0].astype(jnp.float32), w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _done():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, *, block_c: int = 128,
                   block_f: int = 128, block_d: int = 512,
                   interpret: bool = False) -> jax.Array:
    """x (E,C,d) @ w (E,d,f) -> (E,C,f)."""
    E, C, d = x.shape
    f = w.shape[2]
    block_c = min(block_c, C)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    nc, nf, nd = -(-C // block_c), -(-f // block_f), -(-d // block_d)
    Cp, fp, dp = nc * block_c, nf * block_f, nd * block_d
    if (Cp, dp) != (C, d):
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, dp - d)))
    if (dp, fp) != (d, f):
        w = jnp.pad(w, ((0, 0), (0, dp - d), (0, fp - f)))

    out = pl.pallas_call(
        _gmm_kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d),
                         lambda e, ic, jf, kd: (e, ic, kd)),
            pl.BlockSpec((1, block_d, block_f),
                         lambda e, ic, jf, kd: (e, kd, jf)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, ic, jf, kd: (e, ic, jf)),
        out_shape=jax.ShapeDtypeStruct((E, Cp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:, :C, :f]


__all__ = ["grouped_matmul"]
