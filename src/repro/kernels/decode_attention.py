"""Pallas TPU flash-decode: one query token against a long KV cache.

Memory-bound kernel (arithmetic intensity ~2 FLOP/byte): the point on TPU is
streaming the KV cache HBM->VMEM exactly once at full bandwidth while the
G grouped q-heads of each kv head ride along in registers.  Grid is
(batch, kv_head, kv_blocks); m/l/acc scratch carries across kv_blocks.

Layouts: q (B, K, G, D); k,v (B, K, T, D); lengths (B,) valid prefix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, block_k: int, scale: float):
    b = pl.program_id(0)
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)           # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    t_pos = it * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_pos < len_ref[b], s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(it == nt - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, *, block_k: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q (B,K,G,D); k,v (B,K,T,D); lengths (B,) -> (B,K,G,D)."""
    B, K, G, D = q.shape
    T = k.shape[2]
    block_k = min(block_k, T)
    nt = -(-T // block_k)
    T_p = nt * block_k
    if T_p != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, T_p - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, T_p - T), (0, 0)))

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               scale=D ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, nt),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, it, len_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, it, len_ref: (b, h, it, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, it, len_ref: (b, h, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, it, len_ref: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q, k, v)
    return out


__all__ = ["flash_decode"]
