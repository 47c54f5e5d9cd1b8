"""Pallas TPU flash attention (training / prefill), GQA + causal + SWA.

TPU adaptation of the FlashAttention idea: online-softmax accumulation over
KV blocks held in VMEM, with the MXU doing the (bq x D) @ (D x bk) and
(bq x bk) @ (bk x D) matmuls.  The grid is (batch, q_head, q_blocks,
kv_blocks); TPU executes the minor-most grid dimension sequentially per core,
so the m/l/acc scratch accumulators persist across the kv_block axis.

Layouts: q (B, H, S, D), k/v (B, K, T, D) with H = K * G (GQA: the k/v
index_map folds the q head onto its kv head).  Block sizes default to
128 (MXU-aligned); D is kept whole in the lane dimension.

Validated against ref.mha_reference in interpret mode (tests sweep shapes,
dtypes, causal/window flags).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    ok = k_pos < kv_len
    if causal:
        ok &= q_pos >= k_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q (B,H,S,D); k,v (B,K,T,D); H % K == 0. Returns (B,H,S,D)."""
    B, H, S, D = q.shape
    K, T = k.shape[1], k.shape[2]
    if H % K:
        raise ValueError(f"H={H} not a multiple of K={K}")
    G = H // K
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    nq = -(-S // block_q)
    nk = -(-T // block_k)
    S_p, T_p = nq * block_q, nk * block_k
    if S_p != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, S_p - S), (0, 0)))
    if T_p != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, T_p - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, T_p - T), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, kv_len=T)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),       # running max m
            pltpu.VMEM((block_q,), jnp.float32),       # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S, :]


__all__ = ["flash_attention"]
