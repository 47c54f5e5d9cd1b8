"""GQA attention: full / causal / sliding-window / cross, with KV caches.

Two XLA implementations:
  * ``dense``   — classic einsum softmax (smoke tests, short seqs, decode);
  * ``chunked`` — memory-efficient online-softmax attention (lax.map over
    query chunks, lax.scan over KV chunks).  This is the lowering/dry-run
    path for long sequences; the TPU-native equivalent is the Pallas flash
    kernel in ``repro.kernels.flash_attention`` (same math, VMEM tiling).

Layout: q (B, S, K, G, Dh) where H = K*G; k, v (B, T, K, Dh).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import apply_rope
from .params import P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def gqa_spec(d: int, n_heads: int, n_kv: int, head_dim: int,
             qk_norm: bool = False, bias: bool = False) -> Dict:
    spec = {
        "wq": P((d, n_heads, head_dim), ("d_model", "heads", "head_dim")),
        "wk": P((d, n_kv, head_dim), ("d_model", "kv_heads", "head_dim")),
        "wv": P((d, n_kv, head_dim), ("d_model", "kv_heads", "head_dim")),
        "wo": P((n_heads, head_dim, d), ("heads", "head_dim", "d_model")),
    }
    if qk_norm:  # Qwen3-style per-head RMSNorm on q and k
        spec["q_norm"] = P((head_dim,), ("head_dim",), init="ones")
        spec["k_norm"] = P((head_dim,), ("head_dim",), init="ones")
    if bias:     # whisper-style projection biases
        spec["bq"] = P((n_heads, head_dim), ("heads", "head_dim"), init="zeros")
        spec["bv"] = P((n_kv, head_dim), ("kv_heads", "head_dim"), init="zeros")
        spec["bo"] = P((d,), ("d_model",), init="zeros")
    return spec


def _head_rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def project_qkv(params: Dict, x: jax.Array, x_kv: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns q (B,S,H,Dh), k (B,T,K,Dh), v (B,T,K,Dh)."""
    x_kv = x if x_kv is None else x_kv
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x_kv, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x_kv, params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
        v = v + params["bv"].astype(v.dtype)
    if "q_norm" in params:
        q = _head_rmsnorm(q, params["q_norm"])
        k = _head_rmsnorm(k, params["k_norm"])
    return q, k, v


def project_out(params: Dict, o: jax.Array) -> jax.Array:
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    if "bo" in params:
        out = out + params["bo"].astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def _mask_bias(q_pos: jax.Array, kv_pos: jax.Array, causal: bool,
               window: int, kv_valid: Optional[jax.Array] = None) -> jax.Array:
    """(…, Sq, Tk) additive bias from causality / sliding window / validity."""
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = jnp.ones(d.shape, bool)
    if causal:
        ok &= d >= 0
    if window > 0:
        ok &= d < window
    if kv_valid is not None:
        ok &= kv_valid[..., None, :]
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,T,K,D) -> (B,T,H,D) by repeating each kv head G=H/K times.

    The grouped (B,K,G,S,D) layout cannot shard K=8 kv heads over a 16-way
    model axis — XLA then *replicates* the whole attention computation.
    Expanding to H query heads restores head sharding for train/prefill;
    decode keeps the grouped path (expansion would multiply KV-cache reads
    by G in a memory-bound kernel)."""
    K = k.shape[2]
    G = n_heads // K
    if G == 1:
        return k
    return jnp.repeat(k, G, axis=2)


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    kv_valid: Optional[jax.Array] = None,
                    expand_heads: bool = True) -> jax.Array:
    """q (B,S,H,Dh), k/v (B,T,K,Dh) -> (B,S,H,Dh)."""
    B, S, H, Dh = q.shape
    scale = Dh ** -0.5
    if expand_heads:
        k = expand_kv(k, H)
        v = expand_kv(v, H)
        scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) \
            * scale
        bias = _mask_bias(q_pos, kv_pos, causal, window, kv_valid)
        scores = scores + (bias[..., None, :, :] if bias.ndim == 3 else bias)
        w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhst,bthd->bshd", w, v)
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, Dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    bias = _mask_bias(q_pos, kv_pos, causal, window, kv_valid)  # (B?,S,T)
    scores = scores + bias[..., None, None, :, :] if bias.ndim == 3 \
        else scores + bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return o.reshape(B, S, H, Dh)


def _mea_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                 window: int, q_chunk: int, kv_chunk: int):
    """Online-softmax forward. q, k (…, Dh); v (B,T,K,Dv).

    Returns (out (B,S,H,Dv), lse (B,K,G,S) f32). Positions are arange.
    """
    B, S, H, Dh = q.shape
    T, K, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // K
    nq, nk = -(-S // q_chunk), -(-T // kv_chunk)
    scale = Dh ** -0.5

    qg = (q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4)
          .reshape(B, K, G, nq, q_chunk, Dh))
    kc = k.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dh)
    vc = v.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dv)

    def per_q_chunk(inputs):
        qc, iq = inputs                    # (B,K,G,qc,Dh), ()
        qpos = iq * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs2):
            m, l, acc = carry
            kb, vb, j = inputs2            # (B,K,kvc,Dh), (B,K,kvc,Dh), ()
            kpos = j * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qc, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qpos, kpos, causal, window)
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqt,bktd->bkgqd", p.astype(vb.dtype), vb)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_chunk, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
             jnp.arange(nk)))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qc.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return o, lse

    o, lse = jax.lax.map(per_q_chunk,
                         (qg.transpose(3, 0, 1, 2, 4, 5), jnp.arange(nq)))
    # o: (nq,B,K,G,qc,Dv) -> (B,S,H,Dv);  lse: (nq,B,K,G,qc) -> (B,K,G,S)
    o = o.transpose(1, 2, 3, 0, 4, 5).reshape(B, K, G, S, Dv)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dv)
    lse = lse.transpose(1, 2, 3, 0, 4).reshape(B, K, G, S)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mea_attention(q, k, v, causal, window, q_chunk, kv_chunk):
    out, _ = _mea_forward(q, k, v, causal, window, q_chunk, kv_chunk)
    return out


def _mea_fwd(q, k, v, causal, window, q_chunk, kv_chunk):
    out, lse = _mea_forward(q, k, v, causal, window, q_chunk, kv_chunk)
    return out, (q, k, v, out, lse)


def _mea_bwd(causal, window, q_chunk, kv_chunk, res, dout):
    """Flash-style backward: scores recomputed blockwise, never saved.

    Live memory is O(block) + the dq accumulator — this is what keeps the
    train_4k/prefill_32k cells inside 16 GB/chip (the naive scan VJP would
    save the full f32 score matrix: B*H*S*T*4 bytes, tens of GB/device).
    """
    q, k, v, out, lse = res
    B, S, H, Dh = q.shape
    T, K, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // K
    nq, nk = -(-S // q_chunk), -(-T // kv_chunk)
    scale = Dh ** -0.5

    qg = (q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4)
          .reshape(B, K, G, nq, q_chunk, Dh))
    do_g = (dout.reshape(B, S, K, G, Dv).transpose(0, 2, 3, 1, 4)
            .reshape(B, K, G, nq, q_chunk, Dv))
    lse_c = lse.reshape(B, K, G, nq, q_chunk)
    # delta_i = sum_d dO_i * O_i
    delta = jnp.einsum("bshd,bshd->bsh", dout.astype(jnp.float32),
                       out.astype(jnp.float32))
    delta_c = (delta.reshape(B, S, K, G).transpose(0, 2, 3, 1)
               .reshape(B, K, G, nq, q_chunk))
    kc = k.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dh)
    vc = v.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dv)

    def kv_step(dq_acc, inputs):
        kb, vb, j = inputs                 # (B,K,kvc,Dh) x2, ()
        kpos = j * kv_chunk + jnp.arange(kv_chunk)

        def per_q(inputs2):
            qc, doc, lsec, dlc, iq = inputs2
            qpos = iq * q_chunk + jnp.arange(q_chunk)
            s = jnp.einsum("bkgqd,bktd->bkgqt", qc, kb).astype(jnp.float32)
            s = s * scale + _mask_bias(qpos, kpos, causal, window)
            p = jnp.exp(s - lsec[..., None])
            dv_p = jnp.einsum("bkgqt,bkgqd->bktd", p,
                              doc.astype(jnp.float32))
            dp = jnp.einsum("bkgqd,bktd->bkgqt", doc.astype(jnp.float32),
                            vb.astype(jnp.float32))
            ds = p * (dp - dlc[..., None]) * scale
            dq_c = jnp.einsum("bkgqt,bktd->bkgqd", ds,
                              kb.astype(jnp.float32))
            dk_p = jnp.einsum("bkgqt,bkgqd->bktd", ds,
                              qc.astype(jnp.float32))
            return dq_c, dk_p, dv_p

        dq_cs, dk_ps, dv_ps = jax.lax.map(
            per_q, (qg.transpose(3, 0, 1, 2, 4, 5),
                    do_g.transpose(3, 0, 1, 2, 4, 5),
                    lse_c.transpose(3, 0, 1, 2, 4),
                    delta_c.transpose(3, 0, 1, 2, 4),
                    jnp.arange(nq)))
        # dq contribution of this kv chunk, for all q
        dq_j = (dq_cs.transpose(1, 2, 3, 0, 4, 5)
                .reshape(B, K, G, S, Dh))
        return dq_acc + dq_j, (dk_ps.sum(axis=0), dv_ps.sum(axis=0))

    dq0 = jnp.zeros((B, K, G, S, Dh), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        kv_step, dq0,
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(nk)))
    dq = dq.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dh).astype(q.dtype)
    # dk_c/dv_c: (nk, B, K, kvc, Dh) -> (B, K, T, Dh) -> (B, T, K, Dh)
    dk = (dk_c.transpose(1, 2, 0, 3, 4).reshape(B, K, T, Dh)
          .transpose(0, 2, 1, 3).astype(k.dtype))
    dv = (dv_c.transpose(1, 2, 0, 3, 4).reshape(B, K, T, Dv)
          .transpose(0, 2, 1, 3).astype(v.dtype))
    return dq, dk, dv


_mea_attention.defvjp(_mea_fwd, _mea_bwd)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array = None, kv_pos: jax.Array = None, *,
                      causal: bool = True, window: int = 0,
                      q_chunk: int = 1024, kv_chunk: int = 1024) -> jax.Array:
    """Memory-efficient attention with a flash-style custom VJP; v may be
    narrower than q and k (the scale follows q's width).

    Positions are implicit arange (the q_pos/kv_pos arguments are accepted
    for API parity with dense_attention but must be arange if given).
    Equivalent to dense_attention — validated in tests, fwd and grad.
    """
    B, S, H, Dh = q.shape
    k = expand_kv(k, H)          # TP-friendly GQA (see expand_kv docstring)
    v = expand_kv(v, H)
    T = k.shape[1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    nq, nk = -(-S // q_chunk), -(-T // kv_chunk)
    S_p, T_p = nq * q_chunk, nk * kv_chunk
    if S_p != S:
        q = jnp.pad(q, ((0, 0), (0, S_p - S), (0, 0), (0, 0)))
    if T_p != T:
        k = jnp.pad(k, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_p - T), (0, 0), (0, 0)))
        # padded keys are masked by causality only if S_p >= T_p; enforce
        # explicitly via the window/causal mask positions (padded kpos > any
        # valid qpos when causal). For non-causal use, pad must be handled by
        # the caller; all in-repo callers are causal or exact-multiple.
    out = _mea_attention(q, k, v, causal, window, q_chunk, kv_chunk)
    return out[:, :S]


# ---------------------------------------------------------------------------
# KV caches (decode)
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_len: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Dict:
    return {
        "k": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),     # tokens filled so far
    }


def cache_specs(batch: int, max_len: int, n_kv: int, head_dim: int,
                dtype=jnp.bfloat16) -> Dict:
    return {
        "k": jax.ShapeDtypeStruct((batch, max_len, n_kv, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, max_len, n_kv, head_dim), dtype),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def decode_attention(params: Dict, cache: Dict, x: jax.Array, *,
                     window: int = 0, rope_theta: float = 10_000.0,
                     use_rope: bool = True) -> Tuple[jax.Array, Dict]:
    """One-token step: x (B,1,d). Updates cache in place (donated buffer)."""
    B = x.shape[0]
    q, k_new, v_new = project_qkv(params, x)
    pos = cache["pos"]
    if use_rope:
        posv = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, posv[None, :], rope_theta)
        k_new = apply_rope(k_new, posv[None, :], rope_theta)
    T = cache["k"].shape[1]
    if window > 0:
        slot = jnp.mod(pos, T)        # ring buffer for sliding-window caches
    else:
        slot = pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    kv_idx = jnp.arange(T)
    if window > 0:
        # valid = written and within window; positions in ring order
        age = jnp.mod(slot - kv_idx, T)
        valid = (age < jnp.minimum(pos + 1, T))
        kv_pos = pos - age
    else:
        valid = kv_idx <= pos
        kv_pos = kv_idx
    q_pos = jnp.full((1,), pos, jnp.int32)
    o = dense_attention(q, k, v, q_pos[None, :], kv_pos[None, :],
                        causal=False, window=0,
                        kv_valid=jnp.broadcast_to(valid, (B, T)),
                        expand_heads=False)
    out = project_out(params, o)
    new_cache = {"k": k, "v": v, "pos": pos + 1}
    return out, new_cache


__all__ = ["gqa_spec", "project_qkv", "project_out", "dense_attention",
           "chunked_attention", "init_kv_cache", "cache_specs",
           "decode_attention", "NEG_INF"]
