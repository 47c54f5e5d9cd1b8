"""GQA attention: full / causal / sliding-window / cross, with KV caches.

Two XLA implementations:
  * ``dense``   — classic einsum softmax (smoke tests, short seqs, decode);
  * ``chunked`` — memory-efficient online-softmax attention (lax.map over
    query chunks, a loop over the KV chunks each sees; blocks that causality
    or the window mask wholly are skipped).  This is the lowering/dry-run
    path for long sequences; the TPU-native equivalent is the Pallas flash
    kernel in ``repro.kernels.flash_attention`` (same math, VMEM tiling).

Layout: q (B, S, K, G, Dh) where H = K*G; k, v (B, T, K, Dh).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


def _grid(S: int, T: int, q_chunk: int, kv_chunk: int
          ) -> Tuple[int, int, int, int]:
    """(S_p, T_p, q_chunk, kv_chunk) of a ``chunked_attention`` call: the
    chunks clamped to the lengths, the lengths padded to whole chunks."""
    q_chunk, kv_chunk = min(q_chunk, S), min(kv_chunk, T)
    return (-(-S // q_chunk) * q_chunk, -(-T // kv_chunk) * kv_chunk,
            q_chunk, kv_chunk)


def _block_grid(S: int, T: int, q_chunk: int, kv_chunk: int, causal: bool,
                window: int) -> Tuple[np.ndarray, np.ndarray]:
    """Two (nq, nk) bool grids over the (query chunk, KV chunk) blocks:
    those holding at least one pair ``_mask_bias`` lets through, and those
    holding only such pairs.  S and T are multiples of their chunks."""
    i = np.arange(S // q_chunk)[:, None]
    j = np.arange(T // kv_chunk)[None, :]
    d_min = i * q_chunk - (j + 1) * kv_chunk + 1     # least q - k in a block
    d_max = (i + 1) * q_chunk - 1 - j * kv_chunk     # largest
    lo = 0 if causal else -np.inf
    hi = window - 1 if window > 0 else np.inf
    return (d_max >= lo) & (d_min <= hi), (d_min >= lo) & (d_max <= hi)


def _spans(visible: np.ndarray, full: np.ndarray) -> jax.Array:
    """(rows, 4) int32: for each row of the grids, ``[a, b)`` and ``[c, d)``
    its visible blocks that need the mask, ``[b, c)`` the wholly visible
    ones.  Both sets are contiguous in a row: a block's range of q - k
    moves by whole chunks along a row or a column."""
    out = np.zeros((visible.shape[0], 4), np.int32)
    for r, (vis, ful) in enumerate(zip(visible, full)):
        v, f = np.flatnonzero(vis), np.flatnonzero(ful)
        a, d = (v[0], v[-1] + 1) if v.size else (0, 0)
        b, c = (f[0], f[-1] + 1) if f.size else (d, d)
        out[r] = a, b, c, d
    return jnp.asarray(out)


def attention_blocks(S: int, T: int, *, causal: bool = True, window: int = 0,
                     q_chunk: int = 1024, kv_chunk: int = 1024
                     ) -> Tuple[int, int]:
    """(blocks computed, blocks in the grid) of a ``chunked_attention`` call
    with these lengths and options; the backward computes the same blocks."""
    visible, _ = _block_grid(*_grid(S, T, q_chunk, kv_chunk), causal, window)
    return int(visible.sum()), visible.size


def _scores(qc: jax.Array, kb: jax.Array, i, j, masked: bool, q_chunk: int,
            kv_chunk: int, causal: bool, window: int) -> jax.Array:
    """f32 scores (B,K,G,qc,kvc) of the block (query chunk i, KV chunk j),
    scaled by q's width; the mask is added only where ``masked``."""
    s = jnp.einsum("bkgqd,bktd->bkgqt", qc, kb).astype(jnp.float32) \
        * qc.shape[-1] ** -0.5
    if masked:
        s = s + _mask_bias(i * q_chunk + jnp.arange(q_chunk),
                           j * kv_chunk + jnp.arange(kv_chunk), causal, window)
    return s


def _fori_spans(span: jax.Array, step, carry):
    """Runs ``step(masked)`` over a row's visible blocks in order: the masked
    ones before the wholly visible ones, those, then the masked ones after."""
    a, b, c, d = span
    carry = jax.lax.fori_loop(a, b, step(True), carry)
    carry = jax.lax.fori_loop(b, c, step(False), carry)
    return jax.lax.fori_loop(c, d, step(True), carry)


def _mea_forward(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                 window: int, q_chunk: int, kv_chunk: int):
    """Online-softmax forward. q, k (…, Dh); v (B,T,K,Dv).

    Each query chunk visits only the KV chunks it sees, in order; a block
    wholly masked would add exact zeros.  Returns (out (B,S,H,Dv), lse
    (B,K,G,S) f32). Positions are arange.
    """
    with jax.named_scope("attn.fwd"):
        B, S, H, Dh = q.shape
        T, K, Dv = k.shape[1], k.shape[2], v.shape[3]
        G = H // K
        nq, nk = S // q_chunk, T // kv_chunk
        spans = _spans(*_block_grid(S, T, q_chunk, kv_chunk, causal, window))

        # per chunk, on a leading axis: (nq, B, K, G, qc, Dh), (nk, B, K, kvc, D)
        qg = (q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4)
              .reshape(B, K, G, nq, q_chunk, Dh).transpose(3, 0, 1, 2, 4, 5))
        kc = (k.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dh)
              .transpose(2, 0, 1, 3, 4))
        vc = (v.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dv)
              .transpose(2, 0, 1, 3, 4))

        def per_q_chunk(inputs):
            qc, iq = inputs                # (B,K,G,qc,Dh), ()

            def kv_step(masked):
                def body(j, carry):
                    m, l, acc = carry
                    kb = jax.lax.dynamic_index_in_dim(kc, j, keepdims=False)
                    vb = jax.lax.dynamic_index_in_dim(vc, j, keepdims=False)
                    s = _scores(qc, kb, iq, j, masked, q_chunk, kv_chunk,
                                causal, window)
                    m_new = jnp.maximum(m, s.max(axis=-1))
                    corr = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new[..., None])
                    l_new = l * corr + p.sum(axis=-1)
                    pv = jnp.einsum("bkgqt,bktd->bkgqd", p.astype(vb.dtype),
                                    vb)
                    acc_new = acc * corr[..., None].astype(acc.dtype) + pv
                    return m_new, l_new, acc_new
                return body

            m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
            l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
            a0 = jnp.zeros((B, K, G, q_chunk, Dv), jnp.float32)
            m, l, acc = _fori_spans(spans[iq], kv_step, (m0, l0, a0))
            o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qc.dtype)
            lse = m + jnp.log(jnp.maximum(l, 1e-30))
            return o, lse

        o, lse = jax.lax.map(per_q_chunk, (qg, jnp.arange(nq)))
        # o: (nq,B,K,G,qc,Dv) -> (B,S,H,Dv); lse: (nq,B,K,G,qc) -> (B,K,G,S)
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

    Each KV chunk visits only the query chunks that see it (the forward's
    blocks), and adds its part of dq into those chunks' rows alone.  Live
    memory is O(block) + the dq accumulator — this is what keeps the
    train_4k/prefill_32k cells inside 16 GB/chip (the naive scan VJP would
    save the full f32 score matrix: B*H*S*T*4 bytes, tens of GB/device).
    """
    with jax.named_scope("attn.bwd"):
        q, k, v, out, lse = res
        B, S, H, Dh = q.shape
        T, K, Dv = k.shape[1], k.shape[2], v.shape[3]
        G = H // K
        nq, nk = S // q_chunk, T // kv_chunk
        scale = Dh ** -0.5
        visible, full = _block_grid(S, T, q_chunk, kv_chunk, causal, window)
        spans = _spans(visible.T, full.T)

        # per query chunk, on a leading axis: (nq, B, K, G, qc, ...)
        qg = (q.reshape(B, S, K, G, Dh).transpose(0, 2, 3, 1, 4)
              .reshape(B, K, G, nq, q_chunk, Dh).transpose(3, 0, 1, 2, 4, 5))
        do_g = (dout.reshape(B, S, K, G, Dv).transpose(0, 2, 3, 1, 4)
                .reshape(B, K, G, nq, q_chunk, Dv)
                .transpose(3, 0, 1, 2, 4, 5))
        lse_c = lse.reshape(B, K, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
        # delta_i = sum_d dO_i * O_i
        delta = jnp.einsum("bshd,bshd->bsh", dout.astype(jnp.float32),
                           out.astype(jnp.float32))
        delta_c = (delta.reshape(B, S, K, G).transpose(0, 2, 3, 1)
                   .reshape(B, K, G, nq, q_chunk).transpose(3, 0, 1, 2, 4))
        kc = k.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dh)
        vc = v.transpose(0, 2, 1, 3).reshape(B, K, nk, kv_chunk, Dv)

        def kv_step(dq_acc, inputs):
            kb, vb, j = inputs             # (B,K,kvc,Dh) x2, ()

            def q_step(masked):
                def body(i, carry):
                    dq_acc, dk, dv = carry
                    qc, doc, lsec, dlc = (
                        jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
                        for a in (qg, do_g, lse_c, delta_c))
                    s = _scores(qc, kb, i, j, masked, q_chunk, kv_chunk,
                                causal, window)
                    p = jnp.exp(s - lsec[..., None])
                    dv_p = jnp.einsum("bkgqt,bkgqd->bktd", p,
                                      doc.astype(jnp.float32))
                    dp = jnp.einsum("bkgqd,bktd->bkgqt",
                                    doc.astype(jnp.float32),
                                    vb.astype(jnp.float32))
                    ds = p * (dp - dlc[..., None]) * scale
                    dq_c = jnp.einsum("bkgqt,bktd->bkgqd", ds,
                                      kb.astype(jnp.float32))
                    dk_p = jnp.einsum("bkgqt,bkgqd->bktd", ds,
                                      qc.astype(jnp.float32))
                    dq_i = jax.lax.dynamic_index_in_dim(dq_acc, i,
                                                        keepdims=False)
                    dq_acc = jax.lax.dynamic_update_index_in_dim(
                        dq_acc, dq_i + dq_c, i, 0)
                    return dq_acc, dk + dk_p, dv + dv_p
                return body

            dk0 = jnp.zeros((B, K, kv_chunk, Dh), jnp.float32)
            dv0 = jnp.zeros((B, K, kv_chunk, Dv), jnp.float32)
            dq_acc, dk_j, dv_j = _fori_spans(spans[j], q_step,
                                             (dq_acc, dk0, dv0))
            return dq_acc, (dk_j, dv_j)

        dq0 = jnp.zeros((nq, B, K, G, q_chunk, Dh), jnp.float32)
        dq, (dk_c, dv_c) = jax.lax.scan(
            kv_step, dq0,
            (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
             jnp.arange(nk)))
        # dq: (nq, B, K, G, qc, Dh) -> (B, S, H, Dh)
        dq = (dq.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, Dh)
              .astype(q.dtype))
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
    S_p, T_p, q_chunk, kv_chunk = _grid(S, T, q_chunk, kv_chunk)
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
           "chunked_attention", "attention_blocks", "init_kv_cache",
           "cache_specs", "decode_attention", "NEG_INF"]
