"""Multi-head latent attention (DeepSeek-V2/V3), without query compression.

Per head, ``q = x Wq`` splits into ``[q_nope, q_rope]``.  Keys and values
come from one latent: ``[c_kv, k_rope] = x Wkv_a``, ``c_kv`` is
RMS-normed, and ``[k_nope, v] = c_kv Wkv_b`` per head.  ``k_rope`` is one
rotary part that every head shares.  Scores use ``[q_nope, rope(q_rope)]``
against ``[k_nope, rope(k_rope)]``, scaled by ``1/sqrt(nope + rope)``; the
value width differs from the query/key width.  A decode cache holds the
latent and the rotated shared key part, not per-head keys and values.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn
from .layers import apply_rope
from .params import P

# The latent's RMSNorm keeps the published code's default epsilon.
LATENT_NORM_EPS = 1e-6


def mla_spec(d: int, n_heads: int, kv_rank: int, nope: int, rope: int,
             v_dim: int) -> Dict:
    return {
        "wq": P((d, n_heads, nope + rope), ("d_model", "heads", "head_dim")),
        "wkv_a": P((d, kv_rank + rope), ("d_model", "kv_lora")),
        "kv_norm": P((kv_rank,), ("kv_lora",), init="ones"),
        "wkv_b": P((kv_rank, n_heads, nope + v_dim),
                   ("kv_lora", "heads", "head_dim")),
        "wo": P((n_heads, v_dim, d), ("heads", "head_dim", "d_model")),
    }


def _norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + LATENT_NORM_EPS)
    return (x * scale.astype(jnp.float32)).astype(dt)


def _latent(params: Dict, x: jax.Array, positions: jax.Array, rope: int,
            theta: float) -> Tuple[jax.Array, jax.Array]:
    """The normed latent (B,S,r) and the rotated shared key part (B,S,rope)."""
    kva = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    c, k_rope = kva[..., :-rope], kva[..., -rope:]
    k_rope = apply_rope(k_rope[..., None, :], positions, theta)[..., 0, :]
    return _norm(c, params["kv_norm"]), k_rope


def _queries(params: Dict, x: jax.Array, positions: jax.Array, rope: int,
             theta: float) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    return jnp.concatenate(
        [q[..., :-rope], apply_rope(q[..., -rope:], positions, theta)], -1)


def _keys_values(params: Dict, c: jax.Array, k_rope: jax.Array, nope: int
                 ) -> Tuple[jax.Array, jax.Array]:
    kv = jnp.einsum("btr,rhk->bthk", c, params["wkv_b"])
    H = kv.shape[2]
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :],
                              k_rope.shape[:2] + (H, k_rope.shape[-1]))
    return jnp.concatenate([kv[..., :nope], k_rope], -1), kv[..., nope:]


def mla_attention(params: Dict, x: jax.Array, positions: jax.Array, *,
                  nope: int, rope: int, theta: float,
                  dense_max_seq: int) -> jax.Array:
    """Causal self-attention of x (B,S,d) -> (B,S,d)."""
    with jax.named_scope("mla"):
        q = _queries(params, x, positions, rope, theta)
        c, k_rope = _latent(params, x, positions, rope, theta)
        k, v = _keys_values(params, c, k_rope, nope)
        if x.shape[1] <= dense_max_seq:
            o = attn.dense_attention(q, k, v, positions[0], positions[0],
                                     causal=True)
        else:
            o = attn.chunked_attention(q, k, v, causal=True)
        return jnp.einsum("bshk,hkd->bsd", o, params["wo"])


def init_mla_cache(batch: int, max_len: int, kv_rank: int, rope: int,
                   dtype=jnp.bfloat16) -> Dict:
    return {"ckv": jnp.zeros((batch, max_len, kv_rank), dtype),
            "krope": jnp.zeros((batch, max_len, rope), dtype),
            "pos": jnp.zeros((), jnp.int32)}


def mla_cache_specs(batch: int, max_len: int, kv_rank: int, rope: int,
                    dtype=jnp.bfloat16) -> Dict:
    return {"ckv": jax.ShapeDtypeStruct((batch, max_len, kv_rank), dtype),
            "krope": jax.ShapeDtypeStruct((batch, max_len, rope), dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32)}


MLA_CACHE_AXES = {"ckv": ("layers", "batch", "kv_seq", "kv_lora"),
                  "krope": ("layers", "batch", "kv_seq", "head_dim"),
                  "pos": ("layers",)}


def mla_decode(params: Dict, cache: Dict, x: jax.Array, *, nope: int,
               rope: int, theta: float) -> Tuple[jax.Array, Dict]:
    """One token x (B,1,d) against the latent cache; returns the output and
    the cache with the token's latent and shared key part written."""
    B = x.shape[0]
    pos = cache["pos"]
    positions = jnp.full((B, 1), pos, jnp.int32)
    q = _queries(params, x, positions, rope, theta)
    c, k_rope = _latent(params, x, positions, rope, theta)
    ckv = jax.lax.dynamic_update_slice(
        cache["ckv"], c.astype(cache["ckv"].dtype), (0, pos, 0))
    krope = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope.astype(cache["krope"].dtype), (0, pos, 0))
    T = ckv.shape[1]
    k, v = _keys_values(params, ckv.astype(x.dtype), krope.astype(x.dtype),
                        nope)
    valid = jnp.broadcast_to(jnp.arange(T) <= pos, (B, T))
    o = attn.dense_attention(q, k, v, positions[:1], jnp.arange(T)[None, :],
                             causal=False, kv_valid=valid)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"])
    return out, {"ckv": ckv, "krope": krope, "pos": pos + 1}


__all__ = ["mla_spec", "mla_attention", "init_mla_cache", "mla_cache_specs",
           "mla_decode", "MLA_CACHE_AXES", "LATENT_NORM_EPS"]
