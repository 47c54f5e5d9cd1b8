"""Decoder-only LM covering the dense, MoE and VLM architecture families.

One scanned pre-norm block: x += attn(norm(x)); x += ffn|moe(norm(x)).
Layers are stacked along a leading "layers" axis and executed with
``jax.lax.scan`` (+ optional remat) so the HLO stays depth-independent —
required to compile the 61-layer/1T-param configs in the dry-run.  A
DeepSeek-style model runs its leading dense layers ("dense_blocks") as a
stack of their own before the expert layers ("blocks"); its attention is
latent (``models/mla.py``) where ``kv_lora_rank`` is set.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig

from . import attention as attn
from . import mla
from . import moe as moe_mod
from .layers import (embed, embed_spec, output_head, output_head_spec,
                     rmsnorm, rmsnorm_spec, softmax_xent, swiglu, swiglu_spec,
                     unembed)
from .params import (P, abstract_params, init_params, logical_axes,
                     stack_layer_specs)

DENSE_ATTN_MAX_SEQ = 2048   # above this, use chunked (memory-efficient) attn

# How each layer's counter is reduced over the layers.
_AUX_REDUCE = {"moe_aux_loss": jnp.mean, "moe_z_loss": jnp.mean,
               "moe_dropped_frac": jnp.mean, "moe_balance_loss": jnp.sum,
               "moe.slots_here": jnp.sum, "moe.load_max": jnp.max,
               "attn.blocks_computed": jnp.sum}


class DecoderLM:
    """dense / moe / vlm decoder LM built from an ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.is_moe = cfg.n_experts > 0
        self.is_vlm = cfg.n_patches > 0
        self.is_mla = cfg.kv_lora_rank > 0
        self.dropless = cfg.router == "sigmoid"
        if cfg.router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router {cfg.router!r}")
        self.dtype = jnp.dtype(cfg.dtype)
        # optional sharding constrainers (set by launchers)
        self.constrain_act = None
        self.constrain_q = None
        self.constrain_kv = None
        self.constrain_moe = None

    # -- specs ---------------------------------------------------------------
    def block_spec(self, moe: Optional[bool] = None) -> Dict:
        c = self.cfg
        moe = self.is_moe if moe is None else moe
        if self.is_mla:
            attn_spec = mla.mla_spec(c.d_model, c.n_heads, c.kv_lora_rank,
                                     c.qk_nope_dim, c.qk_rope_dim,
                                     c.v_head_dim)
        else:
            attn_spec = attn.gqa_spec(c.d_model, c.n_heads, c.n_kv_heads,
                                      c.resolved_head_dim, qk_norm=c.qk_norm)
        spec = {"ln1": rmsnorm_spec(c.d_model), "attn": attn_spec,
                "ln2": rmsnorm_spec(c.d_model)}
        if moe and self.dropless:
            spec["moe"] = moe_mod.dropless_spec(
                c.d_model, c.expert_width, c.n_experts, c.n_experts_here,
                c.n_shared_experts)
        elif moe:
            spec["moe"] = moe_mod.moe_spec(c.d_model, c.d_ff, c.n_experts)
        else:
            spec["mlp"] = swiglu_spec(c.d_model, c.d_ff)
        return spec

    def param_specs(self) -> Dict:
        c = self.cfg
        n_dense = c.first_dense_layers
        spec = {
            "embed": embed_spec(c.vocab, c.d_model),
            "blocks": stack_layer_specs(self.block_spec(),
                                        c.n_layers - n_dense),
            "ln_f": rmsnorm_spec(c.d_model),
        }
        if n_dense:
            spec["dense_blocks"] = stack_layer_specs(
                self.block_spec(moe=False), n_dense)
        if not c.tie_embeddings:
            spec["head"] = output_head_spec(c.d_model, c.vocab)
        if self.is_vlm:
            spec["mm_proj"] = {"w": P((c.d_model, c.d_model),
                                      ("d_model", "d_model_out"))}
        return spec

    def init(self, key: jax.Array, dtype=None) -> Dict:
        return init_params(self.param_specs(), key, dtype or self.dtype)

    def abstract_params(self) -> Dict:
        return abstract_params(self.param_specs(), self.dtype)

    def param_logical_axes(self) -> Dict:
        return logical_axes(self.param_specs())

    # -- forward ---------------------------------------------------------------
    def _attention(self, p: Dict, h: jax.Array, positions: jax.Array
                   ) -> Tuple[jax.Array, Dict]:
        """The sublayer's output and, where it runs chunked, the blocks
        that ``chunked_attention`` computes (``attn.blocks_computed``)."""
        c = self.cfg
        S = h.shape[1]
        window = 0 if self.is_mla else c.window
        aux = {} if S <= DENSE_ATTN_MAX_SEQ else {
            "attn.blocks_computed": attn.attention_blocks(
                S, S, causal=True, window=window)[0]}
        if self.is_mla:
            return mla.mla_attention(p, h, positions, nope=c.qk_nope_dim,
                                     rope=c.qk_rope_dim, theta=c.rope_theta,
                                     dense_max_seq=DENSE_ATTN_MAX_SEQ), aux
        q, k, v = attn.project_qkv(p, h)
        q = attn.apply_rope(q, positions, c.rope_theta)
        k = attn.apply_rope(k, positions, c.rope_theta)
        k = attn.expand_kv(k, c.n_heads)     # TP-friendly GQA
        v = attn.expand_kv(v, c.n_heads)
        if self.constrain_q is not None:
            q = self.constrain_q(q)
            k = self.constrain_kv(k)
            v = self.constrain_kv(v)
        if S <= DENSE_ATTN_MAX_SEQ:
            o = attn.dense_attention(q, k, v, positions[0], positions[0],
                                     causal=True, window=window)
        else:
            o = attn.chunked_attention(q, k, v, positions[0], positions[0],
                                       causal=True, window=window)
        return attn.project_out(p, o), aux

    def _ffn(self, layer_params: Dict, h: jax.Array, constrain=None
             ) -> Tuple[jax.Array, Dict]:
        c = self.cfg
        if "mlp" in layer_params:
            return swiglu(layer_params["mlp"], h), {}
        if self.dropless:
            return moe_mod.dropless_apply(
                layer_params["moe"], h, top_k=c.top_k,
                offset=c.experts_offset, scale=c.routed_scale)
        return moe_mod.moe_apply(layer_params["moe"], h, top_k=c.top_k,
                                 capacity_factor=c.capacity_factor,
                                 constrain=constrain)

    def _block(self, layer_params: Dict, x: jax.Array, positions: jax.Array
               ) -> Tuple[jax.Array, Dict]:
        c = self.cfg
        h = rmsnorm(layer_params["ln1"], x, c.norm_eps)
        o, aux = self._attention(layer_params["attn"], h, positions)
        x = x + o
        h = rmsnorm(layer_params["ln2"], x, c.norm_eps)
        y, ffn_aux = self._ffn(layer_params, h, self.constrain_moe)
        return x + y, {**aux, **ffn_aux}

    def _run_stack(self, blocks: Dict, x: jax.Array, positions: jax.Array
                   ) -> Tuple[jax.Array, Dict]:
        """One stack of layers; each layer's counters, stacked."""
        c = self.cfg
        cst = self.constrain_act or (lambda t: t)

        def body(h, layer_params):
            h, aux = self._block(layer_params, h, positions)
            return cst(h), {k: jnp.asarray(v, jnp.float32)
                            for k, v in aux.items()}

        fn = body
        if c.remat:
            fn = jax.checkpoint(body,
                                policy=jax.checkpoint_policies.nothing_saveable)
        if c.scan_layers:
            return jax.lax.scan(fn, x, blocks)
        n = jax.tree.leaves(blocks)[0].shape[0]
        auxs = []
        for i in range(n):
            x, aux = fn(x, jax.tree.map(lambda p: p[i], blocks))
            auxs.append(aux)
        return x, jax.tree.map(lambda *a: jnp.stack(a), *auxs)

    def _run_blocks(self, params: Dict, x: jax.Array, positions: jax.Array
                    ) -> Tuple[jax.Array, Dict]:
        cst = self.constrain_act or (lambda t: t)
        x = cst(x)
        stacks = []
        if "dense_blocks" in params:
            x, aux = self._run_stack(params["dense_blocks"], x, positions)
            stacks.append(aux)
        x, aux = self._run_stack(params["blocks"], x, positions)
        stacks.append(aux)
        # each counter over the layers of every stack that reports it
        per_layer = {k: jnp.concatenate([a[k] for a in stacks if k in a])
                     for k in dict.fromkeys(k for a in stacks for k in a)}
        return x, {k: _AUX_REDUCE[k](v) for k, v in per_layer.items()}

    def _logits(self, params: Dict, x: jax.Array) -> jax.Array:
        if "head" in params:
            return output_head(params["head"], x)
        return unembed(params["embed"], x)

    def forward(self, params: Dict, tokens: jax.Array,
                extras: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
        """Full-sequence logits (training / prefill)."""
        c = self.cfg
        B, S = tokens.shape
        x = embed(params["embed"], tokens, self.dtype)
        if self.is_vlm:
            patches = extras["patch_embeds"].astype(self.dtype)
            patches = jnp.einsum("bpd,de->bpe", patches,
                                 params["mm_proj"]["w"])
            x = jax.lax.dynamic_update_slice(x, patches, (0, 0, 0))
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x, aux = self._run_blocks(params, x, positions)
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        return self._logits(params, x), aux

    # -- losses ---------------------------------------------------------------
    def train_loss(self, params: Dict, batch: Dict
                   ) -> Tuple[jax.Array, Dict]:
        tokens = batch["tokens"]
        logits, aux = self.forward(params, tokens, batch)
        targets = tokens[:, 1:]
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        if self.is_vlm and mask is None:
            # text-only loss: skip the patch positions
            pos = jnp.arange(targets.shape[1])[None, :]
            mask = (pos >= self.cfg.n_patches).astype(jnp.float32)
        loss = softmax_xent(logits[:, :-1], targets, mask)
        metrics = {"xent": loss, **aux}
        if self.dropless:
            loss = loss + self.cfg.balance_coef * aux["moe_balance_loss"]
        elif self.is_moe:
            loss = loss + 0.01 * aux["moe_aux_loss"] + 1e-3 * aux["moe_z_loss"]
        metrics["loss"] = loss
        return loss, metrics

    # -- decode ----------------------------------------------------------------
    def _cache_len(self, seq_len: int) -> int:
        c = self.cfg
        return min(c.window, seq_len) if c.window else seq_len

    def init_cache(self, batch: int, seq_len: int) -> Dict:
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_specs(batch, seq_len))

    def cache_specs(self, batch: int, seq_len: int) -> Dict:
        c = self.cfg
        T = self._cache_len(seq_len)
        if self.is_mla:
            spec = mla.mla_cache_specs(batch, T, c.kv_lora_rank,
                                       c.qk_rope_dim, self.dtype)
        else:
            spec = attn.cache_specs(batch, T, c.n_kv_heads,
                                    c.resolved_head_dim, self.dtype)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((c.n_layers,) + s.shape, s.dtype),
            spec)

    def decode_step(self, params: Dict, cache: Dict, tokens: jax.Array
                    ) -> Tuple[jax.Array, Dict]:
        """tokens (B,1) -> logits (B,1,V), updated stacked cache."""
        c = self.cfg
        x = embed(params["embed"], tokens, self.dtype)

        def body(x, scanned):
            layer_params, layer_cache = scanned
            h = rmsnorm(layer_params["ln1"], x, c.norm_eps)
            if self.is_mla:
                o, new_cache = mla.mla_decode(
                    layer_params["attn"], layer_cache, h, nope=c.qk_nope_dim,
                    rope=c.qk_rope_dim, theta=c.rope_theta)
            else:
                o, new_cache = attn.decode_attention(
                    layer_params["attn"], layer_cache, h, window=c.window,
                    rope_theta=c.rope_theta)
            x = x + o
            h = rmsnorm(layer_params["ln2"], x, c.norm_eps)
            y, _ = self._ffn(layer_params, h)
            return x + y, new_cache

        n_dense = c.first_dense_layers
        caches = []
        if n_dense:
            x, dense_cache = jax.lax.scan(
                body, x, (params["dense_blocks"],
                          jax.tree.map(lambda a: a[:n_dense], cache)))
            caches.append(dense_cache)
        x, moe_cache = jax.lax.scan(
            body, x, (params["blocks"],
                      jax.tree.map(lambda a: a[n_dense:], cache)))
        caches.append(moe_cache)
        new_cache = jax.tree.map(lambda *a: jnp.concatenate(a), *caches)
        x = rmsnorm(params["ln_f"], x, c.norm_eps)
        return self._logits(params, x), new_cache

    # -- shape plumbing ----------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict:
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                    "cache": self.cache_specs(B, S)}
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if self.is_vlm:
            specs["patch_embeds"] = jax.ShapeDtypeStruct(
                (B, c.n_patches, c.d_model), self.dtype)
        return specs

    def make_batch(self, key: jax.Array, shape: ShapeConfig) -> Dict:
        c = self.cfg
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"tokens": jax.random.randint(key, (B, 1), 0, c.vocab),
                    "cache": self.init_cache(B, S)}
        batch = {"tokens": jax.random.randint(key, (B, S), 0, c.vocab)}
        if self.is_vlm:
            # frontend-stub embeddings at token-embedding scale
            batch["patch_embeds"] = 0.02 * jax.random.normal(
                key, (B, c.n_patches, c.d_model), self.dtype)
        return batch

    def input_logical_axes(self, shape: ShapeConfig) -> Dict:
        if shape.kind == "decode" and self.is_mla:
            return {"tokens": ("batch", None), "cache": mla.MLA_CACHE_AXES}
        kv_cache_axes = {"k": ("layers", "batch", "kv_seq", "kv_heads",
                               "head_dim"),
                         "v": ("layers", "batch", "kv_seq", "kv_heads",
                               "head_dim"),
                         "pos": ("layers",)}
        if shape.kind == "decode":
            return {"tokens": ("batch", None), "cache": kv_cache_axes}
        axes = {"tokens": ("batch", "seq")}
        if self.is_vlm:
            axes["patch_embeds"] = ("batch", "patches", "d_model")
        return axes


__all__ = ["DecoderLM", "DENSE_ATTN_MAX_SEQ"]
