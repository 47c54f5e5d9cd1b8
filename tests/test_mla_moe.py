"""The DeepSeek-style decoder (latent attention, a leading dense layer,
dropless chip-share experts) against the plain float32 reference of
``chipbench/ref/mla_moe_lm.py``, at a small size on seeded random weights."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.ref import mla_moe_lm as ref
from chipbench.ref.dense_lm import Adam, identity
from repro.configs.base import get_arch
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.params import init_params
from repro.train.optimizer import OptimizerConfig, adamw_init, adamw_update
from repro.train.step import make_train_step

SEED = 2 ** 33 + 7
B, S = 2, 32
SHAPE = ref.Shape(n_layers=3, n_dense=1, d_model=64, n_heads=4, kv_rank=32,
                  nope=16, rope_dim=8, v_dim=16, d_ff=128, d_expert=32,
                  n_experts=8, n_here=4, offset=0, top_k=2, n_shared=2,
                  routed_scale=2.446, vocab=256, rope_theta=50_000.0,
                  norm_eps=1e-5, balance_coef=0.01)
OPT = dict(peak_lr=3e-4, warmup_steps=0, total_steps=1000, min_lr_ratio=0.1,
           b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, clip_norm=1.0)


def program(s: ref.Shape = SHAPE):
    arch = dataclasses.replace(
        get_arch("moonlight_16b_a3b"), n_layers=s.n_layers,
        first_dense_layers=s.n_dense, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_heads, kv_lora_rank=s.kv_rank, qk_nope_dim=s.nope,
        qk_rope_dim=s.rope_dim, v_head_dim=s.v_dim, d_ff=s.d_ff,
        d_ff_expert=s.d_expert, n_experts=s.n_experts, experts_here=s.n_here,
        experts_offset=s.offset, top_k=s.top_k, n_shared_experts=s.n_shared,
        routed_scale=s.routed_scale, vocab=s.vocab, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, balance_coef=s.balance_coef, dtype="float32",
        remat=False)
    return build_model(arch)


def batch():
    rng = np.random.default_rng(SEED)
    tokens = rng.integers(0, SHAPE.vocab, size=(B, S)).astype(np.int32)
    return tokens, np.ones((B, S), np.float32)


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def stacked(g):
    return jnp.stack(g) if isinstance(g, list) else g


def test_forward_loss_and_every_gradient_match_the_reference():
    model = program()
    params = ref.init_weights(SEED, SHAPE, jnp.float32)
    tokens, mask = batch()
    (loss, metrics), grads = jax.value_and_grad(
        model.train_loss, has_aux=True)(
            params, {"tokens": jnp.asarray(tokens),
                     "loss_mask": jnp.asarray(mask)})
    want_loss, want = ref.Reference(SHAPE, Adam(**OPT), chunk=S).grads(
        ref.flatten(params), tokens, mask)
    assert float(loss) == pytest.approx(want_loss, rel=1e-5)
    assert float(metrics["moe_balance_loss"]) > 0
    got = ref.flatten(grads)
    assert set(got) == set(want)
    assert got["blocks/moe/w_gate"].ndim == 4
    for k, g in want.items():
        assert rel(got[k], stacked(g)) < 2e-4, k


def test_one_optimizer_step_matches_the_reference():
    """int8 first moment and factored second moment, the 4-D expert leaves
    factored per layer and expert."""
    model = program()
    cfg = OptimizerConfig(state_dtype="int8_factored", **OPT)
    params = ref.init_weights(SEED, SHAPE, jnp.float32)
    tokens, mask = batch()
    state = {"params": params, "opt": adamw_init(params, cfg)}
    new, metrics = jax.jit(make_train_step(model, cfg))(
        state, {"tokens": jnp.asarray(tokens), "loss_mask": jnp.asarray(mask)})
    r = ref.TrainState(ref.Reference(SHAPE, Adam(**OPT), chunk=S),
                       ref.init_weights(SEED, SHAPE, jnp.float32))
    out = r.step(tokens, mask)
    assert min(float(metrics["grad_norm"]), OPT["clip_norm"]) == pytest.approx(
        np.sqrt(sum(n ** 2 for n in out["grad_norms"].values())), rel=1e-4)
    before = ref.flatten(params)
    got = ref.flatten(new["params"])
    for k, p in r.flat.items():
        change = np.asarray(p) - np.asarray(before[k])
        assert rel(np.asarray(got[k]) - np.asarray(before[k]), change) < 1e-3, k


def test_chunked_update_of_stacked_expert_leaves_matches_whole(monkeypatch):
    """The same arithmetic a layer at a time: equal to float32 rounding
    (the fused programs differ in the order of a few operations)."""
    from repro.train import optimizer

    cfg = OptimizerConfig(state_dtype="int8_factored", **OPT)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    p = {"w": jax.random.normal(keys[0], (3, 4, 16, 8))}
    g = {"w": jax.random.normal(keys[1], (3, 4, 16, 8))}
    whole = adamw_update(g, adamw_init(p, cfg), p, cfg)
    monkeypatch.setattr(optimizer, "CHUNK_ELEMENTS", 100)
    chunked = adamw_update(g, adamw_init(p, cfg), p, cfg)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(chunked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=0)


def expert_params(s: ref.Shape, key) -> dict:
    spec = moe_mod.dropless_spec(s.d_model, s.d_expert, s.n_experts,
                                 s.n_experts, s.n_shared)
    return init_params(spec, key, jnp.float32)


def share(p: dict, lo: int, hi: int) -> dict:
    out = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        out[k] = p[k][lo:hi]
    return out


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips of 4 experts each: their parts, the shared experts
    counted once, are what the reference gives for all 8."""
    s = SHAPE
    p = expert_params(s, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (B, S, s.d_model))
    parts = [moe_mod.dropless_apply(share(p, lo, lo + 4), x, top_k=s.top_k,
                                    offset=lo, scale=s.routed_scale)
             for lo in (0, 4)]
    shared = moe_mod.swiglu(p["shared"], x)
    got = parts[0][0] + parts[1][0] - shared
    uncut = dataclasses.replace(s, n_here=8)
    want, bal = ref.experts(p, x, uncut, identity, 16)
    assert rel(got, want) < 1e-5
    assert sum(float(a["moe.slots_here"]) for _, a in parts) == B * S * 2
    assert float(parts[0][1]["moe_balance_loss"]) == pytest.approx(
        float(bal), rel=1e-5)


def test_dropless_layer_computes_every_token_routed_onto_one_expert():
    """With every token routed to expert 0, the dropless layer computes all
    of them; the capacity-bounded layer at 1.25 drops most."""
    s = SHAPE
    p = expert_params(s, jax.random.PRNGKey(7))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, 64, s.d_model)))
    p["router"] = p["router"].at[:, 0].set(1.0)
    held = share(p, 0, 4)
    y, aux = moe_mod.dropless_apply(held, x, top_k=1, offset=0,
                                    scale=s.routed_scale)
    assert float(aux["moe.slots_here"]) == 64
    assert float(aux["moe.load_max"]) == 64
    one = dataclasses.replace(s, top_k=1)
    want, _ = ref.experts(held, x, one, identity, 16)
    assert rel(y, want) < 1e-5
    # the capacity-bounded layer: the same gate weights, the same experts
    capped = dataclasses.replace(one, capacity_factor=1.25)
    dropped, _ = ref.experts(held, x, capped, identity, 16)
    shared = np.asarray(moe_mod.swiglu(p["shared"], x))
    lost = np.all(np.abs(np.asarray(dropped) - shared) < 1e-6, axis=-1)
    assert lost.mean() >= 0.8
    cap = {k: p[k] for k in ("router", "w_gate", "w_up", "w_down")}
    _, m = moe_mod.moe_apply(cap, x, top_k=1, capacity_factor=1.25)
    assert float(m["moe_dropped_frac"]) >= 0.8


def test_chunked_attention_with_narrower_values_matches_dense():
    from repro.models.attention import chunked_attention, dense_attention

    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 24))
    k = jax.random.normal(ks[1], (1, 64, 2, 24))
    v = jax.random.normal(ks[2], (1, 64, 2, 8))
    pos = jnp.arange(64)

    def loss(fn, q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v)))

    chunked = lambda q, k, v: chunked_attention(q, k, v, causal=True,
                                                q_chunk=16, kv_chunk=16)
    dense = lambda q, k, v: dense_attention(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(chunked(q, k, v), dense(q, k, v), rtol=1e-5,
                               atol=1e-5)
    ga = jax.grad(lambda *a: loss(chunked, *a), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(lambda *a: loss(dense, *a), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_latent_cache_decode_matches_the_full_forward():
    model = program()
    params = ref.init_weights(SEED, SHAPE, jnp.float32)
    tokens, _ = batch()
    logits, _ = model.forward(params, jnp.asarray(tokens))
    cache = model.init_cache(B, S)
    assert cache["ckv"].shape == (SHAPE.n_layers, B, S, SHAPE.kv_rank)
    for t in range(8):
        step, cache = model.decode_step(params, cache,
                                        jnp.asarray(tokens[:, t:t + 1]))
        assert rel(step[:, 0], logits[:, t]) < 1e-4, t


def test_chunked_attention_blocks_reach_the_step_metrics():
    """Above ``DENSE_ATTN_MAX_SEQ`` each layer of both stacks runs chunked
    attention, and the step reports the blocks it computes, summed over the
    layers, among the counters ``run_training`` keeps."""
    from repro.models.attention import attention_blocks
    from repro.models.transformer import DENSE_ATTN_MAX_SEQ
    from repro.train.loop import STEP_COUNTERS

    model = program()
    params = ref.init_weights(SEED, SHAPE, jnp.float32)
    long = 2 * DENSE_ATTN_MAX_SEQ            # a 4 x 4 grid of 1,024 chunks
    tokens = np.random.default_rng(SEED).integers(
        0, SHAPE.vocab, size=(1, long)).astype(np.int32)
    _, metrics = jax.jit(model.train_loss)(
        params, {"tokens": jnp.asarray(tokens),
                 "loss_mask": jnp.ones((1, long), jnp.float32)})
    assert attention_blocks(long, long) == (10, 16)
    assert float(metrics["attn.blocks_computed"]) == SHAPE.n_layers * 10
    assert "attn.blocks_computed" in STEP_COUNTERS
    tokens, mask = batch()
    _, short = model.train_loss(params, {"tokens": jnp.asarray(tokens),
                                         "loss_mask": jnp.asarray(mask)})
    assert "attn.blocks_computed" not in short


def test_step_counters_are_kept_per_step_and_counted():
    from repro.core import stats

    ss = stats.StepStats()
    ss.on_counter("moe.slots_here", 10.0)
    rec = stats.enable()
    try:
        ss.on_counter("moe.slots_here", 12.0)
        ss.on_counter("moe.load_max", 5.0)
    finally:
        stats.disable()
    assert ss.counters == {"moe.slots_here": [10.0, 12.0],
                           "moe.load_max": [5.0]}
    assert rec.counters == {"moe.slots_here": 12.0, "moe.load_max": 5.0}


def unwritten_outside_groups(monkeypatch):
    """``jax.lax.ragged_dot`` as the chip's kernel gives it: rows past the
    last group are not written, in the output and in the gradient of the
    left operand (NaN here)."""
    real = jax.lax.ragged_dot

    def mark(y, gs):
        rows = jnp.arange(y.shape[0])[:, None] < jnp.sum(gs)
        return jnp.where(rows, y, jnp.nan)

    @jax.custom_vjp
    def rd(x, w, gs):
        return mark(real(x, w, gs), gs)

    def fwd(x, w, gs):
        return rd(x, w, gs), (x, w, gs)

    def bwd(res, dy):
        x, w, gs = res
        _, vjp = jax.vjp(lambda x_, w_: real(x_, w_, gs), x, w)
        dx, dw = vjp(dy)
        return mark(dx, gs), dw, None

    rd.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda x, w, gs, **kw: rd(x, w, gs))


def test_rows_the_kernel_leaves_unwritten_reach_nothing(monkeypatch):
    s = SHAPE
    p = share(expert_params(s, jax.random.PRNGKey(10)), 0, 4)
    x = jax.random.normal(jax.random.PRNGKey(11), (B, S, s.d_model))

    def loss(p, x):
        y, aux = moe_mod.dropless_apply(p, x, top_k=s.top_k, offset=0,
                                        scale=s.routed_scale)
        return jnp.sum(jnp.sin(y)) + aux["moe_balance_loss"]

    want = jax.grad(loss, argnums=(0, 1))(p, x)
    unwritten_outside_groups(monkeypatch)
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
