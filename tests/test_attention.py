"""Attention equivalences: chunked(custom-VJP) == dense; decode cache paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models.params import init_params


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 19), (False, 0),
                                           (True, 70)])
@pytest.mark.parametrize("S,qc,kc", [(128, 32, 32), (96, 64, 32),
                                     (256, 32, 32), (128, 32, 64),
                                     (128, 64, 32)])
def test_chunked_matches_dense_forward(causal, window, S, qc, kc):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, H, K, D = 2, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    pos = jnp.arange(S)
    want = attn.dense_attention(q, k, v, pos, pos, causal=causal,
                                window=window)
    got = attn.chunked_attention(q, k, v, causal=causal, window=window,
                                 q_chunk=qc, kv_chunk=kc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 23), (False, 0),
                                           (True, 70)])
def test_chunked_matches_dense_gradients(causal, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, K, D = 2, 96, 4, 2, 16
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    pos = jnp.arange(S)

    def loss_c(q, k, v):
        o = attn.chunked_attention(q, k, v, causal=causal, window=window,
                                   q_chunk=32, kv_chunk=32)
        return jnp.sum(o * o)

    def loss_d(q, k, v):
        o = attn.dense_attention(q, k, v, pos, pos, causal=causal,
                                 window=window)
        return jnp.sum(o * o)

    gc = jax.grad(loss_c, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


# (S, q chunk, KV chunk, causal, window, value width): chunks of unequal
# width both ways, S padded to neither chunk, a window over several blocks,
# an 8 x 8 grid as Moonlight's at 8k, no mask, and MLA's narrower values
GRIDS = [(128, 64, 32, True, 0, 16), (128, 32, 64, True, 0, 16),
         (100, 32, 48, True, 0, 16), (100, 32, 48, True, 40, 16),
         (256, 32, 32, True, 80, 16), (200, 64, 32, True, 70, 16),
         (256, 32, 32, True, 0, 16), (256, 32, 32, False, 0, 16),
         (128, 64, 32, False, 0, 16), (256, 32, 32, True, 0, 8)]


@pytest.mark.parametrize("S,qc,kc,causal,window,Dv", GRIDS)
def test_chunked_matches_dense_on_block_grids(S, qc, kc, causal, window, Dv):
    """Forward and every gradient, where the loops skip masked blocks."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    B, H, D = 2, 4, 24
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    ct = jax.random.normal(ks[3], (B, S, H, Dv))
    pos = jnp.arange(S)
    chunked = lambda q, k, v: attn.chunked_attention(
        q, k, v, causal=causal, window=window, q_chunk=qc, kv_chunk=kc)
    dense = lambda q, k, v: attn.dense_attention(
        q, k, v, pos, pos, causal=causal, window=window)
    got, vjp_c = jax.vjp(chunked, q, k, v)
    want, vjp_d = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(vjp_c(ct), vjp_d(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("S,T,causal,window,qc,kc,want", [
    (8192, 8192, True, 0, 1024, 1024, (36, 64)),     # Moonlight at 8k
    (8192, 8192, False, 0, 1024, 1024, (64, 64)),
    (8192, 8192, True, 2048, 1024, 1024, (21, 64)),  # 8 + 7 + 6 diagonals
    (8192, 8192, True, 0, 1024, 512, (72, 128)),     # 2i + 2 in row i
    (8192, 8192, True, 0, 512, 1024, (72, 128)),     # 1, 1, 2, 2, ..., 8, 8
    (2048, 2048, True, 0, 1024, 1024, (3, 4)),
])
def test_attention_blocks_counts(S, T, causal, window, qc, kc, want):
    assert attn.attention_blocks(S, T, causal=causal, window=window,
                                 q_chunk=qc, kv_chunk=kc) == want


@pytest.mark.parametrize("S,qc,kc,causal,window", [
    (128, 32, 32, True, 0), (100, 32, 48, True, 0), (100, 48, 32, True, 33),
    (256, 32, 32, True, 80), (96, 64, 32, False, 0), (96, 32, 64, False, 40),
    (64, 16, 16, True, 16), (64, 16, 16, True, 1)])
def test_block_grid_matches_the_mask(S, qc, kc, causal, window):
    """A block is visible iff ``_mask_bias`` lets one of its pairs through,
    and wholly visible iff it lets all through."""
    S_p, T_p, qc, kc = attn._grid(S, S, qc, kc)
    ok = np.asarray(attn._mask_bias(jnp.arange(S_p), jnp.arange(T_p), causal,
                                    window)) == 0
    blocks = ok.reshape(S_p // qc, qc, T_p // kc, kc)
    visible, full = attn._block_grid(S_p, T_p, qc, kc, causal, window)
    np.testing.assert_array_equal(visible, blocks.any(axis=(1, 3)))
    np.testing.assert_array_equal(full, blocks.all(axis=(1, 3)))


def test_backward_and_remat_visit_the_forwards_blocks(monkeypatch):
    """The forward, its rerun under remat and the backward each compute
    exactly the visible blocks, the mask only on the partial ones."""
    seen = []
    scores = attn._scores

    def spy(qc, kb, i, j, masked, *rest):
        jax.debug.callback(
            lambda i, j: seen.append((int(i), int(j), masked)), i, j)
        return scores(qc, kb, i, j, masked, *rest)

    monkeypatch.setattr(attn, "_scores", spy)
    S, qc, kc, window = 96, 16, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, S, 2, 8))
    k = jax.random.normal(ks[1], (1, S, 2, 8))
    v = jax.random.normal(ks[2], (1, S, 2, 8))
    f = jax.checkpoint(lambda q, k, v: attn.chunked_attention(
        q, k, v, causal=True, window=window, q_chunk=qc, kv_chunk=kc))
    out, vjp = jax.vjp(f, q, k, v)
    jax.effects_barrier()
    fwd = sorted(seen)
    del seen[:]
    jax.block_until_ready(vjp(jnp.ones_like(out)))
    jax.effects_barrier()
    rerun_and_bwd = sorted(seen)
    visible, full = attn._block_grid(S, S, qc, kc, True, window)
    want = sorted((int(i), int(j), not full[i, j])
                  for i, j in zip(*np.nonzero(visible)))
    assert fwd == want
    assert rerun_and_bwd == sorted(want + want)
    assert len(want) == attn.attention_blocks(S, S, window=window,
                                              q_chunk=qc, kv_chunk=kc)[0]
    assert len(want) < visible.size


def test_decode_attention_matches_full_forward():
    """Prefill-by-decode: step-by-step cache attention == full causal attn."""
    c = {"d": 32, "H": 4, "K": 2, "Dh": 8}
    spec = attn.gqa_spec(c["d"], c["H"], c["K"], c["Dh"])
    params = init_params(spec, jax.random.PRNGKey(0), jnp.float32)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, c["d"])) * 0.3

    # full-sequence path (with rope)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = attn.project_qkv(params, x)
    q = attn.apply_rope(q, pos, 10_000.0)
    k = attn.apply_rope(k, pos, 10_000.0)
    o = attn.dense_attention(q, k, v, pos[0], pos[0], causal=True)
    want = attn.project_out(params, o)

    # decode path token by token
    cache = attn.init_kv_cache(B, S, c["K"], c["Dh"], jnp.float32)
    outs = []
    for t in range(S):
        o_t, cache = attn.decode_attention(params, cache, x[:, t:t + 1])
        outs.append(o_t)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_decode_ring_buffer_matches_sliding_window():
    """SWA ring cache == full attention with window mask."""
    c = {"d": 32, "H": 4, "K": 2, "Dh": 8}
    W = 5
    spec = attn.gqa_spec(c["d"], c["H"], c["K"], c["Dh"])
    params = init_params(spec, jax.random.PRNGKey(2), jnp.float32)
    B, S = 1, 14
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, c["d"])) * 0.3

    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = attn.project_qkv(params, x)
    q = attn.apply_rope(q, pos, 10_000.0)
    k = attn.apply_rope(k, pos, 10_000.0)
    o = attn.dense_attention(q, k, v, pos[0], pos[0], causal=True, window=W)
    want = attn.project_out(params, o)

    cache = attn.init_kv_cache(B, W, c["K"], c["Dh"], jnp.float32)
    outs = []
    for t in range(S):
        o_t, cache = attn.decode_attention(params, cache, x[:, t:t + 1],
                                           window=W)
        outs.append(o_t)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_qk_norm_applied():
    spec = attn.gqa_spec(16, 2, 2, 8, qk_norm=True)
    params = init_params(spec, jax.random.PRNGKey(4), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 4, 16))
    q, k, _ = attn.project_qkv(params, x)
    # per-head rmsnorm => unit rms rows
    rms = np.sqrt(np.mean(np.asarray(q) ** 2, axis=-1))
    np.testing.assert_allclose(rms, np.ones_like(rms), rtol=1e-3)
