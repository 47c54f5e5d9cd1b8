"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,S,D,bq,bk", [
    (1, 4, 4, 128, 64, 64, 64),      # MHA
    (2, 8, 2, 256, 64, 128, 128),    # GQA
    (1, 4, 2, 96, 32, 64, 64),       # padded (non-multiple) seq
    (1, 2, 1, 128, 128, 64, 32),     # rectangular blocks
])
def test_flash_attention_sweep(dtype, B, H, K, S, D, bq, bk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, K, S, D), dtype)
    v = jax.random.normal(ks[2], (B, K, S, D), dtype)
    got = ops.flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
    want = ref.mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [16, 100])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 32))
    k = jax.random.normal(ks[1], (1, 2, 256, 32))
    v = jax.random.normal(ks[2], (1, 2, 256, 32))
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,K,G,T,D,bk", [
    (2, 2, 2, 256, 64, 128),
    (1, 4, 1, 100, 32, 64),          # padded T
    (3, 1, 8, 512, 128, 256),
])
def test_flash_decode_sweep(dtype, B, K, G, T, D, bk):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, K, G, D), dtype)
    k = jax.random.normal(ks[1], (B, K, T, D), dtype)
    v = jax.random.normal(ks[2], (B, K, T, D), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    got = ops.flash_decode(q, k, v, lengths, block_k=bk, interpret=True)
    want = ref.decode_reference(q.reshape(B, K * G, D), k, v, lengths)
    np.testing.assert_allclose(np.asarray(got.reshape(B, K * G, D), np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@given(oy=st.integers(0, 15), ox=st.integers(0, 15),
       mirror=st.booleans(), out_h=st.integers(4, 17), out_w=st.integers(4, 17))
@settings(max_examples=20, deadline=None)
def test_crop_mirror_normalize_property(oy, ox, mirror, out_h, out_w):
    img = jax.random.randint(jax.random.PRNGKey(3), (2, 32, 32, 3), 0, 256
                             ).astype(jnp.uint8)
    oys = jnp.array([oy, (oy + 5) % 16])
    oxs = jnp.array([ox, (ox + 3) % 16])
    mir = jnp.array([mirror, not mirror])
    mean = jnp.array([120.0, 115.0, 100.0])
    inv_std = 1.0 / jnp.array([60.0, 61.0, 62.0])
    got = ops.crop_mirror_normalize(img, oys, oxs, mir, mean, inv_std,
                                    out_h=out_h, out_w=out_w, interpret=True)
    want = ref.crop_mirror_normalize_reference(img, oys, oxs, mir, mean,
                                               inv_std, out_h, out_w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@given(seed=st.integers(0, 2 ** 16), mirror=st.booleans(),
       out_h=st.integers(4, 24), out_w=st.integers(4, 24))
@settings(max_examples=20, deadline=None)
def test_crop_mirror_normalize_matches_numpy_ref(seed, mirror, out_h, out_w):
    """Kernel == pure-NumPy reference on uint8 data that includes the edge
    values 0 and 255 (where a uint8->f32 conversion bug would show)."""
    rng = np.random.default_rng(seed)
    B, H, W, C = 3, 24, 24, 3
    img = rng.integers(0, 256, size=(B, H, W, C)).astype(np.uint8)
    img[0, 0, 0, :] = 0
    img[0, -1, -1, :] = 255
    img[1] = 255                                   # saturated frame
    oy = rng.integers(0, H - out_h + 1, size=B).astype(np.int32)
    ox = rng.integers(0, W - out_w + 1, size=B).astype(np.int32)
    mir = np.array([mirror, not mirror, mirror], dtype=np.int32)
    mean = np.array([120.0, 115.0, 100.0], dtype=np.float32)
    inv_std = np.float32(1) / np.array([60.0, 61.0, 62.0], dtype=np.float32)
    got = ops.crop_mirror_normalize(
        jnp.asarray(img), jnp.asarray(oy), jnp.asarray(ox), jnp.asarray(mir),
        jnp.asarray(mean), jnp.asarray(inv_std), out_h=out_h, out_w=out_w,
        interpret=True)
    want = ref.crop_mirror_normalize_np(img, oy, ox, mir, mean, inv_std,
                                        out_h, out_w)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_crop_mirror_normalize_clamps_offsets():
    """Out-of-range crop offsets degrade to edge crops in BOTH the kernel
    and the NumPy reference (same clamping semantics)."""
    rng = np.random.default_rng(7)
    img = rng.integers(0, 256, size=(2, 16, 16, 3)).astype(np.uint8)
    oy = np.array([100, -5], dtype=np.int32)       # way past both edges
    ox = np.array([-3, 99], dtype=np.int32)
    mir = np.zeros(2, dtype=np.int32)
    mean = np.zeros(3, dtype=np.float32)
    inv_std = np.ones(3, dtype=np.float32)
    got = ops.crop_mirror_normalize(
        jnp.asarray(img), jnp.asarray(oy), jnp.asarray(ox), jnp.asarray(mir),
        jnp.asarray(mean), jnp.asarray(inv_std), out_h=8, out_w=8,
        interpret=True)
    want = ref.crop_mirror_normalize_np(img, oy, ox, mir, mean, inv_std, 8, 8)
    clamped = ref.crop_mirror_normalize_np(
        img, np.array([8, 0]), np.array([0, 8]), mir, mean, inv_std, 8, 8)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(want, clamped, rtol=1e-6, atol=1e-6)


def test_flash_attention_matches_model_chunked_path():
    """Kernel and the XLA chunked path implement the same math."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, S, H, K, D = 1, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    xla = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    pallas = ops.flash_attention(q.transpose(0, 2, 1, 3),
                                 k.transpose(0, 2, 1, 3),
                                 v.transpose(0, 2, 1, 3),
                                 causal=True, block_q=64, block_k=64,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(pallas.transpose(0, 2, 1, 3)),
                               np.asarray(xla), rtol=2e-5, atol=2e-5)
