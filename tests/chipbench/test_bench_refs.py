"""The benchmark's plain references and traffic copies against the program,
at small sizes on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.drivers import train_loop as drv
from chipbench.ref import crop, dense_lm
from chipbench.traffic import pixels, tokens

MEAN = [123.675, 116.28, 103.53]
STD = [58.395, 57.12, 57.375]


def test_pixel_frames_follow_the_programs_rule():
    from repro.data.datasets import SyntheticPixelDataset

    ds = SyntheticPixelDataset(h=40, w=48, c=3)
    for label in (0, 5, 11):
        a = pixels.make_frame(np.random.default_rng(7), label, 40, 48, 3)
        b = ds.make_frame(np.random.default_rng(7), label)
        np.testing.assert_array_equal(a, b)


def test_pixel_frames_at_once_equal_the_loop():
    labels = np.random.default_rng(2).integers(0, 1000, size=12)
    for h, w in ((256, 256), (40, 48)):
        ra, rb = np.random.default_rng(5), np.random.default_rng(5)
        loop = np.stack([pixels.make_frame(ra, int(lab), h, w, 3)
                         for lab in labels])
        np.testing.assert_array_equal(pixels.make_frames(rb, labels, h, w, 3),
                                      loop)


def test_pixel_rows_share_frames_and_are_seeded():
    a = pixels.generate(2 ** 40 + 1, 64, 4, 16, 16, 3, 1000)
    b = pixels.generate(2 ** 40 + 1, 64, 4, 16, 16, 3, 1000)
    c = pixels.generate(2 ** 40 + 2, 64, 4, 16, 16, 3, 1000)
    assert a.keys == b.keys and a.keys != c.keys
    np.testing.assert_array_equal(a.frames, b.frames)
    assert len(set(a.keys)) == 64 and a.frames.shape == (4, 16, 16, 3)
    assert set(a.frame_of_key.tolist()) <= set(range(4))
    np.testing.assert_array_equal(a.key_labels,
                                  a.frame_labels[a.frame_of_key])


def test_token_records_decode_with_the_programs_reader():
    from repro.data.datasets import decode_token_record

    recs = tokens.generate(2 ** 40, 5, 32, 1000)
    for key_toks, lab in zip(recs.tokens, recs.labels):
        got, got_lab = decode_token_record(tokens.encode(key_toks, int(lab)))
        np.testing.assert_array_equal(got, key_toks)
        assert got_lab == lab
    assert recs.tokens.min() >= 0 and recs.tokens.max() < 1000
    steps = np.diff(recs.tokens.astype(np.int64), axis=1) % 1000
    assert np.all((steps <= 32) | (steps >= 1000 - 32))


def test_augment_replay_matches_the_feeds_draws():
    from repro.data.pipeline import augment_draws

    ra, rb = np.random.default_rng(3), np.random.default_rng(3)
    for _ in range(3):
        for x, y in zip(crop.augment_draws(ra, 8, 256, 256, 224, 224),
                        augment_draws(rb, 8, 256, 256, 224, 224)):
            np.testing.assert_array_equal(x, y)


def test_crop_reference_matches_the_kernel_bit_for_bit():
    from repro.kernels import ops

    rows = pixels.generate(5, 16, 6, 40, 48, 3, 1000)
    img = rows.frames[rows.frame_of_key[:6]]
    oy, ox, mirror = crop.augment_draws(np.random.default_rng(1), 6, 40, 48,
                                        32, 24)
    inv = crop.inv_std(STD)
    got = ops.crop_mirror_normalize(
        jnp.asarray(img), jnp.asarray(oy), jnp.asarray(ox),
        jnp.asarray(mirror), jnp.asarray(MEAN, jnp.float32), jnp.asarray(inv),
        out_h=32, out_w=24, interpret=True)
    want = crop.crop_mirror_normalize(img, oy, ox, mirror, MEAN, STD, 32, 24)
    np.testing.assert_array_equal(np.asarray(got), want)
    # and a mirrored crop really is the reversed one
    i = int(np.argmax(mirror))
    if mirror[i]:
        plain = crop.crop_mirror_normalize(img[i:i + 1], oy[i:i + 1],
                                           ox[i:i + 1], [0], MEAN, STD, 32, 24)
        np.testing.assert_array_equal(want[i], plain[0][:, :, ::-1])


def test_crop_reference_refuses_an_offset_outside_the_image():
    img = np.zeros((1, 8, 8, 3), np.uint8)
    with pytest.raises(ValueError):
        crop.crop_mirror_normalize(img, [2], [0], [0], MEAN, STD, 7, 7)


SMALL = dense_lm.Shape(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                       head_dim=8, d_ff=48, vocab=96, rope_theta=10000.0,
                       norm_eps=1e-5)
ADAM = dense_lm.Adam(peak_lr=3e-4, warmup_steps=0, total_steps=1000,
                     min_lr_ratio=0.1, b1=0.9, b2=0.95, eps=1e-8,
                     weight_decay=0.1, clip_norm=1.0)


def small_model():
    from repro.configs.base import ArchConfig
    from repro.models import build_model

    return build_model(ArchConfig(
        name="small", family="dense", n_layers=2, d_model=32, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=48, vocab=96, rope_theta=10000.0,
        norm_eps=1e-5, dtype="float32", remat=False))


def batch():
    toks = tokens.generate(9, 2, 32, 96).tokens
    mask = np.ones((2, 32), np.float32)
    mask[1, 20:] = 0.0
    return toks, mask


def test_dense_lm_reference_matches_the_program_loss_and_grads():
    model = small_model()
    w = dense_lm.init_weights(11, SMALL, jnp.float32)
    toks, mask = batch()
    (loss, _), grads = jax.value_and_grad(model.train_loss, has_aux=True)(
        w, {"tokens": jnp.asarray(toks), "loss_mask": jnp.asarray(mask)})
    ref = dense_lm.Reference(SMALL, ADAM, chunk=16)
    r_loss, layers, d_table, d_lnf = ref.loss_and_grads(w, toks, mask)
    assert r_loss == pytest.approx(float(loss), rel=1e-5)
    np.testing.assert_allclose(d_table, grads["embed"]["embedding"],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(d_lnf, grads["ln_f"]["scale"], rtol=1e-4,
                               atol=1e-7)
    b = grads["blocks"]
    for i in range(SMALL.n_layers):
        for g, ks in dense_lm.BLOCK_KEYS.items():
            for k in ks:
                np.testing.assert_allclose(layers[i][k], b[g][k][i],
                                           rtol=1e-4, atol=1e-7)
        for n in dense_lm.NORMS:
            np.testing.assert_allclose(layers[i][n], b[n]["scale"][i],
                                       rtol=1e-4, atol=1e-7)


def test_dense_lm_reference_step_matches_the_programs_optimizer():
    from repro.train.optimizer import OptimizerConfig, adamw_init
    from repro.train.step import make_train_step

    model = small_model()
    opt = OptimizerConfig(state_dtype="int8_factored",
                          **{k: getattr(ADAM, k) for k in ADAM.__dataclass_fields__})
    toks, mask = batch()
    w = dense_lm.init_weights(11, SMALL, jnp.bfloat16)
    state = {"params": w, "opt": adamw_init(w, opt)}
    step = jax.jit(make_train_step(model, opt))
    ts = dense_lm.TrainState(dense_lm.Reference(SMALL, ADAM, chunk=16),
                             dense_lm.init_weights(11, SMALL, jnp.bfloat16))
    for _ in range(2):
        state, metrics = step(state, {"tokens": jnp.asarray(toks),
                                      "loss_mask": jnp.asarray(mask)})
        out = ts.step(toks, mask)
    # the program computes in bfloat16, the reference in float32
    assert out["loss"] == pytest.approx(float(metrics["loss"]), rel=1e-2)
    moved = drv.diff_norms(state["params"], dense_lm.init_weights(
        11, SMALL, jnp.bfloat16))
    want = drv.diff_norms(ts.params, dense_lm.init_weights(
        11, SMALL, jnp.bfloat16))
    assert set(moved) == set(want)
    for k in want:
        assert moved[k] == pytest.approx(want[k], rel=0.1), k


def test_weights_follow_the_programs_layout():
    model = small_model()
    w = dense_lm.init_weights(3, SMALL, jnp.float32)
    want = model.abstract_params()
    assert jax.tree.structure(w) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(want)):
        assert a.shape == b.shape
    again = dense_lm.init_weights(3, SMALL, jnp.float32)
    other = dense_lm.init_weights(4, SMALL, jnp.float32)
    e = lambda t: np.asarray(t["embed"]["embedding"])
    np.testing.assert_array_equal(e(w), e(again))
    assert not np.array_equal(e(w), e(other))
    assert float(np.std(e(w))) == pytest.approx(0.02, rel=0.2)
