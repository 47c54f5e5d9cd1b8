"""Checkpoint manager: atomic save/restore, GC, loader-position roundtrip."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train.checkpoint import CheckpointManager


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (4, 8)),
                       "b": jnp.zeros((8,))},
            "opt": {"m": {"w": jnp.ones((4, 8)), "b": jnp.zeros((8,))},
                    "step": jnp.asarray(7, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    state = _state()
    mgr.save(10, state, extra={"loader": {"epoch": 1, "cursor": 320}})
    restored, manifest = mgr.restore(state)
    assert manifest["step"] == 10
    assert manifest["extra"]["loader"]["cursor"] == 320
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = _state()
    for s in (10, 20, 30, 40):
        mgr.save(s, state)
    assert mgr.latest_step() == 40
    assert mgr.all_steps() == [30, 40]           # keep=2 GC'd older


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(5, _state(), blocking=False)
    mgr.wait()
    assert mgr.latest_step() == 5


def test_no_tmp_dirs_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_restore_rejects_shape_mismatch(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _state())
    bad = _state()
    bad["params"]["w"] = jnp.zeros((5, 8))
    with pytest.raises(ValueError):
        mgr.restore(bad)


def test_restore_missing_dir_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path / "empty"))
    with pytest.raises(FileNotFoundError):
        mgr.restore(_state())


def test_elastic_restore_with_shardings(tmp_path):
    """Restore with explicit target shardings (single-device here — the
    mechanism is device_put against a sharding tree)."""
    from jax.sharding import NamedSharding, PartitionSpec
    mgr = CheckpointManager(str(tmp_path))
    state = _state()
    mgr.save(3, state)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, PartitionSpec()), state)
    restored, _ = mgr.restore(state, shardings=sh)
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(state["params"]["w"]))


def test_bf16_state_restores_bit_exact(tmp_path):
    """A published config's state is bf16 params plus int8/f32 optimizer
    leaves; every leaf comes back with its dtype and its exact bits."""
    from repro.configs.base import get_arch
    from repro.models import build_model
    from repro.train.optimizer import OptimizerConfig
    from repro.train.step import init_state

    cfg = get_arch("stablelm_1_6b").smoke_config().scaled(dtype="bfloat16")
    opt = OptimizerConfig(state_dtype="int8_factored")
    state = init_state(build_model(cfg), opt, jax.random.PRNGKey(0))
    assert {l.dtype for l in jax.tree.leaves(state["params"])} == {
        jnp.dtype(jnp.bfloat16)}
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(4, state)
    template = jax.tree.map(jnp.zeros_like, state)
    restored, manifest = mgr.restore(template)
    assert manifest["step"] == 4
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_array_equal(a.view(f"u{a.itemsize}"),
                                      b.view(f"u{b.itemsize}"))
