"""Multi-device features (pipeline parallelism, compressed DP all-reduce,
small-mesh dry-run cells) — run in subprocesses with 8 forced host devices
so the main pytest process keeps its single-device view.
"""

import os
import subprocess
import sys
import textwrap

import pytest

ENV = dict(os.environ,
           JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=8",
           PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str, timeout: int = 420) -> str:
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=ENV, capture_output=True, text=True,
                         timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


def test_pipeline_parallel_matches_sequential():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline_parallel import (pipeline_forward,
                                                   stack_stage_params)
        S, M = 4, 8                      # stages, microbatches
        mesh = jax.make_mesh((S,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        L, d = 8, 16
        key = jax.random.PRNGKey(0)
        w = jax.random.normal(key, (L, d, d)) * 0.2

        def stage_fn(params, x):         # params (L/S, d, d)
            def body(h, wl):
                return jnp.tanh(h @ wl), None
            h, _ = jax.lax.scan(body, x, params)
            return h

        x = jax.random.normal(jax.random.PRNGKey(1), (M, 4, d))
        piped = pipeline_forward(stage_fn, S, M, mesh)
        got = piped(stack_stage_params(w, S), x)

        # sequential reference
        def ref_one(xi):
            h = xi
            for l in range(L):
                h = jnp.tanh(h @ w[l])
            return h
        want = jax.vmap(ref_one)(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        print("PP-OK")
    """)
    assert "PP-OK" in out


def test_compressed_psum_error_feedback_converges():
    """Single-step int8 psum is approximate (mean-scale); error feedback
    must make the CUMULATIVE applied update converge to the true mean."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.compression import compressed_psum_grads
        from jax import shard_map
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        grads = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 4, 16))}
        errors = {"w": jnp.zeros((8, 4, 16))}

        f = shard_map(lambda g, e: compressed_psum_grads(g, e, "data"),
                      mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")))
        applied = jnp.zeros((8, 4, 16))
        steps = 12
        for _ in range(steps):
            out, errors = f(grads, errors)
            applied = applied + out["w"]
        mean = grads["w"].mean(axis=0, keepdims=True) * steps
        err = np.abs(np.asarray(applied) - np.asarray(mean)).max()
        scale = np.abs(np.asarray(mean)).max()
        assert err < 0.08 * scale, (err, scale)
        print("EF-OK")
    """)
    assert "EF-OK" in out


@pytest.mark.parametrize("arch,shape", [
    ("qwen3_4b", "train_4k"),          # dense + qk_norm + GQA
    ("grok_1_314b", "prefill_32k"),    # MoE dispatch
    ("hymba_1_5b", "long_500k"),       # hybrid SWA+SSM decode
    ("whisper_tiny", "decode_32k"),    # enc-dec cross-attention cache
])
def test_dryrun_cell_compiles_small_mesh(arch, shape):
    out = _run(f"""
        import jax, dataclasses
        import repro.configs.base as B
        B.SHAPES = {{k: dataclasses.replace(v,
                        seq_len=min(v.seq_len, 256),
                        global_batch=min(v.global_batch, 8))
                    for k, v in B.SHAPES.items()}}
        import repro.launch.dryrun_lib as D
        D.SHAPES = B.SHAPES
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        res = D.run_cell("{arch}", "{shape}", mesh, verbose=False)
        assert res["flops_per_device"] > 0
        assert res["memory"]["temp_bytes"] >= 0
        print("CELL-OK", res["arch"], res["shape"])
    """)
    assert "CELL-OK" in out
