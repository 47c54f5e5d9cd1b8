"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses here what interpret mode accepts (unsupported
primitives, misaligned blocks, too much fast memory) and reports a
program's device memory.  Nothing runs, so these tests say nothing about
results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

GiB = 2 ** 30


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_crop_kernel_compiles_at_imagenet_size(one_chip):
    """The image feed's kernel at its real size: B=128, 256x256x3 uint8 ->
    224x224, compiled as a Pallas custom call (not interpreted)."""
    from repro.kernels import ops

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B = 128
    compiled = ops.crop_mirror_normalize.lower(
        spec((B, 256, 256, 3), jnp.uint8), spec((B,), jnp.int32),
        spec((B,), jnp.int32), spec((B,), jnp.int32),
        spec((3,), jnp.float32), spec((3,), jnp.float32),
        out_h=224, out_w=224).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes == \
        B * 3 * 224 * 224 * 4


def test_stablelm_train_step_fits_one_chip(one_chip):
    """stablelm-1.6b at its published width, batch 2 x seq 2048, bf16
    params with int8 m and factored v: the donated train step's arguments
    plus temporaries fit the 16 GiB of one v5e."""
    from repro.configs.base import get_arch
    from repro.models import build_model
    from repro.train.optimizer import OptimizerConfig
    from repro.train.step import init_state, make_train_step

    model = build_model(get_arch("stablelm_1_6b"))
    opt = OptimizerConfig(state_dtype="int8_factored")
    state = jax.eval_shape(lambda: init_state(model, opt,
                                              jax.random.PRNGKey(0)))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), dt, sharding=one_chip)
             for k, dt in (("tokens", jnp.int32),
                           ("loss_mask", jnp.float32))}
    compiled = jax.jit(make_train_step(model, opt), donate_argnums=(0,)
                       ).lower(state, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0            # the state is donated
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * GiB


def test_dropless_expert_layer_compiles_at_moonlight_width(one_chip):
    """Moonlight's expert layer at its published widths (d 2,048, experts
    of 1,408, 8 of 64 held, 6 a token, 2 shared) over 4,096 tokens, forward
    and backward: the grouped matmuls lower to the chip's ragged-dot kernel
    (a Mosaic custom call), not to a dense expansion."""
    from repro.models.moe import dropless_apply, dropless_spec
    from repro.models.params import abstract_params

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(dropless_spec(2048, 1408, 64, 8, 2), jnp.bfloat16))
    x = jax.ShapeDtypeStruct((1, 4096, 2048), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        y, aux = dropless_apply(p, x, top_k=6, offset=0, scale=2.446)
        return jnp.sum(y.astype(jnp.float32)) + aux["moe_balance_loss"]

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert "tpu_custom_call" in text and "ragged-dot" in text


def test_chunked_attention_compiles_at_moonlight_shape(one_chip):
    """Moonlight's latent attention at 8k (B 4, S 8,192, H 16, qk 192, v
    128, bf16), forward and backward, through the block loops that skip
    what causality masks.  Its temporaries stay within the 3,093,751,808 B
    that this same compile gave for the loops over the whole 8 x 8 grid
    (JAX 0.9.0, libtpu 0.0.34)."""
    from repro.models.attention import chunked_attention

    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        o = chunked_attention(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32))

    B, S, H = 4, 8192, 16
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(B, S, H, 192), spec(B, S, H, 192), spec(B, S, H, 128)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= 3_093_751_808
