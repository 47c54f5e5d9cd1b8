"""GPipe-style pipeline parallelism over a "stage" mesh axis via shard_map.

The layer stack is split into S contiguous stages; microbatches stream
through stages with ``jax.lax.ppermute`` moving activations to the next
stage.  Schedule: plain GPipe (fill S-1 bubbles, then steady state) —
bubble fraction (S-1)/(M+S-1) with M microbatches.

This is an optional parallelism mode (the production mesh in this repo uses
DPxTP(+SP); PP composes on top when depth x width exceeds a pod), exercised
by tests/test_multidevice.py on an 8-device host mesh.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, n_stages: int, n_microbatches: int,
                     mesh: Mesh, axis: str = "stage"):
    """Build a pipelined forward: x (M, mb, ...) -> y (M, mb, ...).

    ``stage_fn(stage_params, x)`` applies one stage's layers.
    ``stage_params`` leaves carry a leading stage axis (sharded over
    ``axis``); x microbatches are processed GPipe-style.
    """

    def pipelined(stage_params, x_mb):
        M = n_microbatches
        S = n_stages

        def per_stage(params_local, x_local):
            # params_local: this stage's params (leading axis 1); x_local:
            # full microbatch stream (replicated batch entry point).
            params_local = jax.tree.map(lambda p: p[0], params_local)
            stage_id = jax.lax.axis_index(axis)
            T = M + S - 1               # total schedule ticks

            def tick(carry, t):
                buf, outputs = carry    # buf: activation entering this stage
                # stage s works on microbatch (t - s) when 0 <= t-s < M
                mb_idx = t - stage_id
                active = (mb_idx >= 0) & (mb_idx < M)
                x_in = jnp.where(
                    stage_id == 0,
                    x_local[jnp.clip(mb_idx, 0, M - 1)],
                    buf)
                y = stage_fn(params_local, x_in)
                y = jnp.where(active, y, buf)
                # pass activation to the next stage
                nxt = jax.lax.ppermute(
                    y, axis, [(i, i + 1) for i in range(S - 1)])
                # last stage writes its finished microbatch
                out_idx = jnp.clip(mb_idx, 0, M - 1)
                write = active & (stage_id == S - 1)
                outputs = jnp.where(
                    write,
                    outputs.at[out_idx].set(y),
                    outputs)
                return (nxt, outputs), None

            buf0 = jnp.zeros_like(x_local[0])
            out0 = jnp.zeros_like(x_local)
            # the carry becomes device-varying after ppermute: mark it so
            buf0 = jax.lax.pcast(buf0, (axis,), to="varying")
            out0 = jax.lax.pcast(out0, (axis,), to="varying")
            (_, outputs), _ = jax.lax.scan(tick, (buf0, out0),
                                           jnp.arange(T))
            # only stage S-1 holds real outputs; broadcast via psum of masked
            outputs = jax.lax.psum(
                jnp.where(stage_id == S - 1, outputs, 0.0), axis)
            return outputs

        return jax.shard_map(
            per_stage, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P(),
        )(stage_params, x_mb)

    return pipelined


def stack_stage_params(layer_params: Any, n_stages: int) -> Any:
    """(L, ...) layer-stacked params -> (S, L/S, ...) stage-stacked."""
    def resh(p):
        L = p.shape[0]
        return p.reshape((n_stages, L // n_stages) + p.shape[1:])

    return jax.tree.map(resh, layer_params)


__all__ = ["pipeline_forward", "stack_stage_params"]
