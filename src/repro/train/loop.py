"""End-to-end training loop: loader -> device feed -> jitted step ->
checkpoint, with mid-epoch fault-tolerant restart.

This is the driver the examples use (single host, real payloads).  On a
cluster the same loop runs per host with ``LoaderConfig.shard_id`` /
``num_shards`` set from the process index (each host fetches exactly its
shard of the global batch, as the paper partitions per GPU).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax

from repro.core import KVStore, LoaderConfig, VirtualClock, build_stack
from repro.core.stats import span
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptimizerConfig
from repro.train.step import init_state, make_train_step

# Counters a step may report among its metrics, kept per step in StepStats.
STEP_COUNTERS = ("moe.slots_here", "moe.load_max", "attn.blocks_computed")


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    seq_len: int = 128
    log_every: int = 10
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = None
    seed: int = 0
    # Compute seconds charged to the timeline per step instead of the
    # measured wall time of the jitted step.  With a virtual-clock loader
    # this pins the consumer side of the simulation, so stall and goodput
    # numbers are deterministic for tests; None (default) charges the
    # measured step time.
    charge_step_time: Optional[float] = None


def run_training(model, store: KVStore, uuids, loader_cfg: LoaderConfig,
                 loop_cfg: TrainLoopConfig,
                 opt_cfg: Optional[OptimizerConfig] = None,
                 state: Optional[Dict] = None,
                 on_metrics: Optional[Callable] = None) -> Dict:
    """Train `model` from the network loader.

    Returns ``{"state", "history", "stats", "step_stats"}`` — history
    records carry ``loss``/``sps``, the host-clock ``step_s`` of that step
    (its jitted call through ``block_until_ready``) plus per-step data-stall
    accounting (``stall_frac``, ``goodput_sps``), ``stats`` is the
    ``StepStats.summary`` at skip=1 (the jit-compile step excluded) and
    ``step_stats`` the raw ``core.stats.StepStats`` for custom skips.
    """
    opt_cfg = opt_cfg or OptimizerConfig(total_steps=loop_cfg.total_steps)
    step_fn = jax.jit(make_train_step(model, opt_cfg), donate_argnums=(0,))

    ckpt = (CheckpointManager(loop_cfg.checkpoint_dir)
            if loop_cfg.checkpoint_dir else None)
    start_step = 0
    loader_pos = {"epoch": 0, "cursor": 0}
    if state is None:
        if ckpt and ckpt.latest_step() is not None:
            # shapes only: a materialized template would hold a second copy
            # of the state on the device for the whole run
            template = jax.eval_shape(lambda: init_state(
                model, opt_cfg, jax.random.PRNGKey(loop_cfg.seed)))
            state, manifest = ckpt.restore(template)
            start_step = manifest["step"]
            loader_pos = manifest["extra"].get("loader", loader_pos)
        else:
            state = init_state(model, opt_cfg, jax.random.PRNGKey(loop_cfg.seed))

    stack = build_stack(store=store, uuids=uuids, config=loader_cfg,
                        feed="device", seq_len=loop_cfg.seq_len)
    loader, feed = stack.loader, stack.feed
    loader.start(epoch=loader_pos["epoch"], cursor=loader_pos["cursor"])
    # adaptive runs resume at the checkpointed operating point instead of
    # re-slow-starting from scratch (no-op in static mode / old checkpoints)
    loader.restore_flow(loader_pos.get("flow"))
    ss = feed.step_stats
    clk = loader.clock
    virtual = isinstance(clk, VirtualClock)
    B = loader_cfg.batch_size

    def ckpt_extra() -> Dict:
        # the *feed's* position (loader cursor rewound by device-queued
        # batches) — checkpointing loader.state() directly would skip the
        # in-flight batches on restore
        pos = feed.state()
        flow = loader.flow_snapshot()
        if flow is not None:
            pos["flow"] = flow
        return {"loader": pos}

    history = []
    t0 = None                 # set after the first step: sps excludes the
    #                           jit compile baked into step one
    for step in range(start_step, loop_cfg.total_steps):
        n = step + 1                 # the step as history numbers it
        with span("train.next", step=n):
            dev_batch, _meta = next(feed)
        batch = {"tokens": dev_batch["tokens"],
                 "loss_mask": dev_batch["loss_mask"]}
        with span("train.step", step=n):
            c0 = time.perf_counter()
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            compute = step_s = time.perf_counter() - c0
        reported = {k: metrics[k] for k in STEP_COUNTERS if k in metrics}
        for name, value in jax.device_get(reported).items():
            ss.on_counter(name, value)
        if loop_cfg.charge_step_time is not None:
            compute = loop_cfg.charge_step_time
        if virtual:
            # charge compute to the sim timeline: in-flight transfers
            # progress during the step, and wait/compute share one clock
            clk.sleep(compute)
        ss.on_compute(compute, t_end=clk.now())
        if t0 is None:
            t0 = time.time()
        if n % loop_cfg.log_every == 0 or step == start_step:
            with span("train.log", step=n):
                loss = float(metrics["loss"])
                rec = {"step": n, "loss": loss, "step_s": step_s,
                       "sps": (step - start_step) * B
                       / max(time.time() - t0, 1e-9),
                       "stall_frac": ss.stall_frac(skip=1),
                       "goodput_sps": ss.goodput_sps(B, skip=1)}
                history.append(rec)
                if on_metrics:
                    on_metrics(rec)
        if ckpt and n % loop_cfg.checkpoint_every == 0:
            with span("train.ckpt", step=n):
                ckpt.save(n, state, extra=ckpt_extra(), blocking=False)
    if ckpt:
        ckpt.save(loop_cfg.total_steps, state, extra=ckpt_extra(),
                  blocking=True)
    stack.close()
    return {"state": state, "history": history,
            "stats": ss.summary(B, skip=1), "step_stats": ss}


__all__ = ["TrainLoopConfig", "run_training"]
