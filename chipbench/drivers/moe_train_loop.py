"""Training cells of the MLA + routed-expert family: the program's
``run_training`` over a token store, with the route elapsing in wall time,
checked as ``train_loop`` checks the dense family.

It differs from ``train_loop`` in what it builds and what it compares
with: the program's model is the configuration's DeepSeek-style decoder at
its chip's share (the experts held here, the vocabulary slice), its
weights come from ``ref.mla_moe_lm.init_weights``, the rows are Zipf
tokens over the slice (``traffic.zipf_tokens``), and the plain reference
is ``ref.mla_moe_lm``.  The checked calls (one step, then two), the
window's stamps and the three checks ``loss_rel_gap``, ``grad_norm_gap``
and ``update_norm_gap`` are ``train_loop``'s own.

The window's steps report ``moe.slots_here`` (slots routed to the held
experts, summed over the layers) and ``moe.load_max`` (the largest held
expert's slots in any layer); the ``window:`` line prints their means
beside the expectation ``B S k E_here / E`` of the first.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import counts_moe, timing
from chipbench.drivers import train_loop as base
from chipbench.harness import Run
from chipbench.ref.dense_lm import identity
from chipbench.ref import mla_moe_lm as ref
from chipbench.traffic import tokens as token_records
from chipbench.traffic import zipf_tokens


def shape_of(c: Dict, capacity_factor: float = 0.0) -> ref.Shape:
    return ref.Shape(
        n_layers=c["num_hidden_layers"], n_dense=c["first_k_dense_replace"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
        rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], d_expert=c["moe_intermediate_size"],
        n_experts=c["n_routed_experts"], n_here=c["n_routed_experts_here"],
        offset=c["experts_offset"], top_k=c["num_experts_per_tok"],
        n_shared=c["n_shared_experts"],
        routed_scale=c["routed_scaling_factor"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        balance_coef=c["seq_aux_alpha"],
        capacity_factor=capacity_factor)


def program_model(c: Dict):
    """The program's model at the configuration's sizes and share."""
    from repro.configs.base import get_arch
    from repro.models import build_model

    s = shape_of(c)
    arch = dataclasses.replace(
        get_arch(c["program_arch"]), n_layers=s.n_layers,
        first_dense_layers=s.n_dense, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_heads, kv_lora_rank=s.kv_rank, qk_nope_dim=s.nope,
        qk_rope_dim=s.rope_dim, v_head_dim=s.v_dim, d_ff=s.d_ff,
        d_ff_expert=s.d_expert, n_experts=s.n_experts, experts_here=s.n_here,
        experts_offset=s.offset, top_k=s.top_k, n_shared_experts=s.n_shared,
        routed_scale=s.routed_scale, vocab=s.vocab, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, balance_coef=s.balance_coef,
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        remat=c["train"]["remat"])
    return build_model(arch)


def records(c: Dict, wl: Dict, seed: int):
    S = c["train"]["seq_len"]
    return zipf_tokens.generate(seed, c["records"]["n_records"], S,
                                c["vocab_size"], c["records"]["n_classes"],
                                s=wl["zipf_s"])


def reference_run(seed: int, s: ref.Shape, adam, batches: List, chunk: int,
                  cast=identity, dtype="bfloat16") -> Dict:
    """The reference's checked steps from the same weights over the same
    rows: losses, first clipped gradient norms, change norms."""
    state = ref.TrainState(ref.Reference(s, adam, chunk=chunk, cast=cast),
                           ref.init_weights(seed, s, dtype))
    losses, grad_norms = [], None
    for toks, mask in batches:
        out = state.step(toks, mask)
        losses.append(out["loss"])
        grad_norms = grad_norms or out["grad_norms"]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": base.diff_norms(state.params,
                                            ref.init_weights(seed, s, dtype))}


def flops_per_token(c: Dict) -> float:
    s = shape_of(c)
    return counts_moe.train_flops_per_token(
        s.n_layers, s.n_dense, s.d_model, s.n_heads, s.kv_rank, s.nope,
        s.rope_dim, s.v_dim, s.d_ff, s.d_expert, s.n_experts, s.n_here,
        s.top_k, s.n_shared, s.vocab, c["train"]["seq_len"])


def counters_report(ss, first: int, steps: int, expected: float) -> str:
    slots = np.asarray(ss.counters["moe.slots_here"][first:first + steps])
    load = np.asarray(ss.counters["moe.load_max"][first:first + steps])
    return (f"moe: slots here {slots.mean():.1f} a step (expected "
            f"{expected:.1f}), load max {load.mean():.1f}")


def run(run: Run) -> None:
    from repro.core import LoaderConfig
    from repro.core.kvstore import DataRow, MetaRow
    from repro.train.loop import TrainLoopConfig, run_training
    from repro.train.optimizer import OptimizerConfig

    c, wl = run.config, run.workload
    t, s = c["train"], shape_of(c)
    B, S = t["batch_size"], t["seq_len"]
    model = program_model(c)       # first: a program without it stops here
    recs = records(c, wl, run.seed)
    store = base.recording_store()
    for key, toks, lab in zip(recs.keys, recs.tokens, recs.labels):
        blob = token_records.encode(toks, int(lab))
        store.insert_atomic(DataRow(key, int(lab), len(blob), payload=blob),
                            MetaRow(key, "", int(lab), {}))
    index_of = {k: i for i, k in enumerate(recs.keys)}
    ld = c["loader"]
    loader_cfg = LoaderConfig(
        batch_size=B, prefetch_buffers=ld["prefetch_buffers"],
        io_threads=ld["io_threads"], out_of_order=ld["out_of_order"],
        route=wl["route"], seed=wl["route_seed"], materialize=True,
        virtual_clock=False)
    opt_cfg = OptimizerConfig(**t["optimizer"])
    state = base.program_state(
        model, ref.init_weights(run.seed, s, c["torch_dtype"]), opt_cfg)
    every = wl["log_every"]

    def call(keys, steps, on_metrics=None):
        with run.span("bench.run_training"):
            return run_training(
                model, store, keys, loader_cfg,
                TrainLoopConfig(total_steps=steps, seq_len=S,
                                log_every=every),
                opt_cfg, state=state, on_metrics=on_metrics)

    # the checked calls, each over rows of its own, at the window's log_every
    prog = {"losses": []}
    rows, n0, known = [], 0, True
    for steps in base.CHECKED_CALLS:
        keys = recs.keys[n0:n0 + steps * B]
        del store.reads[:]
        out = call(keys, steps)
        state = out["state"]
        prog["losses"].append(out["history"][0]["loss"])
        if n0 == 0:
            prog["grad_norms"] = base.first_moment_norms(state["opt"],
                                                         opt_cfg.b1)
        got = base.steps_rows(store.reads, keys, steps, B)
        if got is None:
            print("checked call: the loader's first batches are not the "
                  "call's rows, each once", file=sys.stderr, flush=True)
            known = False
            got = [keys[i * B:(i + 1) * B] for i in range(steps)]
        rows.extend(got)
        n0 += steps * B
    if not known:
        prog["losses"] = [math.inf]
    prog["change_norms"] = base.diff_norms(
        state["params"], ref.init_weights(run.seed, s, c["torch_dtype"]))
    # the last checked call's last step did not compile: it sizes the window
    step_s = out["step_stats"].compute_s[-1]
    total = every * max(1, round((run.seconds / step_s + 1) / every))

    stamps = []

    def on_metrics(rec):
        stamps.append((time.perf_counter(), rec["step"]))
        if len(stamps) == 1:
            run.setup_done()
            run.open_window()
        if rec["step"] == total:
            run.end_window()

    try:
        out = call(recs.keys[n0:], total, on_metrics)
    finally:
        if run.setup_s is not None:
            run.close_window()
    steps, secs = timing.stamped_window(stamps)
    run.attempted = steps
    run.e2e["tokens_per_s"] = steps * B * S / secs
    ss, first = out["step_stats"], stamps[0][1]
    slots = ss.counters["moe.slots_here"][first:first + steps]
    run.counters.update(
        flops_per_step=flops_per_token(c) * B * S,
        slots_here=float(np.mean(slots)),
        expert_shape=(s.n_layers - s.n_dense, s.n_here, s.d_model,
                      s.d_expert),
        wait_s=list(ss.wait_s[first:first + steps]))
    print(base.window_report(ss, first, steps), file=sys.stderr, flush=True)
    print(counters_report(ss, first, steps,
                          B * S * s.top_k * s.n_here / s.n_experts
                          * (s.n_layers - s.n_dense)),
          file=sys.stderr, flush=True)
    run.read_memory_peak()
    del state, out
    gc.collect()

    # -- the comparison with the plain reference, after the window ----------
    batches = [(recs.tokens[[index_of[k] for k in keys]],
                np.ones((B, S), np.float32)) for keys in rows]
    if run.substitute is not None:
        prog = run.substitute("train", prog, batches)
    t0 = time.perf_counter()
    refd = reference_run(run.seed, s, base.adam_of(c), batches,
                         chunk=min(512, S), dtype=c["torch_dtype"])
    print(f"reference: {time.perf_counter() - t0:.1f} s for "
          f"{base.CHECKED_STEPS} steps", file=sys.stderr, flush=True)
    for name, value in base.compare(prog, refd).items():
        run.check(name, value, c["limits"][name])
