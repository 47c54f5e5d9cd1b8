"""Training cells: the program's ``run_training`` over a token store, with
the route elapsing in wall time.

Set-up builds the program's model and hands it a state made of the
benchmark's weights (``ref.dense_lm.init_weights``, from the seed) and the
program's own optimizer state for them.  That state goes through the
checked calls of ``run_training``, made as the window's call is made, at
its ``log_every``: one step, then two steps in one call, so that the
passing of state from step to step inside a call is among what is checked.
The store records the rows the loader asks for; the loader ramps up from
one batch in flight, so each step's rows are the rows asked for together.
The window's call runs the same state on, over the rest of the store.

The window's call logs every ``log_every`` steps as the loop does by
default, and the harness stamps the wall clock in ``on_metrics``: the
window runs from the stamp of its first step, whose call compiled, to the
last, and counts the steps between.  The harness blocks on nothing of its
own.

Correct means, against the float32 reference of the same equations run
over the same rows from the same weights, for the three checked steps:
the loss of each step the loop reports, the first of each call
(``loss_rel_gap``), each leaf's clipped first gradient as the optimizer
received it, read from the int8 first moment the first step left
(``grad_norm_gap``), and each leaf's change over the three steps
(``update_norm_gap``), which the third step's update enters unlogged.  A
norm's gap is taken against the larger of that leaf's reference norm and
the median leaf's.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List

import numpy as np

from chipbench import counts, timing
from chipbench.harness import Run
from chipbench.ref import dense_lm as ref
from chipbench.traffic import tokens as traffic

CHECKED_CALLS = (1, 2)   # steps of each checked call of run_training
CHECKED_STEPS = sum(CHECKED_CALLS)
SMALL_LEAF = 1e-3       # of the median leaf's gradient norm


def shape_of(c: Dict) -> ref.Shape:
    return ref.Shape(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                     n_heads=c["num_attention_heads"],
                     n_kv_heads=c["num_key_value_heads"],
                     head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                     vocab=c["vocab_size"], rope_theta=c["rope_theta"],
                     norm_eps=c["layer_norm_eps"])


def adam_of(c: Dict) -> ref.Adam:
    o = c["train"]["optimizer"]
    return ref.Adam(**{f.name: o[f.name] for f in dataclasses.fields(ref.Adam)})


def program_model(c: Dict):
    """The program's model at the configuration's sizes."""
    from repro.configs.base import get_arch
    from repro.models import build_model

    arch = dataclasses.replace(
        get_arch(c["program_arch"]), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        rope_theta=c["rope_theta"], norm_eps=c["layer_norm_eps"],
        dtype=c["torch_dtype"], remat=c["train"]["remat"])
    return build_model(arch)


def program_state(model, weights, opt_cfg) -> Dict:
    import jax

    from repro.train.optimizer import adamw_init

    want = model.abstract_params()
    if jax.tree.structure(want) != jax.tree.structure(weights) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(weights))):
        raise ValueError("the program's parameters are not laid out as the "
                         "reference's weights")
    return {"params": weights, "opt": jax.jit(adamw_init, static_argnums=1)(
        weights, opt_cfg)}


def _path(path) -> str:
    return "/".join(p.key for p in path)


def diff_norms(a, b) -> Dict[str, float]:
    """Per-leaf norm of ``a - b`` in float32, fused leaf by leaf on the
    device."""
    import jax
    import jax.numpy as jnp

    def norm(x, y):
        d = x.astype(jnp.float32) - y.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(d)))

    out = jax.jit(lambda a_, b_: jax.tree.map(norm, a_, b_))(a, b)
    flat, _ = jax.tree_util.tree_flatten_with_path(out)
    return {_path(path): float(v) for path, v in flat}


def first_moment_norms(opt_state, b1: float) -> Dict[str, float]:
    """Per-leaf norm of the clipped gradient of step one, from the first
    moment it left: ``m = (1 - b1) * g``, int8 with a scale per row."""
    import jax
    import jax.numpy as jnp

    is_q = lambda x: isinstance(x, dict) and set(x) == {"q", "scale"}
    deq = jax.jit(lambda m: jax.tree.map(
        lambda q: jnp.sqrt(jnp.sum(jnp.square(
            q["q"].astype(jnp.float32) * q["scale"]))), m, is_leaf=is_q))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        deq(opt_state["m"]), is_leaf=is_q)
    return {_path(path): float(v) / (1.0 - b1) for path, v in flat}


def change_norms(params, seed: int, s: ref.Shape, dtype) -> Dict[str, float]:
    return diff_norms(params, ref.init_weights(seed, s, dtype))


def gap(got: Dict[str, float], want: Dict[str, float], keep=None) -> float:
    """Largest gap between two sets of leaf norms, each against the larger
    of its leaf's reference norm and the median leaf's."""
    keys = [k for k in want if keep is None or k in keep]
    median = float(np.median([want[k] for k in want]))
    worst = 0.0
    for k in keys:
        denom = max(want[k], median)
        if denom == 0.0:
            continue
        worst = max(worst, abs(got.get(k, math.nan) - want[k]) / denom)
    return worst if math.isfinite(worst) else math.inf


def compare(prog: Dict, refd: Dict) -> Dict[str, float]:
    """The numbers compared: loss, first-gradient and change gaps."""
    want = as_reported(refd)["losses"]
    losses = [abs(a - b) / abs(b) for a, b in zip(prog["losses"], want)]
    if len(losses) != len(want) or not all(map(math.isfinite, losses)):
        losses = [math.inf]
    g = refd["grad_norms"]
    median = float(np.median(list(g.values())))
    moved = {k for k, v in g.items() if v >= SMALL_LEAF * median}
    return {"loss_rel_gap": max(losses),
            "grad_norm_gap": gap(prog["grad_norms"], g),
            "update_norm_gap": gap(prog["change_norms"], refd["change_norms"],
                                   keep=moved)}


def logged_steps() -> List[int]:
    """The checked steps whose loss the loop reports: the first of each
    call, since a call shorter than ``log_every`` logs no other."""
    return [sum(CHECKED_CALLS[:i]) for i in range(len(CHECKED_CALLS))]


def as_reported(refd: Dict) -> Dict:
    """A run of the reference as a run of the program reports it: the
    losses of the logged steps only."""
    return dict(refd, losses=[refd["losses"][i] for i in logged_steps()])


def steps_rows(reads: List, keys: List, steps: int, B: int):
    """Each step's keys in a checked call, from the keys the loader asked
    the store for, in order: one batch at a time while the loader's ramp
    holds one batch in flight.  None where the first ``steps`` batches are
    not the call's keys, each once."""
    first = reads[:steps * B]
    if sorted(first) != sorted(keys):
        return None
    return [first[i * B:(i + 1) * B] for i in range(steps)]


def window_report(ss, first: int, steps: int) -> str:
    """Where the window's steps went on the host clock, per step."""
    ends = ss.step_end_t[first - 1:first + steps]
    per = np.diff(ends) if len(ends) > 1 else np.zeros(1)
    comp = np.asarray(ss.compute_s[first:first + steps])
    wait = np.asarray(ss.wait_s[first:first + steps])
    return (f"window: {steps} steps, {1e3 * per.mean():.2f} ms a step "
            f"(max {1e3 * per.max():.2f}), step call {1e3 * comp.mean():.2f} "
            f"ms, feed wait {1e3 * wait.mean():.3f} ms, rest "
            f"{1e3 * (per.mean() - comp.mean() - wait.mean()):.2f} ms")


def reference_run(seed: int, s: ref.Shape, adam: ref.Adam, batches: List,
                  chunk: int, cast=ref.identity, dtype="bfloat16") -> Dict:
    """The reference's three steps from the same weights over the same
    rows: losses, first clipped gradient norms, change norms."""
    state = ref.TrainState(ref.Reference(s, adam, chunk=chunk, cast=cast),
                           ref.init_weights(seed, s, dtype))
    losses, grad_norms = [], None
    for toks, mask in batches:
        out = state.step(toks, mask)
        losses.append(out["loss"])
        grad_norms = grad_norms or out["grad_norms"]
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms(state.params, seed, s, dtype)}


def recording_store():
    """The program's store, keeping the keys of the rows read from it in
    the order the loader asks for them."""
    from repro.core import KVStore

    class RecordingStore(KVStore):
        def __init__(self) -> None:
            super().__init__()
            self.reads: List = []

        def get_data(self, key):
            self.reads.append(key)
            return super().get_data(key)

    return RecordingStore()


def run(run: Run) -> None:
    from repro.core import LoaderConfig
    from repro.core.kvstore import DataRow, MetaRow
    from repro.train.loop import TrainLoopConfig, run_training
    from repro.train.optimizer import OptimizerConfig

    c, wl = run.config, run.workload
    t, s = c["train"], shape_of(c)
    B, S = t["batch_size"], t["seq_len"]
    recs = traffic.generate(run.seed, c["records"]["n_records"], S, s.vocab,
                            c["records"]["n_classes"])
    store = recording_store()
    for key, toks, lab in zip(recs.keys, recs.tokens, recs.labels):
        blob = traffic.encode(toks, int(lab))
        store.insert_atomic(DataRow(key, int(lab), len(blob), payload=blob),
                            MetaRow(key, "", int(lab), {}))
    index_of = {k: i for i, k in enumerate(recs.keys)}
    ld = c["loader"]
    loader_cfg = LoaderConfig(
        batch_size=B, prefetch_buffers=ld["prefetch_buffers"],
        io_threads=ld["io_threads"], out_of_order=ld["out_of_order"],
        route=wl["route"], seed=wl["route_seed"], materialize=True,
        virtual_clock=False)
    opt_cfg = OptimizerConfig(**c["train"]["optimizer"])
    model = program_model(c)
    state = program_state(model, ref.init_weights(run.seed, s, c["torch_dtype"]),
                          opt_cfg)
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
    for steps in CHECKED_CALLS:
        keys = recs.keys[n0:n0 + steps * B]
        del store.reads[:]
        out = call(keys, steps)
        state = out["state"]
        prog["losses"].append(out["history"][0]["loss"])
        if n0 == 0:
            prog["grad_norms"] = first_moment_norms(state["opt"],
                                                    opt_cfg.b1)
        got = steps_rows(store.reads, keys, steps, B)
        if got is None:
            print("checked call: the loader's first batches are not the "
                  "call's rows, each once", file=sys.stderr, flush=True)
            known = False
            got = [keys[i * B:(i + 1) * B] for i in range(steps)]
        rows.extend(got)
        n0 += steps * B
    if not known:
        prog["losses"] = [math.inf]
    prog["change_norms"] = change_norms(state["params"], run.seed, s,
                                        c["torch_dtype"])
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
    flops = counts.dense_lm_train_flops_per_token(
        s.n_layers, s.d_model, s.n_heads, s.n_kv_heads, s.head_dim, s.d_ff,
        s.vocab, S) * B * S
    first = stamps[0][1]
    run.counters.update(
        flops_per_step=flops,
        wait_s=list(out["step_stats"].wait_s[first:first + steps]))
    print(window_report(out["step_stats"], first, steps), file=sys.stderr,
          flush=True)
    run.read_memory_peak()
    del state, out
    gc.collect()

    # -- the comparison with the plain reference, after the window ----------
    batches = [(recs.tokens[[index_of[k] for k in keys]],
                np.ones((B, S), np.float32)) for keys in rows]
    if run.substitute is not None:
        prog = run.substitute("train", prog, batches)
    t0 = time.perf_counter()
    refd = reference_run(run.seed, s, adam_of(c), batches,
                         chunk=min(512, S), dtype=c["torch_dtype"])
    print(f"reference: {time.perf_counter() - t0:.1f} s for "
          f"{CHECKED_STEPS} steps", file=sys.stderr, flush=True)
    for name, value in compare(prog, refd).items():
        run.check(name, value, c["limits"][name])
