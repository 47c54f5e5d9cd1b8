"""The expert layers' grouped matmuls' share of their roofline, in %: the
least time the chip could take for the FLOPs and bytes they need
(``counts_moe.expert_gmm_flops`` / ``expert_gmm_bytes`` at the window's
mean ``moe.slots_here`` a step) for each run of the step program, over the
summed device time of the grouped-matmul ops (``jax.lax.ragged_dot``'s
kernels and their group metadata) in the trace.  The forward's
recomputation under remat runs the same ops again and is not counted as
work."""

from chipbench import counts, counts_moe, trace

PROGRAM = r"train_step"
OPS = r"ragged-dot"


def read(run, reduced, peaks):
    if reduced is None or "slots_here" not in run.counters:
        return None
    ops = reduced.op_events(OPS)
    steps = len(reduced.module_events(PROGRAM))
    if not ops or not steps:
        return None
    layers, n_here, d, f = run.counters["expert_shape"]
    slots = run.counters["slots_here"]
    return counts.roofline_share(
        steps * counts_moe.expert_gmm_flops(slots, d, f),
        steps * counts_moe.expert_gmm_bytes(slots, layers, n_here, d, f),
        trace.seconds(ops), peaks["bf16_flops_per_s"],
        peaks["hbm_bytes_per_s"])
