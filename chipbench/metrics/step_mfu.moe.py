"""The train step's model FLOP utilization in the MLA + routed-expert cells,
in %: the FLOPs the forward and backward passes require
(``counts_moe.train_flops_per_token``: the held experts at their expected
share of the slots, no recomputation counted) for each run of the step
program in the trace, over that program's summed device time times the
chip's bf16 peak."""

from chipbench import trace

PROGRAM = r"train_step"


def read(run, reduced, peaks):
    if reduced is None or "flops_per_step" not in run.counters:
        return None
    events = reduced.module_events(PROGRAM)
    if not events:
        return None
    flops = len(events) * run.counters["flops_per_step"]
    return 100.0 * flops / (trace.seconds(events) * peaks["bf16_flops_per_s"])
