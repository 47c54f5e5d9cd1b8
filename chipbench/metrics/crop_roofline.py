"""The crop layer's share of its roofline, in %: the least time the chip
could take for the bytes the transform needs (``counts.crop_bytes``: each
crop window read once, each float32 output written once) and its two
FLOPs an element, over the summed device time of the program that runs
it.  That program holds the Pallas kernel and the relayout copies XLA puts
around it, so work moved between the kernel and those copies stays in the
time."""

from chipbench import counts, trace

# the jitted wrapper of the kernel, one run per batch
PROGRAM = r"^jit_crop_mirror_normalize\("


def read(run, reduced, peaks):
    if reduced is None or "crop_shape" not in run.counters:
        return None
    events = reduced.module_events(PROGRAM)
    if not events:
        return None
    calls = len(events)
    shape = run.counters["crop_shape"]
    return counts.roofline_share(
        calls * counts.crop_flops(*shape), calls * counts.crop_bytes(*shape),
        trace.seconds(events), peaks["bf16_flops_per_s"],
        peaks["hbm_bytes_per_s"])
