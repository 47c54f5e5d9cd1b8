"""Run one benchmark cell once and print its result as one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the same window.  Every
run checks what the timed path produced against the benchmark's plain
reference and prints each number compared beside its limit, on standard
error and under ``checks`` in the result line.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints no
result.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]

from chipbench import harness  # noqa: E402

STARTED = harness.process_start()


def parse(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    import json

    args = parse(argv)
    cell = harness.Cell.find(args.workload)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chipbench: no TPU found (JAX platform is {dev.platform!r})")
    if len(devices) < cell.chips:
        sys.exit(f"chipbench: {cell.name} needs {cell.chips} chips, JAX "
                 f"sees {len(devices)}")
    peaks = harness.peaks_for(dev.device_kind)
    harness.enable_compile_cache()

    run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                      STARTED, device=dev)
    try:
        harness.driver_for(cell).run(run)
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": run.memory_peak_bytes}
        breakdown = None
        if args.trace:
            reduced = run.reduce_trace()
            if not reduced.ops:
                raise RuntimeError("no op ran on the chip in the traced "
                                   "window")
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            metrics = harness.read_per_layer(run, reduced, peaks)
            breakdown = reduced.breakdown()
        else:
            metrics = {m["name"]: {"value": float(run.e2e[m["name"]]),
                                   "unit": m["unit"]}
                       for m in cell.end_to_end if m["name"] != "setup_s"}
            metrics["setup_s"] = {"value": run.setup_s, "unit": "s"}
    finally:
        run.cleanup()
    harness.print_checks(run)
    print(json.dumps(harness.result_line(run, device, metrics, breakdown)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
