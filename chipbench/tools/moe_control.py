"""Readings of the two controls of the MLA + routed-expert training cells,
at a cell's own size.

    python3 chipbench/tools/moe_control.py --workload <cell> --seeds 1 2 3

Never part of a benchmark run.  It reads the lower ends from which the
limits of ``correct`` are set: no window and no program.  For each seed
the reference is run over the seed's first rows from the seed's weights,
and so are its two controls, each compared with it as a run of the program
would be:

* ``capacity 1.25``: the capacity-bounded layer of ``models/moe.py`` at
  ``capacity_factor`` 1.25 in place of the dropless one;
* ``float8 operands``: every matmul operand rounded to float8 e4m3, the
  precision below the bfloat16 the configuration states.

It prints one JSON line per seed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

CAPACITY = 1.25


def readings(cell, seed: int) -> dict:
    import numpy as np

    from chipbench.drivers import moe_train_loop as drv
    from chipbench.drivers import train_loop as base
    from chipbench.tools.control import fp8

    c = cell.config
    adam = base.adam_of(c)
    B, S = c["train"]["batch_size"], c["train"]["seq_len"]
    recs = drv.records(c, cell.workload, seed)
    batches = [(recs.tokens[k * B:(k + 1) * B], np.ones((B, S), np.float32))
               for k in range(base.CHECKED_STEPS)]
    seconds = []

    def timed(s, cast=drv.identity):
        t0 = time.perf_counter()
        out = drv.reference_run(seed, s, adam, batches, chunk=min(512, S),
                                cast=cast, dtype=c["torch_dtype"])
        seconds.append(time.perf_counter() - t0)
        return out

    want = timed(drv.shape_of(c))
    got = {"capacity 1.25": timed(drv.shape_of(c, capacity_factor=CAPACITY)),
           "float8 operands": timed(drv.shape_of(c), fp8)}
    return {"seed": seed, "reference_s": seconds,
            "reference_losses": want["losses"],
            "readings": {k: base.compare(base.as_reported(v), want)
                         for k, v in got.items()}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.Cell.find(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("moe_control: no TPU found")
    harness.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(cell, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
