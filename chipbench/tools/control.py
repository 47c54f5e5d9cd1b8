"""Readings of the control and of the planted faults, at a cell's own size.

    python3 chipbench/tools/control.py --workload <cell> --seeds 1 2 3 [--seconds 10]

Never part of a benchmark run.  It reads the upper ends from which the
limits of ``correct`` are set, and prints one JSON line per seed:

* image cells: the whole run, window and all, with the reference computed
  in bfloat16 (the precision below the float32 that the configuration
  states) put in place of the kernel's output on the checked batches;
* training cells: no window and no program.  The reference is run three
  steps from the seed's weights in float32, and so is its control with
  every matmul operand rounded to float8 e4m3 (the precision below the
  bfloat16 that the configuration states), and a faulty step that takes
  the mean over half of each batch.  Each is compared with the float32
  reference as a run of the program would be.
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


def fp8(x):
    """Round a matmul operand to float8 e4m3 under a per-tensor scale, as
    fp8 matmuls are fed; gradients pass through unrounded."""
    import jax
    import jax.numpy as jnp

    scale = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def raise_kind(kind, want):
    if kind != want:
        raise ValueError(f"substitute for {want!r} called for {kind!r}")


def image_control(cell, seeds, seconds):
    import jax
    import ml_dtypes
    import numpy as np

    from chipbench.ref import crop

    c = cell.config["crop"]

    def substitute(kind, batch, pixels, draws):
        raise_kind(kind, "images")
        oy, ox, mirror = draws
        out = crop.crop_mirror_normalize(pixels, oy, ox, mirror, c["mean"],
                                         c["std"], c["out_h"], c["out_w"],
                                         dtype=ml_dtypes.bfloat16)
        return out.astype(np.float32)

    for seed in seeds:
        run = harness.Run(cell, seed, seconds, False, time.monotonic(),
                          device=jax.devices()[0])
        run.substitute = substitute
        harness.driver_for(cell).run(run)
        print(json.dumps({"seed": seed, "control": "bfloat16 transform",
                          "checks": {k.name: k.value for k in run.checks},
                          "correct": run.correct}), flush=True)


def train_control(cell, seeds):
    import numpy as np

    from chipbench.drivers import train_loop as drv
    from chipbench.ref import dense_lm as ref
    from chipbench.traffic import tokens as traffic

    c = cell.config
    s, adam = drv.shape_of(c), drv.adam_of(c)
    B, S = c["train"]["batch_size"], c["train"]["seq_len"]

    for seed in seeds:
        recs = traffic.generate(seed, c["records"]["n_records"], S, s.vocab,
                                c["records"]["n_classes"])
        batches = [(recs.tokens[k * B:(k + 1) * B],
                    np.ones((B, S), np.float32))
                   for k in range(drv.CHECKED_STEPS)]
        half = [(t[:B // 2], m[:B // 2]) for t, m in batches]
        run = lambda b, cast=ref.identity: drv.reference_run(
            seed, s, adam, b, chunk=min(512, S), cast=cast,
            dtype=c["torch_dtype"])
        seconds = []

        def timed(*args):
            t0 = time.perf_counter()
            out = run(*args)
            seconds.append(time.perf_counter() - t0)
            return out

        want = timed(batches)
        readings = {
            "float8 operands": drv.compare(
                drv.as_reported(timed(batches, fp8)), want),
            "half batch": drv.compare(drv.as_reported(timed(half)), want)}
        print(json.dumps({"seed": seed, "reference_s": seconds,
                          "reference_losses": want["losses"],
                          "readings": readings}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = harness.Cell.find(args.workload)
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("control: no TPU found")
    harness.enable_compile_cache()
    if cell.config["driver"] == "train_loop":
        train_control(cell, args.seeds)
    else:
        image_control(cell, args.seeds, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
