"""Small cells for the benchmark's CPU tests: the real configurations and
traffic with their scale cut to what a test run holds, on the 50 us route,
with the Pallas kernel in the interpreter."""

from __future__ import annotations

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness  # noqa: E402

SEED = 2 ** 40 + 12345        # larger than 32 bits, as the driver's are


def small_image_cell() -> harness.Cell:
    cell = copy.deepcopy(harness.Cell.find("img-local"))
    cell.config["rows"].update(n_keys=256, n_frames=8, h=32, w=32)
    cell.config["loader"].update(batch_size=8, io_threads=2,
                                 prefetch_buffers=4)
    cell.config["crop"].update(out_h=24, out_w=24)
    cell.workload.update(warmup_batches=4, sample_batches=3)
    return cell


def small_train_cell(dtype: str = "float32") -> harness.Cell:
    cell = copy.deepcopy(harness.Cell.find("lm-high"))
    cell.config.update(num_hidden_layers=2, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=4,
                       head_dim=16, intermediate_size=128, vocab_size=512,
                       torch_dtype=dtype)
    cell.config["train"].update(seq_len=64, remat=False)
    cell.config["records"]["n_records"] = 48
    cell.workload.update(route="local")
    return cell


def small_run(cell: harness.Cell, seed: int = SEED,
              seconds: float = 1.0) -> harness.Run:
    return harness.Run(cell, seed, seconds, trace=False,
                       started=time.monotonic(), interpret=True)


def drive(cell: harness.Cell, **kw) -> harness.Run:
    run = small_run(cell, **kw)
    harness.driver_for(cell).run(run)
    return run


def checks(run: harness.Run) -> dict:
    return {c.name: c.value for c in run.checks}
