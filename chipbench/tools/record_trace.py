"""Record a small profiler trace on the chip, for the trace reduction's tests.

    python3 chipbench/tools/record_trace.py OUT_DIR

Traces as the harness does (host tracer off, the window between the two
marker programs) three calls of the crop kernel on 16 images, each in a
``bench.next_batch`` span followed by a 2 ms sleep, and three of a jitted
2048 x 2048 matmul, each in a ``bench.check`` span followed by 4 ms.
Writes ``OUT_DIR/small.xplane.pb`` and ``OUT_DIR/small.spans.json`` (the
spans on the host clock and the time the window opened) and prints every
plane and line of the trace with a sample of events.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(os.path.dirname(HERE)),
                os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]


def main() -> int:
    out_dir = sys.argv[1]
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, ProfileOptions

    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        sys.exit("record_trace: no TPU found")
    B = 16
    img = jnp.zeros((B, 256, 256, 3), jnp.uint8)
    idx = jnp.zeros((B,), jnp.int32)
    mean = jnp.zeros((3,), jnp.float32)
    crop = lambda: ops.crop_mirror_normalize(img, idx, idx, idx, mean, mean,
                                             out_h=224, out_w=224)
    matmul = jax.jit(lambda a: (a @ a).sum())
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    crop().block_until_ready()
    matmul(a).block_until_ready()

    from chipbench import harness

    tmp = tempfile.mkdtemp()
    opts = ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    spans = []
    jax.profiler.start_trace(tmp, profiler_options=opts)
    harness.run_marker(harness.bench_window_open)
    opened = time.perf_counter()
    time.sleep(0.01)
    for _ in range(3):
        t0 = time.perf_counter()
        crop().block_until_ready()
        time.sleep(0.002)
        t1 = time.perf_counter()
        matmul(a).block_until_ready()
        time.sleep(0.004)
        t2 = time.perf_counter()
        spans += [("bench.next_batch", t0, t1), ("bench.check", t1, t2)]
    time.sleep(0.01)
    harness.run_marker(harness.bench_window_close)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    with open(os.path.join(out_dir, "small.spans.json"), "w") as f:
        json.dump({"opened": opened, "spans": spans}, f)
    print("xplane bytes", os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, [(ln.name, len(list(ln.events)))
                                    for ln in lines])
        for ln in lines:
            for ev in list(ln.events)[:4]:
                stats = {k: (v if len(str(v)) < 160 else str(v)[:160])
                         for k, v in ev.stats}
                print("  ", ln.name, "|", ev.name, ev.start_ns,
                      ev.duration_ns, stats)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
