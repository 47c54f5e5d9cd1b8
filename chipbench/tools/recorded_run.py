"""Run a cell with the program's span recorder installed over its window.

    python3 chipbench/tools/recorded_run.py --workload <cell> --seeds 1 2 3 \
        [--seconds 51] [--trace] [--save DIR]
    python3 chipbench/tools/recorded_run.py --workload <cell> --seeds 1 2 3 \
        [--seconds 51] --cost

Never part of a benchmark run: ``chipbench/run.py`` installs no recorder.
Here the harness's run is kept whole, and the recorder of
``repro.core.stats`` is installed as the window opens and removed as it
closes; the harness's own ``bench.*`` spans are recorded through it too.

Each recorded run prints on standard error the ``spans:`` line (each span
name's count and seconds in the window) and the per-batch or per-step
readings, and with ``--trace`` the ``clock:`` line: how far the closing
marker started on the device from where its dispatch on the host maps
through the opening mark.  Standard output gets one JSON line a run: the
end-to-end values, the per-layer metrics of ``chipbench/metrics/`` that
read the recorder, what the spans leave unaccounted, and with ``--trace``
the longest idle gaps labelled by the window thread's innermost span.
``--save DIR`` keeps each traced run's ``.xplane.pb`` and its spans.

``--cost`` makes, for each seed, one untraced run with the recorder off and
one with it on, in turn, and prints the end-to-end values of both.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, spans  # noqa: E402

# the per-layer metrics that read the recorder, by the cell's driver
METRICS = {
    "image_tight_loop": {"kernel_wait_ms.img": "ms", "upload_ms.img": "ms",
                         "assemble_ms.img": "ms", "event_busy.img": "%",
                         "event_lag_ms.img": "ms"},
    "train_loop": {"step_host_ms.lm": "ms"},
}
THROUGHPUT = {"image_tight_loop": "images_per_s",
              "train_loop": "tokens_per_s"}


class RecordedRun(harness.Run):
    """A harness run with the program's recorder installed from the
    window's opening to its close.

    Traced, the two marker programs are compiled and run once before the
    profiler starts, and each is stamped on the host clock as soon as it
    is enqueued: the spans are placed by the opening marker's start, and
    the closing marker's start checks that placement.  (The harness's own
    marks are a fresh ``jax.jit`` each and the host's return from the
    opening one, some milliseconds off while the event thread holds the
    interpreter.)
    """

    def __init__(self, *args, record: bool = True, **kw) -> None:
        super().__init__(*args, **kw)
        self.record = record
        self.recording = None            # the window's Recorder
        self.window_thread = None
        self.open_dispatched = None      # host clock, each marker enqueued
        self.close_dispatched = None
        self._markers = None

    @contextlib.contextmanager
    def span(self, name: str):
        from repro.core import stats

        with super().span(name), stats.span(name):
            yield

    def open_window(self) -> None:
        if self.trace:
            self._open_traced()
        else:
            super().open_window()
        if self.record:
            from repro.core import stats

            self.window_thread = threading.get_ident()
            self.recording = stats.enable()

    def _open_traced(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileOptions

        if self.setup_s is None:
            raise RuntimeError("the window opened before setup_done()")
        arg = jnp.zeros((), jnp.int32)
        self._markers = (arg, jax.jit(harness.bench_window_open),
                         jax.jit(harness.bench_window_close))
        for marker in self._markers[1:]:
            marker(arg).block_until_ready()
        self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        out = self._markers[1](arg)
        self.open_dispatched = time.perf_counter()
        out.block_until_ready()
        self._open_at = time.perf_counter()

    def end_window(self) -> None:
        try:
            if self._open_at is not None and not self._closed:
                self._closed = True
                out = self._markers[2](self._markers[0])
                self.close_dispatched = time.perf_counter()
                out.block_until_ready()
        finally:
            self._stop_recording()

    def close_window(self) -> None:
        try:
            super().close_window()
        finally:
            self._stop_recording()

    def _stop_recording(self) -> None:
        from repro.core import stats

        if self.recording is not None and stats.active is self.recording:
            stats.disable()


def read_metrics(run, reduced) -> dict:
    out = {}
    for name, unit in METRICS[run.config["driver"]].items():
        reader = harness.load_module(
            os.path.join(harness.HERE, "metrics", f"{name}.py"),
            "chipbench_metric_" + name.replace(".", "_"))
        value = reader.read(run, reduced, None)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def report(run, reduced) -> dict:
    """The readings of one recorded run, with its stderr lines printed."""
    rec = run.recording
    metrics = read_metrics(run, reduced)
    out = {"metrics": metrics}
    print(spans.totals_line(rec), file=sys.stderr, flush=True)
    if run.config["driver"] == "image_tight_loop":
        out["feed_next_self_share"] = spans.self_share(rec, "feed.next")
        words = [f"{name} {m['value']:.3f} {m['unit']}"
                 for name, m in metrics.items()]
        print("recorded: " + ", ".join(words) + "; feed.next self time "
              f"{100 * out['feed_next_self_share']:.2f}%", file=sys.stderr,
              flush=True)
    else:
        out["step_cover"] = spans.step_cover(rec)
        host = metrics.get("step_host_ms.lm", {}).get("value", float("nan"))
        print(f"recorded: host time between steps {host:.3f} ms; "
              f"train.next + train.step + train.log cover "
              f"{100 * out['step_cover']:.2f}% of the steps", file=sys.stderr,
              flush=True)
    if reduced is not None:
        skew = spans.skew_ns(reduced, run.open_dispatched,
                             run.close_dispatched)
        out["clock_skew_us"] = skew / 1e3
        print(f"clock: skew {skew / 1e3:.1f} us over the "
              f"{reduced.window_s:.3f} s window", file=sys.stderr, flush=True)
        out["idle_gaps"] = spans.idle_gaps(run, reduced)
    return out


def save(run, out_dir: str, tag: str) -> None:
    """The run's trace and what the recorder kept, for the CPU tests."""
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(run.trace_path, os.path.join(out_dir, f"{tag}.xplane.pb"))
    rec = run.recording
    with open(os.path.join(out_dir, f"{tag}.recorded.json"), "w") as f:
        json.dump({"open_dispatched": run.open_dispatched,
                   "close_dispatched": run.close_dispatched,
                   "window_thread": run.window_thread,
                   "batches": run.counters.get("batches"),
                   "enabled_at": rec.enabled_at,
                   "disabled_at": rec.disabled_at,
                   "counters": rec.counters,
                   "spans": [list(s) for s in rec.spans]}, f)


def recorded(cell, seed: int, seconds: float, trace: bool, record: bool,
             device=None, interpret: bool = False, save_dir=None) -> dict:
    run = RecordedRun(cell, seed, seconds, trace, time.monotonic(),
                      device=device, interpret=interpret, record=record)
    try:
        harness.driver_for(cell).run(run)
        out = {"workload": cell.name, "seed": seed, "recorder": record,
               "trace": trace, "correct": run.correct, "e2e": run.e2e}
        reduced = run.reduce_trace() if trace else None
        if record:
            out.update(report(run, reduced))
        if trace and save_dir:
            save(run, save_dir, f"{cell.name}-{seed}")
    finally:
        run.cleanup()
    harness.print_checks(run)
    return out


def cost(cell, seeds, seconds: float, device=None) -> dict:
    """Untraced runs with the recorder off and on, in turn, on each seed."""
    metric = THROUGHPUT[cell.config["driver"]]
    got = {False: [], True: []}
    for i, seed in enumerate(seeds):
        order = (False, True) if i % 2 == 0 else (True, False)
        for record in order:
            out = recorded(cell, seed, seconds, False, record, device)
            print(json.dumps(out), flush=True)
            got[record].append(out["e2e"][metric])
    off, on = statistics.median(got[False]), statistics.median(got[True])
    return {"workload": cell.name, "metric": metric, "off": got[False],
            "on": got[True], "median_off": off, "median_on": on,
            "on_over_off": on / off}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--cost", action="store_true")
    ap.add_argument("--save", default=None)
    args = ap.parse_args()
    cell = harness.Cell.find(args.workload)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("recorded_run: no TPU found")
    harness.enable_compile_cache()
    if args.cost:
        print(json.dumps(cost(cell, args.seeds, args.seconds, dev)),
              flush=True)
        return 0
    for seed in args.seeds:
        print(json.dumps(recorded(cell, seed, args.seconds, args.trace, True,
                                  dev, save_dir=args.save)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
