"""What every cell shares: finding its files by name, the device and its
peaks, the compile cache, the measured window and its trace, the checks
that decide ``correct``, and the one JSON line a run prints.

A cell is found by name alone: ``BENCHMARK.json`` names it, its traffic is
``chipbench/workloads/<cell>.json``, that file names its configuration
``chipbench/configs/<config>.json``, the configuration names its driver
``chipbench/drivers/<driver>.py``, and each per-layer metric is read by
``chipbench/metrics/<metric>.py``.  Adding any of them is adding files.
"""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def process_start() -> float:
    """``time.monotonic()`` at which this process started, so that set-up
    counts interpreter start and imports too."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = os.sysconf("SC_CLK_TCK")
        started = int(fields[19]) / ticks           # seconds after boot
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.monotonic() - (uptime - started)
    except (OSError, ValueError, IndexError):
        return time.monotonic()


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its traffic and configuration files."""

    name: str
    chips: int
    workload: Dict
    config: Dict
    end_to_end: List[Dict]       # the BENCHMARK.json metrics this cell reports
    per_layer: List[Dict]

    @classmethod
    def find(cls, name: str, root: str = CHECKOUT) -> "Cell":
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        workload = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
        config = load_json(os.path.join(HERE, "configs",
                                        f"{entry['config']}.json"))
        e2e = [m for m in bench["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [m for m in bench["per_layer"]
                     if name in m.get("workloads", [name])
                     and m["moves"] in reported]
        return cls(name, int(entry["chips"]), workload, config, e2e,
                   per_layer)


def peaks_for(device_kind: str) -> Dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


def enable_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Check:
    """One number compared with the plain reference: correct while
    ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def bench_window_open(x):
    """A marker program: its run on the chip opens the traced window."""
    return x + 1


def bench_window_close(x):
    """A marker program: its run on the chip closes the traced window."""
    return x + 2


def run_marker(fn) -> None:
    import jax
    import jax.numpy as jnp

    jax.jit(fn)(jnp.zeros((), jnp.int32)).block_until_ready()


class Run:
    """One run of one cell, handed to the cell's driver.

    The driver builds the system under test, warms it up, calls
    :meth:`setup_done`, drives the measured window inside :meth:`window`,
    reads :meth:`read_memory_peak`, frees the system's state and then adds
    its checks against the plain reference.  It reports end-to-end values
    in ``e2e`` and what the per-layer readers need in ``counters``.
    """

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 started: float, device=None, interpret: bool = False
                 ) -> None:
        self.cell = cell
        self.config = cell.config
        self.workload = cell.workload
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.started = started
        self.device = device
        self.interpret = interpret          # Pallas interpreter (CPU tests)
        # Put in the program's place on the checked path by the control and
        # by the fault tests; None on every benchmark run.
        self.substitute: Optional[Callable] = None
        self.e2e: Dict[str, float] = {}
        self.counters: Dict[str, Any] = {}
        self.checks: List[Check] = []
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes = 0
        self.trace_path: Optional[str] = None
        self.spans: List[tuple] = []        # (name, start, end), host clock
        self._trace_dir: Optional[str] = None
        self._open_at: Optional[float] = None
        self._closed = False

    def setup_done(self) -> None:
        """Set-up ends where the first timed batch or step starts."""
        self.setup_s = time.monotonic() - self.started

    @contextlib.contextmanager
    def span(self, name: str):
        """A span of the harness's own, kept on the host clock while the
        window is traced, for labelling the device's idle gaps."""
        start = time.perf_counter()
        try:
            yield
        finally:
            if self._open_at is not None:
                self.spans.append((name, start, time.perf_counter()))

    def open_window(self) -> None:
        """Start the measured window; where the run is traced, start the
        profiler (host tracer off) and run the opening marker program."""
        if self.setup_s is None:
            raise RuntimeError("the window opened before setup_done()")
        if not self.trace:
            return
        import jax
        from jax.profiler import ProfileOptions

        self._trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = ProfileOptions()
        opts.host_tracer_level = 0
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        run_marker(bench_window_open)
        self._open_at = time.perf_counter()

    def end_window(self) -> None:
        """Run the closing marker; the profiler runs on until
        :meth:`close_window`."""
        if self._open_at is not None and not self._closed:
            self._closed = True
            run_marker(bench_window_close)

    def close_window(self) -> None:
        if not self.trace:
            return
        import jax

        self.end_window()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self._trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        self.trace_path = sorted(found)[-1]

    @contextlib.contextmanager
    def window(self):
        """The measured window, traced when the run asks for it."""
        self.open_window()
        try:
            yield
        finally:
            self.close_window()

    def reduce_trace(self):
        from chipbench import trace

        return trace.reduce_file(self.trace_path, self.spans, self._open_at)

    def read_memory_peak(self) -> None:
        if self.device is not None:
            stats = self.device.memory_stats() or {}
            self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append(Check(name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)

    def cleanup(self) -> None:
        if self._trace_dir:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


def driver_for(cell: Cell):
    name = cell.config["driver"]
    return load_module(os.path.join(HERE, "drivers", f"{name}.py"),
                       f"chipbench_driver_{name}")


def read_per_layer(run: Run, reduced, peaks: Dict) -> Dict[str, Dict]:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in run.cell.per_layer:
        reader = load_module(os.path.join(HERE, "metrics", f"{m['name']}.py"),
                             "chipbench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run, reduced, peaks)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, device_info: Dict, metrics: Dict,
                breakdown: Optional[Dict] = None) -> Dict:
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device_info}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    return line


def print_checks(run: Run, stream=None) -> None:
    stream = stream or sys.stderr
    for c in run.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=stream, flush=True)
