"""Reduction of the profiler's trace to device busy time, kernel time and a
breakdown of where the window went.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, taken with
the host tracer off: with it on, each 100 MB upload of the image feed logs
millions of ``Transpose`` events and runs twenty times slower.  From the
device planes (``/device:TPU:n``) we keep, in nanoseconds on the trace's
timeline:

* device ops: every event of the ``XLA Ops`` line, one per HLO op run on
  the chip (a Pallas kernel is one op);
* device programs: the events of the ``XLA Modules`` line, one per run of
  a compiled program.

The window is bounded on the device's own timeline by two marker programs
that the harness runs as it opens and closes it (``jit_bench_window_open``
and ``jit_bench_window_close``; the runtime may name both after whichever
compiled first): from the end of the first to the start of the second.  The harness's own spans (``bench.next_batch`` and the like),
kept on the host clock, are placed on that timeline by the same two marks.

Busy time is the union of the device ops' intervals inside the window,
averaged over the chips that ran any op; the idle share is one minus busy
over the window.  An idle gap is labelled with the innermost span that
covers most of it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int, str]          # (start_ns, end_ns, name)

MARK = "jit_bench_window_"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    ops: Dict[str, List[Interval]]         # device plane -> ops
    modules: Dict[str, List[Interval]]     # device plane -> programs
    spans: List[Interval]                  # host spans, on the same timeline


def read_xplane(path: str, host_spans: Sequence[Tuple[str, float, float]] = (),
                host_open: Optional[float] = None) -> Trace:
    """The intervals this module reduces, read from a profiler trace.

    ``host_spans`` are ``(name, start, end)`` in seconds of the host clock
    on which the window opened at ``host_open``."""
    from jax.profiler import ProfileData

    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
            if dest is not None:
                dest.setdefault(plane.name, []).extend(
                    (int(e.start_ns), int(e.end_ns), e.name)
                    for e in line.events)
    marks = sorted(m for v in modules.values() for m in v
                   if m[2].startswith(MARK))
    if len(marks) != 2:
        raise ValueError(f"expected the window's two marker programs, found "
                         f"{len(marks)} in {path}")
    lo, hi = marks[0][1], marks[1][0]
    spans = []
    if host_open is not None:
        spans = [(lo + round((a - host_open) * 1e9),
                  lo + round((b - host_open) * 1e9), name)
                 for name, a, b in host_spans]
    return Trace((lo, hi), ops, modules, spans)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """Intervals cut to ``[lo, hi]``; those outside it dropped."""
    out = []
    for s, e, name in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, name))
    return out


def union(intervals: Iterable[Interval]) -> List[Tuple[int, int]]:
    """Sorted, disjoint ``(start, end)`` covering the same time."""
    merged: List[List[int]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def union_ns(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in union(intervals))


def gaps(intervals: Iterable[Interval], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short(name: str) -> str:
    """An op's instruction name, without the HLO text that follows it."""
    return name.split(" = ", 1)[0]


def label(gap: Tuple[int, int], spans: Sequence[Interval]) -> str:
    """The host span that covers most of ``gap``; of equals, the shortest
    (innermost).  ``no host span`` where none touches it."""
    best, key = "no host span", None
    for s, e, name in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        k = (overlap, -(e - s))
        if key is None or k > key:
            best, key = name, k
    return best


class Reduced:
    """The numbers of one traced window."""

    def __init__(self, trace: Trace) -> None:
        self.trace = trace
        lo, hi = trace.window
        if hi <= lo:
            raise ValueError(f"empty window {trace.window}")
        self.lo, self.hi = lo, hi
        self.ops = {dev: clip(v, lo, hi) for dev, v in trace.ops.items()}
        self.ops = {dev: v for dev, v in self.ops.items() if v}
        self.modules = {dev: clip(v, lo, hi)
                        for dev, v in trace.modules.items()}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the chips that ran any op."""
        if not self.ops:
            return 0.0
        return sum(union_ns(v) for v in self.ops.values()) / len(self.ops) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_events(self, pattern: str) -> List[Interval]:
        """Device ops whose name matches ``pattern`` (a regular expression),
        on every chip."""
        rx = re.compile(pattern)
        return [iv for v in self.ops.values() for iv in v if rx.search(iv[2])]

    def module_events(self, pattern: str) -> List[Interval]:
        rx = re.compile(pattern)
        return [iv for v in self.modules.values() for iv in v
                if rx.search(iv[2])]

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle gaps of the first chip that ran any op (the whole window
        where none did)."""
        if not self.ops:
            return [(self.lo, self.hi)]
        first = sorted(self.ops)[0]
        return gaps(self.ops[first], self.lo, self.hi)

    def breakdown(self, top: int = TOP) -> Dict[str, List]:
        """The device ops that took most time, summed by instruction name
        over all chips, and the longest idle gaps by the host span around
        them."""
        by_name: Dict[str, int] = {}
        for v in self.ops.values():
            for s, e, name in v:
                by_name[short(name)] = by_name.get(short(name), 0) + (e - s)
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                "idle_gaps": [[label(g, self.trace.spans), (g[1] - g[0]) / 1e9]
                              for g in idle]}


def reduce_file(path: str, host_spans=(), host_open=None) -> Reduced:
    return Reduced(read_xplane(path, host_spans, host_open))


def seconds(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e, _ in intervals) / 1e9


__all__ = ["Trace", "Reduced", "read_xplane", "reduce_file", "union",
           "union_ns", "gaps", "label", "clip", "seconds"]
