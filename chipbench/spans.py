"""Readings of the program's span recorder over a measured window.

The program times the host work of each batch and step in named spans and
keeps running sums of its simulator's event thread
(``repro.core.stats``); ``chipbench/tools/recorded_run.py`` installs the
recorder over a cell's window.  This module turns what it recorded into
the numbers of the per-layer metrics, places the spans on the device's
timeline by the window's opening mark, as ``trace.read_xplane`` places the
harness's own, and labels the device's idle gaps with the span of the
window's thread under which they passed.

Every reader takes a run that carries ``recording`` (the recorder of its
window) and returns None where there is nothing to read: a run of the
plain harness, or of a program without the recorder.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from chipbench import trace

NO_SPAN = "no host span"


class Placed(NamedTuple):
    """A recorded span on the device's timeline, in nanoseconds."""

    start: int
    end: int
    span: object                 # repro.core.stats.Span


def recording(run):
    return getattr(run, "recording", None)


def named(rec, name: str) -> List:
    return [s for s in rec.spans if s.name == name]


def window_s(rec) -> float:
    return rec.disabled_at - rec.enabled_at


def per_batch_ms(run, name: str) -> Optional[float]:
    """Summed seconds of the spans ``name`` over the window's batches, in
    ms a batch."""
    rec = recording(run)
    batches = run.counters.get("batches")
    if rec is None or not batches:
        return None
    found = named(rec, name)
    if not found:
        return None
    return 1e3 * sum(s.end - s.start for s in found) / batches


def event_busy(run) -> Optional[float]:
    """Share of the window the event thread spent inside its events, %."""
    rec = recording(run)
    if rec is None or not rec.counters.get("clock.events"):
        return None
    return 100.0 * rec.counters["clock.busy_s"] / window_s(rec)


def event_lag_ms(run) -> Optional[float]:
    """Mean time from an event's due time to its firing, in ms."""
    rec = recording(run)
    if rec is None or not rec.counters.get("clock.events"):
        return None
    return 1e3 * rec.counters["clock.lag_s"] / rec.counters["clock.events"]


def steps(rec) -> List:
    """The window's ``train.step`` spans, in order."""
    return sorted(named(rec, "train.step"), key=lambda s: s.start)


def step_host_ms(run) -> Optional[float]:
    """Mean time from the end of one window step's ``train.step`` to the
    start of the next, in ms."""
    rec = recording(run)
    if rec is None:
        return None
    st = steps(rec)
    if len(st) < 2:
        return None
    return 1e3 * sum(b.start - a.end for a, b in zip(st, st[1:])) / (
        len(st) - 1)


def step_cover(rec, parts: Sequence[str] = ("train.next", "train.step",
                                            "train.log")) -> Optional[float]:
    """Share of the window's steps, from the end of the first
    ``train.step`` to the end of the last, that the spans ``parts`` cover."""
    st = steps(rec)
    if len(st) < 2:
        return None
    lo, hi = st[0].end, st[-1].end
    covered = sum(max(0.0, min(s.end, hi) - max(s.start, lo))
                  for s in rec.spans if s.name in parts)
    return covered / (hi - lo)


def self_share(rec, name: str) -> Optional[float]:
    """Mean self time of the spans ``name`` (less what their children
    cover) over their mean duration."""
    parents = {s.id: s for s in rec.spans if s.name == name}
    if not parents:
        return None
    kids: Dict[int, List] = {}
    for s in rec.spans:
        if s.parent in parents:
            kids.setdefault(s.parent, []).append(
                (round(s.start * 1e9), round(s.end * 1e9), s.name))
    total = sum(s.end - s.start for s in parents.values())
    inner = sum(trace.union_ns(v) for v in kids.values()) / 1e9
    return (total - inner) / total


def marks(reduced) -> Tuple[int, int]:
    """Where the opening and the closing marker programs started on the
    device."""
    found = sorted(m for v in reduced.trace.modules.values() for m in v
                   if m[2].startswith(trace.MARK))
    return found[0][0], found[-1][0]


def place(rec, host_open: float, dev_open: int) -> List[Placed]:
    """The recorder's spans on the device's timeline: ``host_open`` on the
    host clock (the opening marker enqueued) is ``dev_open``, where it
    started on the device."""
    return [Placed(dev_open + round((s.start - host_open) * 1e9),
                   dev_open + round((s.end - host_open) * 1e9), s)
            for s in rec.spans]


def placed(run, reduced) -> Optional[List[Placed]]:
    rec = recording(run)
    if rec is None or reduced is None or run.open_dispatched is None:
        return None
    return place(rec, run.open_dispatched, marks(reduced)[0])


def upload_ms(run, reduced) -> Optional[float]:
    """Per window batch, the part of ``feed.kernel_wait`` in which no op
    ran on the chip, in ms: the wait for the upload, with the kernel's own
    device time taken out."""
    spans = placed(run, reduced)
    batches = run.counters.get("batches")
    if not spans or not batches:
        return None
    waits = [p for p in spans if p.span.name == "feed.kernel_wait"]
    if not waits:
        return None
    ops = [iv for v in reduced.ops.values() for iv in v]
    idle = sum(e - s for p in waits for s, e in trace.gaps(ops, p.start,
                                                            p.end))
    return idle / 1e6 / batches


def label(gap: Tuple[int, int], spans: Sequence[Placed],
          thread: int) -> str:
    """The innermost span of ``thread`` under which most of ``gap``
    passed: of the outermost spans, the one that covers most of it, then
    its child that covers most, and so on down.  Spans of other threads
    (the loader's event thread) never label a gap of the window's."""
    mine = [p for p in spans if p.span.thread == thread]
    ids = {p.span.id for p in mine}
    kids: Dict[Optional[int], List[Placed]] = {}
    for p in mine:
        parent = p.span.parent if p.span.parent in ids else None
        kids.setdefault(parent, []).append(p)
    best, level = None, kids.get(None, [])
    while level:
        scored = [(min(p.end, gap[1]) - max(p.start, gap[0]),
                   -(p.end - p.start), i) for i, p in enumerate(level)]
        scored = [k for k in scored if k[0] > 0]
        if not scored:
            break
        best = level[max(scored)[2]]
        level = kids.get(best.span.id, [])
    return best.span.name if best is not None else NO_SPAN


def idle_gaps(run, reduced, top: int = trace.TOP) -> Optional[List]:
    """The longest idle gaps, each with the span of the window's thread
    under which it passed."""
    spans = placed(run, reduced)
    if spans is None:
        return None
    gaps = sorted(reduced.gaps(), key=lambda g: g[0] - g[1])[:top]
    return [[label(g, spans, run.window_thread), (g[1] - g[0]) / 1e9]
            for g in gaps]


def skew_ns(reduced, host_open: float, host_close: float) -> int:
    """How far the closing marker started on the device from where its
    dispatch on the host maps through the opening mark."""
    dev_open, dev_close = marks(reduced)
    return dev_close - (dev_open + round((host_close - host_open) * 1e9))


def totals_line(rec) -> str:
    """Each span name's count and seconds, as the ``spans:`` line."""
    totals = sorted(rec.totals().items())
    return "spans: " + "; ".join(f"{name} {n} {secs:.3f} s"
                                 for name, (n, secs) in totals)


__all__ = ["Placed", "per_batch_ms", "event_busy", "event_lag_ms",
           "step_host_ms", "step_cover", "self_share", "marks", "place",
           "placed",
           "upload_ms", "label", "idle_gaps", "skew_ns", "totals_line"]
