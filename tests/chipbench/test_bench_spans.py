"""The program's spans over a cell's window, on the CPU: the small cells
driven with the recorder installed emit every span with its batch or step,
and the readings of ``chipbench/spans.py`` and of the metrics that read the
recorder come out right on hand-made spans and a hand-made trace."""

from __future__ import annotations

import json
import os
import threading
import types
from collections import Counter

import pytest

from benchcells import ROOT, SEED, drive
from chipbench import harness, spans, trace
from chipbench.tools import recorded_run
from repro.core import stats

IMAGE_SPANS = {"feed.next", "feed.loader_wait", "feed.upload", "feed.kernel",
               "feed.kernel_wait", "feed.release"}
TRAIN_SPANS = {"train.next", "train.step", "train.log", "feed.next",
               "feed.loader_wait", "feed.decode", "feed.upload"}


def recorded(cell, trace_it=False):
    run = recorded_run.RecordedRun(cell, SEED, 1.0, trace_it, 0.0,
                                   interpret=True)
    harness.driver_for(cell).run(run)
    assert run.correct
    assert stats.active is None              # removed as the window closed
    return run


def test_image_cell_emits_every_span_of_a_batch(image_cell):
    run = recorded(image_cell)
    rec = run.recording
    names = Counter(s.name for s in rec.spans)
    assert IMAGE_SPANS | {"loader.assemble", "bench.next_batch"} <= set(names)
    window = run.counters["batches"]
    for name in IMAGE_SPANS | {"bench.next_batch"}:
        assert names[name] == window, name
    by_id = {s.id: s for s in rec.spans}
    consumer = run.window_thread
    for s in rec.spans:
        if s.name in IMAGE_SPANS:
            assert s.thread == consumer and s.request.startswith("batch=")
        if s.name == "feed.next":
            assert by_id[s.parent].name == "bench.next_batch"
        elif s.name in IMAGE_SPANS:
            assert by_id[s.parent].name == "feed.next"
    # each batch's assembly on the event thread joins its delivery
    assembled = {s.request: s for s in rec.spans
                 if s.name == "loader.assemble"}
    for s in rec.spans:
        if s.name == "feed.upload" and s.request in assembled:
            a = assembled[s.request]
            assert a.thread != consumer and a.end <= s.start
    # only the batches assembled before the window opened have none
    uploads = sorted((s for s in rec.spans if s.name == "feed.upload"),
                     key=lambda s: s.start)
    joined = [s.request in assembled for s in uploads]
    assert joined[-1] and joined == sorted(joined)
    # the batch each pull waited on is the one it uploaded
    kids: dict = {}
    for s in rec.spans:
        kids.setdefault(s.parent, []).append(s)
    for nxt in (s for s in rec.spans if s.name == "feed.next"):
        assert len({k.request for k in kids[nxt.id]}) == 1
    assert rec.counters["clock.events"] > 0
    assert rec.counters["clock.busy_s"] > 0


def test_train_cell_emits_every_span_of_a_step(train_cell):
    run = recorded(train_cell)
    rec = run.recording
    names = {s.name for s in rec.spans}
    assert TRAIN_SPANS <= names
    by_id = {s.id: s for s in rec.spans}
    steps = [s for s in rec.spans if s.name == "train.step"]
    numbers = sorted(int(s.request.split("=")[1]) for s in steps)
    assert numbers == list(range(numbers[0], numbers[-1] + 1))
    per_step = {s.request for s in rec.spans if s.name == "train.next"}
    assert {s.request for s in steps} <= per_step
    for s in rec.spans:
        if s.name == "feed.next":
            assert by_id[s.parent].name == "train.next"
        if s.name in ("feed.loader_wait", "feed.decode", "feed.upload"):
            assert by_id[s.parent].name == "feed.next"
            assert s.request.startswith("batch=")
        if s.name.startswith("train."):
            assert s.request.startswith("step=")
    logs = [s for s in rec.spans if s.name == "train.log"]
    assert logs and all(int(s.request[5:]) % 10 == 0 for s in logs)


def test_recorded_run_reports_the_cells_metrics(image_cell, train_cell):
    img = recorded_run.recorded(image_cell, SEED, 1.0, False, True,
                                interpret=True)
    assert img["correct"]
    assert set(img["metrics"]) == {"kernel_wait_ms.img", "assemble_ms.img",
                                   "event_busy.img", "event_lag_ms.img"}
    assert 0 <= img["feed_next_self_share"] < 1
    lm = recorded_run.recorded(train_cell, SEED, 1.0, False, True,
                               interpret=True)
    assert set(lm["metrics"]) == {"step_host_ms.lm"}
    assert 0.9 < lm["step_cover"] <= 1.0


def test_the_plain_harness_installs_no_recorder(image_cell, monkeypatch):
    def refuse():
        raise AssertionError("the harness installed a recorder")

    monkeypatch.setattr(stats, "enable", refuse)
    run = drive(image_cell)
    assert run.correct and stats.active is None
    for name in recorded_run.METRICS["image_tight_loop"]:
        assert metric(name).read(run, None, None) is None


# -- readings on hand-made spans ---------------------------------------------

def metric(name):
    return harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", f"{name}.py"),
        "test_metric_" + name.replace(".", "_"))


def span(name, start, end, thread=1, id=0, parent=None, request=None):
    return stats.Span(name, start, end, thread, id, parent, request)


def hand_run(spans_, counters=None, batches=2, open_at=10.0):
    rec = stats.Recorder()
    rec.spans.extend(spans_)
    for name, value in (counters or {}).items():
        rec.count(name, value)
    rec.enabled_at, rec.disabled_at = open_at, open_at + 4.0
    return types.SimpleNamespace(recording=rec, counters={"batches": batches},
                                 open_dispatched=open_at, window_thread=1)


def hand_reduced(ops, hi=4_000_000_000):
    """A trace whose opening marker starts at 0 ns and runs 1 us, and whose
    closing marker starts at the window's end, ``hi``."""
    marks = [(0, 1_000, "jit_bench_window_open(1)"),
             (hi, hi + 1_000, "jit_bench_window_close(2)")]
    return trace.Reduced(trace.Trace(
        window=(1_000, hi), ops={"/device:TPU:0": ops},
        modules={"/device:TPU:0": marks}, spans=[]))


def test_per_batch_readers_by_hand():
    run = hand_run([span("feed.kernel_wait", 10.1, 10.15),
                    span("feed.kernel_wait", 10.3, 10.31),
                    span("loader.assemble", 10.0, 10.008, thread=2),
                    span("loader.assemble", 10.2, 10.212, thread=2)],
                   counters={"clock.events": 400.0, "clock.busy_s": 1.0,
                             "clock.lag_s": 0.2})
    assert metric("kernel_wait_ms.img").read(run, None, None) == \
        pytest.approx(30.0)
    assert metric("assemble_ms.img").read(run, None, None) == \
        pytest.approx(10.0)
    assert metric("event_busy.img").read(run, None, None) == \
        pytest.approx(25.0)
    assert metric("event_lag_ms.img").read(run, None, None) == \
        pytest.approx(0.5)
    assert metric("step_host_ms.lm").read(run, None, None) is None


def test_upload_is_the_kernel_wait_the_chip_sat_idle():
    # placed by the opening mark: host 10.0 s is device 0 ns
    run = hand_run([span("feed.kernel_wait", 10.100, 10.150, id=1),
                    span("feed.kernel_wait", 10.300, 10.310, id=2),
                    span("feed.upload", 10.050, 10.100, id=3)])
    ms = 1_000_000
    reduced = hand_reduced([(120 * ms, 125 * ms, "copy.1"),      # inside
                            (140 * ms, 160 * ms, "crop.1"),      # 10 inside
                            (155 * ms, 158 * ms, "copy.2"),      # overlaps
                            (60 * ms, 70 * ms, "other")])        # outside
    # (50 - 5 - 10) + 10 ms over two batches
    assert metric("upload_ms.img").read(run, reduced, None) == \
        pytest.approx(22.5)
    assert metric("upload_ms.img").read(run, None, None) is None


def test_host_time_between_steps_and_their_cover():
    run = hand_run([span("train.next", 0.0, 0.002, request="step=1"),
                    span("train.step", 0.002, 1.0, request="step=1"),
                    span("train.next", 1.003, 1.004, request="step=2"),
                    span("train.step", 1.004, 2.0, request="step=2"),
                    span("train.log", 2.0, 2.001, request="step=2"),
                    span("train.next", 2.005, 2.006, request="step=3"),
                    span("train.step", 2.006, 3.0, request="step=3")])
    assert metric("step_host_ms.lm").read(run, None, None) == \
        pytest.approx(5.0)
    # 2 s from the first step's end to the last's, 7 ms of it uncovered
    assert spans.step_cover(run.recording) == pytest.approx(1.993 / 2.0)


def test_self_share_takes_out_what_the_children_cover():
    rec = hand_run([span("feed.next", 0.0, 0.1, id=1),
                    span("feed.loader_wait", 0.0, 0.04, id=2, parent=1),
                    span("feed.upload", 0.05, 0.09, id=3, parent=1),
                    span("jax.compile", 0.06, 0.07, id=4, parent=3),
                    span("feed.next", 0.2, 0.3, id=5),
                    span("feed.kernel", 0.2, 0.3, id=6, parent=5)]).recording
    assert spans.self_share(rec, "feed.next") == pytest.approx(0.02 / 0.2)


def test_label_descends_to_the_innermost_span_of_the_window_thread():
    spans_ = [span("bench.next_batch", 0.0, 0.3, id=1),
              span("feed.next", 0.001, 0.3, id=2, parent=1),
              span("feed.loader_wait", 0.001, 0.2, id=3, parent=2),
              span("feed.upload", 0.2, 0.25, id=4, parent=2),
              span("feed.kernel_wait", 0.25, 0.3, id=5, parent=2),
              # the event thread, busy all through the gap
              span("loader.assemble", 0.0, 0.3, thread=2, id=6)]
    placed = spans.place(hand_run(spans_).recording, 0.0, 0)
    ms = 1_000_000
    assert spans.label((0, 290 * ms), placed, 1) == "feed.loader_wait"
    assert spans.label((190 * ms, 260 * ms), placed, 1) == "feed.upload"
    assert spans.label((0, 290 * ms), placed, 2) == "loader.assemble"
    assert spans.label((0, 290 * ms), placed, 3) == spans.NO_SPAN
    assert spans.label((400 * ms, 500 * ms), placed, 1) == spans.NO_SPAN
    # the old rule takes the outermost span where the gap outruns the child
    flat = [(p.start, p.end, p.span.name) for p in placed]
    assert trace.label((0, 290 * ms), flat) == "bench.next_batch"


def test_idle_gaps_are_labelled_on_the_device_timeline():
    run = hand_run([span("bench.next_batch", 10.0, 10.9, id=1),
                    span("feed.loader_wait", 10.0, 10.6, id=2, parent=1),
                    span("feed.kernel_wait", 10.6, 10.9, id=3, parent=1),
                    span("loader.assemble", 10.0, 11.0, thread=2, id=4)])
    ms = 1_000_000
    reduced = hand_reduced([(700 * ms, 710 * ms, "crop")], hi=1000 * ms)
    gaps = spans.idle_gaps(run, reduced)
    assert gaps[0] == ["feed.loader_wait", pytest.approx(0.7, abs=1e-5)]
    assert gaps[1] == ["feed.kernel_wait", pytest.approx(0.29)]


def test_clock_skew_by_hand():
    # dispatched 2 s after the opening marker; started 400 ns later
    reduced = hand_reduced([(5_000, 6_000, "op")], hi=2_000_000_400)
    assert spans.skew_ns(reduced, 100.0, 102.0) == 400
    assert spans.marks(reduced) == (0, 2_000_000_400)


def test_totals_line_names_count_and_seconds():
    rec = hand_run([span("feed.next", 0.0, 0.5), span("feed.next", 1.0, 1.25),
                    span("train.step", 0.0, 2.0)]).recording
    assert spans.totals_line(rec) == \
        "spans: feed.next 2 0.750 s; train.step 1 2.000 s"


def test_recorded_run_stops_recording_when_the_window_fails(image_cell):
    run = recorded_run.RecordedRun(image_cell, SEED, 1.0, False, 0.0,
                                   interpret=True)
    run.setup_done()
    with pytest.raises(RuntimeError):
        with run.window():
            assert stats.active is run.recording
            assert run.window_thread == threading.get_ident()
            raise RuntimeError("the window failed")
    assert stats.active is None


# -- a recorded window of img-local on a TPU v5e -----------------------------
# chipbench/testdata/recorded.*: ``recorded_run.py --workload img-local
# --seconds 1 --trace --save``, 12 batches; the trace and what the recorder
# kept, with the host clock's stamps of the two marker programs.

@pytest.fixture(scope="module")
def chip_window():
    data = os.path.join(ROOT, "chipbench", "testdata")
    with open(os.path.join(data, "recorded.json")) as f:
        saved = json.load(f)
    rec = stats.Recorder()
    rec.spans.extend(stats.Span(*s) for s in saved["spans"])
    for name, value in saved["counters"].items():
        rec.count(name, value)
    rec.enabled_at, rec.disabled_at = saved["enabled_at"], saved["disabled_at"]
    run = types.SimpleNamespace(
        recording=rec, counters={"batches": saved["batches"]},
        open_dispatched=saved["open_dispatched"],
        window_thread=saved["window_thread"])
    reduced = trace.reduce_file(os.path.join(data, "recorded.xplane.pb"))
    return run, reduced, saved


def test_chip_window_clock_skew_is_under_a_millisecond(chip_window):
    run, reduced, saved = chip_window
    skew = spans.skew_ns(reduced, saved["open_dispatched"],
                         saved["close_dispatched"])
    assert 0 <= skew < 1_000_000


def test_chip_window_places_each_crop_inside_its_batch(chip_window):
    run, reduced, _ = chip_window
    placed = spans.placed(run, reduced)
    dispatch = {p.span.request: p for p in placed
                if p.span.name == "feed.kernel"}
    waited = {p.span.request: p for p in placed
              if p.span.name == "feed.kernel_wait"}
    crops = reduced.module_events(r"^jit_crop_mirror_normalize\(")
    assert len(crops) == run.counters["batches"] == len(waited)
    for start, end, _ in crops:
        # dispatched before it ran, and waited on until it was done
        owners = [r for r in waited
                  if dispatch[r].start <= start and end <= waited[r].end]
        assert len(owners) == 1
        assert waited[owners[0]].end - end < 1_000_000


def test_chip_window_readings(chip_window):
    run, reduced, _ = chip_window
    kernel_wait = metric("kernel_wait_ms.img").read(run, reduced, None)
    upload = metric("upload_ms.img").read(run, reduced, None)
    # the crop program's 2.7 ms a batch is the only device time in the wait
    crop_ms = trace.seconds(reduced.module_events(
        r"^jit_crop_mirror_normalize\(")) * 1e3 / run.counters["batches"]
    assert upload == pytest.approx(kernel_wait - crop_ms, abs=0.01)
    assert 0 < metric("event_busy.img").read(run, reduced, None) < 100
    assert metric("event_lag_ms.img").read(run, reduced, None) > 0
    assert spans.self_share(run.recording, "feed.next") < 0.05


def test_chip_window_gaps_fall_to_the_consumers_spans(chip_window):
    run, reduced, _ = chip_window
    gaps = spans.idle_gaps(run, reduced)
    assert len(gaps) == 10
    assert all(name.startswith("feed.") for name, _ in gaps)
    # the event thread was busy in the same gaps, and labels them only
    # when asked for by its own id
    (other,) = {s.thread for s in run.recording.spans} - {run.window_thread}
    theirs = spans.idle_gaps(types.SimpleNamespace(
        **{**vars(run), "window_thread": other}), reduced)
    assert {name for name, _ in theirs} == {"loader.assemble"}
