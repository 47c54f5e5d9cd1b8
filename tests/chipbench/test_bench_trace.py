"""The trace reduction on a small trace recorded on a TPU v5e by
``chipbench/tools/record_trace.py``: between the window's two marker
programs, three calls of the crop kernel on 16 images (``bench.next_batch``,
then a 2 ms sleep) and three of a 2048 x 2048 bf16 matmul (``bench.check``,
then 4 ms)."""

from __future__ import annotations

import json
import os

import types

import pytest

from benchcells import ROOT
from chipbench import counts, harness, trace

DATA = os.path.join(ROOT, "chipbench", "testdata")
KERNEL = r"^%crop_mirror_normalize[.\d]* = .* custom-call\("


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(DATA, "small.spans.json")) as f:
        host = json.load(f)
    return trace.reduce_file(os.path.join(DATA, "small.xplane.pb"),
                             host["spans"], host["opened"])


def test_planes_window_and_spans(reduced):
    assert list(reduced.ops) == ["/device:TPU:0"]
    names = [s[2] for s in reduced.trace.spans]
    assert names.count("bench.next_batch") == 3
    assert names.count("bench.check") == 3
    # the window holds the six calls and 38 ms of sleeps
    assert 0.038 < reduced.window_s < 0.2
    # the spans land inside the window, in order
    lo, hi = reduced.trace.window
    starts = [s[0] for s in reduced.trace.spans]
    assert starts == sorted(starts) and lo < starts[0] and \
        reduced.trace.spans[-1][1] < hi


def test_kernel_time_by_name(reduced):
    calls = reduced.op_events(KERNEL)
    assert len(calls) == 3
    per_call = trace.seconds(calls) / 3
    assert 10e-6 < per_call < 200e-6
    assert len(reduced.module_events(r"^jit_crop_mirror_normalize\(")) == 3
    assert len(reduced.module_events(r"^jit__lambda\(")) == 3
    # a share of the roofline from the byte count cannot pass 100%
    share = counts.roofline_share(
        3 * counts.crop_flops(16, 224, 224, 3),
        3 * counts.crop_bytes(16, 224, 224, 3), trace.seconds(calls),
        197e12, 819e9)
    assert 0 < share < 100


def test_union_busy_and_idle(reduced):
    ops = reduced.ops["/device:TPU:0"]
    assert reduced.busy_s * 1e9 == pytest.approx(trace.union_ns(ops))
    # the ops do not overlap much, and most of the window is sleep
    assert reduced.busy_s <= trace.seconds(ops)
    assert 0.9 < reduced.idle_share < 1.0


def test_gaps_are_labelled_by_the_spans(reduced):
    bd = reduced.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][0].startswith("%convolution_reduce_fusion")
    labels = [g[0] for g in bd["idle_gaps"]]
    assert set(labels) <= {"bench.next_batch", "bench.check", "no host span"}
    assert "bench.check" in labels and "bench.next_batch" in labels
    gaps = [g[1] for g in bd["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # every gap lies in the window, and gaps and busy time fill it
    all_gaps = reduced.gaps()
    total = sum(e - s for s, e in all_gaps) / 1e9
    assert total + reduced.busy_s == pytest.approx(reduced.window_s)


def crop_reader():
    return harness.load_module(
        os.path.join(ROOT, "chipbench", "metrics", "crop_roofline.py"),
        "crop_roofline_under_test")


def test_crop_roofline_counts_the_whole_crop_program(reduced):
    peaks = harness.peaks_for("TPU v5 lite")
    run = types.SimpleNamespace(counters={"crop_shape": (16, 224, 224, 3)})
    share = crop_reader().read(run, reduced, peaks)
    kernel = trace.seconds(reduced.op_events(KERNEL))
    program = trace.seconds(reduced.module_events(
        r"^jit_crop_mirror_normalize\("))
    copies = trace.seconds(reduced.op_events(r"^%copy[.\d]* = u8"))
    # the program's time holds the kernel and the relayouts of its input
    assert program >= kernel + copies > kernel
    kernel_only = counts.roofline_share(
        3 * counts.crop_flops(16, 224, 224, 3),
        3 * counts.crop_bytes(16, 224, 224, 3), kernel,
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"])
    assert share == pytest.approx(kernel_only * kernel / program)
    assert 0 < share < kernel_only < 100


def test_crop_roofline_reads_nothing_where_there_is_nothing(reduced):
    peaks = harness.peaks_for("TPU v5 lite")
    reader = crop_reader()
    assert reader.read(types.SimpleNamespace(counters={}), reduced,
                       peaks) is None
    run = types.SimpleNamespace(counters={"crop_shape": (16, 224, 224, 3)})
    assert reader.read(run, None, peaks) is None
    no_crop = trace.Reduced(trace.Trace(
        window=(0, 10), ops={"/device:TPU:0": [(1, 2, "fusion.1")]},
        modules={"/device:TPU:0": [(1, 2, "jit_step(1)")]}, spans=[]))
    assert reader.read(run, no_crop, peaks) is None
