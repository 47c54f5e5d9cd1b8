"""The benchmark's arithmetic against hand counts: percentiles and windows,
operation and byte counts, the peaks table, and the trace reduction."""

from __future__ import annotations

import pytest

from chipbench import counts, harness, timing, trace


# -- percentiles and windows --------------------------------------------------

def test_percentile_nearest_rank():
    values = list(range(1, 101))          # 1 .. 100
    assert timing.percentile(values, 90) == 90
    assert timing.percentile(values, 100) == 100
    assert timing.percentile([5.0], 90) == 5.0
    # ten values: the 9th smallest is the p90, one value beyond it
    assert timing.percentile([3, 1, 4, 1, 5, 9, 2, 6, 5, 8], 90) == 8


@pytest.mark.parametrize("bad", [[], None])
def test_percentile_refuses_nothing(bad):
    with pytest.raises(ValueError):
        timing.percentile(bad or [], 90)


def test_rate_and_stamped_window():
    assert timing.rate(1024, 10.0, 12.0) == 512.0
    with pytest.raises(ValueError):
        timing.rate(1, 2.0, 2.0)
    # stamps after steps 1, 10, 20: the window holds steps 2 .. 20
    items, secs = timing.stamped_window([(1.0, 1), (9.1, 10), (18.1, 20)])
    assert items == 19 and secs == pytest.approx(17.1)
    with pytest.raises(ValueError):
        timing.stamped_window([(1.0, 1)])


# -- operations and bytes -------------------------------------------------------

def test_crop_bytes_hand_count():
    # one 224x224x3 uint8 window read, one 3x224x224 float32 output written
    assert counts.crop_bytes(1, 224, 224, 3) == 224 * 224 * 3 * 5 == 752_640
    assert counts.crop_bytes(512, 224, 224, 3) == 512 * 752_640
    assert counts.crop_flops(2, 224, 224, 3) == 2 * 2 * 224 * 224 * 3


def test_roofline_share_takes_the_larger_bound():
    # 1 GB at 1 GB/s is 1 s; 1 GFLOP at 1 TFLOP/s is 1 ms: memory bound
    assert counts.roofline_share(1e9, 1e9, 2.0, 1e12, 1e9) == pytest.approx(50.0)
    assert counts.roofline_share(4e12, 0, 8.0, 1e12, 1e9) == pytest.approx(50.0)
    with pytest.raises(ValueError):
        counts.roofline_share(1, 1, 0.0, 1, 1)


def test_dense_lm_flops_hand_count():
    # stablelm-2-1.6b as the program runs it: 24 layers, d 2048, 32 heads
    # of 64 (kv 32), d_ff 5632, vocab 100352, 2048 tokens a row
    attn = 4 * 2048 * 2048
    mlp = 3 * 2048 * 5632
    assert counts.dense_lm_params(24, 2048, 32, 32, 64, 5632) == \
        24 * (attn + mlp) == 1_233_125_376
    per_token = counts.dense_lm_train_flops_per_token(
        24, 2048, 32, 32, 64, 5632, 100352, 2048)
    assert per_token == 6 * (1_233_125_376 + 2048 * 100352) + \
        12 * 24 * 2048 * 2048 == 9_839_837_184
    # grouped kv heads shrink only k and v
    assert counts.dense_lm_params(1, 8, 4, 2, 2, 16) == 8 * 2 * (8 + 4) + 3 * 8 * 16


# -- peaks ----------------------------------------------------------------------

def test_peaks_lookup():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_peaks_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")


# -- trace reduction ---------------------------------------------------------------

def test_union_and_gaps_by_hand():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (29, 31, "d")]
    assert trace.union(ivs) == [(0, 15), (20, 31)]
    assert trace.union_ns(ivs) == 26
    assert trace.gaps(ivs, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert trace.gaps(ivs, 2, 25) == [(15, 20)]
    assert trace.clip(ivs, 12, 22) == [(12, 15, "b"), (20, 22, "c")]


def test_gap_label_takes_the_innermost_span_that_covers_most():
    spans = [(0, 100, "bench.run_training"), (40, 60, "bench.next_batch"),
             (90, 95, "bench.check")]
    assert trace.label((42, 58), spans) == "bench.next_batch"
    assert trace.label((10, 30), spans) == "bench.run_training"
    assert trace.label((200, 300), spans) == "no host span"


def small_trace() -> trace.Trace:
    return trace.Trace(
        window=(100, 1100),
        ops={"/device:TPU:0": [(50, 150, "fusion.1"),      # half inside
                               (200, 300, "crop.kernel"),
                               (250, 350, "fusion.2"),     # overlaps
                               (700, 800, "crop.kernel")],
             "/device:TPU:1": [(200, 400, "fusion.1")]},
        modules={"/device:TPU:0": [(200, 350, "jit_step(1)"),
                                   (700, 800, "jit_step(1)")]},
        spans=[(100, 600, "bench.next_batch"), (600, 1100, "bench.check")])


def test_reduced_busy_idle_kernel_and_breakdown():
    red = trace.Reduced(small_trace())
    assert red.window_s == pytest.approx(1000e-9)
    # chip 0: [100,150] + [200,350] + [700,800] = 300; chip 1: 200
    assert red.busy_s == pytest.approx(250e-9)
    assert red.idle_share == pytest.approx(0.75)
    kernel = red.op_events(r"^crop\.kernel$")
    assert len(kernel) == 2 and trace.seconds(kernel) == pytest.approx(200e-9)
    assert trace.seconds(red.module_events("jit_step")) == pytest.approx(250e-9)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    # chip 0's gaps: [350, 700] mostly in next_batch, [800, 1100] in check,
    # [150, 200] in next_batch
    assert [g[0] for g in bd["idle_gaps"]] == ["bench.next_batch", "bench.check",
                                               "bench.next_batch"]
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx([350e-9, 300e-9,
                                                            50e-9])
