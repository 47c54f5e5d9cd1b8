"""Whole runs of a small image cell on the CPU, past the harness's look for
a chip: a sound run is correct, and each fault the cell can have, planted
in the timed path, and the control, put in the program's place, make it
not."""

from __future__ import annotations

import ml_dtypes
import numpy as np

from benchcells import checks, drive, small_run
from chipbench import harness
from chipbench.ref import crop


def test_image_cell_sound_run_is_correct(image_cell):
    run = drive(image_cell)
    assert run.correct, checks(run)
    assert set(checks(run)) == {"unknown_keys", "epoch_repeats",
                                "label_mismatches", "pixel_max_abs_diff"}
    assert run.attempted > 0 and run.e2e["images_per_s"] > 0
    assert run.e2e["next_batch_p90_ms"] > 0
    assert len(run.counters["wait_s"]) == run.counters["batches"]


def test_image_cell_pixel_altered_by_the_kernel_is_caught(image_cell,
                                                         monkeypatch):
    from repro.kernels import ops

    real = ops.crop_mirror_normalize

    def altered(*args, **kw):
        return real(*args, **kw).at[0, 0, 0, 0].add(1e-3)

    monkeypatch.setattr(ops, "crop_mirror_normalize", altered)
    run = drive(image_cell)
    assert not run.correct
    assert checks(run)["pixel_max_abs_diff"] > 0


def test_image_cell_label_altered_by_the_loader_is_caught(image_cell,
                                                         monkeypatch):
    from repro.core.batch_loader import AssembledBatch

    real = AssembledBatch.labels.fget
    monkeypatch.setattr(AssembledBatch, "labels",
                        property(lambda self: real(self) + 1))
    run = drive(image_cell)
    assert not run.correct
    assert checks(run)["label_mismatches"] > 0


def test_image_cell_repeated_key_is_caught(image_cell, monkeypatch):
    from repro.core.batch_loader import AssembledBatch

    real = AssembledBatch.uuids.fget

    def repeated(self):
        keys = real(self)
        return keys[:1] * 2 + keys[2:]

    monkeypatch.setattr(AssembledBatch, "uuids", property(repeated))
    run = drive(image_cell)
    assert not run.correct
    assert checks(run)["epoch_repeats"] > 0


def test_image_cell_control_in_bfloat16_is_not_correct(image_cell):
    c = image_cell.config["crop"]

    def control(kind, batch, pixels, draws):
        out = crop.crop_mirror_normalize(pixels, *draws, c["mean"], c["std"],
                                         c["out_h"], c["out_w"],
                                         dtype=ml_dtypes.bfloat16)
        return out.astype(np.float32)

    run = small_run(image_cell)
    run.substitute = control
    harness.driver_for(image_cell).run(run)
    assert not run.correct
    assert checks(run)["pixel_max_abs_diff"] > 0
