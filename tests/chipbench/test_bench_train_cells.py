"""Whole runs of a small training cell on the CPU, past the harness's look
for a chip: a sound run is correct, and each fault the cell can have,
planted in the timed path, and the control, put in the program's place,
make it not."""

from __future__ import annotations

import uuid

import jax
import pytest

from benchcells import SEED, checks, drive, small_run
from chipbench import harness


def test_train_cell_sound_run_is_correct(train_cell):
    run = drive(train_cell)
    assert run.correct, checks(run)
    assert set(checks(run)) == {"loss_rel_gap", "grad_norm_gap",
                                "update_norm_gap"}
    assert run.attempted >= 9 and run.e2e["tokens_per_s"] > 0
    assert len(run.counters["wait_s"]) == run.attempted


def broken_step(monkeypatch, how):
    """Plant a fault in the step of every ``run_training`` call."""
    from repro.train import loop

    real = loop.make_train_step

    def make(model, opt_cfg, **kw):
        step = real(model, opt_cfg, **kw)

        def broken(state, batch):
            if how == "half batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half)
            _, metrics = step(state, batch)
            return state, metrics          # the state left unchanged

        return broken

    monkeypatch.setattr(loop, "make_train_step", make)


class CallFaults:
    """Stands for ``jax`` in the training loop, so that the step it jits
    goes wrong from the second step of each call on: the update is dropped,
    or the step starts again from the state the call began with."""

    def __init__(self, how: str) -> None:
        self.how = how

    def __getattr__(self, name):
        return getattr(jax, name)

    def jit(self, fn, **kw):
        step = jax.jit(fn)               # no donation: the fault keeps states
        seen = {"n": 0, "first": None}

        def call(state, batch):
            seen["n"] += 1
            if seen["n"] == 1:
                seen["first"] = state
                return step(state, batch)
            if self.how == "update dropped from a call's second step":
                return state, step(state, batch)[1]
            return step(seen["first"], batch)

        return call


CALL_FAULTS = ["update dropped from a call's second step",
               "stale state from a call's second step"]


@pytest.mark.parametrize("how", ["state unchanged", "half batch"] + CALL_FAULTS)
def test_train_cell_broken_step_is_caught(train_cell, monkeypatch, how):
    if how in CALL_FAULTS:
        from repro.train import loop

        monkeypatch.setattr(loop, "jax", CallFaults(how))
    else:
        broken_step(monkeypatch, how)
    run = drive(train_cell)
    assert not run.correct, checks(run)
    if how in CALL_FAULTS:
        # only the unlogged third step goes wrong: its change catches it
        assert checks(run)["update_norm_gap"] > \
            train_cell.config["limits"]["update_norm_gap"]


def test_train_cell_control_in_float8_is_not_correct(train_cell):
    from chipbench.drivers import train_loop as drv
    from chipbench.tools import control

    cell = train_cell
    cell.config["torch_dtype"] = "bfloat16"
    c = cell.config
    s, adam = drv.shape_of(c), drv.adam_of(c)
    S = c["train"]["seq_len"]

    def substitute(kind, prog, batches):
        return drv.as_reported(drv.reference_run(
            SEED, s, adam, batches, chunk=min(512, S), cast=control.fp8,
            dtype=c["torch_dtype"]))

    run = small_run(cell)
    run.substitute = substitute
    harness.driver_for(cell).run(run)
    assert not run.correct, checks(run)


# -- which rows each checked step got ---------------------------------------------

def keys(n):
    return [uuid.UUID(int=i + 1) for i in range(n)]


def test_steps_rows_from_the_order_of_reads():
    from chipbench.drivers import train_loop as drv

    k = keys(4)
    reads = [k[2], k[0], k[3], k[1], k[0], k[2]]   # then the next epoch's
    assert drv.steps_rows(reads, k, 2, 2) == [[k[2], k[0]], [k[3], k[1]]]
    assert drv.steps_rows(k[:2], k[:2], 1, 2) == [k[:2]]


@pytest.mark.parametrize("reads", [
    "repeated",     # a key read twice before the call's others
    "short",        # fewer reads than the call's rows
    "foreign",      # a key that is not the call's
])
def test_steps_rows_refuses_reads_it_cannot_place(reads):
    from chipbench.drivers import train_loop as drv

    k = keys(5)
    got = {"repeated": [k[0], k[1], k[0], k[2]],
           "short": [k[0], k[1], k[2]],
           "foreign": [k[0], k[1], k[2], k[4]]}[reads]
    assert drv.steps_rows(got, k[:4], 2, 2) is None


def test_logged_steps_are_the_first_of_each_call():
    from chipbench.drivers import train_loop as drv

    assert drv.CHECKED_CALLS == (1, 2) and drv.CHECKED_STEPS == 3
    assert drv.logged_steps() == [0, 1]
    ref = {"losses": [3.0, 2.0, 1.0], "grad_norms": {}, "change_norms": {}}
    assert drv.as_reported(ref)["losses"] == [3.0, 2.0]
