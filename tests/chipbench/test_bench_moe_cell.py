"""The MLA + routed-expert training cell on the CPU, at a small size: a
sound run is correct, a broken step and both controls are caught, the
FLOP and byte counts follow hand arithmetic, and the Zipf traffic is
seeded, in range and read back by the program's decoder."""

from __future__ import annotations

import copy
import math

import numpy as np
import pytest

from benchcells import SEED, checks, drive, small_run
from chipbench import harness


def small_moe_cell() -> harness.Cell:
    cell = copy.deepcopy(harness.Cell.find("moonlight-8k-high"))
    cell.config.update(
        num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, n_routed_experts_here=4, num_experts_per_tok=2,
        vocab_size=512, torch_dtype="float32")
    cell.config["train"].update(seq_len=64, remat=False)
    cell.config["records"]["n_records"] = 48
    cell.workload.update(route="local")
    return cell


@pytest.fixture(scope="module")
def sound_run():
    return drive(small_moe_cell())


def test_moe_cell_sound_run_is_correct(sound_run):
    run = sound_run
    assert run.correct, checks(run)
    assert set(checks(run)) == {"loss_rel_gap", "grad_norm_gap",
                                "update_norm_gap"}
    assert run.attempted >= 2 and run.e2e["tokens_per_s"] > 0
    assert len(run.counters["wait_s"]) == run.attempted


def test_moe_cell_counts_the_held_slots(sound_run):
    """Two expert layers of 4 of 8 experts, 2 a token: a step routes ~half
    of its 2 x 64 x 2 slots a layer here, at most all of them."""
    run = sound_run
    layers, n_here, d, f = run.counters["expert_shape"]
    assert (layers, n_here, d, f) == (2, 4, 64, 32)
    assert 0 < run.counters["slots_here"] <= layers * 2 * 64 * 2
    assert run.counters["flops_per_step"] > 0


def test_moe_cell_broken_step_is_caught(monkeypatch):
    from repro.train import loop

    real = loop.make_train_step

    def make(model, opt_cfg, **kw):
        step = real(model, opt_cfg, **kw)

        def unchanged(state, batch):
            return state, step(state, batch)[1]

        return unchanged

    monkeypatch.setattr(loop, "make_train_step", make)
    run = drive(small_moe_cell())
    assert not run.correct, checks(run)


@pytest.mark.parametrize("control", ["capacity 1.25", "float8 operands"])
def test_moe_cell_controls_are_not_correct(control):
    from chipbench.drivers import moe_train_loop as drv
    from chipbench.drivers import train_loop as base
    from chipbench.tools.control import fp8

    cell = small_moe_cell()
    c = cell.config
    c["torch_dtype"] = "bfloat16"
    S = c["train"]["seq_len"]
    shape, cast = drv.shape_of(c), drv.identity
    if control == "capacity 1.25":
        shape = drv.shape_of(c, capacity_factor=1.25)
    else:
        cast = fp8

    def substitute(kind, prog, batches):
        return base.as_reported(drv.reference_run(
            SEED, shape, base.adam_of(c), batches, chunk=min(512, S),
            cast=cast, dtype=c["torch_dtype"]))

    run = small_run(cell)
    run.substitute = substitute
    harness.driver_for(cell).run(run)
    assert not run.correct, checks(run)


# -- counts ------------------------------------------------------------------

def test_counts_moe_by_hand():
    from chipbench import counts_moe as cm

    # Moonlight's widths: MLA 6,291,456 + 1,179,648 + 2,097,152 + 4,194,304
    assert cm.mla_params(2048, 16, 512, 128, 64, 128) == 13_762_560
    expert = 3 * 2048 * 1408                          # 8,650,752
    moe_layer = 2048 * 64 + 2 * expert + 6 * 8 / 64 * expert
    want = (5 * 13_762_560 + 3 * 2048 * 11264 + 4 * moe_layer
            + 2048 * 20480)
    got = cm.active_params_per_token(5, 1, 2048, 16, 512, 128, 64, 128,
                                     11264, 1408, 64, 8, 6, 2, 20480)
    assert got == pytest.approx(want)
    flops = cm.train_flops_per_token(5, 1, 2048, 16, 512, 128, 64, 128,
                                     11264, 1408, 64, 8, 6, 2, 20480, 8192)
    assert flops == pytest.approx(6 * want + 6 * 5 * 8192 * 16 * 320)
    assert cm.expert_gmm_flops(10, 4, 3) == 18 * 10 * 4 * 3
    assert cm.expert_gmm_bytes(10, 2, 5, 4, 3) == \
        9 * 2 * 5 * 4 * 3 * 2 + 9 * 10 * 7 * 2


def test_flops_per_token_reads_the_config():
    from chipbench import counts_moe as cm
    from chipbench.drivers import moe_train_loop as drv

    c = harness.Cell.find("moonlight-8k-high").config
    assert drv.flops_per_token(c) == cm.train_flops_per_token(
        5, 1, 2048, 16, 512, 128, 64, 128, 11264, 1408, 64, 8, 6, 2, 20480,
        8192)
    # about 2.9 GFLOP a token: 6 x 276 M active parameters + attention
    assert 2.7e9 < drv.flops_per_token(c) < 3.1e9


# -- traffic -----------------------------------------------------------------

def test_zipf_tokens_are_seeded_in_range_and_decode():
    from chipbench.traffic import tokens, zipf_tokens
    from repro.data.datasets import decode_token_record

    a = zipf_tokens.generate(SEED, 16, 256, 512)
    b = zipf_tokens.generate(SEED, 16, 256, 512)
    c = zipf_tokens.generate(SEED + 1, 16, 256, 512)
    assert a.keys == b.keys and np.array_equal(a.tokens, b.tokens)
    assert not np.array_equal(a.tokens, c.tokens)
    assert a.tokens.dtype == np.int32 and a.tokens.shape == (16, 256)
    assert a.tokens.min() >= 0 and a.tokens.max() < 512
    toks, label = decode_token_record(tokens.encode(a.tokens[3],
                                                    int(a.labels[3])))
    assert np.array_equal(toks, a.tokens[3]) and label == a.labels[3]


def test_zipf_tokens_follow_the_rank_law():
    """The most frequent id takes about 1 / H(V) of the tokens and the
    second about half of that."""
    from chipbench.traffic import zipf_tokens

    V = 512
    t = zipf_tokens.generate(SEED, 64, 1024, V).tokens.ravel()
    freq = np.sort(np.bincount(t, minlength=V))[::-1] / t.size
    h = sum(1.0 / r for r in range(1, V + 1))
    assert freq[0] == pytest.approx(1.0 / h, rel=0.05)
    assert freq[1] == pytest.approx(0.5 / h, rel=0.1)
    assert math.isclose(freq.sum(), 1.0)
