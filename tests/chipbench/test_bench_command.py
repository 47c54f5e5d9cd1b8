"""The benchmark's command without a chip or without the program, and the
files that ``BENCHMARK.json`` names."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from benchcells import ROOT, SEED
from chipbench import harness


def run_cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "img-high",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_without_a_tpu_fails_and_prints_no_result():
    out = run_cmd(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_run_without_the_program_fails_and_prints_no_result(tmp_path):
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = run_cmd(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_benchmark_names_its_files():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    here = os.path.join(ROOT, "chipbench")
    for cfg in bench["configs"]:
        c = harness.load_json(os.path.join(ROOT, cfg["file"]))
        assert c["name"] == cfg["name"]
        assert os.path.exists(os.path.join(here, "drivers",
                                           c["driver"] + ".py"))
        assert set(cfg["reduced"]) == set(c["reduced"])
    for w in bench["workloads"]:
        cell = harness.Cell.find(w["name"])
        assert cell.workload["config"] == w["config"]
        assert cell.end_to_end and cell.per_layer
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(here, "metrics",
                                           m["name"] + ".py"))
    json.dumps(bench)
