"""bench/run.py: refuses to run off the TPU, and a cell added as files plus
manifest entries alone runs through the whole harness and checks correct."""
import os
import subprocess
import sys

import pytest

from tiny_cell import ROOT, add_tiny_cell, run_tiny


def test_refuses_off_tpu(capsys, monkeypatch):
    from bench import run as brun

    # main() points these at the checkout; give them back after the test
    for var in ("JAX_COMPILATION_CACHE_DIR", "TPU_LOG_DIR"):
        monkeypatch.setenv(var, "unused")

    rc = brun.main(["--workload", "olmo-1b.mcts-swe", "--seed", str(2**33), "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.mcts-swe", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and p.stdout == ""


@pytest.mark.parametrize("source", ["olmo-1b.mcts-swe", "qwen2-vl-2b.mcts-swe-8k", "olmo-1b.rl-fanout"])
def test_added_cell_runs_and_is_correct(source, tmp_path, capsys, monkeypatch):
    name = add_tiny_cell(str(tmp_path), source)
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
