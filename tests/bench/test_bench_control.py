"""The control of the logit-gap comparison fails it: the float32 reference
computed with float8 weights, put in the program's place, reads a widest
gap several times the program's on the same prompts and tokens, and a run
judged on it comes out not correct.

A tiny cell on the CPU, with a limit of its own set between the two
readings (``TINY_LIMIT``); the readings at the cell's own size, which set
the cells' limits, are taken on the chip by ``bench/control.py``."""
import json
import os
import time

import numpy as np
import pytest

from tiny_cell import add_tiny_cell, run_tiny


TINY_LIMIT = 0.04      # tiny readings (CPU, seed 99): program 0.016 / 0.009, control 0.087 / 0.19

LONGER = {
    "olmo-1b.mcts-swe": dict(max_depth=6, tokens_per_action=8, pages_per_session=32, check_nodes=8),
    "qwen2-vl-2b.mcts-swe-8k": dict(max_depth=6, tokens_per_action=8, pages_per_session=32, check_nodes=8),
    "olmo-1b.rl-fanout": dict(turns=4, tokens_per_turn=16, pages_per_session=16, check_sequences=4),
}


@pytest.mark.parametrize("source", sorted(LONGER))
def test_control_fails_program_passes(source, tmp_path, monkeypatch):
    import jax

    from bench import run as brun
    from repro.launch import cache

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: "off in tests")
    name = add_tiny_cell(str(tmp_path), source, **LONGER[source])
    manifest, cell, config, traffic = brun.cell_spec(str(tmp_path), name)
    _, counter = brun.prepare(str(tmp_path))
    run = brun.measure(str(tmp_path), manifest, cell, config, traffic, 99, 4.0, 0,
                       jax.devices()[:1], counter, time.time())
    # the same sequences whatever the window reached: the first ones served
    if hasattr(run, "node_tokens"):
        from bench.drivers.mcts import greedy_mask

        T = run.traffic
        seqs = [run.node_tokens[c] for c in sorted(run.node_tokens)][:8]
        run.served = [(np.asarray(t, np.int32),
                       greedy_mask(len(t), T["prompt_tokens"], T["tokens_per_action"])) for t in seqs]
    else:
        seqs = run.served_pool[:2]
        run.served = [(np.asarray(t, np.int32), np.arange(len(t)) >= run.traffic["prompt_tokens"])
                      for t in seqs]
    assert len(run.served) >= 2
    program, n = brun.reference_check(run)
    control, _ = brun.reference_check(run, control=True)
    assert n > 0
    print(f"tiny readings: program {program} control {control}")
    assert program < TINY_LIMIT < control
    assert control >= 3 * program


def _tiny_limit(dst: str, name: str) -> None:
    path = os.path.join(dst, "bench", "limits", name + ".json")
    limits = json.load(open(path))
    limits["checks"]["logit_gap"]["max"] = TINY_LIMIT
    json.dump(limits, open(path, "w"))


@pytest.mark.parametrize("source", sorted(LONGER))
def test_control_in_program_place_is_not_correct(source, tmp_path, capsys, monkeypatch):
    """``run_cell`` with the control in the program's place: the run's own
    limit comparison reads ``correct`` false, on the logit gap alone."""
    name = add_tiny_cell(str(tmp_path), source, **LONGER[source])
    _tiny_limit(str(tmp_path), name)
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch, seed=99, control=True)
    assert res["correct"] is False
    failed = [k for k, c in res["checks"].items()
              if not ((c["value"] <= c["limit"]) if c["is"] == "max" else (c["value"] >= c["limit"]))]
    assert failed == ["logit_gap"], res["checks"]
