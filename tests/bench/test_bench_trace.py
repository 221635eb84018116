"""The reduction from a device trace to busy time, kernel time and idle
gaps attributed to the benchmark's host spans."""
import json
import os

import pytest

from bench import trace as bt
from bench.common import load_module

DATA = os.path.join(os.path.dirname(__file__), "data")
KERNELS = os.path.join(os.path.dirname(__file__), "..", "..", "bench", "kernels")


def test_reduce_by_hand():
    trace = {
        "devices": {"/device:TPU:0": [
            ["a", 100.0, 50.0, "jit_decode_step"],
            ["b", 120.0, 60.0, "jit_decode_step"],       # overlaps a: busy once
            ["c", 300.0, 100.0, "jit_fused_encode"],
            ["d", 600.0, 10.0, "jit_other"],             # after the window
        ]},
        "host": [
            ["bench.window", 0.0, 500.0],
            ["bench.decode_step", 0.0, 200.0],
            ["bench.ckpt", 250.0, 40.0],
            ["bench.rollback", 380.0, 100.0],
            ["bench.tool", 390.0, 20.0],                 # innermost inside rollback
        ],
    }
    red = bt.reduce(trace)
    assert red["window_s"] == pytest.approx(500e-9)
    assert red["busy_s"] == pytest.approx(180e-9)
    assert red["op_s"]["b"] == pytest.approx(60e-9)
    assert "d" not in red["op_s"]
    gaps = red["idle_gaps"]
    assert gaps["decode_step"] == pytest.approx(100e-9)              # 0..100
    assert gaps["host: outside benchmark spans"] == pytest.approx(120e-9)   # 180..300
    assert gaps["rollback"] == pytest.approx(100e-9)                 # 400..500
    assert bt.kernel_seconds(red, lambda op, mod: "fused_encode" in mod) == pytest.approx(100e-9)
    assert bt.top({"x": 1.0, "y": 3.0}, 1) == [["y", 3.0]]


def test_module_of_an_op_from_the_program_runs():
    """An operation event without an ``hlo_module`` stat takes the name of
    the program run that holds it on the device's ``XLA Modules`` line."""
    from types import SimpleNamespace as E

    line = E(events=[E(name="jit_decode_step(7)", start_ns=100, duration_ns=50),
                     E(name="jit_fused_encode(9)", start_ns=10, duration_ns=20)])
    mods = bt._modules(line)
    assert bt._module_at(mods, 15.0) == "jit_fused_encode(9)"
    assert bt._module_at(mods, 150.0) == "jit_decode_step(7)"
    assert bt._module_at(mods, 60.0) == ""
    assert bt._module_at(bt._modules(None), 15.0) == ""


def test_no_window_no_reading():
    assert bt.reduce({"devices": {"/device:TPU:0": [["a", 0.0, 1.0, ""]]}, "host": []}) is None


@pytest.mark.parametrize("name", sorted(f for f in os.listdir(DATA) if f.endswith(".json"))
                         if os.path.isdir(DATA) else [])
def test_recorded_chip_trace(name):
    """A slice of a trace recorded on a TPU v5e by ``bench/run.py --trace 1``."""
    trace = json.load(open(os.path.join(DATA, name)))
    red = bt.reduce(trace)
    assert red is not None
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = red["window_s"] - red["busy_s"]
    assert sum(red["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
    assert sum(red["op_s"].values()) >= red["busy_s"] * (1 - 1e-9)
    # the v5e names no module on an operation: the program run holding it does
    attention = load_module(os.path.join(KERNELS, "paged_attention.py"))
    assert bt.kernel_seconds(red, attention.match) > 0
