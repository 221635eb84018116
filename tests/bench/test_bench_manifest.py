"""BENCHMARK.json resolves by name: every cell, configuration, traffic mix,
limit file, metric reader and kernel count exists where the harness looks,
and the manifest keeps to the benchmark's shape rules."""
import json
import os
import re

import pytest

from bench.common import load_json, load_module

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
MANIFEST = load_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.isfile(os.path.join(ROOT, MANIFEST["command"][1]))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    from bench import run as brun

    manifest, spec, config, traffic = brun.cell_spec(ROOT, cell)
    assert spec["chips"] in (1, 4) and len(spec["why"]) <= 200
    assert os.path.isfile(os.path.join(ROOT, "bench", "drivers", traffic["driver"] + ".py"))
    limits = load_json(os.path.join(ROOT, "bench", "limits", cell + ".json"))
    assert limits["checks"]
    e2e = brun.metrics_of(manifest, cell, trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert brun.metrics_of(manifest, cell, trace=True)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_reader(metric):
    mod = load_module(os.path.join(ROOT, "bench", "metrics", metric + ".py"))
    assert callable(mod.read)


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_fields(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if metric in MANIFEST["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        moved = next(m for m in MANIFEST["end_to_end"] if m["name"] == metric["moves"])
        for cell in metric["workloads"]:
            assert "workloads" not in moved or cell in moved["workloads"]
    if "roofline" in metric["name"]:
        kernel = metric["name"].split("_roofline")[0]
        mod = load_module(os.path.join(ROOT, "bench", "kernels", kernel + ".py"))
        assert callable(mod.cost) and callable(mod.match)


def test_layers_are_named_alike():
    layers = {}
    for m in MANIFEST["per_layer"]:
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=[c["name"] for c in MANIFEST["configs"]])
def test_config_file(config):
    data = load_json(os.path.join(ROOT, config["file"]))
    assert data["name"] == config["name"] and data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    assert os.path.isfile(os.path.join(ROOT, "bench", "models", data["model"] + ".py"))
    assert any(w["config"] == config["name"] for w in MANIFEST["workloads"])


def test_every_cell_has_a_device_metric():
    for cell in CELLS:
        per_layer = [m for m in MANIFEST["per_layer"] if cell in m.get("workloads", [cell])]
        assert any("mfu" in m["name"] for m in per_layer)
        assert len(json.dumps(MANIFEST)) < 64 * 1024
