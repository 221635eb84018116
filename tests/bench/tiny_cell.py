"""A tiny cell added to a copy of the benchmark as files plus manifest
entries alone, and driven through the harness on the CPU."""
import json
import os
import shutil
import time
import types

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

TINY_MODEL = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
TINY_TRAFFIC = {
    "mcts": dict(prompt_tokens=40, max_depth=3, tokens_per_action=4, pool_pages=256,
                 pages_per_session=16, check_nodes=4, check_images=3),
    "fanout": dict(prompts=2, prompt_tokens=32, rollouts_per_prompt=3, turns=2,
                   tokens_per_turn=4, pool_pages=256, pages_per_session=8,
                   check_sequences=2, check_images=3),
}


def tiny_config(config_file: str) -> dict:
    """The configuration in ``config_file`` (relative to the checkout) at
    the tiny sizes of ``TINY_MODEL``."""
    cfg = json.load(open(os.path.join(ROOT, config_file)))
    cfg.update(name="tiny", source="test", **TINY_MODEL)
    if cfg.get("rope_scaling"):                        # M-RoPE bands over the tiny head
        cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 3, 3])
    return cfg


def add_tiny_cell(dst: str, source_cell: str, *, family=None, **traffic_over) -> str:
    """Copy BENCHMARK.json and bench/ into ``dst`` and add a cell named
    ``tiny.<mix>`` whose configuration, mix and limits are new files, and
    whose metrics are those of ``source_cell``.  ``family``, a pair (name,
    source), also adds ``bench/models/<name>.py`` and has the configuration
    name it.  Returns the cell name."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    src = next(w for w in manifest["workloads"] if w["name"] == source_cell)
    src_cfg = next(c for c in manifest["configs"] if c["name"] == src["config"])
    cfg = tiny_config(src_cfg["file"])
    if family is not None:
        fam_name, fam_source = family
        with open(os.path.join(dst, "bench", "models", fam_name + ".py"), "w") as f:
            f.write(fam_source)
        cfg["model"] = fam_name
    json.dump(cfg, open(os.path.join(dst, "bench", "configs", "tiny.json"), "w"))
    traffic = json.load(open(os.path.join(ROOT, "bench", "traffic", src["traffic"] + ".json")))
    traffic.update(TINY_TRAFFIC[traffic["driver"]], **traffic_over)
    mix = "tiny-" + src["traffic"]
    json.dump(traffic, open(os.path.join(dst, "bench", "traffic", mix + ".json"), "w"))
    name = "tiny." + src["traffic"]
    limits = json.load(open(os.path.join(ROOT, "bench", "limits", source_cell + ".json")))
    limits["checks"]["greedy_tokens"]["min"] = 1
    json.dump(limits, open(os.path.join(dst, "bench", "limits", name + ".json"), "w"))
    manifest["configs"].append({"name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
                                "reduced": [], "why": "tiny"})
    manifest["workloads"].append({"name": name, "config": "tiny", "traffic": mix, "chips": 1,
                                  "why": "tiny"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if source_cell in m.get("workloads", []):
            m["workloads"].append(name)
    json.dump(manifest, open(os.path.join(dst, "BENCHMARK.json"), "w"))
    peaks = json.load(open(os.path.join(dst, "bench", "peaks.json")))
    peaks["cpu"] = dict(peaks["TPU v5 lite"])         # CPU readings stand in for the test only
    json.dump(peaks, open(os.path.join(dst, "bench", "peaks.json"), "w"))
    return name


def run_tiny(dst: str, name: str, capsys, monkeypatch, *, seconds=1.5, trace=0, seed=2**33 + 7,
             control=False):
    """Drive the cell through ``bench.run.run_cell`` on the CPU: the look for
    a chip is skipped, everything after it runs.  Returns the result line."""
    import jax

    from bench import run as brun
    from repro.launch import cache

    monkeypatch.setattr(cache, "enable_compile_cache", lambda: "off in tests")
    manifest, cell, config, traffic = brun.cell_spec(dst, name)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    capsys.readouterr()
    rc = brun.run_cell(dst, manifest, cell, config, traffic, args, jax.devices()[:1], time.time(),
                       control=control)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
