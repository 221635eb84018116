"""Model families: a configuration names its family, and the harness takes
every model-specific piece from that family's module.

* The dense family's weights and reference gaps, plain and float8 control,
  are pinned bit for bit at tiny sizes: an edit that moves the yardstick
  fails here.
* A family added as a file under ``bench/models/`` plus a configuration
  naming it runs through the whole harness; a planted wrong reference in it
  makes the run not correct, so the configuration's reference is the one
  used.
* A configuration naming no family, or a missing one, is refused by name.
"""
import hashlib
import os

import numpy as np
import pytest

from bench.model import family
from tiny_cell import ROOT, add_tiny_cell, run_tiny, tiny_config

CONFIGS = {"olmo-1b": "bench/configs/olmo-1b.json", "qwen2-vl-2b": "bench/configs/qwen2-vl-2b.json"}

# blake2b-128 of every leaf (path, dtype, shape, bytes) of make_weights at
# the tiny sizes; recorded from bench/model.py before it became the loader
WEIGHTS = {
    ("olmo-1b", 7): "018173f5f99795b409be1f47605784f4",
    ("olmo-1b", 2**33 + 7): "783e6646ae496a605b25883e975d0c81",
    ("qwen2-vl-2b", 7): "5851a26da360da65ea033ff00d6a3a94",
    ("qwen2-vl-2b", 2**33 + 7): "988f0a7b3877022017ace153d409ff95",
}
# blake2b-128 of Reference.gaps (float32) over weights of seed 7, a fixed
# 39-token prompt and 128 tokens after it, and the widest gap
GAPS = {
    ("olmo-1b", False): ("122ea58c79ab9ed0d27b9f2aac40a05d", 5.871158599853516),
    ("olmo-1b", True): ("3177da15d49bd07e44cdb1a48c4432c4", 0.38638949394226074),
    ("qwen2-vl-2b", False): ("e5f0ff78ea6c7a603f8d72cce9feea33", 5.227522850036621),
    ("qwen2-vl-2b", True): ("368a20c6702c21523a5a9165f0ad686d", 0.2629852294921875),
}


def _dense(config_name):
    cfg = tiny_config(CONFIGS[config_name])
    fam = family(cfg, ROOT)
    return fam, fam.dims(cfg)


def _weights_digest(weights) -> str:
    import jax

    h = hashlib.blake2b(digest_size=16)
    for path, leaf in jax.tree_util.tree_flatten_with_path(weights)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("config_name,seed", sorted(WEIGHTS))
def test_dense_weights_are_pinned(config_name, seed):
    fam, m = _dense(config_name)
    assert _weights_digest(fam.make_weights(m, seed)) == WEIGHTS[(config_name, seed)]


@pytest.mark.parametrize("config_name,control", sorted(GAPS))
def test_dense_reference_gaps_are_pinned(config_name, control):
    fam, m = _dense(config_name)
    weights = fam.make_weights(m, 7)
    g = np.random.default_rng(0)
    prompt = g.integers(0, m.vocab, size=39).astype(np.int32)
    rest = g.integers(0, m.vocab, size=128).astype(np.int32)
    check = np.ones(128, bool)
    check[100:] = False
    gaps = fam.Reference(m, control=control).gaps(weights, prompt, rest, check)
    assert gaps.dtype == np.float32 and gaps.shape == (127,)
    digest = hashlib.blake2b(gaps.tobytes(), digest_size=16).hexdigest()
    assert (digest, float(gaps.max())) == GAPS[(config_name, control)]


def test_dense_counts_every_layer_as_attention():
    fam, m = _dense("olmo-1b")
    assert fam.attention_layers(m) == m.layers == 2


def _dense_source() -> str:
    return open(os.path.join(ROOT, "bench", "models", "dense.py")).read()


# a reference whose logits are planted wrong: token 0 always leads by 100
PLANTED = '''

class _Planted(Reference):
    def __init__(self, m, *, control=False):
        super().__init__(m, control=control)
        suffix = self.suffix
        self.suffix = lambda w, kv, toks: suffix(w, kv, toks).at[:, 0].add(100.0)


Reference = _Planted
'''


def test_family_from_files_alone_runs_correct(tmp_path, capsys, monkeypatch):
    name = add_tiny_cell(str(tmp_path), "olmo-1b.mcts-swe", family=("dense_copy", _dense_source()))
    assert not os.path.exists(os.path.join(ROOT, "bench", "models", "dense_copy.py"))
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["greedy_tokens"]["value"] > 0


def test_family_reference_is_the_one_used(tmp_path, capsys, monkeypatch):
    name = add_tiny_cell(str(tmp_path), "olmo-1b.mcts-swe",
                         family=("dense_planted", _dense_source() + PLANTED))
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch)
    assert res["correct"] is False
    failed = [k for k, c in res["checks"].items()
              if not ((c["value"] <= c["limit"]) if c["is"] == "max" else (c["value"] >= c["limit"]))]
    assert failed == ["logit_gap"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] > 50


@pytest.mark.parametrize("model,error,words", [
    (None, KeyError, ["'tiny'", "model"]),
    ("no_such_family", FileNotFoundError, ["'tiny'", "bench/models/no_such_family.py"]),
])
def test_family_refused_by_name(model, error, words):
    cfg = tiny_config(CONFIGS["olmo-1b"])
    if model is None:
        del cfg["model"]
    else:
        cfg["model"] = model
    with pytest.raises(error) as exc:
        family(cfg, ROOT)
    assert all(w in str(exc.value) for w in words), str(exc.value)
