"""With the timed path broken underneath, a run reports ``correct`` false.

Each fault is planted in the program for one tiny run (the harness's look
for a chip skipped, everything else as on the chip): a served token altered
where it is produced; a rollback that leaves the session as it was; a dump
image that decodes to other bytes; half of a decode batch left out."""
import numpy as np
import pytest

from tiny_cell import add_tiny_cell, run_tiny


def _altered_sample(orig):
    def sample(self, sess, logits):
        tok = orig(self, sess, logits)
        return (tok + 1) % len(logits)
    return sample


def _no_rollback(orig):
    def restore(self, ckpt_id):
        return "fast"
    return restore


def _corrupt_decode(orig):
    def decode(self, image, parent_image):
        payload = orig(self, image, parent_image)
        for k in sorted(payload):
            if k.startswith("kv/"):
                a = np.array(payload[k], copy=True)
                flat = a.reshape(-1).view(np.uint8)
                flat[0] ^= 0x40
                payload[k] = a
                break
        return payload
    return decode


def _half_batch(orig):
    def step(self, sessions):
        half = max(1, len(sessions) // 2)
        out = orig(self, list(sessions[:half]))
        for i, s in enumerate(sessions[half:]):
            s.tokens.append(out[i % half])
        return out + [out[i % half] for i in range(len(sessions) - half)]
    return step


def _target(name):
    from repro.core.delta_pipeline import DeltaDumpPipeline
    from repro.core.state_manager import StateManager
    from repro.serve.engine import Engine

    return {
        "token": (Engine, "_sample", _altered_sample, "logit_gap"),
        "rollback": (StateManager, "restore", _no_rollback, "rollback_mismatch"),
        "image": (DeltaDumpPipeline, "decode", _corrupt_decode, "image_mismatch"),
        "half_batch": (Engine, "step", _half_batch, "logit_gap"),
    }[name]


@pytest.mark.parametrize(
    "source,fault",
    [
        ("olmo-1b.mcts-swe", "token"),
        ("olmo-1b.mcts-swe", "rollback"),
        ("olmo-1b.mcts-swe", "image"),
        ("qwen2-vl-2b.mcts-swe-8k", "token"),
        ("qwen2-vl-2b.mcts-swe-8k", "rollback"),
        ("qwen2-vl-2b.mcts-swe-8k", "image"),
        ("olmo-1b.rl-fanout", "token"),
        ("olmo-1b.rl-fanout", "half_batch"),
    ],
)
def test_fault_is_not_correct(source, fault, tmp_path, capsys, monkeypatch):
    cls, attr, wrap, check = _target(fault)
    monkeypatch.setattr(cls, attr, wrap(getattr(cls, attr)))
    name = add_tiny_cell(str(tmp_path), source)
    res = run_tiny(str(tmp_path), name, capsys, monkeypatch, seed=1234)
    assert res["correct"] is False
    c = res["checks"][check]
    assert (c["value"] > c["limit"]) if c["is"] == "max" else (c["value"] < c["limit"])
