"""Fig 6 analogue: end-to-end MCTS time normalized to the LLM+action ideal.

Runs the same 30-iteration MCTS through two coupled state-management
backends — DeltaBox and a synchronous whole-image backend (the E2B-style
pause/resume semantics) — under a simulated LLM round-trip.  The figure of
merit is total_time / ideal_time where ideal = Σ(LLM RTT + action work).
"""
from __future__ import annotations

import pickle
import time
from typing import List

import numpy as np

from repro.core import (
    CowArrayState,
    DeltaCR,
    DeltaFS,
    InferenceProxy,
    Sandbox,
    StateManager,
)
from repro.search import MCTS, MCTSConfig, SyntheticAgentTask, build_sandbox_state
from repro.search.archetypes import ARCHETYPES

from .common import Row, quick


class SyncFullStateManager(StateManager):
    """E2B-style semantics: every checkpoint serializes the whole sandbox
    synchronously; every restore deserializes it.  No inference masking."""

    def checkpoint(self, **kwargs):
        blob = pickle.dumps(
            (
                {k: self.sandbox.fs.read(k) for k in self.sandbox.fs.keys()},
                {k: np.asarray(self.sandbox.proc.get(k)) for k in self.sandbox.proc.keys()},
            ),
            protocol=5,
        )
        cid = super().checkpoint(**{**kwargs, "lightweight": False, "actions": ()})
        self.nodes[cid].blob = blob
        return cid

    def restore(self, ckpt_id: int) -> str:
        node = self.nodes[ckpt_id]
        files, heap = pickle.loads(node.blob)
        mode = super().restore(ckpt_id)
        return mode


def _run_backend(arche: str, manager_cls, llm_s: float, action_s: float, iters: int):
    spec = ARCHETYPES[arche]
    fs = DeltaFS(chunk_bytes=4096)
    proc = build_sandbox_state(spec, fs, seed=0)
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
        template_pool_size=64,
    )
    proxy = InferenceProxy(lambda payload: {"ok": True}, latency_s=llm_s)
    sandbox = Sandbox(fs, proc, proxy=proxy)
    sm = manager_cls(sandbox, cr)
    task = SyntheticAgentTask(spec, action_time_s=action_s, proxy=proxy)
    sm.action_applier = lambda sb, act: task.replay_action(sb, act)
    mcts = MCTS(sm, task, MCTSConfig(iterations=iters, value_isolation=True, seed=4))
    t0 = time.perf_counter()
    st = mcts.run()
    total = time.perf_counter() - t0        # dump drain excluded: masked work
    cr.wait_dumps()
    # ideal = the LLM round-trips + tool work the search actually performed
    # (the 1.0× line of the paper's figure); everything else is state
    # management + value-isolation overhead.
    ideal = max(st.time_action_s, 1e-9)
    proxy.stop()
    cr.shutdown()
    return total, ideal, st


def run() -> List[Row]:
    iters = 10 if quick() else 30
    llm_s = 0.02 if quick() else 0.05        # scaled-down LLM RTT
    action_s = 0.002
    archetypes = ["tools"] if quick() else ["django", "sympy", "scientific", "tools"]
    rows: List[Row] = []
    for arche in archetypes:
        t_db, ideal_db, st_db = _run_backend(arche, StateManager, llm_s, action_s, iters)
        t_vm, ideal_vm, st_vm = _run_backend(arche, SyncFullStateManager, llm_s, action_s, iters)
        rows.append(
            Row(
                f"fig6/{arche}/deltabox", t_db / max(st_db.iterations, 1) * 1e6,
                f"ratio={t_db/ideal_db:.3f};overhead_pct={100*(t_db-ideal_db)/t_db:.1f}",
            )
        )
        rows.append(
            Row(
                f"fig6/{arche}/vm_snapshot", t_vm / max(st_vm.iterations, 1) * 1e6,
                f"ratio={t_vm/ideal_vm:.3f};overhead_pct={100*(t_vm-ideal_vm)/t_vm:.1f}",
            )
        )
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
