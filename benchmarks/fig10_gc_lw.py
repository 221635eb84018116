"""Fig 10 analogue: adaptive optimizations.

(a) Lightweight-skip checkpoint latency: pure-read events route to the LW
    path (metadata marker) and skip the dump; FS-mutating events take the
    standard path.
(b) Reachability-aware GC: end-of-trajectory dump storage vs retaining
    every checkpoint.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import (
    CowArrayState,
    DeltaCR,
    DeltaFS,
    Sandbox,
    StateManager,
    reachability_gc,
)
from repro.search import MCTS, MCTSConfig, SyntheticAgentTask, build_sandbox_state
from repro.search.archetypes import ARCHETYPES

from .common import EventTimer, Row, quick
from .workload import SandboxState, apply_event, init_state, make_trace


def run() -> List[Row]:
    rows: List[Row] = []
    # ---------------------------------------------------------- (a) LW skip
    spec = ARCHETYPES["sympy"]                 # read-heavy: most events LW
    fs = DeltaFS(chunk_bytes=4096)
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
    )
    sandbox = Sandbox(fs, CowArrayState({}))
    sm = StateManager(sandbox, cr)
    api = SandboxState(sandbox)
    init_state(spec, api)
    n_events = 20 if quick() else 60
    trace = make_trace(spec, n_events, seed=8)
    timer = EventTimer()
    lw = std = 0
    for ev in trace:
        apply_event(spec, api, ev)
        cr.wait_dumps()     # 1-core container: keep background dump work out
        # of the timed blocking interval (a real host has spare cores)
        if ev.readonly:
            timer.timeit("lw", lambda: sm.checkpoint(lightweight=True, actions=(ev,)))
            lw += 1
        else:
            timer.timeit("std", lambda: sm.checkpoint())
            std += 1
    cr.wait_dumps()
    rows.append(
        Row(
            "fig10a/lw_checkpoint", timer.mean_ms("lw") * 1e3,
            f"events={lw};share={lw/(lw+std):.2f}",
        )
    )
    rows.append(Row("fig10a/std_checkpoint", timer.mean_ms("std") * 1e3, f"events={std}"))
    cr.shutdown()

    # ------------------------------------------------------------- (b) GC
    def run_mcts(gc_every: int):
        spec = ARCHETYPES["tools"]
        fs = DeltaFS(chunk_bytes=4096)
        proc = build_sandbox_state(spec, fs, seed=0)
        cr = DeltaCR(
            store=fs.store,
            restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
            template_pool_size=16,
        )
        sm = StateManager(Sandbox(fs, proc), cr)
        task = SyntheticAgentTask(spec)
        sm.action_applier = lambda sb, act: task.replay_action(sb, act)
        iters = 12 if quick() else 30
        MCTS(sm, task, MCTSConfig(iterations=iters, gc_every=gc_every,
                                  expand_width=1, max_depth=4, seed=6)).run()
        cr.wait_dumps()
        if gc_every:
            reachability_gc(sm)
        return fs.store.stats.physical_bytes

    keep_all = run_mcts(0)
    with_gc = run_mcts(10)
    rows.append(
        Row(
            "fig10b/gc_storage", 0.0,
            f"keep_all_mb={keep_all/1e6:.1f};gc_mb={with_gc/1e6:.1f};"
            f"reduction_pct={100*(keep_all-with_gc)/keep_all:.0f}",
        )
    )
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
