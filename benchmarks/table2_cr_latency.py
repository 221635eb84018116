"""Table 2 analogue: per-event mean blocking time (ms) on MCTS trajectories.

Replays the identical archetype workload trace through every backend and
measures the checkpoint and restore blocking intervals.  DeltaBox's dump is
asynchronous (masked under the LLM window), so its checkpoint number is the
API call-to-return interval — exactly the paper's measurement convention.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from repro.search.archetypes import ARCHETYPES

from .baselines import BASELINES, ReplayCR
from .common import EventTimer, Row, quick
from .workload import apply_event, make_trace


def run() -> List[Row]:
    n_events = 10 if quick() else 24
    archetypes = ["tools"] if quick() else ["django", "sympy", "scientific", "tools"]
    rows: List[Row] = []
    summary: Dict[str, Dict[str, float]] = {}
    for arche in archetypes:
        spec = ARCHETYPES[arche]
        trace = make_trace(spec, n_events, seed=11)
        rng = np.random.default_rng(5)
        restore_points = rng.integers(0, n_events, size=n_events // 3)
        for backend_cls in BASELINES:
            backend = backend_cls(spec)
            timer = EventTimer()
            ckpts: List[int] = []
            for i, ev in enumerate(trace):
                apply_event(spec, backend.api(), ev)
                if isinstance(backend, ReplayCR):
                    backend.note_event(ev)
                if hasattr(backend, "wait_async"):
                    backend.wait_async()   # 1-core host: drain background
                    # dump work out of the timed API-blocking interval
                cid = timer.timeit("ck", lambda: backend.checkpoint())
                ckpts.append(cid)
                if i in restore_points and len(ckpts) > 1:
                    target = ckpts[int(rng.integers(0, len(ckpts) - 1))]
                    if isinstance(backend, ReplayCR):
                        # replay invalidates later checkpoints; restore to target
                        timer.timeit("rs", lambda: backend.restore(target))
                        ckpts = ckpts[: ckpts.index(target) + 1]
                    else:
                        timer.timeit("rs", lambda: backend.restore(target))
            if hasattr(backend, "wait_async"):
                backend.wait_async()
            ck, rs = timer.mean_ms("ck"), timer.mean_ms("rs")
            summary.setdefault(backend.name, {})[arche] = (ck, rs)
            rows.append(
                Row(
                    f"table2/{arche}/{backend.name}/ck", ck * 1e3,
                    f"restore_ms={rs:.3f};events={n_events}",
                )
            )
            rows.append(Row(f"table2/{arche}/{backend.name}/rs", rs * 1e3, ""))
    # weighted average across archetypes (event-weighted, equal events)
    for backend_cls in BASELINES:
        name = backend_cls.name
        if name in summary:
            cks = [v[0] for v in summary[name].values()]
            rss = [v[1] for v in summary[name].values()]
            rows.append(
                Row(
                    f"table2/weighted_avg/{name}/ck", float(np.mean(cks)) * 1e3,
                    f"rs_ms={float(np.mean(rss)):.3f}",
                )
            )
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
