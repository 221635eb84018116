"""Table 4 analogue: per-component C/R latency over a standard-path replay.

Components: overlay layer switch (DeltaFS ioctl analogue), template fork,
async dump wall time (off the perceived path), fast-path restore, slow-path
restore (eviction fallback), agent-perceived blocking per path.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import CowArrayState, DeltaCR, DeltaFS, Sandbox, StateManager
from repro.search.archetypes import ARCHETYPES

from .common import EventTimer, Row, quick
from .workload import SandboxState, apply_event, init_state, make_trace


def run() -> List[Row]:
    spec = ARCHETYPES["scientific"]
    n_events = 8 if quick() else 20
    fs = DeltaFS(chunk_bytes=4096)
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
        template_pool_size=4,
    )
    sandbox = Sandbox(fs, CowArrayState({}, hot_keys=("heap_0",)))
    sm = StateManager(sandbox, cr)
    api = SandboxState(sandbox)
    init_state(spec, api)
    trace = make_trace(spec, n_events, seed=3)

    timer = EventTimer()
    ckpts = []
    for ev in trace:
        apply_event(spec, api, ev)
        cr.wait_dumps()     # 1-core host: drain async dumps between events
        # component: overlay switch (the synchronous ioctl)
        t0 = time.perf_counter()
        config = fs.checkpoint()
        timer.record("overlay_ckpt", time.perf_counter() - t0)
        fs.release_config(config)
        # component: template fork
        t0 = time.perf_counter()
        tpl = sandbox.proc.fork()
        timer.record("fork", time.perf_counter() - t0)
        tpl.release()
        # full coupled checkpoint (agent-perceived blocking)
        cid = timer.timeit("ckpt_blocking", lambda: sm.checkpoint())
        ckpts.append(cid)
    # async dump wall (hidden under inference)
    cr.wait_dumps()
    dump_walls = [cr.dump_future(c).result().wall_ms for c in ckpts if cr.dump_future(c)]
    # fast restores
    for cid in ckpts[-4:]:
        timer.timeit("rs_fast", lambda: sm.restore(cid))
    # slow restores: evict then restore
    for cid in ckpts[:3]:
        cr.evict_template(cid)
        timer.timeit("rs_slow", lambda: sm.restore(cid))
    rows = [
        Row("table4/overlay_switch", timer.mean_ms("overlay_ckpt") * 1e3, ""),
        Row("table4/template_fork", timer.mean_ms("fork") * 1e3, ""),
        Row("table4/criu_dump_async", float(np.mean(dump_walls)) * 1e3,
            "off_perceived_path=true"),
        Row("table4/ckpt_agent_blocking", timer.mean_ms("ckpt_blocking") * 1e3, ""),
        Row("table4/restore_fast", timer.mean_ms("rs_fast") * 1e3,
            f"fast={cr.stats.fast_restores}"),
        Row("table4/restore_slow", timer.mean_ms("rs_slow") * 1e3,
            f"slow={cr.stats.slow_restores}"),
    ]
    cr.shutdown()
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
