"""Fig 7 analogue: RL rollout fan-out over real engine sessions.

(a) End-to-end time to fork N memory-bearing children from one frozen
    source through the paged KV pool (table copy + refcounts) vs a
    full-materialization baseline (copy every page — the createSnapshot+
    create semantics).  Each child reads its state back and verifies.
(b/c) Expected synchronous GPU occupation and async staleness from the
    paper's timing model, using the measured substrate fan-out cost.
"""
from __future__ import annotations

import time
from typing import List

import jax
import numpy as np

from repro.configs import get_config
from repro.models import Model
from repro.search import staleness, sync_gpu_occupation
from repro.serve import Engine, PagePool, SamplingParams

from .common import Row, quick


def _copy_fork(session, pool):
    """Baseline: materialize a full copy of every page the session owns."""
    clone = session.fork()
    src = [int(p) for p in session.active_pages()]
    dst = []
    for i, _ in enumerate(src):
        p = pool.alloc()
        dst.append(p)
        clone.table[i] = p
    pool.copy_pages(src, dst)
    pool.decref(np.asarray(src))          # clone's refs move to the copies
    return clone


def run() -> List[Row]:
    cfg = get_config("olmo-1b-tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagePool(cfg, num_pages=2048, page_size=8, max_pages_per_session=64)
    eng = Engine(model, params, pool)
    # warm source: prompt + a short trajectory in the KV cache
    sess = eng.new_session(list(range(1, 33)), SamplingParams())
    eng.generate(sess, 8)

    rows: List[Row] = []
    widths = [1, 4, 16] if quick() else [1, 4, 16, 64]
    fanout_s = {}
    for n in widths:
        # DeltaBox path: page-table forks
        t0 = time.perf_counter()
        kids = [sess.fork() for _ in range(n)]
        dt_fork = time.perf_counter() - t0
        # verify: children read their state back
        for k in kids:
            assert k.tokens == sess.tokens and k.seq_len == sess.seq_len
        for k in kids:
            k.release()
        # baseline: full page materialization per child
        t0 = time.perf_counter()
        copies = [_copy_fork(sess, pool) for _ in range(n)]
        dt_copy = time.perf_counter() - t0
        for c in copies:
            c.release()
        fanout_s[n] = dt_fork
        rows.append(
            Row(
                f"fig7a/fork_n{n}", dt_fork / n * 1e6,
                f"total_ms={dt_fork*1e3:.3f};copy_total_ms={dt_copy*1e3:.3f};"
                f"speedup={dt_copy/max(dt_fork,1e-9):.1f}x",
            )
        )
    # (b,c) occupation + staleness with the paper's T_gen/T_train scales
    t_gen, t_train16, t_train64 = 1.1, 1.3, 4.51
    for n, t_train in ((16, t_train16), (64, t_train64)):
        t_sb = fanout_s.get(n, fanout_s[max(fanout_s)])
        occ = sync_gpu_occupation(t_sb, t_gen, t_train)
        stale = staleness(t_sb, t_gen, t_train)
        # E2B-style comparison: substrate cost = measured copy path scaled
        rows.append(
            Row(
                f"fig7c/occupation_n{n}", t_sb * 1e6,
                f"occupation={occ:.3f};staleness={stale:.2f}",
            )
        )
    sess.release()
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
