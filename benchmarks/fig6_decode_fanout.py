"""Fig 6 serving-loop analogue: e2e MCTS with *real model decode*.

Part A — exact CoW gates (noise-free block accounting):
  forking N live decoders from one checkpoint must copy **zero** KV block
  bytes (``share_ok``), their decoded streams must be bit-identical to N
  fresh prefills force-fed the same actions (``parity_ok``), and the first
  divergent write must privatize exactly N shared tail pages.

Part B — nodes explored per fixed wall-clock budget: the same MCTS
  (:class:`DecodeSearchTask`, greedy decode through the engine) driven two
  ways:

  * **serial re-prefill** — one leaf at a time, and every expansion rebuilds
    its session by prefilling the node's full token prefix from scratch:
    the no-CoW substrate, where "restoring" decoder state means recomputing
    it (the template restore MCTS itself performs is O(metadata) noise on
    top — the baseline is dominated by the prefill it cannot avoid).
  * **forked CoW** — parallel leaves forked from checkpoints (zero-copy
    page-table forks) admitted into the scheduler's continuous batching, so
    sibling leaves decode in one stacked engine step.

  Gate: nodes-per-second ratio >= 2x (rate-normalized, wall budgets fixed).

Writes ``BENCH_decode_fanout.json`` (override with ``REPRO_BENCH_OUT`` or
``--out``); ``--quick`` / ``REPRO_BENCH_QUICK=1`` shrinks budgets for CI.
All jit programs both arms touch — every re-prefill length the tree can
reach and every decode batch width — are compiled before the timed regions.
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

if __package__ in (None, ""):  # run as a plain script (CI invokes it this way)
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.dirname(__file__))
    from common import Row, quick  # type: ignore
else:
    from .common import Row, quick

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import DeltaCR, DeltaFS, Sandbox, SandboxTree, StateManager  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.search import MCTS, MCTSConfig, DecodeSearchTask, decode_fanout  # noqa: E402
from repro.search.fanout import fork_sandboxes  # noqa: E402
from repro.serve import (  # noqa: E402
    Engine,
    PagePool,
    PagedSession,
    Scheduler,
    SchedulerConfig,
)

# A long shared prefix is the workload the CoW fork exists for: the serial
# baseline must recompute all of it per expansion, the fork shares it for a
# page-table copy.  768 tokens keeps the fork point page-unaligned once the
# first decode lands (psz 8), and makes re-prefill cost honest.
PROMPT = [int(t) % 200 + 1 for t in range(768)]
K_TOKENS = 1                         # decode per action (node depth step)
N_FORK = 4                           # fan-out width / parallel leaves


class _ReprefillTask(DecodeSearchTask):
    """The no-CoW baseline task: every expansion pays a full prefill of the
    node's token prefix before decoding — state restoration by recompute."""

    def apply_action(self, sandbox, action):
        old = sandbox.proc
        tokens = list(old.tokens)
        old.release()
        sess = self.engine.new_session(tokens[:-1])
        sess.tokens[-1] = int(action)
        sandbox.proc = sess
        for _ in range(self.k_tokens):
            self.engine.step([sess])


def _mk_world(eng, pool, sess, *, pool_size=512):
    cr = DeltaCR(
        template_pool_size=pool_size,
        restore_fn=lambda p: PagedSession.restore_from_payload(pool, p),
        async_warm=False,
        stream=False,
    )
    sm = StateManager(Sandbox(DeltaFS(chunk_bytes=256), sess), cr)
    return SandboxTree(sm), sm, cr


def _warmup(eng, max_depth: int) -> None:
    """Compile every jit program the timed regions can reach: re-prefill
    lengths len(PROMPT)+k*d (node prefixes at depth d), decode batches 1..N,
    and the CoW privatization / boundary-alloc kernels at copy counts 1..N
    (batched materialize specializes on how many pages move)."""
    lens = {len(PROMPT)} | {len(PROMPT) + K_TOKENS * d for d in range(max_depth + 2)}
    for L in sorted(lens):
        s = eng.new_session(list((np.arange(L) % 200) + 1))
        eng.step([s])
        s.release()
    base = eng.new_session(PROMPT)
    eng.step([base])                     # leave an unaligned shared tail
    for b in range(1, N_FORK + 1):
        kids = [base.fork() for _ in range(b)]
        for i, kid in enumerate(kids):
            kid.tokens[-1] = i + 2
        for _ in range(3):
            eng.step(kids)               # CoW copies count=b, then fresh allocs
        for kid in kids:
            kid.release()
    base.release()


def _batched_streams(eng, sessions, k):
    out = [[] for _ in sessions]
    for _ in range(k):
        for i, t in enumerate(eng.step(sessions)):
            out[i].append(int(t))
    return out


def _part_a(eng, pool) -> Dict[str, object]:
    sess = eng.new_session(PROMPT)
    eng.generate(sess, 4)
    prefix = list(sess.tokens[:-1])
    tree, sm, cr = _mk_world(eng, pool, sess)
    ck = sm.checkpoint(dump=False)
    sched = Scheduler(eng, cr, SchedulerConfig(max_batch=2 * N_FORK,
                                               min_free_pages=2,
                                               auto_suspend_free_pages=2))
    actions = [3, 7, 11, 13][:N_FORK]

    copied0 = pool.stats.copied_pages
    children, _ = fork_sandboxes(tree, ck, N_FORK)
    fork_copied = pool.stats.copied_pages - copied0
    for c in children:
        tree.release(c.sandbox_id)

    cow0 = pool.stats.cow_copies
    streams, _, _ = decode_fanout(tree, ck, N_FORK, sched, K_TOKENS + 2,
                                  actions=actions)
    divergence_copies = pool.stats.cow_copies - cow0

    fresh = [eng.new_session(prefix) for _ in range(N_FORK)]
    for f, a in zip(fresh, actions):
        f.tokens[-1] = a
    fresh_streams = _batched_streams(eng, fresh, K_TOKENS + 2)
    for f in fresh:
        f.release()
    tree.release_all()
    pool.debug_validate()
    cr.shutdown()
    return {
        "n": N_FORK,
        "k": K_TOKENS + 2,
        "share_ok": bool(fork_copied == 0),
        "fork_copied_pages": int(fork_copied),
        "parity_ok": bool(streams == fresh_streams),
        "divergence_cow_copies": int(divergence_copies),
    }


def _search_arm(eng, pool, *, budget_s: float, forked: bool) -> Dict[str, float]:
    sess = eng.new_session(PROMPT)
    tree, sm, cr = _mk_world(eng, pool, sess)
    cfg = MCTSConfig(
        iterations=100_000,          # the wall budget is the stop condition
        expand_width=3,
        max_depth=8,
        dump=False,
        time_budget_s=budget_s,
        parallel_leaves=N_FORK if forked else 1,
    )
    if forked:
        # max_batch == the leaf cohort: the batching window early-exits the
        # instant every parallel leaf's request arrives
        sched = Scheduler(eng, cr, SchedulerConfig(max_batch=N_FORK,
                                                   min_free_pages=2,
                                                   auto_suspend_free_pages=2,
                                                   batch_window_ms=2.0))
        task = DecodeSearchTask(eng, scheduler=sched, k_tokens=K_TOKENS, width=3)
        mcts = MCTS(sm, task, cfg, tree=tree, scheduler=sched)
    else:
        task = _ReprefillTask(eng, k_tokens=K_TOKENS, width=3)
        mcts = MCTS(sm, task, cfg)
    stats = mcts.run()
    out = {
        "nodes": int(stats.nodes),
        "forks": int(getattr(stats, "forks", 0)),
        "wall_s": float(stats.wall_s),
        "nodes_per_s": stats.nodes / max(stats.wall_s, 1e-9),
    }
    tree.release_all()
    pool.debug_validate()
    cr.shutdown()
    return out


def run() -> List[Row]:
    budget_s = 0.8 if quick() else 2.0
    cfg = get_config("olmo-1b-tiny")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagePool(cfg, num_pages=16384, page_size=8, max_pages_per_session=128)
    eng = Engine(model, params, pool)
    _warmup(eng, max_depth=8)

    rows: List[Row] = []
    cow = _part_a(eng, pool)
    rows.append(
        Row("fig6/cow_gates", 0.0,
            f"share_ok={cow['share_ok']};parity_ok={cow['parity_ok']};"
            f"divergence_copies={cow['divergence_cow_copies']}")
    )

    # Both arms are timed on a shared, contended container: a single sample
    # of either can stall 2-3x on scheduler noise.  Best-of-R per arm
    # measures each arm's capability; the ratio compares capabilities.
    repeats = 3
    serial_runs = [_search_arm(eng, pool, budget_s=budget_s, forked=False)
                   for _ in range(repeats)]
    forked_runs = [_search_arm(eng, pool, budget_s=budget_s, forked=True)
                   for _ in range(repeats)]
    serial = max(serial_runs, key=lambda r: r["nodes_per_s"])
    forked = max(forked_runs, key=lambda r: r["nodes_per_s"])
    serial["all_rates"] = [round(r["nodes_per_s"], 1) for r in serial_runs]
    forked["all_rates"] = [round(r["nodes_per_s"], 1) for r in forked_runs]
    ratio = forked["nodes_per_s"] / max(serial["nodes_per_s"], 1e-9)
    rows.append(
        Row("fig6/serial_reprefill", serial["wall_s"] * 1e6 / max(serial["nodes"], 1),
            f"nodes={serial['nodes']};rate={serial['nodes_per_s']:.1f}/s")
    )
    rows.append(
        Row("fig6/forked_cow", forked["wall_s"] * 1e6 / max(forked["nodes"], 1),
            f"nodes={forked['nodes']};rate={forked['nodes_per_s']:.1f}/s;"
            f"forks={forked['forks']};ratio={ratio:.2f}x")
    )

    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_decode_fanout.json")
    with open(out_path, "w") as f:
        json.dump(
            {
                "config": {
                    "arch": "olmo-1b-tiny",
                    "prompt_len": len(PROMPT),
                    "k_tokens": K_TOKENS,
                    "n_fork": N_FORK,
                    "budget_s": budget_s,
                },
                "results": {
                    "cow": cow,
                    "search": {
                        "serial": serial,
                        "forked": forked,
                        "forked_over_serial_rate": ratio,
                    },
                },
            },
            f,
            indent=1,
        )
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    if args.quick:
        os.environ["REPRO_BENCH_QUICK"] = "1"
    if args.out:
        os.environ["REPRO_BENCH_OUT"] = args.out
    for row in run():
        print(row.csv())


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
