"""Table 3 analogue, tree edition: end-to-end sandbox-tree fork fan-out and
nodes-explored-per-budget for serial vs parallel MCTS.

Two measurements, both CI-gated through ``benchmarks/baselines/fork_fanout.json``:

* **Fork fan-out** — ``SandboxTree.fork(ckpt, n)`` latency/footprint vs
  width.  Unlike the old bare ``ForkableState.fork`` loop this pays the
  *whole* fork: DeltaCR template fork + a fresh NamespaceView over the
  shared LayerStore.  The structural gate asserts the paper's sharing
  claim via ChunkStore accounting: a fan-out of any width copies **zero**
  chunk bytes (``fork_share_ok``); the bare-template fork is kept as a
  reference row so the view overhead stays visible.

* **Nodes per budget** — the same archetype task explored by the serial
  driver (rollback-in-place, one live sandbox) and the parallel driver
  (``parallel_leaves`` forked sandboxes per batch) under one wall-clock
  budget, with action execution modeling a tool/LLM round-trip
  (``action_time_s``).  The gated ratio is the paper's payoff: the
  parallel tree must explore ≥ 2× the nodes of the serial baseline.

Writes ``BENCH_fork_fanout.json``; ``--quick`` / ``REPRO_BENCH_QUICK=1``
shrinks widths and budget for CI smoke runs.

    PYTHONPATH=src python benchmarks/table3_fork_fanout.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/table3_fork_fanout.py`
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.dirname(__file__))
    from common import Row, quick  # type: ignore
else:
    from .common import Row, quick

from repro.core import (
    CowArrayState,
    DeltaCR,
    DeltaFS,
    Sandbox,
    SandboxTree,
    StateManager,
)
from repro.search import (
    ARCHETYPES,
    MCTS,
    MCTSConfig,
    SyntheticAgentTask,
    build_sandbox_state,
    fork_n,
    fork_sandboxes,
)


def _rig(archetype: str = "tools", *, action_time_s: float = 0.0, pool: int = 32):
    spec = ARCHETYPES[archetype]
    fs = DeltaFS(chunk_bytes=4096)
    proc = build_sandbox_state(spec, fs, seed=0)
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
        template_pool_size=pool,
    )
    sm = StateManager(Sandbox(fs, proc), cr)
    task = SyntheticAgentTask(spec, action_time_s=action_time_s)
    sm.action_applier = lambda sb, act: task.replay_action(sb, act)
    return sm, task, cr, fs


# ---------------------------------------------------------------------------
# Part A: sandbox-tree fork fan-out
# ---------------------------------------------------------------------------

def bench_fork(rows: List[Row], results: Dict) -> None:
    heap_mb = 15
    elems = heap_mb * (1 << 20) // 4
    rng = np.random.default_rng(0)
    fs = DeltaFS(chunk_bytes=64 * 1024)
    fs.write("repo/src", rng.integers(0, 255, size=1 << 20).astype(np.uint8))
    proc = CowArrayState(
        {f"seg{i}": rng.standard_normal(elems // 8).astype(np.float32) for i in range(8)}
    )
    cr = DeltaCR(
        store=fs.store,
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
        template_pool_size=8,
    )
    sm = StateManager(Sandbox(fs, proc), cr)
    ckpt = sm.checkpoint(dump=False)     # fork source; no durable dump needed
    tree = SandboxTree(sm)

    results["fork"] = {}
    widths = [1, 4, 16] if quick() else [1, 4, 16, 64]
    reps = 3 if quick() else 5
    share_ok = True
    for n in widths:
        p50s, p99s, fps, rss = [], [], [], []
        for _ in range(reps):
            phys = fs.store.stats.physical_bytes
            logical = fs.store.stats.logical_bytes
            children, res = fork_sandboxes(tree, ckpt, n)
            # the sharing gate: a fork of any width moves zero chunk bytes
            share_ok = share_ok and fs.store.stats.physical_bytes == phys
            share_ok = share_ok and fs.store.stats.logical_bytes == logical
            p50s.append(res.p50_ms)
            p99s.append(res.p99_ms)
            fps.append(res.forks_per_s)
            rss.append(res.resident_bytes)
            tree.release_all()
        rec = {
            "p50_ms": float(np.median(p50s)),
            "p99_ms": float(np.median(p99s)),
            "forks_per_s": float(np.median(fps)),
            "resident_mb": float(np.median(rss)) / 1e6,
        }
        results["fork"][f"n{n}"] = rec
        rows.append(
            Row(
                f"table3/tree_fork_n{n}",
                rec["p50_ms"] * 1e3,
                f"p99_ms={rec['p99_ms']:.3f};forks_per_s={rec['forks_per_s']:.0f};"
                f"rss_mb={rec['resident_mb']:.1f}",
            )
        )
    results["fork"]["share_ok"] = bool(share_ok)
    # sub-linear per-fork cost: widest fan-out's p50 stays within ~2x of n=1
    n_wide = widths[-1]
    results["fork"]["p50_flat_ratio"] = (
        results["fork"][f"n{n_wide}"]["p50_ms"]
        / max(results["fork"]["n1"]["p50_ms"], 1e-9)
    )

    # reference row: bare template fork (process dimension only), so the
    # namespace-view overhead of the end-to-end fork stays observable
    template = proc.fork()
    children, res = fork_n(template, 16)
    for c in children:
        c.release()
    template.release()
    results["fork"]["bare_template_p50_ms"] = res.p50_ms
    rows.append(Row("table3/bare_fork_n16", res.p50_ms * 1e3, "process-dim only"))

    # write-sensitivity: a child dirtying W MB grows residency by ~that (CoW)
    children, _ = fork_sandboxes(tree, ckpt, 4)
    child = children[0]
    before = child.proc.resident_bytes()
    child.proc.mutate("seg0", lambda a: a.__setitem__(slice(None), 1.0))
    child.proc.mutate("seg1", lambda a: a.__setitem__(slice(None), 1.0))
    grown = child.proc.resident_bytes() - before
    results["fork"]["write_sensitivity_mb"] = grown / 1e6
    rows.append(
        Row(
            "table3/write_sensitivity", 0.0,
            f"dirtied_mb={2 * (elems // 8) * 4 / 1e6:.1f};resident_growth_mb={grown / 1e6:.1f}",
        )
    )
    tree.release_all()
    cr.shutdown()


# ---------------------------------------------------------------------------
# Part B: nodes explored per wall-clock budget, serial vs parallel MCTS
# ---------------------------------------------------------------------------

def bench_search(rows: List[Row], results: Dict) -> None:
    # action_time_s models the tool/LLM round-trip the paper's workloads
    # spend most wall-clock in; it is what the parallel driver overlaps.
    if quick():
        budget_s, action_time_s, leaves = 1.5, 0.03, 8
    else:
        budget_s, action_time_s, leaves = 3.0, 0.03, 8
    results["search"] = {
        "budget_s": budget_s,
        "action_time_s": action_time_s,
        "parallel_leaves": leaves,
    }
    rates: Dict[str, float] = {}
    nodes: Dict[str, int] = {}
    for mode, k in (("serial", 1), ("parallel", leaves)):
        sm, task, cr, fs = _rig(action_time_s=action_time_s, pool=64)
        cfg = MCTSConfig(
            iterations=1_000_000,          # budget-limited, not count-limited
            parallel_leaves=k,
            time_budget_s=budget_s,
            expand_width=4,
            max_depth=64,
            gc_every=0,
            seed=3,
        )
        st = MCTS(sm, task, cfg).run()
        nodes[mode] = st.nodes
        rates[mode] = st.nodes / max(st.wall_s, 1e-9)
        results["search"][mode] = {
            "nodes": st.nodes,
            "nodes_per_s": rates[mode],
            "iterations": st.iterations,
            "forks": st.forks,
            "restores": st.restores,
            "wall_s": st.wall_s,
        }
        rows.append(
            Row(
                f"table3/mcts_{mode}_nodes",
                float(st.nodes),
                f"iters={st.iterations};forks={st.forks};wall_s={st.wall_s:.2f}",
            )
        )
        cr.wait_dumps()
        cr.shutdown()
    # gate the *rate* ratio: both drivers stop starting work at the same
    # deadline but finish in-flight quanta, so nodes/s is the overshoot-proof
    # comparison (raw node counts are reported alongside)
    ratio = rates["parallel"] / max(rates["serial"], 1e-9)
    results["search"]["parallel_over_serial_nodes"] = nodes["parallel"] / max(nodes["serial"], 1)
    results["search"]["parallel_over_serial_rate"] = ratio
    rows.append(Row("table3/parallel_over_serial", ratio, "rate-normalized;gate>=2.0"))


def run() -> List[Row]:
    rows: List[Row] = []
    results: Dict = {}
    bench_fork(rows, results)
    bench_search(rows, results)
    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_fork_fanout.json")
    with open(out_path, "w") as f:
        json.dump({"config": {"quick": quick()}, "results": results}, f, indent=1)
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
