"""Fig. 13b analogue: O(delta) persistence — incremental snapshot chains.

Measures what the incremental plane buys over the full-snapshot-per-save
baseline, on the paper's serving-shaped workload (a live snapshot tree with
a small dirty set per step):

* ``bytes_ratio`` — bytes written by one full save divided by bytes written
  by the incremental save of the *same* step (1% dirty set on an 8-node
  tree).  The acceptance gate is >= 5x: delta saves must scale with the
  dirty set, not with resident state.
* ``latency / bytes flatness`` — delta-save cost as the snapshot tree grows
  (8 -> 16 -> 32 nodes): the write path must track the delta, not the tree.
* ``compaction correctness`` — folding the delta chain into a fresh full
  anchor preserves the recovered state bit-for-bit and actually shrinks
  the manifest.
* ``dedupe accounting`` — four forked sandboxes sharing a base image
  persist into one root; the shared chunks land in the packs once, so
  total pack bytes stay near 1x the base, not 4x.

Writes ``BENCH_incremental_persist.json``; gated by
``benchmarks/baselines/incremental_persist.json``.  ``--quick`` (or
``REPRO_BENCH_QUICK=1``) shrinks state sizes for CI smoke runs.

    PYTHONPATH=src python benchmarks/fig13b_incremental_persist.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time
from typing import Dict, List

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/fig13b_incremental_persist.py`
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
    StateManager,
    compact_state,
    recover,
    save_state,
)
from repro.core.persist import PersistencePlane, _read_manifest


def _restore(payload):
    return CowArrayState({k: v.copy() for k, v in payload.items()})


def _build(n_nodes: int, state_kb: int, chunk_bytes: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    fs = DeltaFS(chunk_bytes=chunk_bytes)
    fs.write("repo/blob", rng.integers(0, 255, state_kb * 1024 // 2).astype(np.uint8))
    n_elems = state_kb * 1024 // 8
    proc = CowArrayState(
        {
            "heap": rng.standard_normal(n_elems).astype(np.float32),
            "regs": rng.standard_normal(256).astype(np.float32),
        }
    )
    cr = DeltaCR(store=fs.store, restore_fn=_restore, template_pool_size=4)
    sm = StateManager(Sandbox(fs, proc), cr)
    for _ in range(n_nodes):
        sm.checkpoint()
    cr.wait_dumps()
    return sm, fs, cr, n_elems, rng


def _dirty_step(sm, cr, rng, n_elems: int, dirty_frac: float) -> None:
    dirty = max(1, int(n_elems * dirty_frac))
    lo = int(rng.integers(0, n_elems - dirty))
    val = float(rng.random())
    sm.sandbox.proc.mutate("heap", lambda h: h.__setitem__(slice(lo, lo + dirty), val))
    sm.checkpoint()
    cr.wait_dumps()


def _full_save_bytes(sm) -> int:
    """Bytes a from-scratch full snapshot of this state costs right now."""
    d = tempfile.mkdtemp(prefix="dbox-bench-fullref-")
    try:
        stats: Dict = {}
        save_state(d, sm=sm, mode="full", stats_out=stats)
        return int(stats["bytes_written"])
    finally:
        shutil.rmtree(d, ignore_errors=True)


def run() -> List[Row]:
    q = quick()
    state_kb = 128 if q else 1024
    n_steps = 4 if q else 8
    chunk_bytes = 16 * 1024
    dirty_frac = 0.01
    rows: List[Row] = []
    results: Dict[str, Dict] = {}

    # ---- bytes ∝ delta: 1% dirty on an 8-node tree ------------------------
    sm, fs, cr, n_elems, rng = _build(8, state_kb, chunk_bytes)
    root = tempfile.mkdtemp(prefix="dbox-bench-incr-")
    try:
        plane = PersistencePlane(root, keep_snapshots=8, full_every=64)
        plane.save(sm=sm)                      # the full anchor
        anchor_bytes = plane.last_save_stats["bytes_written"]
        delta_bytes: List[int] = []
        full_ref_bytes: List[int] = []
        delta_ms: List[float] = []
        for _ in range(n_steps):
            _dirty_step(sm, cr, rng, n_elems, dirty_frac)
            t0 = time.perf_counter()
            plane.save(sm=sm)
            delta_ms.append((time.perf_counter() - t0) * 1e3)
            assert plane.last_save_stats["kind"] == "delta"
            delta_bytes.append(plane.last_save_stats["bytes_written"])
            full_ref_bytes.append(_full_save_bytes(sm))
        bytes_ratio = float(np.mean(full_ref_bytes)) / float(np.mean(delta_bytes))
        results["incremental"] = {
            "tree_nodes": 8,
            "state_kb": state_kb,
            "dirty_frac": dirty_frac,
            "anchor_bytes": int(anchor_bytes),
            "delta_bytes_mean": float(np.mean(delta_bytes)),
            "full_bytes_mean": float(np.mean(full_ref_bytes)),
            "bytes_ratio": bytes_ratio,
            "delta_save_ms_p50": float(np.percentile(delta_ms, 50)),
        }
        rows.append(
            Row(
                "fig13b/incremental",
                bytes_ratio,
                f"delta={int(np.mean(delta_bytes))}B;full={int(np.mean(full_ref_bytes))}B",
            )
        )

        # ---- compaction correctness over the chain just written ----------
        before = recover(root)
        probe_heap = before.state_manager.sandbox.proc.get("heap").copy()
        probe_blob = before.state_manager.sandbox.fs.read("repo/blob").copy()
        entries_before = len(_read_manifest(root))
        compact_state(root, keep_snapshots=1)
        entries_after = len(_read_manifest(root))
        after = recover(root)
        compact_ok = bool(
            np.array_equal(after.state_manager.sandbox.proc.get("heap"), probe_heap)
            and np.array_equal(
                after.state_manager.sandbox.fs.read("repo/blob"), probe_blob
            )
            and entries_after < entries_before
        )
        results["compaction"] = {
            "entries_before": entries_before,
            "entries_after": entries_after,
            "state_preserved": compact_ok,
        }
        rows.append(
            Row(
                "fig13b/compaction",
                float(compact_ok),
                f"entries={entries_before}->{entries_after}",
            )
        )
        before.deltacr.shutdown()
        after.deltacr.shutdown()
    finally:
        cr.shutdown()
        shutil.rmtree(root, ignore_errors=True)

    # ---- flatness: delta-save cost vs snapshot-tree size ------------------
    flat: Dict[int, Dict[str, float]] = {}
    for n_nodes in (8, 16, 32):
        sm, fs, cr, n_elems, rng = _build(n_nodes, state_kb, chunk_bytes)
        root = tempfile.mkdtemp(prefix="dbox-bench-flat-")
        try:
            plane = PersistencePlane(root, keep_snapshots=8, full_every=64)
            plane.save(sm=sm)
            best_ms = float("inf")
            sizes: List[int] = []
            for _ in range(3):
                _dirty_step(sm, cr, rng, n_elems, dirty_frac)
                t0 = time.perf_counter()
                plane.save(sm=sm)
                best_ms = min(best_ms, (time.perf_counter() - t0) * 1e3)
                sizes.append(plane.last_save_stats["bytes_written"])
            flat[n_nodes] = {"save_ms": best_ms, "delta_bytes": float(np.mean(sizes))}
        finally:
            cr.shutdown()
            shutil.rmtree(root, ignore_errors=True)
    latency_growth = flat[32]["save_ms"] / max(flat[8]["save_ms"], 1e-9)
    bytes_growth = flat[32]["delta_bytes"] / max(flat[8]["delta_bytes"], 1e-9)
    results["flatness"] = {
        "per_tree": {str(k): v for k, v in flat.items()},
        "latency_growth_8_to_32": float(latency_growth),
        "delta_bytes_growth_8_to_32": float(bytes_growth),
    }
    rows.append(
        Row(
            "fig13b/flatness",
            float(latency_growth),
            f"bytes_growth={bytes_growth:.2f}",
        )
    )

    # ---- dedupe: 4 forked sandboxes, shared base stored once --------------
    root = tempfile.mkdtemp(prefix="dbox-bench-dedupe-")
    try:
        pack_bytes: List[int] = []
        crs = []
        for i in range(4):
            sm, fs, cr, n_elems, rng = _build(2, state_kb, chunk_bytes, seed=11)
            crs.append(cr)
            # each fork diverges by its own private 1% dirty set
            _dirty_step(sm, cr, np.random.default_rng(100 + i), n_elems, dirty_frac)
            stats: Dict = {}
            save_state(root, sm=sm, keep_snapshots=16, stats_out=stats)
            pack_bytes.append(int(stats["pack_bytes"]))
        base = pack_bytes[0]
        total = sum(pack_bytes)
        growth_ratio = total / max(base, 1)
        results["dedupe"] = {
            "sandboxes": 4,
            "base_pack_bytes": base,
            "per_save_pack_bytes": pack_bytes,
            "total_pack_bytes": total,
            "pack_growth_ratio": float(growth_ratio),
        }
        rows.append(
            Row(
                "fig13b/dedupe",
                float(growth_ratio),
                f"base={base}B;total={total}B",
            )
        )
        for cr in crs:
            cr.shutdown()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_incremental_persist.json")
    with open(out_path, "w") as f:
        json.dump(
            {
                "config": {
                    "state_kb": state_kb,
                    "chunk_bytes": chunk_bytes,
                    "dirty_frac": dirty_frac,
                    "n_steps": n_steps,
                },
                "results": results,
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
