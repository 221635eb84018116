"""Fig. 13 analogue: the DeltaState persistence plane — save / recover cost
and correctness over an N-node snapshot tree.

Grows a realistic snapshot tree (a trunk of delta checkpoints with periodic
branches, O(delta) dirty sets per step), then measures:

* ``save_ms`` — wall latency of one crash-consistent manifest commit
  (canonical snapshot + fsync + rename + manifest append), amortized over
  repeated saves of the same tree (the scheduler's coalesced-suspend case),
* ``recover_ms`` — cold ``recover()``: chunk store + LayerStore + ImageStore
  lineage + tree + generation anchors, all rebuilt from one blob,
* ``recovery correctness`` — a sandbox rolled back from the recovered store
  must be byte-identical to one from the pre-crash store
  (``recover_ok``), every persisted chunk digest must verify bit-identically
  (``digests_match``), and the recovered tree must hold every durable node
  (``recovered_nodes``),
* ``drop_inflight_ms`` — reclaim of a parent while a dependent dump is in
  flight: the refcounted ImageStore makes this non-blocking (the old
  behavior waited out the dump), CI-gated with a generous bound.

Writes ``BENCH_persist_recover.json``; gated by
``benchmarks/baselines/persist_recover.json``.  ``--quick`` (or
``REPRO_BENCH_QUICK=1``) shrinks the tree for CI smoke runs.

    PYTHONPATH=src python benchmarks/fig13_persist_recover.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Dict, List

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/fig13_persist_recover.py`
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
    recover,
    save_state,
)


def _restore(payload):
    return CowArrayState({k: v.copy() for k, v in payload.items()})


def _build_tree(n_nodes: int, state_kb: int, dirty_frac: float, chunk_bytes: int):
    """Trunk + every-4th-node branches, O(delta) dirty writes per step."""
    rng = np.random.default_rng(7)
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
    ckpts: List[int] = [sm.checkpoint()]
    dirty = max(1, int(n_elems * dirty_frac))
    while len(ckpts) < n_nodes:
        if len(ckpts) % 4 == 3 and len(ckpts) >= 2:
            sm.restore(ckpts[-2])          # branch off the grandparent
        lo = int(rng.integers(0, n_elems - dirty))
        val = float(rng.random())
        sm.sandbox.proc.mutate("heap", lambda h: h.__setitem__(slice(lo, lo + dirty), val))
        if len(ckpts) % 3 == 0:
            fs.write("repo/note", rng.integers(0, 255, 2048).astype(np.uint8))
        ckpts.append(sm.checkpoint())
    cr.wait_dumps()
    return sm, fs, cr, ckpts


def run() -> List[Row]:
    q = quick()
    n_nodes = 8 if q else 24
    state_kb = 256 if q else 2048
    n_saves = 3 if q else 6
    chunk_bytes = 16 * 1024
    dirty_frac = 0.05

    sm, fs, cr, ckpts = _build_tree(n_nodes, state_kb, dirty_frac, chunk_bytes)
    rows: List[Row] = []
    results: Dict[str, Dict] = {}
    root = tempfile.mkdtemp(prefix="dbox-bench-persist-")
    try:
        # ---- save latency ------------------------------------------------
        save_ms: List[float] = []
        for _ in range(n_saves):
            t0 = time.perf_counter()
            save_state(root, sm=sm)
            save_ms.append((time.perf_counter() - t0) * 1e3)
        snap_files = [p for p in os.listdir(root) if p.startswith("snap-")]
        snap_bytes = max(
            os.path.getsize(os.path.join(root, p)) for p in snap_files
        )

        # ---- pre-crash ground truth -------------------------------------
        probe = ckpts[len(ckpts) // 2]
        sm.restore(probe)
        want_heap = sm.sandbox.proc.get("heap").copy()
        want_blob = sm.sandbox.fs.read("repo/blob").copy()
        durable_nodes = sum(1 for n in sm.live_nodes())
        image_digests = {
            (ckpt, name): meta.digests
            for ckpt, image in cr.images.live_images()
            for name, meta in image.entries.items()
        }

        # ---- cold recover ------------------------------------------------
        t0 = time.perf_counter()
        rec = recover(root)
        recover_ms = (time.perf_counter() - t0) * 1e3
        sm2 = rec.state_manager
        assert sm2 is not None
        recovered_nodes = sum(1 for n in sm2.live_nodes())
        sm2.restore(probe)
        heap_ok = bool(np.array_equal(sm2.sandbox.proc.get("heap"), want_heap))
        blob_ok = bool(np.array_equal(sm2.sandbox.fs.read("repo/blob"), want_blob))
        digests_match = True
        for (ckpt, name), digests in image_digests.items():
            rimg = rec.deltacr.images.image_for(ckpt)
            if rimg is None or rimg.entries[name].digests != digests:
                digests_match = False
                break
            for cid, d in zip(rimg.entries[name].chunk_ids, rimg.entries[name].digests):
                if rec.deltacr.store.digest_of(cid) != d:
                    digests_match = False
                    break

        # ---- non-blocking reclaim under an in-flight dependent dump ------
        cr2 = rec.deltacr
        sm2.sandbox.proc.mutate("heap", lambda h: h.__setitem__(0, -3.0))
        gate = threading.Event()
        cr2._dump_executor.submit(gate.wait)
        child = sm2.checkpoint()           # dump queued behind the stall
        t0 = time.perf_counter()
        sm2.reclaim(probe)                 # parent of the in-flight dump
        drop_inflight_ms = (time.perf_counter() - t0) * 1e3
        deferred = cr2.images.deferred_count()
        gate.set()
        cr2.wait_dumps()
        child_ok = cr2.images.image_for(child) is not None

        results["persist"] = {
            "nodes": n_nodes,
            "durable_nodes": durable_nodes,
            "state_kb": state_kb,
            "save_ms_mean": float(np.mean(save_ms)),
            "save_ms_p50": float(np.percentile(save_ms, 50)),
            "snapshot_bytes": int(snap_bytes),
            "bytes_per_node": int(snap_bytes / max(durable_nodes, 1)),
        }
        results["recover"] = {
            "recover_ms": recover_ms,
            "recovered_nodes": recovered_nodes,
            "all_nodes_recovered": bool(recovered_nodes == durable_nodes),
            "recover_ok": bool(heap_ok and blob_ok),
            "digests_match": digests_match,
            "anchors_recovered": len(rec.deltacr.pipeline.anchored_ids())
            if rec.deltacr.pipeline is not None
            else 0,
        }
        results["reclaim"] = {
            "drop_inflight_ms": drop_inflight_ms,
            "deferred_images": int(deferred),
            "child_dump_committed": bool(child_ok),
        }
        rows.append(
            Row(
                "fig13/save",
                float(np.mean(save_ms)) * 1e3,
                f"nodes={durable_nodes};bytes={snap_bytes}",
            )
        )
        rows.append(
            Row(
                "fig13/recover",
                recover_ms * 1e3,
                f"nodes={recovered_nodes};ok={int(heap_ok and blob_ok)};"
                f"digests={int(digests_match)}",
            )
        )
        rows.append(
            Row(
                "fig13/drop_inflight",
                drop_inflight_ms * 1e3,
                f"deferred={deferred};child_ok={int(child_ok)}",
            )
        )
        rec.deltacr.shutdown()
    finally:
        cr.shutdown()
        shutil.rmtree(root, ignore_errors=True)

    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_persist_recover.json")
    with open(out_path, "w") as f:
        json.dump(
            {
                "config": {
                    "nodes": n_nodes,
                    "state_kb": state_kb,
                    "chunk_bytes": chunk_bytes,
                    "dirty_frac": dirty_frac,
                    "n_saves": n_saves,
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
