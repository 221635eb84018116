"""Fig. 11 analogue: the adaptive dump engine vs every forced dump mode.

Replays an identical checkpoint chain through four DumpPolicy modes and
measures, per checkpoint, the background-dump wall time and the physical
bytes written:

* ``legacy`` — the seed path: ``tobytes()`` the full payload, byte-compare
  every chunk against the parent image.
* ``digest`` — zero-copy memoryview chunking + per-chunk blake2b parent
  compare (hash once per chunk).
* ``delta``  — the kernel pipeline forced on: diff + compaction (fused
  where the plan fits VMEM), dirty-key metadata reuse, O(delta) host bytes.
* ``auto``   — the adaptive engine: per-dump mode selection from dirty-key
  hints calibrated by measured dirty fractions (the PR-8 tentpole).

Workload: K tensors × C chunks each; per checkpoint a target fraction of
(key, chunk) cells is dirtied.  Gated ratios (1%, 10%, 50%) run best-of-3
interleaved rounds so single-core container noise can't fail the
``auto ≥ legacy`` CI gate; a crossover sweep (5%, 25%, 75%) runs one
legacy-vs-auto round per ratio to chart where the engine flips modes.

Writes ``BENCH_dump_pipeline.json`` (override with ``--out``); ``--quick``
(or REPRO_BENCH_QUICK=1) shrinks the state for CI smoke runs.

    PYTHONPATH=src python benchmarks/fig11_dump_pipeline.py --quick
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Dict, List

import numpy as np

if __package__ in (None, ""):  # `python benchmarks/fig11_dump_pipeline.py`
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.path.insert(0, os.path.dirname(__file__))
    from common import Row, quick  # type: ignore
else:
    from .common import Row, quick

from repro.core import ChunkStore, CowArrayState, DeltaCR, DumpPolicy

DIRTY_RATIOS = (0.01, 0.10, 0.50)        # gated: auto ≥ legacy, best-of-3
SWEEP_RATIOS = (0.05, 0.25, 0.75)        # crossover chart, single round
ROUNDS = 3


def _mk_state(n_keys: int, chunks_per_key: int, chunk_bytes: int, seed: int) -> CowArrayState:
    rng = np.random.default_rng(seed)
    elems = chunks_per_key * chunk_bytes // 4
    return CowArrayState(
        {f"t{i}": rng.standard_normal(elems).astype(np.float32) for i in range(n_keys)}
    )


def _dirty_cells(n_keys: int, chunks_per_key: int, ratio: float, rng) -> List[tuple]:
    """Pick n_dirty (key, chunk) cells with *key locality*: agent steps touch
    a few tensors densely (one env buffer, one KV page group), not a random
    sprinkle across the whole namespace — so cells cluster into the minimum
    number of keys."""
    total = n_keys * chunks_per_key
    n_dirty = max(1, int(round(total * ratio)))
    keys = rng.permutation(n_keys)
    cells = []
    for slot in range(n_dirty):
        key = int(keys[slot // chunks_per_key])
        cells.append((key, slot % chunks_per_key))
    return cells


def _warmup(chunks_per_key: int, chunk_bytes: int) -> None:
    """Compile the delta_encode/delta_apply jits for this chunk geometry.

    The measured chains then see steady-state dispatch only — matching
    production, where one checkpoint shape compiles once per process."""
    state = _mk_state(2, chunks_per_key, chunk_bytes, seed=1)
    cr = DeltaCR(
        restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
        chunk_bytes=chunk_bytes,
        policy=DumpPolicy(mode="auto"),
        template_pool_size=1,
    )
    cr.checkpoint(state, 1, None)
    state.mutate("t0", lambda a: a.__setitem__(slice(0, 4), -1.0))
    cr.checkpoint(state, 2, 1)
    cr.wait_dumps()
    cr.evict_template(2)
    cr.restore(2)                        # compile the delta_apply path too
    cr.shutdown()


class _Chain:
    """One dump-mode's checkpoint chain.

    All modes replay the identical workload and the harness *interleaves*
    their steps, so slow-container load spikes hit every mode equally
    instead of biasing whichever chain ran last."""

    def __init__(self, mode: str, *, n_keys: int, chunks_per_key: int, chunk_bytes: int):
        self.mode = mode
        self.n_keys = n_keys
        self.chunks_per_key = chunks_per_key
        self.elems_per_chunk = chunk_bytes // 4
        self.state = _mk_state(n_keys, chunks_per_key, chunk_bytes, seed=7)
        # dedupe off for every mode: the benchmark measures the dump path,
        # not content hashing — with dedupe on, blake2b of the dirty set is
        # a shared additive cost in all modes (reported by fig9 instead)
        self.cr = DeltaCR(
            store=ChunkStore(chunk_bytes=chunk_bytes, dedupe=False),
            restore_fn=lambda p: CowArrayState({k: v.copy() for k, v in p.items()}),
            chunk_bytes=chunk_bytes,
            policy=DumpPolicy(mode=mode),
            template_pool_size=2,
        )
        self.walls: List[float] = []
        self.modes: Dict[str, int] = {}
        self.dirty = 0
        self.ckpt = 1
        self.cr.checkpoint(self.state, 1, None)
        self.cr.wait_dumps()             # baseline image outside the timing
        self.bytes_before = self.cr.store.stats.bytes_written

    def step(self, cells: List[tuple], value: float) -> None:
        for key_i, chunk_i in cells:
            lo = chunk_i * self.elems_per_chunk
            self.state.mutate(
                f"t{key_i}",
                lambda a, lo=lo, v=value: a.__setitem__(slice(lo, lo + 4), v),
            )
        self.ckpt += 1
        self.cr.checkpoint(self.state, self.ckpt, self.ckpt - 1)
        self.cr.wait_dumps()
        img = self.cr.dump_future(self.ckpt).result()
        self.walls.append(img.wall_ms)
        self.modes[img.mode] = self.modes.get(img.mode, 0) + 1
        self.dirty += img.dirtied_chunks

    def finish(self) -> Dict[str, float]:
        import time

        health = self.cr.health()
        out = {
            "mode": self.mode,
            # median: single-core container noise makes the mean swing ±40%
            "dump_ms_per_ckpt": float(np.median(self.walls)),
            "bytes_written": self.cr.store.stats.bytes_written - self.bytes_before,
            "dirty_chunks": self.dirty,
            "state_bytes": self.n_keys * self.chunks_per_key * self.elems_per_chunk * 4,
            "chosen_modes": dict(self.modes),
            "dirty_pred_mae": health.get("dirty_pred_mae"),
        }
        # slow-path restore cost: evict templates, rebuild the newest image
        for ckpt in list(self.cr._templates):
            self.cr.evict_template(ckpt)
        t0 = time.perf_counter()
        self.cr.restore(self.ckpt)
        out["slow_restore_ms"] = (time.perf_counter() - t0) * 1e3
        self.cr.shutdown()
        return out


def _run_ratio(
    ratio: float,
    modes: tuple,
    rounds: int,
    *,
    n_keys: int,
    chunks_per_key: int,
    chunk_bytes: int,
    n_ckpts: int,
) -> Dict[str, Dict[str, float]]:
    """Replay the identical workload through every mode, ``rounds`` times.

    Rounds are whole interleaved replays; each mode's wall is the *best*
    round median, so a load spike has to hit all rounds to bias a mode —
    the noise guard behind the auto ≥ legacy gate."""
    best: Dict[str, Dict[str, float]] = {}
    for rnd in range(rounds):
        chains = [
            _Chain(mode, n_keys=n_keys, chunks_per_key=chunks_per_key, chunk_bytes=chunk_bytes)
            for mode in modes
        ]
        rng = np.random.default_rng(11)   # same seed per round: same cells
        for step in range(n_ckpts):
            cells = _dirty_cells(n_keys, chunks_per_key, ratio, rng)
            for chain in chains:          # identical workload, interleaved
                chain.step(cells, float(step + 2))
        for chain in chains:
            rec = chain.finish()
            prev = best.get(rec["mode"])
            rec["rounds_ms"] = ([] if prev is None else prev["rounds_ms"]) + [
                rec["dump_ms_per_ckpt"]
            ]
            if prev is not None and prev["dump_ms_per_ckpt"] < rec["dump_ms_per_ckpt"]:
                prev["rounds_ms"] = rec["rounds_ms"]
                continue
            best[rec["mode"]] = rec
    return best


def run() -> List[Row]:
    # Many medium tensors, like a sandbox namespace (KV page groups, env
    # buffers, optimizer shards) — the shape the dirty-key hint exploits.
    if quick():
        n_keys, chunks_per_key, chunk_bytes, n_ckpts = 64, 8, 32 * 1024, 5
    else:
        n_keys, chunks_per_key, chunk_bytes, n_ckpts = 128, 8, 64 * 1024, 7
    geom = dict(
        n_keys=n_keys, chunks_per_key=chunks_per_key,
        chunk_bytes=chunk_bytes, n_ckpts=n_ckpts,
    )
    _warmup(chunks_per_key, chunk_bytes)
    rows: List[Row] = []
    results: Dict[str, Dict] = {}
    for ratio in DIRTY_RATIOS:
        tag = f"{int(ratio * 100)}pct"
        results[tag] = _run_ratio(
            ratio, ("legacy", "digest", "delta", "auto"), ROUNDS, **geom
        )
        for mode, rec in results[tag].items():
            rows.append(
                Row(
                    f"fig11/{tag}/{mode}/dump",
                    rec["dump_ms_per_ckpt"] * 1e3,
                    f"bytes={rec['bytes_written']};restore_ms={rec['slow_restore_ms']:.2f}",
                )
            )
        legacy = results[tag]["legacy"]
        auto = results[tag]["auto"]
        speedup = legacy["dump_ms_per_ckpt"] / max(auto["dump_ms_per_ckpt"], 1e-9)
        byte_ratio = auto["bytes_written"] / max(legacy["state_bytes"] * n_ckpts, 1)
        results[tag]["speedup"] = {
            "dump_speedup_x": speedup,
            "auto_vs_legacy_x": speedup,
            "delta_bytes_over_state_bytes": byte_ratio,
            "auto_modes": auto["chosen_modes"],
        }
        rows.append(Row(f"fig11/{tag}/speedup", speedup, f"bytes_frac={byte_ratio:.4f}"))
    # crossover sweep: where does the engine flip, and does auto still win?
    results["crossover"] = {}
    for ratio in SWEEP_RATIOS:
        tag = f"{int(ratio * 100)}pct"
        recs = _run_ratio(ratio, ("legacy", "auto"), 1, **geom)
        x = recs["legacy"]["dump_ms_per_ckpt"] / max(
            recs["auto"]["dump_ms_per_ckpt"], 1e-9
        )
        results["crossover"][tag] = {
            "auto_vs_legacy_x": x,
            "auto_ms": recs["auto"]["dump_ms_per_ckpt"],
            "legacy_ms": recs["legacy"]["dump_ms_per_ckpt"],
            "auto_modes": recs["auto"]["chosen_modes"],
            "dirty_pred_mae": recs["auto"]["dirty_pred_mae"],
        }
        rows.append(Row(f"fig11/crossover/{tag}", x, f"modes={recs['auto']['chosen_modes']}"))
    out_path = os.environ.get("REPRO_BENCH_OUT", "BENCH_dump_pipeline.json")
    with open(out_path, "w") as f:
        json.dump(
            {
                "config": {
                    "n_keys": n_keys,
                    "chunks_per_key": chunks_per_key,
                    "chunk_bytes": chunk_bytes,
                    "n_checkpoints": n_ckpts,
                    "rounds": ROUNDS,
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
