"""Fig 8 analogue: CoW-fault absorption vs post-restore idle window.

After a fork-based restore the child's first writes hit shared pages.  The
async-warm thread privatizes the hot set in the background; the longer the
agent's post-restore idle window (LLM latency), the fewer faults remain on
the critical path.  Sweeps the idle window and reports the inline-fault
fraction absorbed.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from repro.core import CowArrayState, DeltaCR

from .common import Row, quick


def run() -> List[Row]:
    n_hot = 16
    elems = (1 << 20) // 4        # 1 MB per hot array
    rng = np.random.default_rng(0)
    windows_ms = [0.0, 1.0, 5.0] if quick() else [0.0, 0.5, 1.0, 2.0, 5.0, 20.0]
    rows: List[Row] = []
    reps = 3 if quick() else 6
    for window_ms in windows_ms:
        absorbed, inline = 0, 0
        for rep in range(reps):
            state = CowArrayState(
                {f"h{i}": rng.standard_normal(elems).astype(np.float32) for i in range(n_hot)},
                hot_keys=tuple(f"h{i}" for i in range(n_hot)),
            )
            cr = DeltaCR(restore_fn=lambda p: CowArrayState(dict(p)), async_warm=True)
            cr.checkpoint(state, 1, None, dump=False)
            restored, _ = cr.restore(1)       # async warm fires in background
            time.sleep(window_ms / 1e3)       # the agent's idle window
            for i in range(n_hot):            # post-restore turn dirties the heap
                restored.mutate(f"h{i}", lambda a: a.__setitem__(0, 1.0))
            absorbed += restored.warmed_copies
            inline += restored.cow_faults
            restored.release()
            cr.shutdown()
        frac = absorbed / max(absorbed + inline, 1)
        rows.append(
            Row(
                f"fig8/idle_{window_ms:g}ms", window_ms * 1e3,
                f"absorbed_frac={frac:.2f};inline_faults={inline/reps:.1f}",
            )
        )
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
