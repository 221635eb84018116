"""Fig 9 analogue: per-edit copy-up bytes vs edited-file size.

Three storage configurations over real agent-sized edits (4 KB dirtied at a
random offset inside files of 1–256 KB):

* ``full_copy``       — re-materialize the whole file per edit (ext4/XFS
                        without reflink: copy-up grows linearly with size)
* ``chunk_4k``        — DeltaFS with 4 KiB chunks (reflink-grade sharing)
* ``chunk_64k``       — DeltaFS with 64 KiB chunks (coarser blocks)

The reflink claim: copy-up bytes stay flat in file size because only the
dirtied blocks are duplicated, and an unmodified extent is shared by all N
generations.
"""
from __future__ import annotations

from typing import List

import numpy as np

from repro.core import DeltaFS

from .common import Row, quick


def run() -> List[Row]:
    rng = np.random.default_rng(0)
    sizes_kb = [1, 8, 64, 256] if quick() else [1, 4, 8, 16, 32, 64, 128, 256]
    edit_bytes = 4096
    n_edits = 4 if quick() else 10
    rows: List[Row] = []
    for size_kb in sizes_kb:
        n = size_kb * 1024 // 4
        edit_elems = min(edit_bytes // 4, n)
        base = rng.standard_normal(n).astype(np.float32)
        results = {}
        for label, chunk in (("full_copy", None), ("chunk_4k", 4096), ("chunk_64k", 65536)):
            per_edit = []
            if chunk is None:
                cur = base.copy()
                for _ in range(n_edits):
                    pos = int(rng.integers(0, max(n - edit_elems, 1)))
                    cur = cur.copy()
                    cur[pos : pos + edit_elems] = 1.0
                    per_edit.append(cur.nbytes)          # whole file re-copied
            else:
                fs = DeltaFS(chunk_bytes=chunk)
                fs.write("f", base)
                fs.checkpoint()
                cur = base.copy()
                for _ in range(n_edits):
                    pos = int(rng.integers(0, max(n - edit_elems, 1)))
                    cur[pos : pos + edit_elems] = rng.standard_normal(edit_elems)
                    before = fs.store.stats.bytes_written
                    fs.write("f", cur)
                    fs.checkpoint()
                    per_edit.append(fs.store.stats.bytes_written - before)
            results[label] = float(np.median(per_edit))
        for label, med in results.items():
            rows.append(
                Row(
                    f"fig9/{label}/file_{size_kb}kb", 0.0,
                    f"copyup_bytes={med:.0f}",
                )
            )
        amp = results["full_copy"] / max(results["chunk_4k"], 1)
        rows.append(Row(f"fig9/amplification_{size_kb}kb", 0.0, f"fullcopy_vs_4k={amp:.1f}x"))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(r.csv())
