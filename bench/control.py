"""Readings behind a cell's correctness limits, on the chip.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in a process of its own, one after the other (a seed's
device state is gone before the next starts): the cell's own set-up and a
short window of its traffic, the same checks as a benchmark run, then the
widest logit gap
of the served greedy tokens against the float32 reference (the program's
reading) and, on the same prompts and tokens, the gap of the token that
the reference computed with float8 weights puts first (the control's
reading).  A limit lies between the largest program reading and the
smallest control reading.  Each reading is also judged against the cell's
committed limits (``bench/limits/<cell>.json``), as ``bench/run.py``
judges a run: the program's run must come out correct and the control's,
in its place, not.  Prints one JSON line per seed and a summary line last.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run as brun  # noqa: E402
from bench.common import log  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    if len(args.seeds) == 1:
        return one_seed(args.workload, args.seconds, args.seeds[0])
    rows = []
    for seed in args.seeds:          # this process never touches the chip
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload", args.workload,
             "--seconds", str(args.seconds), "--seeds", str(seed)],
            stdout=subprocess.PIPE, text=True,
        )
        if p.returncode != 0:
            log(f"seed {seed}: exit code {p.returncode}")
            return p.returncode
        row = json.loads(p.stdout.strip().splitlines()[-1])
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({
        "workload": args.workload,
        "program_gap_max": max(r["program_gap"] for r in rows),
        "control_gap_min": min(r["control_gap"] for r in rows),
        "program_correct": all(r["program_correct"] for r in rows),
        "control_never_correct": not any(r["control_correct"] for r in rows),
        "seeds": len(rows),
    }), flush=True)
    return 0


def one_seed(workload: str, seconds: float, seed: int) -> int:
    t = time.time()
    brun.use_checkout_dirs()
    manifest, cell, config, traffic = brun.cell_spec(ROOT, workload)
    try:
        devices = brun.require_chip(int(cell["chips"]))
    except brun.NoChip as exc:
        log(f"refusing to run: {exc}")
        return 3
    _, counter = brun.prepare(ROOT)
    run = brun.measure(ROOT, manifest, cell, config, traffic, seed, seconds, 0,
                       devices, counter, t)
    gap, n = brun.reference_check(run)
    ctl, _ = brun.reference_check(run, control=True)
    limits = brun.limits_of(ROOT, cell)
    _, ok = brun.judge(limits, brun.readings_of(run, gap, n))
    _, ctl_ok = brun.judge(limits, brun.readings_of(run, ctl, n))
    print(json.dumps({
        "seed": seed, "program_gap": gap, "control_gap": ctl, "greedy_tokens": n,
        "program_correct": ok, "control_correct": ctl_ok,
        "rollbacks_checked": run.rb[0], "rollback_mismatch": run.rb[1],
        "images_checked": run.im[0], "image_mismatch": run.im[1],
        "compiles_in_window": run.in_window["compiles"],
        "setup_s": run.setup_s, "memory_peak_bytes": int(run.peak),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
