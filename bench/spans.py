"""The program's own spans in a traced run, for the metric readers.

The program marks its phases with ``repro.core.spans`` (host events named
``repro.<layer>.<phase>``, args as event stats) in the same profiler trace
as the device's operations and the benchmark's ``bench.*`` spans.
``bench/trace.py`` reads the device events and the benchmark's spans; this
module reads, from the same ``.xplane.pb``, the program's spans with their
thread and args (``load``), keeps those that overlap the ``bench.window``
span (``in_window``), and gives the readers in ``bench/metrics`` their
arithmetic.  A program that emits no span (one from before they existed)
gives an empty list, and every reader here then returns None.

``idle_by_phase`` is ``bench.trace.reduce`` with the program's spans on the
thread that opened the window taken as host spans beside the benchmark's:
each device idle gap goes to the innermost span over it, a program phase
(``deltacr.upload``, ``engine.sample``) where one covers it.  Spans of the
dump worker and the drain pool run beside that thread and explain no gap
of it; they feed metrics only.  With no program span it reads exactly as
``bench.trace.reduce``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Dict, List, Optional

from bench.trace import WINDOW, reduce

PROGRAM = "repro."


def load(path: str) -> Dict[str, Any]:
    """The program's spans of one trace and the window they ran in:
    ``{"window": [start_ns, end_ns] | None, "window_thread": thread,
    "spans": [[name, start_ns, dur_ns, thread, args], ...]}``, names
    without ``repro.``; a thread is its host line's place."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"window": None, "window_thread": None, "spans": []}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            thread = f"{plane.name}#{i}"
            for e in ln.events:
                if e.name == WINDOW and out["window"] is None:
                    s = float(e.start_ns)
                    out["window"] = [s, s + float(e.duration_ns)]
                    out["window_thread"] = thread
                elif e.name.startswith(PROGRAM):
                    out["spans"].append([e.name[len(PROGRAM):], float(e.start_ns),
                                         float(e.duration_ns), thread, {k: v for k, v in e.stats}])
    return out


def in_window(prog: Dict[str, Any]) -> Optional[List[Dict[str, Any]]]:
    """Spans that overlap the window, in start order, as ``{"name",
    "start", "end", "args", "main"}`` (ns, not cut at the window's edges;
    ``main``: on the window's thread); None without a window."""
    if prog["window"] is None:
        return None
    w0, w1 = prog["window"]
    out = [
        {"name": name, "start": s, "end": s + d, "args": args,
         "main": thread == prog["window_thread"]}
        for name, s, d, thread, args in prog["spans"]
        if s < w1 and s + d > w0
    ]
    return sorted(out, key=lambda sp: sp["start"])


def trace_file(run) -> Optional[str]:
    """The newest ``.xplane.pb`` the run's traced window wrote."""
    files = glob.glob(os.path.join(run.root, ".bench_trace", run.cell["name"], "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def of_run(run) -> Optional[Dict[str, Any]]:
    """``{"window": [w0, w1], "spans": in_window(...)}`` of a traced run,
    read once and kept on the run; None for an untraced run or a trace
    without a window."""
    if not run.trace:
        return None
    if not hasattr(run, "program_spans"):
        path = trace_file(run)
        prog = load(path) if path else {"window": None, "window_thread": None, "spans": []}
        spans = in_window(prog)
        run.program_spans = None if spans is None else {"window": prog["window"], "spans": spans}
    return run.program_spans


# ------------------------------------------------------------ arithmetic
def started(run, name: str, *, main: Optional[bool] = None) -> List[Dict[str, Any]]:
    """Spans named ``name`` that started in the window."""
    ps = of_run(run)
    if ps is None:
        return []
    w0, w1 = ps["window"]
    return [sp for sp in ps["spans"] if sp["name"] == name and w0 <= sp["start"] <= w1
            and (main is None or sp["main"] == main)]


def ended(run, name: str) -> List[Dict[str, Any]]:
    """Spans named ``name`` that ended in the window."""
    ps = of_run(run)
    if ps is None:
        return []
    w0, w1 = ps["window"]
    return [sp for sp in ps["spans"] if sp["name"] == name and w0 <= sp["end"] <= w1]


def ms(sp: Dict[str, Any]) -> float:
    return (sp["end"] - sp["start"]) * 1e-6


def within(run, outer: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    """Spans named ``name`` nested in ``outer`` on its thread."""
    spans = of_run(run)["spans"]
    k = bisect.bisect_left([sp["start"] for sp in spans], outer["start"])
    out = []
    for sp in spans[k:]:
        if sp["start"] > outer["end"]:
            break
        if sp["name"] == name and sp["main"] == outer["main"] and sp["end"] <= outer["end"]:
            out.append(sp)
    return out


def slow_restores(run) -> List[Dict[str, Any]]:
    """Restores on the window's thread that rebuilt the session from its
    dump image (``deltacr.restore`` with ``path`` slow)."""
    return [sp for sp in started(run, "deltacr.restore", main=True)
            if sp["args"].get("path") == "slow"]


def per_slow_restore_ms(run, phase: str) -> Optional[float]:
    """Mean time of ``phase`` per slow restore: the phases' means add up
    to the mean slow restore."""
    slow = slow_restores(run)
    if not slow:
        return None
    return sum(ms(p) for r in slow for p in within(run, r, phase)) / len(slow)


# ---------------------------------------------------------- idle gaps
def idle_by_phase(trace: Dict[str, Any], prog: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``bench.trace.reduce`` of ``trace`` (``bench.trace.load``) with the
    program's spans on the window's thread (``prog``: ``load``) among the
    host spans, each named ``bench.<layer>.<phase>`` so that the gaps it
    covers print as ``<layer>.<phase>``."""
    own = [["bench." + name, s, d] for name, s, d, thread, _ in prog["spans"]
           if thread == prog["window_thread"]]
    return reduce(dict(trace, host=trace["host"] + own))
