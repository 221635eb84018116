"""Profiler capture and the reduction from a device trace to numbers.

``capture`` runs the window under ``jax.profiler`` (Python tracer off: it
would trace every call of the host loop).  ``load`` keeps, from the
``.xplane.pb``, the device planes' operation events and the benchmark's own
host spans (``bench.*`` annotations), in a small JSON-able form.
``reduce`` turns that into:

* ``busy_s`` / ``window_s``: the union of the intervals in which an
  operation ran on a device (averaged over the devices used), within the
  ``bench.window`` span, and that span's length;
* ``op_s``: device seconds by operation name;
* ``idle_gaps``: device idle time within the window, each gap attributed to
  the innermost benchmark host span that covers its midpoint.
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Tuple

OPS_LINES = ("XLA Ops",)
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"


def capture_start(logdir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)


def capture_stop(logdir: str) -> str:
    import jax

    jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {logdir}")
    return files[-1]


def load(path: str) -> Dict[str, Any]:
    """Device operation events and ``bench.*`` host spans of one trace:
    ``{"devices": {plane: [[name, start_ns, dur_ns, module], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            events = []
            lines = {ln.name: ln for ln in plane.lines}
            modules = _modules(lines.get(MODULES_LINE))
            for lname in OPS_LINES:
                ln = lines.get(lname)
                if ln is None:
                    continue
                for e in ln.events:
                    stats = dict(e.stats)
                    start = float(e.start_ns)
                    module = str(stats.get("hlo_module", "")) or _module_at(modules, start)
                    events.append([e.name, start, float(e.duration_ns), module])
            if events:
                out["devices"][plane.name] = events
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def _modules(line) -> Tuple[List[float], List[Tuple[float, float, str]]]:
    """The device's program runs (``XLA Modules`` line), sorted by start."""
    if line is None:
        return [], []
    runs = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns), e.name) for e in line.events)
    return [r[0] for r in runs], runs


def _module_at(modules, t: float) -> str:
    """Name of the program run that holds time ``t`` on the device, for an
    operation event that does not carry its module as a stat."""
    import bisect

    starts, runs = modules
    k = bisect.bisect_right(starts, t) - 1
    return runs[k][2] if k >= 0 and runs[k][0] <= t <= runs[k][1] else ""


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Busy and idle time of the devices within the window span; None if
    the trace holds no window span or no device operation."""
    wins = [h for h in trace["host"] if h[0] == WINDOW]
    if not wins or not trace["devices"]:
        return None
    w0 = wins[0][1]
    w1 = w0 + wins[0][2]
    segs = _innermost([h for h in trace["host"] if h[0] != WINDOW])
    seg_starts = [sg[0] for sg in segs]
    busy_total = 0.0
    op_s: Dict[str, float] = {}
    module_op_s: Dict[Tuple[str, str], float] = {}
    gaps: Dict[str, float] = {}
    for events in trace["devices"].values():
        iv = []
        for name, s, d, module in events:
            e = s + d
            if e <= w0 or s >= w1:
                continue
            s, e = max(s, w0), min(e, w1)
            iv.append((s, e))
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
            key = (module, name)
            module_op_s[key] = module_op_s.get(key, 0.0) + (e - s) * 1e-9
        merged = _merge(iv)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            gaps_name = _covering(segs, seg_starts, (a + b) / 2)
            gaps[gaps_name] = gaps.get(gaps_name, 0.0) + (b - a) * 1e-9
    n = len(trace["devices"])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "op_s": op_s,
        "module_op_s": module_op_s,
        "idle_gaps": {k: v / n for k, v in gaps.items()},
    }


def _innermost(spans: List[List[Any]]) -> List[Tuple[float, float, str]]:
    """Flatten nested host spans into non-overlapping segments, each named
    by the innermost span that covers it."""
    points = sorted({x for _, s, d in spans for x in (s, s + d)})
    segs: List[Tuple[float, float, str]] = []
    if not points:
        return segs
    import bisect

    best: List[Optional[Tuple[float, str]]] = [None] * len(points)
    for name, s, d in spans:
        i, j = bisect.bisect_left(points, s), bisect.bisect_left(points, s + d)
        for k in range(i, j):
            if best[k] is None or d < best[k][0]:
                best[k] = (d, name)
    for k in range(len(points) - 1):
        if best[k] is not None:
            segs.append((points[k], points[k + 1], best[k][1]))
    return segs


def _covering(segs, starts, t: float) -> str:
    """Name of the innermost benchmark span covering time ``t``."""
    import bisect

    k = bisect.bisect_right(starts, t) - 1
    if k >= 0 and segs[k][0] <= t <= segs[k][1]:
        return segs[k][2][len("bench."):]
    return "host: outside benchmark spans"


def kernel_seconds(red: Dict[str, Any], match) -> float:
    """Device seconds of the operations a kernel file's ``match`` accepts."""
    return sum(t for (module, name), t in red["module_op_s"].items() if match(name, module))


def top(d: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
