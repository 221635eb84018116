"""Shared arithmetic of the metric readers in ``bench/metrics``.

Each reader takes the finished ``Run`` and returns a number, or None when
the run has nothing for it to read (then the metric is left out of the
result line; a share of a roofline or a peak is never reported as 0 for
want of a reading).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from bench.common import BENCH_DIR, load_module, percentile


def samples(run, *labels: str) -> List[float]:
    out: List[float] = []
    for label in labels:
        out.extend(run.rec.samples.get(label, []))
    return out


def p90(run, *labels: str) -> Optional[float]:
    return percentile(samples(run, *labels), 90)


def median(xs: List[float]) -> Optional[float]:
    return percentile(xs, 50)


def per_window(run, key: str) -> Optional[float]:
    v = run.values.get(key)
    return None if v is None else v / run.window_s


def decode_step_ms(run) -> Optional[float]:
    """Host time in ``Engine.step`` over the window's steps."""
    xs = samples(run, "decode_step")
    return sum(xs) / len(xs) if xs else None


def _steps(run) -> List[List[int]]:
    return getattr(run.system, "steps", []) if getattr(run, "system", None) else []


def decode_mfu(run) -> Optional[float]:
    """Model FLOPs of every token decoded in the window, over the window,
    as a share of the chip's peak bf16 FLOP/s."""
    from bench.model import family

    steps = _steps(run)
    if not steps:
        return None
    fam = family(run.config, run.root)
    m = fam.dims(run.config)
    flops = sum(fam.decode_flops(m, c) for ctx in steps for c in ctx)
    return 100.0 * flops / run.window_s / run.peaks["bf16_flops_per_s"]


def device_idle(run) -> Optional[float]:
    red = run.red
    if red is None or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def roofline(run, kernel: str, calls: List[Dict[str, Any]]) -> Optional[float]:
    """Least time the chip needs for the recorded calls (the larger of
    operations over peak FLOP/s and bytes over peak bandwidth, per call),
    over the device time of the kernel's operations in the trace."""
    from bench import trace as btrace

    if run.red is None or not calls:
        return None
    mod = load_module(os.path.join(BENCH_DIR, "kernels", kernel + ".py"))
    t = btrace.kernel_seconds(run.red, mod.match)
    if t <= 0:
        return None
    F, B = run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"]
    need = 0.0
    for call in calls:
        flops, nbytes = mod.cost(call)
        need += max(flops / F, nbytes / B)
    return 100.0 * need / t


def attention_calls(run) -> List[Dict[str, Any]]:
    """One paged-attention call per attention layer per decode step of the
    window."""
    from bench.model import family

    fam = family(run.config, run.root)
    m = fam.dims(run.config)
    psz = int(run.traffic["page_size"])
    return [
        {"contexts": ctx, "page_size": psz, "heads": m.heads, "kv_heads": m.kv_heads,
         "head_dim": m.head_dim, "itemsize": 2}
        for ctx in _steps(run) for _ in range(fam.attention_layers(m))
    ]
