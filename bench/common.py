"""Shared pieces of the benchmark harness: timers, spans, compile counting.

Nothing here imports the program under ``src/``; the yardstick lives with
the benchmark so that a program change cannot move it.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import logging
import os
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


# ----------------------------------------------------------------- timers
class EventTimer:
    """Collects per-event wall times by label (milliseconds); the recording
    half of ``benchmarks/common.EventTimer``, copied so that a program
    change cannot move it."""

    def __init__(self):
        self.samples: Dict[str, List[float]] = {}

    def record(self, label: str, seconds: float) -> None:
        self.samples.setdefault(label, []).append(seconds * 1e3)


class Recorder(EventTimer):
    """Host-clock samples, counters and named spans of one run.

    ``span(name)`` times a call into one layer of the program; with tracing
    on it also writes the span into the profiler's trace
    (``jax.profiler.TraceAnnotation``) so that device idle gaps can be
    attributed to what the host was doing.  Samples are only kept while
    ``active`` (the measured window); set-up and the checks after the
    window record nothing.
    """

    def __init__(self):
        super().__init__()
        self.counts: Dict[str, int] = {}
        self.active = False
        self.tracing = False

    def count(self, name: str, n: int = 1) -> None:
        if self.active:
            self.counts[name] = self.counts.get(name, 0) + n

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            if self.active:
                self.record(name, dt)


def percentile(xs: List[float], q: float) -> Optional[float]:
    """``numpy.percentile`` (linear) of ``xs``; None for no samples."""
    import numpy as np

    return float(np.percentile(xs, q)) if xs else None


# ------------------------------------------------------------ compilation
class CompileCounter:
    """Counts jaxpr traces and backend compiles reported by JAX, and names
    the program of each compile and the caller that met it.

    A trace means a jitted function met a new shape in this process; a
    backend compile means the program was compiled or loaded from the
    persistent cache."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.traces = 0
        self.compiles = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        # the program each compile is for, from JAX's own debug log; the
        # debug records stop here, warnings go on to JAX's handlers
        names = self.names

        class _Names(logging.Filter):
            def filter(self, record: logging.LogRecord) -> bool:
                if record.levelno >= logging.INFO:
                    return True
                msg = record.getMessage()
                if msg.startswith("Compiling "):
                    name = msg[len("Compiling "):].split(" with global shapes")[0][:200]
                    names.append(f"{name} from {_caller()}")
                return False

        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.addFilter(_Names())

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1

    def snapshot(self) -> Dict[str, int]:
        return {"traces": self.traces, "compiles": self.compiles, "programs": len(self.names)}


def _caller() -> str:
    """The innermost frame of the program or the benchmark on this thread's
    stack, as ``file:line function``."""
    import traceback

    for f in reversed(traceback.extract_stack()):
        if ("/repro/" in f.filename or "/bench/" in f.filename) and not f.filename.endswith("common.py"):
            return f"{'/'.join(f.filename.split('/')[-2:])}:{f.lineno} {f.name}"
    return "?"


# ---------------------------------------------------------------- process
def process_start_epoch() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        start_ticks = int(fields[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f if line.startswith("btime"))
        return btime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, StopIteration, ValueError, IndexError):
        return time.time()


# ---------------------------------------------------------------- loading
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a benchmark file by path (names may hold dots and dashes).

    The module is registered in ``sys.modules`` under its name: dataclasses
    look up the module of a class they build."""
    import sys

    mod_name = name or "bench_" + os.path.relpath(path, BENCH_DIR).replace(os.sep, "_").replace(
        ".", "_"
    ).replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(msg: str) -> None:
    """Progress and readings go to stderr; stdout's last line is the result."""
    import sys

    print(msg, file=sys.stderr, flush=True)
