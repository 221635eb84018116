"""Named spans of the program's phases, on the profiler's clock.

``span("deltacr.decode", ckpt=7)`` marks a phase as a host event named
``repro.deltacr.decode`` in the JAX profiler's trace, beside the device's
operations and on the same clock, so an idle stretch of the device can be
put down to what the host was doing in it.  Names are ``<layer>.<phase>``;
a span nests under the span open on its own thread, and its keyword
arguments become the event's stats.  Counts ride on spans as such args, so
they are kept at the same boundaries as the time.  The id that joins one
request's spans across threads goes in the args: ``cr`` and ``ckpt`` name a
checkpoint of one ``DeltaCR``, its dump on the worker and any later restore
of it.

With no profiler capturing, ``span`` costs one check of
``TraceMe.is_enabled()``: it builds no name and formats no arg, and ``with
span(...) as sp`` binds ``sp`` to None.  Args known only at the end of a
phase, or costly to compute, go in under ``if sp is not None:
sp.set_metadata(...)``, or under ``enabled()``.

Nothing is stored here: the profiler holds the events and writes them out
when it stops.  Capture with ``jax.profiler.trace(logdir)`` around the
calls to look at.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any

from jax.profiler import TraceAnnotation

__all__ = ["enabled", "span", "timed"]

_OFF = contextlib.nullcontext()


def enabled() -> bool:
    """Whether a profiler is capturing spans now."""
    return TraceAnnotation.is_enabled()


def span(name: str, **args: Any):
    """Context manager marking the phase ``repro.<name>`` with ``args``."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return TraceAnnotation(f"repro.{name}", **args)


class timed:
    """A span that also keeps its own wall time, in ms, in ``.ms``: one
    pair of boundaries for the trace and for a stage timer the program
    reports itself (``DumpImage.encode_ms``)."""

    __slots__ = ("_span", "_t0", "ms")

    def __init__(self, name: str, **args: Any):
        self._span = span(name, **args)
        self.ms = 0.0

    def __enter__(self) -> "timed":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._span.__exit__(*exc)
