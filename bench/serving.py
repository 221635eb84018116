"""Builds the system under test for a cell and puts the benchmark's probes
around its calls.

The program is driven only through its own entry points (``Engine``,
``PagePool``, ``DeltaCR``, ``StateManager``, ``SandboxTree``, ``MCTS``).
The probes wrap those calls from the outside: host-clock spans around
``Engine.step`` and the checkpoint / rollback calls, and a record of the
shapes each dump-encode kernel is called with (for the roofline
counts in ``bench/kernels``).  None of them changes what the call does.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List

import numpy as np

from bench.common import log
from bench.model import family


class System:
    """Model, weights, page pool and engine of one cell."""

    def __init__(self, run, *, num_pages: int, page_size: int, pages_per_session: int):
        import jax

        from repro.models import Model
        from repro.serve import Engine, PagePool

        self.run = run
        fam = family(run.config, run.root)
        self.dims = fam.dims(run.config)
        self.cfg = fam.program_config(run.config)
        self.model = Model(self.cfg)
        t = time.perf_counter()
        self.params = fam.make_weights(self.dims, run.seed)
        want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.model.param_shapes())
        got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), self.params)
        if want != got:
            raise ValueError("benchmark weights do not match the program's parameter tree")
        log(f"weights: {sum(int(a.size) for a in jax.tree.leaves(self.params))} params "
            f"{self.dims.dtype}, {time.perf_counter() - t:.2f} s")
        self.pool = PagePool(
            self.cfg, num_pages=num_pages, page_size=page_size,
            max_pages_per_session=pages_per_session,
        )
        self.engine = Engine(self.model, self.params, self.pool)
        self._wrap_step()

    def _wrap_step(self) -> None:
        """Time every ``Engine.step``, note the live context it decoded and
        the most pool pages in use after a step of the window (the pool is
        reserved whole, so ``memory_peak_bytes`` alone cannot show them)."""
        rec = self.run.rec
        inner = self.engine.step
        steps: List[List[int]] = []
        self.steps = steps

        self.pages_peak = 0

        @functools.wraps(inner)
        def step(sessions):
            ctx = [s.seq_len + 1 for s in sessions]
            with rec.span("decode_step"):
                out = inner(sessions)
            if rec.active:
                steps.append(ctx)
                in_use = self.pool.num_pages - 1 - self.pool.free_pages()
                self.pages_peak = max(self.pages_peak, in_use)
            return out

        self.engine.step = step

    def release(self) -> None:
        """Drop the program's device state (pools, engine); keep weights."""
        self.engine = None
        self.pool = None


def probe_kernels(run) -> None:
    """Record the shapes of every dump-encode kernel call made while the
    window is open (``run.kernel_calls``).  The output that says how much
    work a call did (its dirty-row count) stays on the device and is read
    after the window."""
    from repro.kernels import ops as kops

    calls: Dict[str, List[Any]] = {"fused_encode": []}
    run.kernel_calls = calls
    enc = kops.fused_encode

    def fused_encode(old, new, max_changed):
        out = enc(old, new, max_changed)
        if run.rec.active:
            calls["fused_encode"].append(
                {"rows": int(old.shape[0]), "row_bytes": int(np.prod(old.shape[1:])) * old.dtype.itemsize,
                 "cap": int(max_changed), "count": out[2]}
            )
        return out

    kops.fused_encode = fused_encode


def wrap_state_manager(run, sm, states) -> None:
    """Time every checkpoint and rollback of ``sm`` and record the state it
    saved or gave back (``states``: a ``bench.state.StateLog``)."""
    rec = run.rec
    run.node_tokens = {}
    ckpt, restore = sm.checkpoint, sm.restore

    @functools.wraps(ckpt)
    def checkpoint(*a, **kw):
        with rec.span("ckpt"):
            cid = ckpt(*a, **kw)
        if rec.active:
            with rec.span("check"):
                states.checkpoint(cid, sm.sandbox)
            run.ckpt_ids.append(cid)
            if kw.get("dump", True):
                run.node_tokens[cid] = list(sm.sandbox.proc.tokens)
        return cid

    @functools.wraps(restore)
    def restore_(cid):
        t0 = time.perf_counter()
        with rec.span("rollback"):
            path = restore(cid)
        if rec.active:
            dt = time.perf_counter() - t0
            slow = not path.startswith("fast")
            rec.record("rollback_slow" if slow else "rollback_fast", dt)
            rec.count("rollbacks_slow" if slow else "rollbacks_fast")
            with rec.span("check"):
                states.rollback(cid, path, sm.sandbox)
        return path

    sm.checkpoint = checkpoint
    sm.restore = restore_
