"""GRPO-style rollout fan-out: prompts checkpointed once, rollouts forked
from them every round, decoded together in one batch, checkpointed at
every turn end and rolled back to their prompt at round end.

Per prompt, one sandbox (DeltaFS repository + the prefilled prompt
session) under a ``StateManager`` is checkpointed once; a ``SandboxTree``
forks ``rollouts_per_prompt`` live sandboxes from that checkpoint each
round.  All rollouts of all prompts decode through one ``Engine.step``
batch.  A turn ends with one tool action per rollout on its repository
view and a checkpoint of every rollout (``SandboxTree.checkpoint``: layer
freeze, template fork, dump submit).  A round ends when every rollout has
decoded its turns; the rollouts are released and the next round forks
again from the prompt checkpoints.  One rollout per prompt and round
decodes greedily, the others sample at the mix's temperature with seeds
of their own.
"""
from __future__ import annotations

import time
from typing import Any, List

import numpy as np

from bench import state as bstate
from bench import traffic
from bench.common import log
from bench.serving import System, probe_kernels


def setup(run) -> None:
    from repro.core import DeltaCR, DeltaFS, Sandbox, SandboxTree, StateManager
    from repro.serve import PagedSession, SamplingParams

    T = run.traffic
    sysm = System(run, num_pages=T["pool_pages"], page_size=T["page_size"],
                  pages_per_session=T["pages_per_session"])
    run.system = sysm
    probe_kernels(run)

    def restore_fn(p):
        return PagedSession.restore_from_payload(sysm.pool, p)

    prompts = traffic.prompts(run.seed, T["prompts"], T["prompt_tokens"], sysm.dims.vocab)
    t = time.perf_counter()
    run.trees, run.bases = [], []
    run.states = bstate.StateLog()
    for i, p in enumerate(prompts):
        root = sysm.engine.new_session(p, SamplingParams(temperature=0.0))
        fs = DeltaFS(chunk_bytes=64 * 1024)
        traffic.build_repo(fs, T["repo"], traffic.sub_seed(run.seed, "repo", i))
        # async warm off: it races fork/release of the restored session
        # (PERF.md, Open questions); the template pool keeps its default
        cr = DeltaCR(store=fs.store, restore_fn=restore_fn,
                     async_warm=False)
        sm = StateManager(Sandbox(fs, root), cr)
        base = sm.checkpoint()
        run.states.checkpoint(("prompt", i), sm.sandbox)
        run.trees.append(SandboxTree(sm))
        run.bases.append(base)
        run.crs.append(cr)
    log(f"prefill {len(prompts)} x {T['prompt_tokens']} tokens + prompt checkpoints: "
        f"{time.perf_counter() - t:.2f} s")
    run.ckpt_ids, run.round_ckpts, run.served_pool = [], [], []
    run.tokens_decoded = 0
    # ---- warm-up: one whole round (every shape the window uses), then one
    # fork per prompt through the prompt image (slow path)
    t = time.perf_counter()
    _round(run, -1, deadline=None)
    for i, tree in enumerate(run.trees):
        tree.cr.wait_dumps()
        tree.cr.evict_template(run.bases[i])
        kid = tree.fork(run.bases[i], 1)[0]
        bstate.kv_checksum(kid.proc).block_until_ready()
        tree.release(kid.sandbox_id)
    _reclaim(run, keep=0)
    run.ckpt_ids, run.served_pool, run.tokens_decoded = [], [], 0
    run.attempted = 0
    run.states.rollbacks.clear()
    log(f"warm-up round: {time.perf_counter() - t:.2f} s")


def _round(run, r: int, deadline) -> bool:
    """One round; returns False if the deadline cut it."""
    T = run.traffic
    rec, engine = run.rec, run.system.engine
    kids = []
    greedy = (r % T["rollouts_per_prompt"]) if r >= 0 else 0
    for i, tree in enumerate(run.trees):
        with rec.span("rollback"):
            forks = tree.fork(run.bases[i], T["rollouts_per_prompt"])
        for j, kid in enumerate(forks):
            with rec.span("check"):
                run.states.rollback(("prompt", i), "fork", kid)
            kid.proc.extras["temperature"] = np.asarray(
                [0.0 if j == greedy else T["temperature"]], np.float32)
            kid.proc.extras["rng_seed"] = np.asarray(
                [traffic.sub_seed(run.seed, "rollout", r, i, j)], np.int64)
            kid.proc.extras["rng_counter"] = np.asarray([0], np.int64)
            kids.append((i, j, kid))
    run.live = kids
    run.attempted += len(kids)
    sessions = [k.proc for _, _, k in kids]
    made: List[Any] = []
    run.round_ckpts.append(made)
    done = True
    for turn in range(T["turns"]):
        for _ in range(T["tokens_per_turn"]):
            if deadline is not None and time.perf_counter() >= deadline:
                done = False
                break
            engine.step(sessions)
            if rec.active:
                run.tokens_decoded += len(sessions)
        if not done:
            break
        for i, j, kid in kids:
            tree = run.trees[i]
            with rec.span("tool"):
                traffic.edit_repo(kid.fs, T["repo"],
                                  traffic.sub_seed(run.seed, "tool", r, i, j, turn))
            with rec.span("ckpt"):
                c = tree.checkpoint(kid.sandbox_id, dump=True)
            with rec.span("check"):
                run.states.checkpoint((i, c), kid)
            made.append((i, c))
            if rec.active:
                run.ckpt_ids.append((i, c))
    for i, j, kid in kids:
        if j == greedy:
            run.served_pool.append(list(kid.proc.tokens))
    if done:
        _release(run)
        _reclaim(run, keep=1)
    return done


def _release(run) -> None:
    for i, _, kid in run.live:
        run.trees[i].release(kid.sandbox_id)
    run.live = []


def _reclaim(run, keep: int) -> None:
    """Reclaim the checkpoints of all but the ``keep`` newest rounds."""
    while len(run.round_ckpts) > keep:
        for i, c in run.round_ckpts.pop(0):
            run.trees[i].sm.reclaim(c)


def window(run, seconds: float) -> None:
    deadline = time.perf_counter() + seconds
    r = 0
    while _round(run, r, deadline):
        r += 1
    run.values["rounds"] = r


def after_window(run) -> None:
    T = run.traffic
    futs = {key: run.trees[key[0]].cr.dump_future(key[1]) for key in run.ckpt_ids}
    futs = {k: f for k, f in futs.items() if f is not None}
    landed = [k for k, f in futs.items() if f.done() and f.exception() is None]
    run.values.update(
        tokens=run.tokens_decoded,
        dumps_submitted=len(futs), dumps_landed=len(landed),
        dump_backlog=sum(1 for f in futs.values() if not f.done()),
        dump_wall_ms=[futs[k].result().wall_ms for k in landed],
        dump_failed=sum(1 for f in futs.values() if f.done() and f.exception() is not None),
    )
    _release(run)
    for (i, c), f in futs.items():          # stop the backlog: cancel, do not wait
        if not f.done():
            run.trees[i].cr.drop_checkpoint(c)
    g = traffic.rng(run.seed, "check")
    # ---- landed images decode to their checkpoint's state
    alive = {k for rnd in run.round_ckpts for k in rnd}
    cand = [k for k in landed if k in alive]
    for n in g.permutation(len(cand))[: T["check_images"]]:
        i, c = cand[int(n)]
        tree = run.trees[i]
        tree.cr.evict_template(c)
        kid = tree.fork(c, 1)[0]
        run.image_states.rollback((i, c), "image", kid)
        tree.release(kid.sandbox_id)
    # ---- served tokens of greedy rollouts for the reference
    pool = sorted(run.served_pool, key=len)
    pick = [pool[-1]] + [pool[int(n)] for n in g.permutation(len(pool) - 1)[: T["check_sequences"] - 1]] if pool else []
    run.served = [
        (np.asarray(t, np.int32), np.arange(len(t)) >= T["prompt_tokens"]) for t in pick
    ]


def release(run) -> None:
    for tree in run.trees:
        tree.release_all()
        tree.sm.sandbox.proc.release()
        tree.cr.shutdown()
    run.trees = []
    run.system.release()
