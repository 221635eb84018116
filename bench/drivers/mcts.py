"""Serial MCTS over a served session: the search waits on every call.

One agent sandbox (DeltaFS repository + ``PagedSession``) under a
``StateManager``; ``MCTS`` with ``DecodeSearchTask`` expands a node by
forcing one of the parent's top tokens, decoding ``tokens_per_action``
greedy tokens and running one tool action on the repository, then
checkpoints the child and evaluates it under value isolation.  Every
selection of another node is a rollback.
"""
from __future__ import annotations

import time

import numpy as np

from bench import state as bstate
from bench import traffic
from bench.common import log
from bench.serving import System, probe_kernels, wrap_state_manager


def _task_class():
    from repro.search.decode_task import DecodeSearchTask

    class SweTask(DecodeSearchTask):
        """``DecodeSearchTask`` plus the archetype's tool action on the
        repository and the artifacts its tests leave behind.  The tool
        action and the value (the task's test outcome) follow the node's
        place in the tree, the ranks of the actions from the root, and not
        the seed: every seed searches the same tree, on its own prompt and
        weights, so a run's nodes and rollbacks do not change with it."""

        def __init__(self, engine, *, repo, prompt_len, rec, **kw):
            super().__init__(engine, **kw)
            self.repo, self.prompt_len, self.rec = repo, prompt_len, rec
            self.paths = {}          # tokens after the prompt -> action ranks from the root
            self.ranks = {}          # those tokens plus an action -> its rank there

        def _node(self, sandbox):
            return tuple(sandbox.proc.tokens[self.prompt_len:])

        def propose_actions(self, sandbox, rng_seed):
            actions = super().propose_actions(sandbox, rng_seed)
            node = self._node(sandbox)
            for rank, a in enumerate(actions):
                self.ranks[node + (int(a),)] = rank
            return actions

        def apply_action(self, sandbox, action):
            node = self._node(sandbox)
            path = self.paths.get(node, ()) + (self.ranks.get(node + (int(action),), 0),)
            with self.rec.span("tool"):
                traffic.edit_repo(sandbox.fs, self.repo, traffic.sub_seed(0, "action", *path))
            super().apply_action(sandbox, action)
            self.paths[self._node(sandbox)] = path

        def evaluate(self, sandbox):
            path = self.paths.get(self._node(sandbox), ())
            super().evaluate(sandbox)
            traffic.leave_test_artifacts(sandbox.fs, len(sandbox.proc.tokens))
            return float(traffic.rng(0, "value", *path).random())

    return SweTask


def greedy_mask(n_tokens: int, prompt: int, k: int) -> np.ndarray:
    """Which tokens of a node's sequence were decoded greedily: everything
    after the prompt except the forced action tokens at ``prompt + k*j``
    (the last token, still pending, is greedy)."""
    t = np.arange(n_tokens)
    forced = (t >= prompt) & ((t - prompt) % k == 0) & (t < n_tokens - 1)
    return (t >= prompt) & ~forced


def setup(run) -> None:
    from repro.core import DeltaCR, DeltaFS, Sandbox, StateManager
    from repro.search import MCTSConfig
    from repro.serve import PagedSession, SamplingParams

    T = run.traffic
    sysm = System(run, num_pages=T["pool_pages"], page_size=T["page_size"],
                  pages_per_session=T["pages_per_session"])
    run.system = sysm
    probe_kernels(run)
    prompt = traffic.prompts(run.seed, 1, T["prompt_tokens"], sysm.dims.vocab)[0]
    t = time.perf_counter()
    root = sysm.engine.new_session(prompt, SamplingParams(temperature=0.0))
    log(f"prefill {len(prompt)} tokens: {time.perf_counter() - t:.2f} s")

    def restore_fn(p):
        return PagedSession.restore_from_payload(sysm.pool, p)

    Task = _task_class()

    def make(proc):
        fs = DeltaFS(chunk_bytes=64 * 1024)
        traffic.build_repo(fs, T["repo"], run.seed)
        # async warm off: it races fork/release of the restored session
        # (PERF.md, Open questions); the template pool keeps its default
        cr = DeltaCR(store=fs.store, restore_fn=restore_fn,
                     async_warm=False)
        sm = StateManager(Sandbox(fs, proc), cr)
        task = Task(sysm.engine, repo=T["repo"], prompt_len=T["prompt_tokens"], rec=run.rec,
                    k_tokens=T["tokens_per_action"], width=T["expand_width"])
        return sm, cr, task

    # ---- warm-up on a throwaway fork: one path to full depth, every node
    # dumped, checksummed and evaluated under isolation; a fast rollback
    # and an expansion from it; then every node rolled back through its image
    # over its parent's rebuilt generation, the window's slow path at each depth
    t = time.perf_counter()
    wsm, wcr, wtask = make(root.fork())

    def expand():
        action = wtask.propose_actions(wsm.sandbox, 0)[0]
        wtask.apply_action(wsm.sandbox, action)

    chain = []
    for depth in range(T["max_depth"] + 1):
        if depth:
            expand()
        chain.append(wsm.checkpoint())
        bstate.kv_checksum(wsm.sandbox.proc)     # each live-page bucket the window reads
        wsm.isolated_eval(wtask.evaluate)
    wsm.restore(chain[len(chain) // 2])
    expand()
    wcr.wait_dumps()
    for cid in chain:                # shallow first: each rebuilt parent is the base of the next
        wcr.evict_template(cid)
        wsm.restore(cid)
        wcr.evict_template(cid)      # only the shapes are wanted: free the rebuilt pages
    expand()                         # a decode step from a session rebuilt from its image
    bstate.kv_checksum(wsm.sandbox.proc).block_until_ready()
    wsm.sandbox.proc.release()
    for cid in chain:
        wcr.drop_checkpoint(cid)
    wcr.shutdown()
    log(f"warm-up: {len(chain)} checkpoints, slow and fast rollbacks: "
        f"{time.perf_counter() - t:.2f} s")

    sm, cr, task = make(root)
    run.states = bstate.StateLog()
    run.ckpt_ids = []
    wrap_state_manager(run, sm, run.states)
    run.sm, run.cr, run.task, run.prompt = sm, cr, task, prompt
    run.crs = [cr]
    run.mcts_cfg = MCTSConfig(
        iterations=10**9, expand_width=T["expand_width"], max_depth=T["max_depth"],
        value_isolation=bool(T["value_isolation"]), seed=traffic.sub_seed(run.seed, "mcts"),
        dump=bool(T["dump"]), time_budget_s=None,
    )


def window(run, seconds: float) -> None:
    import dataclasses

    from repro.search import MCTS

    cfg = dataclasses.replace(run.mcts_cfg, time_budget_s=seconds)
    search = MCTS(run.sm, run.task, cfg)
    run.stats = search.run()


def after_window(run) -> None:
    """Read what the window left (dump backlog, landed dumps), stop the
    queue, and roll back to a sample of landed images."""
    cr, st = run.cr, run.stats
    # children with a dump; transient isolation checkpoints have none
    dumped = [c for c in run.ckpt_ids if cr.dump_future(c) is not None]
    futs = {c: cr.dump_future(c) for c in dumped}
    landed = [c for c, f in futs.items() if f.done() and f.exception() is None]
    run.values.update(
        nodes=st.nodes - 1, iterations=st.iterations,
        dumps_submitted=len(dumped), dumps_landed=len(landed),
        dump_backlog=sum(1 for f in futs.values() if not f.done()),
        dump_wall_ms=[futs[c].result().wall_ms for c in landed],
        dump_failed=sum(1 for f in futs.values() if f.done() and f.exception() is not None),
        mcts_restore_s=st.time_restore_s, mcts_checkpoint_s=st.time_checkpoint_s,
    )
    run.attempted, run.failed = st.iterations, 0
    for c, f in futs.items():               # stop the backlog: cancel, do not wait
        if not f.done():
            cr.drop_checkpoint(c)
    # ---- served tokens for the reference: a sample of nodes, the deepest in it
    T = run.traffic
    nodes = [c for c in dumped if c in run.states.at_checkpoint and c in run.node_tokens]
    g = traffic.rng(run.seed, "check")
    nodes.sort(key=lambda c: len(run.node_tokens[c]))
    pick = list(g.choice(nodes[:-1], size=min(T["check_nodes"] - 1, len(nodes) - 1), replace=False)) if len(nodes) > 1 else []
    pick = [nodes[-1]] + [int(c) for c in pick] if nodes else []
    k = T["tokens_per_action"]
    run.served = [
        (np.asarray(run.node_tokens[c], np.int32),
         greedy_mask(len(run.node_tokens[c]), T["prompt_tokens"], k))
        for c in pick
    ]
    # ---- landed images decode to their checkpoint's state
    sample = list(g.choice(landed, size=min(T["check_images"], len(landed)), replace=False)) if landed else []
    for c in sample:
        c = int(c)
        cr.evict_template(c)
        path = run.sm.restore(c)
        run.image_states.rollback(c, path, run.sm.sandbox)


def release(run) -> None:
    run.sm.sandbox.proc.release()
    run.cr.shutdown()
    run.sm = run.cr = run.task = None
    run.system.release()
