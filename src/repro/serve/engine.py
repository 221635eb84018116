"""Serving engine: prefill + batched decode over forkable paged sessions.

The engine owns the model params and the page pool, and exposes the three
operations the agent sandbox needs:

* ``new_session(prompt)`` — prefill a prompt into freshly allocated pages.
* ``step(sessions)``      — one batched decode step: host-side CoW
  preparation (``ensure_writable``), stacked paged decode, per-session
  sampling.  Sessions in the batch may be arbitrary forks of each other —
  the pool's refcounts make sharing safe.
* ``logprobs`` / greedy & temperature sampling with *checkpointable* RNG
  (seed+counter live in session extras, so a restored session replays the
  identical token stream — rollback determinism, §2.2).

Recurrent architectures (mamba/xlstm sublayers) carry their states in
``session.extras`` as immutable jnp arrays: fork is aliasing, restore is
rebinding — the degenerate-but-fastest DeltaCR case (DESIGN.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import spans
from repro.models.model import Model
from .kvcache import PagePool, PagedSession

__all__ = ["Engine", "SamplingParams"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0        # 0 = greedy
    seed: int = 0


class Engine:
    def __init__(
        self,
        model: Model,
        params: Any,
        pool: PagePool,
    ):
        self.model = model
        self.params = params
        self.pool = pool
        self.cfg = model.cfg
        self._decode_jit: Dict[int, Any] = {}
        self._prefill_jit: Dict[int, Any] = {}

    # ------------------------------------------------------------ sessions
    def new_session(
        self,
        prompt_tokens: Sequence[int],
        sampling: SamplingParams = SamplingParams(),
    ) -> PagedSession:
        sess = PagedSession(self.pool)
        sess.extras["rng_seed"] = np.asarray([sampling.seed], np.int64)
        sess.extras["rng_counter"] = np.asarray([0], np.int64)
        sess.extras["temperature"] = np.asarray([sampling.temperature], np.float32)
        prompt = list(int(t) for t in prompt_tokens)
        sess.tokens = list(prompt)
        S = len(prompt)
        with self.pool.lock:
            sess.ensure_writable(extra_tokens=S)
            sess.seq_len = S

            cache = self._build_cache([sess], init_recurrent=True)
            tokens = jnp.asarray([prompt], jnp.int32)
            prefill = self._get_prefill(S)
            logits, new_cache = prefill(self.params, tokens, cache)
            self._absorb_cache([sess], new_cache)
        logits_np = np.asarray(logits[0], np.float32)
        sess.extras["last_logits"] = logits_np
        sess.extras["prompt_len"] = np.asarray([S], np.int64)
        # The first generated token comes from the prefill logits; it is
        # appended as the *pending* token (K/V not yet written — the next
        # step writes it at position seq_len).
        sess.tokens.append(self._sample(sess, logits_np))
        return sess

    # ----------------------------------------------------------- decoding
    def step(self, sessions: Sequence[PagedSession]) -> List[int]:
        """One decode step for every session; returns the sampled tokens.

        Each session's ``tokens[-1]`` is its *pending* token (sampled but not
        yet in the cache); the step commits its K/V at position ``seq_len``
        and samples the next pending token.
        """
        # 1. host-side CoW preparation, batched: every session's page motion
        # is planned first, then committed through ONE transactional
        # materialize call — one stacked-kernel launch per layer tag for the
        # whole batch, and a failure (injected fault, allocator, verify)
        # rolls every plan back before any decode math runs
        # the whole step holds the pool lock: the async-warm worker commits
        # materializations into the same pool arrays this step functionally
        # updates, and an interleaved commit would be silently overwritten
        with spans.span("engine.step", batch=len(sessions)):
            with self.pool.lock:
                with spans.span("engine.plan") as sp:
                    plans: List[Any] = []
                    try:
                        for s in sessions:
                            plans.append(s.plan_writable(extra_tokens=1))
                    except BaseException:
                        self.pool.discard_plans(plans)
                        raise
                    pages = self.pool.materialize(plans)
                    if sp is not None:
                        sp.set_metadata(pages=pages)
                # 2. stacked decode
                with spans.span("engine.build"):
                    last = [s.tokens[-1] for s in sessions]
                    cache = self._build_cache(sessions)
                    tokens = jnp.asarray(last, jnp.int32)
                with spans.span("engine.dispatch"):
                    decode = self._get_decode(len(sessions))
                    logits, new_cache = decode(self.params, tokens, cache)
                with spans.span("engine.absorb"):
                    self._absorb_cache(sessions, new_cache, advance=True)
            # 3. sampling with checkpointable rng, once the logits are here
            with spans.span("engine.fetch"):
                logits_np = np.asarray(logits, np.float32)
            with spans.span("engine.sample"):
                out = []
                for i, s in enumerate(sessions):
                    tok = self._sample(s, logits_np[i])
                    s.tokens.append(tok)
                    s.extras["last_logits"] = logits_np[i]
                    out.append(tok)
        return out

    def generate(self, session: PagedSession, n_tokens: int) -> List[int]:
        """Return the first ``n_tokens`` generated after the prompt, stepping
        as needed (the first one was already sampled at prefill)."""
        plen = int(session.extras["prompt_len"][0])
        while len(session.tokens) < plen + n_tokens:
            self.step([session])
        return [int(t) for t in session.tokens[plen : plen + n_tokens]]

    # ----------------------------------------------------------- internals
    def _sample(self, sess: PagedSession, logits: np.ndarray) -> int:
        temp = float(sess.extras["temperature"][0])
        if temp <= 0.0:
            return int(np.argmax(logits))
        seed = int(sess.extras["rng_seed"][0])
        counter = int(sess.extras["rng_counter"][0])
        rng = np.random.default_rng((seed, counter))
        z = logits / temp
        z = z - z.max()
        p = np.exp(z) / np.sum(np.exp(z))
        tok = int(rng.choice(len(p), p=p))
        sess.extras["rng_counter"] = np.asarray([counter + 1], np.int64)
        return tok

    def _build_cache(self, sessions: Sequence[PagedSession], *, init_recurrent: bool = False):
        """Assemble the stacked cache pytree for a batch of sessions."""
        cfg = self.cfg
        B = len(sessions)
        cache: Dict[str, Any] = {
            "lens": jnp.asarray([s.seq_len for s in sessions], jnp.int32)
        }
        tables = jnp.asarray(np.stack([s.table for s in sessions]), jnp.int32)
        for i, stage in enumerate(cfg.stages):
            entries: Dict[str, Any] = {}
            N = stage.n_periods
            for li, layer in enumerate(stage.period):
                for si, kind in enumerate(layer):
                    tag = f"l{li}_s{si}_{kind}"
                    if kind in ("attn", "attn_local"):
                        entries[tag] = {
                            "pk": self.pool.pools_k[f"stage{i}"][tag],
                            "pv": self.pool.pools_v[f"stage{i}"][tag],
                            "table": jnp.broadcast_to(tables[None], (N,) + tables.shape),
                        }
                    elif kind in ("mamba", "mlstm", "slstm"):
                        from repro.models.model import _init_cache_entry

                        if init_recurrent:
                            proto = _init_cache_entry(kind, cfg, B, 1)
                            entries[tag] = jax.tree.map(
                                lambda a: jnp.broadcast_to(a[None], (N,) + a.shape), proto
                            )
                        else:
                            key = f"stage{i}/{tag}"
                            per = [s.extras[key] for s in sessions]  # each (N, 1, ...)
                            entries[tag] = jax.tree.map(
                                lambda *xs: jnp.concatenate(xs, axis=1), *per
                            )
            cache[f"stage{i}"] = entries
        return cache

    def _absorb_cache(self, sessions, new_cache, *, advance: bool = False) -> None:
        """Write updated pools back and split recurrent states per session."""
        cfg = self.cfg
        for i, stage in enumerate(cfg.stages):
            entries = new_cache[f"stage{i}"]
            for li, layer in enumerate(stage.period):
                for si, kind in enumerate(layer):
                    tag = f"l{li}_s{si}_{kind}"
                    if kind in ("attn", "attn_local"):
                        self.pool.pools_k[f"stage{i}"][tag] = entries[tag]["pk"]
                        self.pool.pools_v[f"stage{i}"][tag] = entries[tag]["pv"]
                    elif kind in ("mamba", "mlstm", "slstm"):
                        key = f"stage{i}/{tag}"
                        for b, s in enumerate(sessions):
                            s.extras[key] = jax.tree.map(
                                lambda a: a[:, b : b + 1], entries[tag]
                            )
        if advance:
            for s in sessions:
                s.seq_len += 1

    def _get_decode(self, batch: int):
        if batch not in self._decode_jit:
            self._decode_jit[batch] = jax.jit(self.model.decode_step)
        return self._decode_jit[batch]

    def _get_prefill(self, seq: int):
        if seq not in self._prefill_jit:
            self._prefill_jit[seq] = jax.jit(self.model.prefill)
        return self._prefill_jit[seq]
