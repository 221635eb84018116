"""What a checkpoint must give back: digests of a sandbox's state.

At every checkpoint the harness records the sandbox's state (tokens,
sequence length, sampling rng extras, the DeltaFS view, a checksum of the
session's live KV pages computed on the device, and, where the model keeps
recurrent state per session, a checksum of that state computed the same
way); at every rollback it records the restored state the same way.  After
the window each rollback is compared with the record of the checkpoint it
rolled back to.  The checksums are dispatched asynchronously and read only
after the window: the window pays no device round trip for them, but their
device time (one read of the session's live pages, and of its recurrent
state) runs in the window and shows in the trace under the ``_kv_checksum``
and ``_recurrent_checksum`` programs, and their host time under the
``check`` span.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

RNG_KEYS = ("rng_seed", "rng_counter", "temperature")
DEVICE_FIELDS = ("kv", "recurrent")     # checksums left on the device until the window closed
PAGE_BUCKET = 16         # live pages are read in multiples of this: few compiled shapes


def _bits(a: jax.Array) -> jax.Array:
    """The bits of each element of ``a`` (2 or 4 bytes), as uint32."""
    uint = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    return jax.lax.bitcast_convert_type(a, uint).astype(jnp.uint32)


@jax.jit
def _kv_checksum(pools: Tuple[jax.Array, ...], table: jax.Array, seq_len: jax.Array) -> jax.Array:
    """Position-weighted uint32 sum of the bits of every live KV entry.

    ``pools`` are ``(layers, pages, page_size, kv_heads, head_dim)`` arrays;
    ``table`` maps a session's first page positions to pool pages.  Slots at or
    past ``seq_len`` are left out: they hold no token of this session."""
    acc = jnp.uint32(0)
    for i, pool in enumerate(pools):
        g = pool[:, table]                                   # (L, maxp, psz, KVH, Hd)
        bits = _bits(g)
        L, P, S = g.shape[:3]
        pos = (jnp.arange(P)[:, None] * S + jnp.arange(S)[None, :]).astype(jnp.uint32)
        live = pos < seq_len.astype(jnp.uint32)
        idx = jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 0)
        for ax in range(1, bits.ndim):
            idx = idx * jnp.uint32(bits.shape[ax]) + jax.lax.broadcasted_iota(
                jnp.uint32, bits.shape, ax
            )
        mult = (idx + jnp.uint32(i) * jnp.uint32(0x9E3779B9)) * jnp.uint32(2654435761) | jnp.uint32(1)
        term = jnp.where(live[None, :, :, None, None], bits * mult, jnp.uint32(0))
        acc = acc + jnp.sum(term, dtype=jnp.uint32)
    return acc


@jax.jit
def _recurrent_checksum(leaves: Tuple[jax.Array, ...]) -> jax.Array:
    """Position-weighted uint32 sum of the bits of every element of
    ``leaves`` (arrays of 2 or 4 bytes an element).  A change of
    any one element changes the sum: its weight is odd."""
    acc = jnp.uint32(0)
    for i, a in enumerate(leaves):
        bits = _bits(a).reshape(-1)
        idx = jnp.arange(bits.size, dtype=jnp.uint32)
        mult = (idx + jnp.uint32(i) * jnp.uint32(0x9E3779B9)) * jnp.uint32(2654435761) | jnp.uint32(1)
        acc = acc + jnp.sum(bits * mult, dtype=jnp.uint32)
    return acc


def recurrent_extras(session) -> Dict[str, Any]:
    """The session's recurrent state: the ``stage<i>/<tag>`` extras that
    ``Engine._absorb_cache`` writes, each a dict of arrays (``conv``,
    ``ssm`` for a Mamba layer)."""
    return {k: v for k, v in session.extras.items() if k.startswith("stage") and isinstance(v, dict)}


def recurrent_checksum(session):
    """Device-side checksum of a session's recurrent state (async), or None
    for a session that keeps none."""
    state = recurrent_extras(session)
    if not state:
        return None
    return _recurrent_checksum(tuple(jax.tree.leaves([state[k] for k in sorted(state)])))


def kv_checksum(session) -> jax.Array:
    """Device-side checksum of a ``PagedSession``'s live KV (async).

    Under the pool lock: the async-warm worker swaps table entries and pool
    arrays together under it, and a table read apart from its arrays could
    point at a page whose copy is not in them yet."""
    pool = session.pool
    with pool.lock:
        arrays = []
        for skey, tag in pool.attn_tags:
            arrays.append(pool.pools_k[skey][tag])
            arrays.append(pool.pools_v[skey][tag])
        seq_len = int(session.seq_len)
        n = -(-seq_len // pool.page_size)
        n = min(-(-max(n, 1) // PAGE_BUCKET) * PAGE_BUCKET, len(session.table))
        table = np.array(session.table[:n], np.int32)
    return _kv_checksum(tuple(arrays), jnp.asarray(table), jnp.asarray(seq_len, jnp.int32))


def fs_digest(fs) -> str:
    """Digest of a DeltaFS view: every live key with its chunk digests."""
    h = hashlib.blake2b(digest_size=16)
    for key in fs.keys():
        meta = fs.read_meta(key)
        h.update(key.encode())
        h.update(repr(meta.shape).encode())
        for d in meta.digests:
            h.update(d)
    return h.hexdigest()


def snapshot(sandbox) -> Dict[str, Any]:
    """State record of a sandbox (its checksums still on the device)."""
    sess = sandbox.proc
    toks = np.asarray(sess.tokens, np.int64)
    rec = {
        "tokens": hashlib.blake2b(toks.tobytes(), digest_size=16).hexdigest(),
        "n_tokens": int(toks.size),
        "seq_len": int(sess.seq_len),
        "fs": fs_digest(sandbox.fs),
        "kv": kv_checksum(sess),
    }
    recurrent = recurrent_checksum(sess)
    if recurrent is not None:
        rec["recurrent"] = recurrent
    for k in RNG_KEYS:
        if k in sess.extras:
            rec[k] = np.asarray(sess.extras[k]).tolist()
    return rec


class StateLog:
    """Checkpoint records and the rollbacks to compare against them."""

    def __init__(self):
        self.at_checkpoint: Dict[Any, Dict[str, Any]] = {}
        self.rollbacks: List[Tuple[Any, str, Dict[str, Any]]] = []

    def checkpoint(self, key: Any, sandbox) -> None:
        self.at_checkpoint[key] = snapshot(sandbox)

    def rollback(self, key: Any, path: str, sandbox) -> None:
        self.rollbacks.append((key, path, snapshot(sandbox)))

    def mismatches(self) -> Tuple[int, int, List[str]]:
        """(rollbacks compared, rollbacks that differ, first differences)."""
        compared, bad, notes = 0, 0, []
        pairs = [
            {f: (self.at_checkpoint[k].get(f), r.get(f)) for f in DEVICE_FIELDS}
            for k, _, r in self.rollbacks if k in self.at_checkpoint
        ]
        pairs = jax.device_get(pairs)
        i = 0
        for key, path, got in self.rollbacks:
            want = self.at_checkpoint.get(key)
            if want is None:
                bad += 1
                notes.append(f"rollback to {key} ({path}): no record of that checkpoint")
                continue
            sums = pairs[i]
            i += 1
            compared += 1
            diff = [f for f in want if f not in DEVICE_FIELDS and want[f] != got.get(f)]
            diff += [f for f in DEVICE_FIELDS if sums[f][0] != sums[f][1]]
            if diff:
                bad += 1
                if len(notes) < 5:
                    notes.append(f"rollback to {key} ({path}) differs in {','.join(diff)}")
        return compared, bad, notes
