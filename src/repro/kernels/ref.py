"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic references the kernel sweeps assert against
(``tests/test_kernels.py``) and the portable fallbacks ``ops.py`` uses when
Pallas is unavailable.  Shapes follow the serving substrate:

* KV pool per layer: ``(num_pages, page_size, kv_heads, head_dim)``
* page table per session: ``(max_pages,)`` int32 page indices
* chunked host/device state: ``(num_chunks, chunk_elems)``
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "paged_attention_ref",
    "page_copy_ref",
    "delta_diff_ref",
    "delta_apply_ref",
    "delta_compact_ref",
    "chunk_checksums_ref",
    "fused_encode_ref",
    "CHECKSUM_LANES",
]

# 4-lane vectorized integrity checksum over chunk bytes (uint32 wraparound).
# NOT a content-address: the chunk store's dedupe/verify key stays blake2b
# (see chunk_store.chunk_digest).  These lanes exist so the fused dump
# kernel can emit a digest of every dirty chunk in the same pass that diffs
# and compacts it — the host then validates the DMA'd bytes against the
# device-computed lanes (bitrot/truncation on the device→host path), and
# the kernel-vs-oracle parity suite asserts them bit-exactly.
CHECKSUM_LANES = 4
_CS_MULT = 2654435761        # Knuth multiplicative-hash constant
_CS_ADD = 40503
_CS_XOR = 2246822519


def paged_attention_ref(
    q: jax.Array,            # (B, KVH, G, D)   query grouped by kv head
    k_pages: jax.Array,      # (P, page_size, KVH, D)
    v_pages: jax.Array,      # (P, page_size, KVH, D)
    page_table: jax.Array,   # (B, max_pages) int32
    seq_lens: jax.Array,     # (B,) int32 — tokens currently in cache
    *,
    scale: Optional[float] = None,
) -> jax.Array:
    """Decode-step attention reading K/V through a page table.

    Returns (B, KVH, G, D).  Positions ≥ seq_len are masked; table entries
    beyond the active page count may be arbitrary valid page ids.
    """
    B, KVH, G, D = q.shape
    P, page_size, _, _ = k_pages.shape
    max_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    # Gather pages: (B, max_pages, page_size, KVH, D) -> (B, S, KVH, D)
    k = k_pages[page_table]      # (B, max_pages, page_size, KVH, D)
    v = v_pages[page_table]
    S = max_pages * page_size
    k = k.reshape(B, S, KVH, D)
    v = v.reshape(B, S, KVH, D)

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # scores: (B, KVH, G, S)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, kf)
    pos = jnp.arange(S)[None, :]                      # (1, S)
    mask = pos < seq_lens[:, None]                    # (B, S)
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, vf)
    return out.astype(q.dtype)


def page_copy_ref(
    pool: jax.Array,         # (P, page_size, KVH, D) or any (P, ...) pool
    src_idx: jax.Array,      # (n,) int32
    dst_idx: jax.Array,      # (n,) int32
) -> jax.Array:
    """CoW privatization: pool[dst_idx[i]] = pool[src_idx[i]].

    dst indices are distinct free pages (the allocator guarantees it), and
    src/dst sets are disjoint, so copy order is irrelevant.
    """
    return pool.at[dst_idx].set(pool[src_idx])


def page_copy_stacked_ref(
    pool: jax.Array,         # (N_periods, P, page_size, KVH, D)
    src_idx: jax.Array,      # (n,) int32
    dst_idx: jax.Array,      # (n,) int32
) -> jax.Array:
    """Stacked-pool CoW: pool[:, dst_idx[i]] = pool[:, src_idx[i]]."""
    return pool.at[:, dst_idx].set(pool[:, src_idx])


def delta_diff_ref(old: jax.Array, new: jax.Array) -> jax.Array:
    """Per-chunk dirty bitmap: any element differs → True.  (N, C) -> (N,)."""
    return jnp.any(old != new, axis=-1)


def delta_compact_ref(
    new: jax.Array,          # (N, C)
    dirty: jax.Array,        # (N,) bool
    max_changed: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack dirty chunks into a fixed-capacity buffer.

    Returns (data (max_changed, C), idx (max_changed,) int32 with -1 padding,
    count ()).  Deterministic: dirty chunks keep ascending order.
    """
    N, C = new.shape
    positions = jnp.cumsum(dirty.astype(jnp.int32)) - 1          # slot per dirty chunk
    count = jnp.sum(dirty.astype(jnp.int32))
    slot = jnp.where(dirty, positions, max_changed)              # overflow slot dropped
    data = jnp.zeros((max_changed + 1, C), new.dtype).at[slot].set(new, mode="drop")
    idx = jnp.full((max_changed + 1,), -1, jnp.int32).at[slot].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop"
    )
    return data[:max_changed], idx[:max_changed], count


def chunk_checksums_ref(chunks: jax.Array) -> jax.Array:
    """Per-row 4-lane uint32 checksums of an (N, C) chunk grid.

    Pure elementwise-multiply + row-sum in uint32 (wraparound) over the
    row's bytes (a wider dtype counts as its little-endian bytes) — the
    exact formulas the fused Pallas kernel evaluates per block, and
    mirrored in numpy by ``ops.chunk_checksums_host``.  Lane 0 is
    order-insensitive; lanes 1-3 weight by byte position so transpositions
    and shifts change the value.  Returns (N, CHECKSUM_LANES) uint32.
    """
    if chunks.dtype != jnp.uint8:
        chunks = jax.lax.bitcast_convert_type(chunks, jnp.uint8).reshape(chunks.shape[0], -1)
    x = chunks.astype(jnp.uint32)
    C = x.shape[-1]
    pos = jax.lax.broadcasted_iota(jnp.uint32, (1, C), 1)
    w = pos * jnp.uint32(_CS_MULT) + jnp.uint32(_CS_ADD)
    s0 = jnp.sum(x, axis=-1, dtype=jnp.uint32)
    s1 = jnp.sum(x * (pos + jnp.uint32(1)), axis=-1, dtype=jnp.uint32)
    s2 = jnp.sum(x * w, axis=-1, dtype=jnp.uint32)
    s3 = jnp.sum((x + jnp.uint32(1)) * (w ^ jnp.uint32(_CS_XOR)), axis=-1, dtype=jnp.uint32)
    return jnp.stack([s0, s1, s2, s3], axis=-1)


def fused_encode_ref(
    old: jax.Array,          # (N, C)
    new: jax.Array,          # (N, C)
    max_changed: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused dump kernel: diff + compact + checksum.

    Returns (data (max_changed, C), idx (max_changed,) int32 with -1
    padding, count () int32 of ALL dirty rows — count > max_changed means
    capacity overflow — and sums (max_changed, CHECKSUM_LANES) uint32,
    zeroed on unused slots).  Identical slot contents and ordering to
    ``delta_compact_ref``; the checksum of each valid slot is over the
    compacted row bytes.
    """
    dirty = delta_diff_ref(old, new)
    data, idx, count = delta_compact_ref(new, dirty, max_changed)
    sums = chunk_checksums_ref(data)
    sums = jnp.where((idx >= 0)[:, None], sums, jnp.uint32(0))
    return data, idx, count, sums


def delta_apply_ref(
    base: jax.Array,         # (N, C)
    data: jax.Array,         # (M, C) compacted dirty chunks
    idx: jax.Array,          # (M,) int32, -1 = padding
) -> jax.Array:
    """Scatter dirty chunks into base: base[idx[j]] = data[j] (idx>=0)."""
    safe = jnp.where(idx >= 0, idx, base.shape[0])               # pad rows dropped
    return base.at[safe].set(data, mode="drop")
