"""Jit'd public entry points for the Pallas kernels.

Backend routing, decided at trace time:

* **TPU** — the Pallas kernels compile natively.
* **CPU/GPU with ``REPRO_INTERPRET=1``** — the kernels execute in
  ``interpret=True`` mode, which runs the kernel body in Python for
  bit-correct validation against ``ref.py`` (this is what the test suite
  pins; see ``tests/conftest.py``).
* **CPU/GPU otherwise** — the pure-jnp oracles from ``ref.py``: identical
  semantics, XLA-vectorized, and orders of magnitude faster than the Python
  interpreter.  This is what production hot paths (the DeltaCR dump
  pipeline, benchmarks) get on non-TPU hosts.
* ``REPRO_FORCE_REF=1`` — bypass Pallas entirely everywhere (escape hatch).

The env vars are read when a call first traces for a given shape; set them
before the first call (the benchmarks and conftest both do).
"""
from __future__ import annotations

import functools
import os
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from . import tile_diff as _tile_diff
from .delta_apply import delta_apply as _delta_apply_kernel
from .delta_diff import delta_diff as _delta_diff_kernel
from .delta_fused import compact_rows as _compact_rows
from .delta_fused import delta_fused as _delta_fused_kernel
from .page_copy import page_copy as _page_copy_kernel
from .page_copy import page_copy_stacked as _page_copy_stacked_kernel
from .paged_attention import paged_attention as _paged_attention_kernel
from .ref import CHECKSUM_LANES

__all__ = [
    "paged_attention",
    "page_copy",
    "page_copy_stacked",
    "delta_diff",
    "delta_apply",
    "delta_compact",
    "delta_encode",
    "fused_encode",
    "shard_block_encode",
    "chunk_checksums_device",
    "chunk_checksums_host",
    "device_fetch",
    "start_host_fetch",
    "start_shard_fetch",
    "shard_fetch",
    "shard_fetch_assemble",
    "use_interpret",
    "CHECKSUM_LANES",
]


def use_interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU backends."""
    return jax.default_backend() != "tpu"


def _force_ref() -> bool:
    return os.environ.get("REPRO_FORCE_REF", "0") == "1"


def _use_kernel() -> bool:
    """Native Pallas on TPU; interpret-mode Pallas only when pinned."""
    if _force_ref():
        return False
    return jax.default_backend() == "tpu" or os.environ.get("REPRO_INTERPRET", "0") == "1"


@functools.partial(jax.jit, static_argnames=("scale",))
def _paged_attention_jit(q, k_pages, v_pages, page_table, seq_lens, scale):
    if not _use_kernel():
        return _ref.paged_attention_ref(q, k_pages, v_pages, page_table, seq_lens, scale=scale)
    return _paged_attention_kernel(
        q, k_pages, v_pages, page_table, seq_lens, scale=scale, interpret=use_interpret()
    )


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *, scale=None):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _paged_attention_jit(q, k_pages, v_pages, page_table, seq_lens, float(scale))


@jax.jit
def _page_copy_jit(pool, src_idx, dst_idx):
    if not _use_kernel():
        return _ref.page_copy_ref(pool, src_idx, dst_idx)
    return _page_copy_kernel(pool, src_idx, dst_idx, interpret=use_interpret())


def page_copy(pool, src_idx, dst_idx):
    return _page_copy_jit(pool, src_idx, dst_idx)


@jax.jit
def _page_copy_stacked_jit(pool, src_idx, dst_idx):
    if not _use_kernel():
        return _ref.page_copy_stacked_ref(pool, src_idx, dst_idx)
    return _page_copy_stacked_kernel(pool, src_idx, dst_idx, interpret=use_interpret())


def page_copy_stacked(pool, src_idx, dst_idx):
    return _page_copy_stacked_jit(pool, src_idx, dst_idx)


@jax.jit
def _delta_diff_jit(old, new):
    if not _use_kernel():
        return _ref.delta_diff_ref(old, new)
    return _delta_diff_kernel(old, new, interpret=use_interpret())


def delta_diff(old, new):
    return _delta_diff_jit(old, new)


@jax.jit
def _delta_apply_jit(base, data, idx):
    if not _use_kernel():
        return _ref.delta_apply_ref(base, data, idx)
    return _delta_apply_kernel(base, data, idx, interpret=use_interpret())


def delta_apply(base, data, idx):
    return _delta_apply_jit(base, data, idx)


@functools.partial(jax.jit, static_argnames=("max_changed",))
def delta_compact(new, dirty, max_changed: int):
    """Fixed-capacity compaction of dirty chunks (pure jnp; shape-static)."""
    return _ref.delta_compact_ref(new, dirty, max_changed)


@functools.partial(jax.jit, static_argnames=("max_changed",))
def delta_encode(old, new, max_changed: int):
    """diff + compact in one jit: (data, idx, count).

    The dump-pipeline hot path: one fused dispatch per tensor, returning the
    fixed-capacity compacted dirty chunks so the host moves O(delta) bytes.
    """
    dirty = (
        _delta_diff_kernel(old, new, interpret=use_interpret())
        if _use_kernel()
        else _ref.delta_diff_ref(old, new)
    )
    return _compact_rows(new, dirty, max_changed)


@functools.partial(jax.jit, static_argnames=("max_changed",))
def fused_encode(old, new, max_changed: int):
    """diff + compact + checksum in ONE kernel pass: (data, idx, count, sums).

    The adaptive dump pipeline's fused hot path — dirty bytes are read once
    on device and come back with 4-lane uint32 integrity checksums
    (``ref.chunk_checksums_ref`` lanes) that the drain stage can verify
    against the DMA'd bytes on host.  Contract (shapes, slot order, -1 idx
    padding, count-over-capacity overflow signal) is identical to
    ``delta_encode`` plus the sums output; ``ref.fused_encode_ref`` is the
    bit-exact oracle.
    """
    if not _use_kernel():
        return _ref.fused_encode_ref(old, new, max_changed)
    return _delta_fused_kernel(
        old, new, max_changed=max_changed, interpret=use_interpret()
    )


@functools.partial(jax.jit, static_argnames=("counts", "tile", "max_changed"))
def shard_block_encode(old, new, counts, tile, max_changed: int):
    """Block-native diff + compact for one shard part (``tile_diff``).

    Same (data, idx, count) contract as ``delta_encode``, but over the
    shard's NATIVE block layout instead of a materialized tile grid: device
    work is O(state) reads + O(delta) writes, where the grid path pays two
    O(state) byte-transposes per dump (old + new) before it ever diffs.
    """
    return _tile_diff.block_encode(
        old, new, counts, tile, max_changed,
        use_kernel=_use_kernel(), interpret=use_interpret(),
    )


@jax.jit
def chunk_checksums_device(chunks):
    """Device-side ``ref.chunk_checksums_ref`` lanes over compacted rows.

    Drain calls this on the power-of-two fetch slice, so the integrity
    lanes cost O(fetched rows * chunk) instead of O(capacity * chunk) —
    the block-native encode never pays for checksums on rows it will not
    ship.
    """
    return _ref.chunk_checksums_ref(chunks)


# numpy mirror constants of ref.chunk_checksums_ref — kept in lockstep
_CS_MULT = np.uint32(2654435761)
_CS_ADD = np.uint32(40503)
_CS_XOR = np.uint32(2246822519)


def chunk_checksums_host(chunks: np.ndarray) -> np.ndarray:
    """Numpy mirror of ``ref.chunk_checksums_ref``: (N, C) → (N, 4) uint32.

    Used by the dump drain stage to validate fetched fused-kernel rows
    against the device-computed lanes without a jax round-trip — one
    vectorized pass at host memory bandwidth.
    """
    x = np.ascontiguousarray(chunks)
    if x.ndim == 1:
        x = x[None, :]
    x = x.view(np.uint8).reshape(x.shape[0], -1).astype(np.uint32)
    C = x.shape[-1]
    pos = np.arange(C, dtype=np.uint32)[None, :]
    w = pos * _CS_MULT + _CS_ADD
    s0 = np.sum(x, axis=-1, dtype=np.uint32)
    s1 = np.sum(x * (pos + np.uint32(1)), axis=-1, dtype=np.uint32)
    s2 = np.sum(x * w, axis=-1, dtype=np.uint32)
    s3 = np.sum((x + np.uint32(1)) * (w ^ _CS_XOR), axis=-1, dtype=np.uint32)
    return np.stack([s0, s1, s2, s3], axis=-1)


def start_host_fetch(*arrays) -> None:
    """Begin async device→host copies without blocking.

    On TPU this starts the DMA for each committed array so a later
    ``np.asarray`` finds the bytes already on host; the streaming dump
    engine calls it at encode time so the copy of window *k* overlaps the
    diff dispatch of window *k+1*.  Backends (or tracers) without
    ``copy_to_host_async`` make this a no-op — ``np.asarray`` then blocks
    as usual, which is still correct.
    """
    for a in arrays:
        fn = getattr(a, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except Exception:
                pass  # best-effort: the blocking fetch below stays correct


def device_fetch(*arrays) -> List[np.ndarray]:
    """Materialize device arrays on host, overlapping the copies."""
    start_host_fetch(*arrays)
    return [np.asarray(a) for a in arrays]


# --------------------------------------------------------------------------
# shard-granular fetches (the gather-free dump path)
# --------------------------------------------------------------------------
def start_shard_fetch(*arrays) -> None:
    """Begin async device→host copies per addressable shard.

    The sharded analogue of :func:`start_host_fetch`: each shard's DMA
    starts from its own device, so no cross-device gather is dispatched.
    Arrays without shard structure fall back to the whole-array prestart."""
    for a in arrays:
        shards = getattr(a, "addressable_shards", None)
        if shards is None:
            start_host_fetch(a)
            continue
        for sh in shards:
            fn = getattr(sh.data, "copy_to_host_async", None)
            if fn is not None:
                try:
                    fn()
                except Exception:
                    pass  # best-effort: the blocking fetch stays correct


def shard_fetch(array) -> List[Tuple[Any, np.ndarray]]:
    """Explicit per-shard device→host fetch: ``[(device, host_block), ...]``.

    Uses ``jax.device_get`` on each shard's single-device block — never
    materializes the global array, so it is legal under a disallow
    transfer guard and moves each block exactly once from its own device.
    Unsharded inputs return a single ``(device_or_None, host_array)``."""
    import jax

    shards = getattr(array, "addressable_shards", None)
    if shards is None:
        dev = None
        devs = getattr(array, "devices", None)
        if devs is not None:
            ds = list(devs())
            dev = ds[0] if len(ds) == 1 else None
        return [(dev, np.asarray(jax.device_get(array)))]
    start_shard_fetch(array)
    out: List[Tuple[Any, np.ndarray]] = []
    seen = set()
    for sh in shards:
        key = tuple(
            (s.start or 0, s.stop if s.stop is not None else dim)
            for s, dim in zip(sh.index, array.shape)
        )
        if key in seen:
            continue  # replicated shard: one copy is enough
        seen.add(key)
        out.append((sh.device, np.asarray(jax.device_get(sh.data))))
    return out


def shard_fetch_assemble(array) -> np.ndarray:
    """Host materialization of a (possibly sharded) array, assembled from
    per-shard fetches — the full-payload fallback (digest/legacy dumps) for
    sharded state.  O(S) bytes move, but each byte leaves its own device
    exactly once and assembly happens in host memory, never on device."""
    shards = getattr(array, "addressable_shards", None)
    if shards is None:
        import jax

        return np.asarray(jax.device_get(array))
    import jax

    start_shard_fetch(array)
    out = np.empty(array.shape, dtype=np.dtype(str(array.dtype)))
    seen = set()
    for sh in shards:
        key = tuple(
            (s.start or 0, s.stop if s.stop is not None else dim)
            for s, dim in zip(sh.index, array.shape)
        )
        if key in seen:
            continue
        seen.add(key)
        out[sh.index] = np.asarray(jax.device_get(sh.data))
    return out
