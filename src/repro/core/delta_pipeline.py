"""Device-to-store delta dump pipeline — the O(delta) checkpoint hot path.

The paper's Key Insight is that a sandbox should "duplicate only the changes
between consecutive checkpoints".  This module is where that happens for the
DeltaCR dump path:

* :class:`DeltaEncodable` extends the ``ForkableState`` protocol with
  :meth:`delta_generation`: a per-checkpoint *chunked view* of the state —
  fixed-size byte-chunk grids per tensor (zero-copy on host, bitcast on
  device) plus a dirty-key hint (keys written since the last checkpoint).
* :class:`DeltaDumpPipeline` diffs each generation against the previous one
  with ``kernels.delta_encode`` (dirty-chunk bitmap + fixed-capacity
  compaction in one jit) and moves **only the compacted dirty chunks**
  device→host.  Unchanged chunks are re-referenced from the parent image at
  the metadata level; keys the dirty hint clears are re-referenced without
  materializing a single byte.
* Slow-path restore runs in reverse: reconstruct from the nearest
  *materialized* base generation plus a ``kernels.delta_apply`` scatter of
  the image's dirty chunks fetched from the store — instead of concatenating
  and copying every chunk of every tensor.

Cost model per checkpoint (S = state bytes, Δ = changed bytes):

=====================  =============  ==========================
stage                  legacy          pipeline
=====================  =============  ==========================
serialize              O(S) host copy  0 (views are zero-copy)
parent compare         O(S) bytes ==   O(S) on-device diff (no PCIe)
device→host            O(S)            O(Δ) compacted chunks
hash + store           O(S)            O(Δ), hashed exactly once
=====================  =============  ==========================

Generations are retained in a small LRU (each anchored by the dump's own
fork, so CoW keeps the viewed pages immutable); a cache miss falls back to
the digest path, which is still O(S) hashing but O(Δ) store writes.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import (
    Any,
    Callable,
    Dict,
    FrozenSet,
    List,
    Optional,
    Protocol,
    Tuple,
    runtime_checkable,
)

import numpy as np

from . import faults, spans
from .chunk_store import ChunkStore, chunk_digest
from .deltafs import TensorMeta, digest_encode_array  # noqa: F401 (re-export)
from .faults import FaultError
from .stream import ChunkStreamEngine, StreamCancelled, WindowItem


_DTYPE_STR: Dict[Any, str] = {}


def dtype_str(dt) -> str:
    """Cached str(dtype) — surprisingly hot when a namespace has hundreds of
    tensors per checkpoint."""
    s = _DTYPE_STR.get(dt)
    if s is None:
        s = _DTYPE_STR[dt] = str(dt)
    return s


def _host_dirty_rows(old: np.ndarray, new: np.ndarray) -> np.ndarray:
    """Row indices where two (N, C) uint8 grids differ.

    Compares at the widest word dividing the row — an 8× smaller boolean
    intermediate than a per-byte compare."""
    n, c = old.shape
    for w in (np.uint64, np.uint32, np.uint16):
        if c % np.dtype(w).itemsize == 0:
            old = old.view(w)
            new = new.view(w)
            break
    return np.flatnonzero((old != new).any(axis=1)).astype(np.int64)


_ON_TPU: Optional[bool] = None


def _on_tpu() -> bool:
    global _ON_TPU
    if _ON_TPU is None:
        import jax

        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU

__all__ = [
    "ChunkedView",
    "DeltaDumpPipeline",
    "DeltaEncodable",
    "DeltaGeneration",
    "StreamCancelled",
    "digest_encode_array",
    "mark_clean",
    "mark_unknown",
]


# --------------------------------------------------------------------------
# Chunked views + generation protocol
# --------------------------------------------------------------------------
@dataclass
class ChunkedView:
    """A tensor as an ``(n_chunks, chunk_bytes)`` uint8 grid, built lazily.

    ``grid_fn`` materializes the grid (numpy for host state, a jax array for
    device state); it is only invoked when the key is actually dirty, so a
    clean tensor costs nothing.  The final row is zero-padded by
    ``trailing_pad`` bytes, matching the store's chunk convention — host and
    device chunks therefore hash identically and dedupe against each other.
    """

    shape: Tuple[int, ...]
    dtype: str                       # logical tensor dtype (e.g. "float32")
    nbytes: int
    chunk_bytes: int                 # bytes per grid row
    n_chunks: int
    trailing_pad: int
    grid_fn: Callable[[], Any] = field(repr=False)
    _grid: Any = field(default=None, repr=False)
    # Non-empty when grid rows are the tiles of a shard-native TilePlan
    # (dist.shard_dump) instead of a flat row-major byte split.  Kernel
    # diff/apply bases only pair with views of the *same* layout; metadata
    # digest compares are layout-independent and need no guard.
    tile_grid: Tuple[int, ...] = ()

    @property
    def grid(self) -> Any:
        if self._grid is None:
            self._grid = self.grid_fn()
        return self._grid

    def drop_cached_device_grid(self) -> None:
        """Free a cached *device* grid (re-gathered on next use; the anchor
        fork keeps the source pages alive).  Host grids are zero-copy views
        and stay cached."""
        if self._grid is not None and not isinstance(self._grid, np.ndarray):
            self._grid = None

    @staticmethod
    def from_host_array(arr: np.ndarray, chunk_bytes: int) -> "ChunkedView":
        """Zero-copy byte-grid over a contiguous host array (copy only for a
        padded tail row).  Requires ``arr.nbytes > 0``."""
        arr = np.ascontiguousarray(arr)
        nbytes = int(arr.nbytes)
        assert nbytes > 0, "empty tensors go through the digest path"
        n_chunks = -(-nbytes // chunk_bytes)
        pad = n_chunks * chunk_bytes - nbytes

        def build() -> np.ndarray:
            flat = arr.reshape(-1).view(np.uint8)
            if pad == 0:
                return flat.reshape(n_chunks, chunk_bytes)
            grid = np.zeros((n_chunks, chunk_bytes), np.uint8)
            grid.reshape(-1)[:nbytes] = flat
            return grid

        return ChunkedView(
            shape=tuple(arr.shape),
            dtype=dtype_str(arr.dtype),
            nbytes=nbytes,
            chunk_bytes=chunk_bytes,
            n_chunks=n_chunks,
            trailing_pad=pad,
            grid_fn=build,
        )


@dataclass
class DeltaGeneration:
    """One checkpoint's chunked snapshot, as produced by a DeltaEncodable.

    ``views`` are the kernel-diffable tensors; ``extras`` are small or
    irregular tensors that go through the per-chunk digest path.
    ``dirty_keys`` is the superset of keys that may differ from the parent
    generation (None = unknown → everything is treated as dirty).
    """

    views: Dict[str, ChunkedView] = field(default_factory=dict)
    extras: Dict[str, np.ndarray] = field(default_factory=dict)
    dirty_keys: Optional[FrozenSet[str]] = None

    def is_dirty(self, key: str) -> bool:
        return self.dirty_keys is None or key in self.dirty_keys


@runtime_checkable
class DeltaEncodable(Protocol):
    """ForkableState that can expose per-generation chunked views."""

    def fork(self) -> "DeltaEncodable": ...
    def release(self) -> None: ...
    def warm(self) -> None: ...
    def dump_payload(self) -> Dict[str, np.ndarray]: ...
    def delta_generation(self, chunk_bytes: int) -> DeltaGeneration: ...


# -- dirty-tracking duck helpers (states opt in by implementing the methods)
def mark_clean(state: Any, base_ckpt: Optional[int] = None) -> None:
    """Reset write tracking: the state is bit-identical to checkpoint
    ``base_ckpt``, and a dump whose parent is that same checkpoint may treat
    the tracked write set as exact.  The hint is *keyed* to the base — a
    dump against any other parent must ignore it (see dirty_base)."""
    fn = getattr(state, "reset_dirty_tracking", None)
    if fn is not None:
        fn(base_ckpt)


def mark_unknown(state: Any) -> None:
    """Invalidate write tracking: the state's lineage no longer matches the
    checkpoint the next dump will delta against (e.g. a transient checkpoint
    was dropped), so every key must be treated as dirty."""
    fn = getattr(state, "invalidate_dirty_tracking", None)
    if fn is not None:
        fn()


def dirty_base(state: Any) -> Optional[int]:
    """The checkpoint id the state's write tracking is relative to, or None
    when tracking is invalid/unanchored (treat everything as dirty)."""
    fn = getattr(state, "dirty_tracking_base", None)
    return fn() if fn is not None else None


# --------------------------------------------------------------------------
# Pipeline
# --------------------------------------------------------------------------
@dataclass
class _GenRecord:
    image_id: int
    views: Dict[str, ChunkedView]
    anchor: Optional[Any]            # fork keeping the viewed memory immutable
    pins: int = 0                    # in-flight encode/decode users
    dead: bool = False               # evicted; release anchor when unpinned

    def release(self) -> None:
        if self.anchor is not None:
            try:
                self.anchor.release()
            except Exception:
                pass
            self.anchor = None


@dataclass
class EncodeResult:
    entries: Dict[str, TensorMeta]
    dirtied: int
    clean_keys: int = 0              # metadata-level reuse (no bytes touched)
    kernel_keys: int = 0             # diffed on device via delta_encode
    full_keys: int = 0               # full materialization (new/overflow)
    # streaming accounting (zeros on the synchronous path)
    streamed: bool = False
    windows: int = 0
    window_bytes: int = 0            # the (possibly adaptive) budget used
    # stage times on either path (the dump.* spans' boundaries)
    encode_ms: float = 0.0
    drain_ms: float = 0.0
    commit_ms: float = 0.0
    stream_wall_ms: float = 0.0
    shard_parts: int = 0             # per-shard tasks run (sharded views only)


@dataclass
class _ShardRows:
    """One shard part's drained rows, keyed by *global* chunk id.

    Produced by the per-shard tasks of a sharded view; holds raw
    ``(payload, digest)`` pairs only — store folding is deferred to
    :meth:`DeltaDumpPipeline._commit_sharded_key`, which assembles every
    part's rows in global chunk order so the resulting metadata is
    chunk-for-chunk identical to a single-device dump.  ``chunk_ids`` stays
    empty: a part holds no store references, so the transactional rollback
    walk sees nothing to decref here."""

    plan_key: str                    # the owning tensor key (task key adds #shardK)
    rows: Dict[int, Tuple[bytes, Optional[bytes]]]
    kind: str                        # "kernel" | "full"
    chunk_ids: Tuple[int, ...] = ()


@dataclass
class _ShardedPlan:
    """Bookkeeping for one sharded view's fan-out: the per-part tasks plus
    the commit-time context (usable parent meta, whether any part diffed
    against a base)."""

    view: Any                        # dist.shard_dump.ShardedView (duck-typed)
    pm: Optional[TensorMeta]
    tasks: List["_KeyTask"]
    used_base: bool


@dataclass
class _KeyTask:
    """One non-clean tensor's dump work as a three-stage window item.

    ``encode`` (caller thread) runs the diff — a ``kernels.delta_encode``
    dispatch for device grids, the exact numpy compare for host grids, or
    nothing for full-materialization keys.  ``drain`` (overlap pool, pure)
    fetches the dirty rows device→host and produces ``(payload, digest)``
    per row — all GIL-releasing copy/hash work, no store access.  ``commit``
    (caller thread again) folds the rows into the store and returns
    ``(meta, dirtied, kind)`` with ``kind`` in {"kernel", "full"} (a
    capacity overflow detected in drain downgrades kernel → full)."""

    key: str
    weight: int
    encode: Callable[[], Any]
    drain: Callable[[Any], Any]
    commit: Callable[[Any], Tuple[TensorMeta, int, str]]

    def as_window_item(self) -> WindowItem:
        return WindowItem(
            key=self.key,
            weight=self.weight,
            encode=self.encode,
            drain=self.drain,
            commit=self.commit,
        )


class DeltaDumpPipeline:
    """Coordinates delta_encode dumps and delta_apply restores for one store."""

    def __init__(
        self,
        store: ChunkStore,
        *,
        capacity_frac: float = 0.5,
        max_generations: int = 4,
        stream: Optional[ChunkStreamEngine] = None,
        fused: bool = True,
        fused_verify: bool = True,
    ):
        self.store = store
        self.capacity_frac = float(capacity_frac)
        self.max_generations = int(max_generations)
        self.stream = stream
        self.fused = bool(fused)
        self.fused_verify = bool(fused_verify)
        self.fused_checksum_mismatches = 0    # host-verify failures (retried)
        self._gens: "OrderedDict[int, _GenRecord]" = OrderedDict()
        self._lock = threading.RLock()

    # ------------------------------------------------------------ gen cache
    #
    # Records are *pinned* while an encode/decode is reading their (lazy)
    # grids: eviction/replacement marks a pinned record dead and its anchor
    # is only released when the last reader unpins — otherwise a concurrent
    # restore could free the anchored pages mid-diff and corrupt the base.
    def record_for(self, image_id: Optional[int]) -> Optional[_GenRecord]:
        """Pinned lookup; pair every non-None return with release_record()."""
        if image_id is None:
            return None
        with self._lock:
            rec = self._gens.get(image_id)
            if rec is not None:
                rec.pins += 1
                self._gens.move_to_end(image_id)
            return rec

    def release_record(self, rec: Optional[_GenRecord]) -> None:
        if rec is None:
            return
        with self._lock:
            rec.pins -= 1
            releasable = rec.dead and rec.pins == 0
        if releasable:
            rec.release()

    def _retire_locked(self, rec: _GenRecord, out: list) -> None:
        rec.dead = True
        if rec.pins == 0:
            out.append(rec)

    def register(
        self, image_id: int, views: Dict[str, ChunkedView], anchor: Optional[Any]
    ) -> None:
        """Retain this image's generation as a future diff/restore base."""
        releasable: list = []
        with self._lock:
            old = self._gens.pop(image_id, None)
            if old is not None:
                self._retire_locked(old, releasable)
            self._gens[image_id] = _GenRecord(image_id=image_id, views=views, anchor=anchor)
            while len(self._gens) > self.max_generations:
                _, rec = self._gens.popitem(last=False)
                self._retire_locked(rec, releasable)
        for rec in releasable:
            rec.release()

    def anchored_ids(self) -> List[int]:
        """Image ids with a registered generation, oldest-first (LRU order).

        The persistence plane records these as the *generation-cache
        anchors*: after a restart, :meth:`rebuild_generation` re-materializes
        them from store chunks so the first post-recovery dumps are already
        O(delta)-chained instead of paying a full-path dump each."""
        with self._lock:
            return list(self._gens.keys())

    def rebuild_generation(self, image: Any) -> bool:
        """Re-register an image's generation from its store chunks.

        Restart recovery: builds host byte-grids for every grid-aligned
        tensor of ``image`` and registers them as a diff/restore base
        (anchor-less — the grids own their memory).  Returns False when no
        tensor was rebuildable (nothing registered)."""
        views: Dict[str, ChunkedView] = {}
        for name, meta in image.entries.items():
            n = len(meta.chunk_ids)
            if n == 0:
                continue
            try:
                grid = self._read_grid(meta)
            except Exception:
                # a quarantined/corrupt chunk must not abort recovery: this
                # tensor just misses the rebuilt anchor — the first dump
                # against it pays the full path, which is correct, only slow
                continue
            if grid is None:
                continue
            grid.flags.writeable = False
            views[name] = ChunkedView(
                shape=meta.shape,
                dtype=meta.dtype,
                nbytes=meta.nbytes,
                chunk_bytes=grid.shape[1],
                n_chunks=n,
                trailing_pad=meta.trailing_pad,
                grid_fn=lambda g=grid: g,
                tile_grid=tuple(meta.tile_grid),
            )
        if not views:
            return False
        self.register(image.image_id, views, anchor=None)
        return True

    def evict(self, image_id: int) -> None:
        releasable: list = []
        with self._lock:
            rec = self._gens.pop(image_id, None)
            if rec is not None:
                self._retire_locked(rec, releasable)
        for rec in releasable:
            rec.release()

    def clear(self) -> None:
        releasable: list = []
        with self._lock:
            for rec in self._gens.values():
                self._retire_locked(rec, releasable)
            self._gens.clear()
        for rec in releasable:
            rec.release()

    def shutdown(self) -> None:
        self.clear()
        if self.stream is not None:
            self.stream.shutdown()

    # --------------------------------------------------------------- encode
    def encode_generation(
        self,
        gen: DeltaGeneration,
        parent_image: Optional[Any],
        *,
        cancel: Optional[threading.Event] = None,
        priority: str = "bg",
        use_base: bool = True,
    ) -> EncodeResult:
        """Build the image entries for one generation (dump-worker thread).

        When the pipeline owns a :class:`ChunkStreamEngine` and the plan is
        large enough to split into windows, the per-tensor work streams
        through it: diff dispatch of window k+1 overlaps the device→host
        copy + store put of window k.  ``cancel`` aborts at the next window
        boundary and rolls back every chunk reference this dump acquired
        (raising :class:`StreamCancelled`); ``priority`` is forwarded to the
        QoS gate ("bg" dumps yield to runnable sessions, "fg" do not).

        ``use_base=False`` is the adaptive engine's *straight-copy* mode for
        mostly-dirty generations: skip the diff kernels entirely (no base
        grid lookup → every dirty-hinted key drains in full) while keeping
        everything else — clean-key metadata reuse, streaming overlap, the
        parent digest compare at commit (dump bytes stay ∝ the dirty set),
        and the generation anchor for future O(delta) chaining.
        """
        res = EncodeResult(entries={}, dirtied=0)
        parent_entries = parent_image.entries if parent_image is not None else {}
        parent_rec = (
            self.record_for(parent_image.image_id)
            if parent_image is not None and use_base
            else None
        )
        try:
            return self._encode_with_parent(
                gen, parent_entries, parent_rec, res, cancel=cancel, priority=priority
            )
        finally:
            # device grids materialized for this diff are O(state) on-device
            # copies — free them; the anchors re-gather lazily next time
            for view in gen.views.values():
                view.drop_cached_device_grid()
            if parent_rec is not None:
                for view in parent_rec.views.values():
                    view.drop_cached_device_grid()
            self.release_record(parent_rec)

    def _encode_with_parent(
        self,
        gen: DeltaGeneration,
        parent_entries: Dict[str, TensorMeta],
        parent_rec: Optional[_GenRecord],
        res: "EncodeResult",
        *,
        cancel: Optional[threading.Event] = None,
        priority: str = "bg",
    ) -> "EncodeResult":
        store = self.store
        tasks: List[_KeyTask] = []
        sharded: "OrderedDict[str, _ShardedPlan]" = OrderedDict()
        for key, view in gen.views.items():
            pm = parent_entries.get(key)
            # NOTE: the kernel path does not require parent digests — its
            # dirty knowledge comes from the generation grids, and decode
            # detects dirty chunks by id inequality.
            pm_ok = pm is not None and pm.dtype == view.dtype
            # --- clean key: metadata-level re-reference, zero bytes moved
            if pm_ok and pm.shape == tuple(view.shape) and not gen.is_dirty(key):
                store.incref_many(pm.chunk_ids)
                res.entries[key] = pm
                res.clean_keys += 1
                continue
            base = parent_rec.views.get(key) if parent_rec is not None else None
            if hasattr(view, "parts"):   # dist.shard_dump.ShardedView
                splan = self._plan_sharded_key(key, view, pm if pm_ok else None, base)
                sharded[key] = splan
                tasks.extend(splan.tasks)
            else:
                tasks.append(self._plan_key(key, view, pm if pm_ok else None, base))

        items = [t.as_window_item() for t in tasks]
        streamed = self.stream is not None and self.stream.should_stream(items)
        shard_out: Dict[str, List[_ShardRows]] = {}
        try:
            if streamed:
                self._run_streamed(tasks, items, res, cancel, priority, shard_out)
            else:
                self._run_sync(tasks, res, cancel, shard_out)
            # assemble each sharded key's parts in global chunk order — the
            # store folds run on this (single) thread, so ids and digests
            # come out exactly as a single-device dump would produce them
            for key, splan in sharded.items():
                self._commit_sharded_key(key, splan, shard_out.get(key, []), res)
            # extras stay inside the transaction: a failure here must also
            # roll back every reference the tasks/clean keys acquired
            for key, arr in gen.extras.items():
                pm = parent_entries.get(key)
                if (
                    pm is not None
                    and pm.shape == tuple(np.shape(arr))
                    and pm.dtype == str(np.asarray(arr).dtype)
                    and not gen.is_dirty(key)
                ):
                    store.incref_many(pm.chunk_ids)
                    res.entries[key] = pm
                    res.clean_keys += 1
                    continue
                meta, n_dirty = digest_encode_array(store, np.asarray(arr), pm)
                res.entries[key] = meta
                res.dirtied += n_dirty
        except BaseException:
            self._rollback(res.entries)
            res.entries = {}
            raise
        return res

    # ----------------------------------------------------- encode: planning
    def _plan_key(
        self,
        key: str,
        view: ChunkedView,
        pm: Optional[TensorMeta],
        base: Optional[ChunkedView],
    ) -> _KeyTask:
        """Classify one dirty tensor into a two-stage task."""
        weight = view.n_chunks * view.chunk_bytes
        if (
            pm is not None
            and base is not None
            # kernel bases must share the flat row layout; tiled metadata or
            # a sharded/tiled base pairs only with the sharded planner
            and not pm.tile_grid
            and not getattr(base, "tile_grid", ())
            and not hasattr(base, "parts")
            and base.chunk_bytes == view.chunk_bytes
            and len(pm.chunk_ids) == base.n_chunks
        ):
            # a padded parent tail row only compares against an identical
            # layout (same row count + pad); otherwise exclude it
            if base.n_chunks == view.n_chunks and base.trailing_pad == view.trailing_pad:
                comparable = base.n_chunks
            else:
                comparable = base.n_chunks - (1 if base.trailing_pad else 0)
            K = min(view.n_chunks, comparable)
            if K > 0:
                old_grid, new_grid = base.grid, view.grid
                if (
                    isinstance(old_grid, np.ndarray)
                    and isinstance(new_grid, np.ndarray)
                    and not _on_tpu()
                ):
                    return self._plan_host_kernel(key, view, pm, old_grid, new_grid, K, weight)
                return self._plan_device_kernel(key, view, pm, old_grid, new_grid, K, weight)
        # --- full path: materialize the grid, digest-delta every row
        return _KeyTask(
            key=key,
            weight=weight,
            encode=lambda: None,
            drain=lambda _enc, v=view: self._drain_rows(np.asarray(v.grid), range(v.n_chunks)),
            commit=lambda rows, v=view, p=pm: (*self._commit_full_grid(v, p, rows), "full"),
        )

    def _drain_rows(
        self, grid, indices, keys=None
    ) -> Dict[int, Tuple[bytes, Optional[bytes]]]:
        """Pure drain body: copy + hash the given grid rows.

        One ``tobytes`` copy and (when the store dedupes) one GIL-releasing
        blake2b per row — exactly the work profile that scales across drain
        workers; no locks, no store access.  ``keys`` remaps grid rows to
        result keys (compacted kernel output, grown-tail offsets); identity
        when omitted."""
        want_digest = self.store.dedupe
        rows: Dict[int, Tuple[bytes, Optional[bytes]]] = {}
        indices = list(indices)
        keys = indices if keys is None else list(keys)
        for k, i in zip(keys, indices):
            payload = np.ascontiguousarray(grid[int(i)]).tobytes()
            rows[int(k)] = (payload, chunk_digest(payload, 0) if want_digest else None)
        return rows

    def _plan_host_kernel(
        self, key, view, pm, old_grid, new_grid, K: int, weight: int
    ) -> _KeyTask:
        # Host grids off-TPU: a vectorized numpy compare IS the delta kernel
        # here — routing 2×K×C bytes through the device would cost more than
        # the diff.  The result is exact, so the fixed-capacity limit (a
        # kernel-compaction artifact) does not apply.  Encode = the compare;
        # drain = per-row copy + hash; commit = store folds.
        def encode() -> np.ndarray:
            return _host_dirty_rows(old_grid[:K], new_grid[:K])

        def drain(hit: np.ndarray) -> Dict[int, Tuple[bytes, Optional[bytes]]]:
            # rows past K (a grown tensor's tail) are new, hence all dirty
            indices = list(hit) + list(range(K, view.n_chunks))
            return self._drain_rows(new_grid, indices)

        def commit(rows) -> Tuple[TensorMeta, int, str]:
            meta, n_dirty = self._commit_kernel_meta(view, pm, K, rows)
            return meta, n_dirty, "kernel"

        return _KeyTask(key=key, weight=weight, encode=encode, drain=drain, commit=commit)

    def _plan_device_kernel(
        self, key, view, pm, old_grid, new_grid, K: int, weight: int
    ) -> _KeyTask:
        from repro.kernels import ops as kops
        import jax.numpy as jnp

        # pow2-pad the row count so delta_encode compiles once per size
        # class, not per chunk count (a growing KV cache changes K every few
        # steps); the identical zero pad rows can never read as dirty
        K2 = 1 << (K - 1).bit_length()
        cap = self._capacity(K2)
        if self.fused:
            return self._plan_device_fused(
                key, view, pm, old_grid, new_grid, K, K2, cap, weight
            )

        def encode():
            old_j = jnp.asarray(old_grid)[:K]
            new_j = jnp.asarray(new_grid)[:K]
            if K2 != K:
                pad_rows = ((0, K2 - K), (0, 0))
                old_j = jnp.pad(old_j, pad_rows)
                new_j = jnp.pad(new_j, pad_rows)
            data, idx, count = kops.delta_encode(old_j, new_j, cap)
            # async dispatch: start the DMA now, materialize in drain
            kops.start_host_fetch(data, idx, count)
            return data, idx, count

        def drain(enc):
            data, idx, count = enc
            if int(count) > cap:
                # capacity overflow: fall back to the full chunk set
                return "full", self._drain_rows(np.asarray(view.grid), range(view.n_chunks))
            data_np, idx_np = np.asarray(data), np.asarray(idx)
            valid = [j for j in range(idx_np.shape[0]) if int(idx_np[j]) >= 0]
            rows = self._drain_rows(data_np, valid, keys=(int(idx_np[j]) for j in valid))
            if view.n_chunks > K:        # grown rows: all dirty, one fetch
                tail = np.asarray(view.grid[K:])
                rows.update(
                    self._drain_rows(
                        tail, range(tail.shape[0]), keys=range(K, K + tail.shape[0])
                    )
                )
            return "kernel", rows

        def commit(tagged) -> Tuple[TensorMeta, int, str]:
            tag, rows = tagged
            if tag == "full":
                return (*self._commit_full_grid(view, pm, rows), "full")
            meta, n_dirty = self._commit_kernel_meta(view, pm, K, rows)
            return meta, n_dirty, "kernel"

        return _KeyTask(key=key, weight=weight, encode=encode, drain=drain, commit=commit)

    def _plan_device_fused(
        self, key, view, pm, old_grid, new_grid, K: int, K2: int, cap: int, weight: int
    ) -> _KeyTask:
        """Single-pass device plan: ``kernels.fused_encode`` diffs, compacts
        and checksums the dirty rows in one kernel launch, so dirty bytes
        cross the device memory hierarchy once instead of three times.

        Drain validates the DMA'd bytes against the device-computed checksum
        lanes (when ``fused_verify``): a mismatch raises a catchable
        :class:`FaultError` that rides the dump path's transactional
        retry/fallback plane — the attempt rolls back and the retry
        re-fetches, exactly like an injected drain fault."""
        from repro.kernels import ops as kops
        import jax.numpy as jnp

        def encode():
            old_j = jnp.asarray(old_grid)[:K]
            new_j = jnp.asarray(new_grid)[:K]
            if K2 != K:
                pad_rows = ((0, K2 - K), (0, 0))
                old_j = jnp.pad(old_j, pad_rows)
                new_j = jnp.pad(new_j, pad_rows)
            data, idx, count, sums = kops.fused_encode(old_j, new_j, cap)
            # double-buffer overlap: start the small control DMAs (idx,
            # count, sums) first so drain can classify immediately, then the
            # bulk rows — by the time drain touches `data` the copy has been
            # running behind window k+1's encode dispatch
            kops.start_host_fetch(idx, count, sums)
            kops.start_host_fetch(data)
            return data, idx, count, sums

        def drain(enc):
            data, idx, count, sums = enc
            if int(count) > cap:
                # capacity overflow: fall back to the full chunk set
                return "full", self._drain_rows(np.asarray(view.grid), range(view.n_chunks))
            data_np, idx_np = np.asarray(data), np.asarray(idx)
            valid = [j for j in range(idx_np.shape[0]) if int(idx_np[j]) >= 0]
            faults.fire("kernels.fused")
            if self.fused_verify and valid:
                got = kops.chunk_checksums_host(data_np[valid])
                want = np.asarray(sums)[valid]
                if not np.array_equal(got, want):
                    bad = np.flatnonzero(np.any(got != want, axis=1))
                    self.fused_checksum_mismatches += len(bad)
                    raise FaultError(
                        f"fused dump checksum mismatch on {key!r}: "
                        f"{len(bad)}/{len(valid)} fetched rows fail the "
                        f"device-computed lanes (attempt rolls back)"
                    )
            rows = self._drain_rows(data_np, valid, keys=(int(idx_np[j]) for j in valid))
            if view.n_chunks > K:        # grown rows: all dirty, one fetch
                tail = np.asarray(view.grid[K:])
                rows.update(
                    self._drain_rows(
                        tail, range(tail.shape[0]), keys=range(K, K + tail.shape[0])
                    )
                )
            return "kernel", rows

        def commit(tagged) -> Tuple[TensorMeta, int, str]:
            tag, rows = tagged
            if tag == "full":
                return (*self._commit_full_grid(view, pm, rows), "full")
            meta, n_dirty = self._commit_kernel_meta(view, pm, K, rows)
            return meta, n_dirty, "kernel"

        return _KeyTask(key=key, weight=weight, encode=encode, drain=drain, commit=commit)

    # ----------------------------------------------- encode: sharded views
    #
    # A dist.shard_dump.ShardedView fans out into one task per shard part:
    # the diff/compact kernel runs on the part's own device against that
    # part's slice of the base, and the drain fetches exactly the compacted
    # dirty rows (front-packed by the kernel) with explicit jax.device_get —
    # no full-array gather ever happens, and the per-device bytes land in
    # the shard_dump.FETCH ledger.  Store folding is deferred: parts return
    # _ShardRows and _commit_sharded_key assembles global chunk order.
    def _plan_sharded_key(
        self, key: str, view: Any, pm: Optional[TensorMeta], base: Optional[Any]
    ) -> _ShardedPlan:
        pm_use = (
            pm
            if (
                pm is not None
                and pm.shape == tuple(view.shape)
                and tuple(pm.tile_grid) == tuple(view.plan.grid)
                and len(pm.chunk_ids) == view.n_chunks
            )
            else None
        )
        # a usable diff base is either the parent's ShardedView under the
        # identical plan (per-part device diff) or a host tile-layout grid
        # from rebuild/decode (rows upload to each part's device — h2d,
        # which the transfer guard permits)
        base_map = None
        host_base = None
        if pm_use is not None and base is not None:
            if hasattr(base, "parts"):
                if tuple(base.plan.grid) == tuple(view.plan.grid):
                    base_map = base.part_map()
            elif (
                tuple(getattr(base, "tile_grid", ())) == tuple(view.plan.grid)
                and base.chunk_bytes == view.chunk_bytes
                and base.n_chunks == view.n_chunks
            ):
                g = base.grid
                if isinstance(g, np.ndarray):
                    host_base = g
        tasks: List[_KeyTask] = []
        used_base = False
        for k, part in enumerate(view.parts):
            tkey = f"{key}#shard{k}"
            weight = part.n_local * view.chunk_bytes
            bpart = (
                base_map.get(part.tile_ids.tobytes()) if base_map is not None else None
            )
            if (
                bpart is not None
                and part.device is not None
                and bpart.device == part.device
            ):
                used_base = True
                tasks.append(
                    self._plan_shard_kernel(
                        tkey,
                        key,
                        view,
                        part,
                        (lambda bp=bpart: bp.grid),
                        weight,
                        base_block_fn=bpart.block_fn,
                    )
                )
            elif host_base is not None and part.device is not None:
                used_base = True
                tasks.append(
                    self._plan_shard_kernel(
                        tkey,
                        key,
                        view,
                        part,
                        (lambda g=host_base, p=part: g[p.tile_ids]),
                        weight,
                    )
                )
            else:
                tasks.append(self._plan_shard_full(tkey, key, part, weight))
        return _ShardedPlan(view=view, pm=pm_use, tasks=tasks, used_base=used_base)

    def _plan_shard_kernel(
        self, tkey: str, plan_key: str, view: Any, part: Any, base_fn, weight: int,
        *, base_block_fn=None
    ) -> _KeyTask:
        from repro.kernels import ops as kops
        import jax
        import jax.numpy as jnp

        K = part.n_local
        K2 = 1 << (K - 1).bit_length()
        cap = self._capacity(K2)
        use_fused = self.fused
        block_path = base_block_fn is not None and part.block_fn is not None

        def encode():
            if block_path:
                # block-native fast path: diff directly on the shards' native
                # layouts (one compare+reduce pass) and extract only the
                # dirty tiles — neither side pays the O(state) tile-grid
                # byte-transpose.  Row bytes are bit-identical to the grid
                # path, so digests and the drain contract are unchanged.
                # Checksum lanes are deferred to drain, where they run over
                # the power-of-two fetch slice instead of the full capacity
                # buffer.
                data, idx, count = kops.shard_block_encode(
                    base_block_fn(),
                    part.block_fn(),
                    tuple(part.counts),
                    tuple(view.plan.tile),
                    cap,
                )
                kops.start_host_fetch(idx, count)
                return data, idx, count, None
            old = base_fn()
            if isinstance(old, np.ndarray):
                old = jax.device_put(old, part.device)
            new = part.grid
            if K2 != K:
                pad_rows = ((0, K2 - K), (0, 0))
                old = jnp.pad(old, pad_rows)
                new = jnp.pad(new, pad_rows)
            if use_fused:
                data, idx, count, sums = kops.fused_encode(old, new, cap)
            else:
                data, idx, count = kops.delta_encode(old, new, cap)
                sums = None
            # prestart only the control DMAs; the bulk rows are fetched as an
            # exact [:count] slice in drain so moved bytes stay ∝ the delta
            kops.start_host_fetch(idx, count)
            return data, idx, count, sums

        def drain(enc):
            from repro.dist import shard_dump as sd

            data, idx, count, sums = enc
            n = int(jax.device_get(count))
            if n > cap:
                # capacity overflow: this part drains in full — still only
                # its own shard's bytes, never a gather
                grid_np = jax.device_get(part.grid)
                sd.FETCH.note_fetch(part.device, grid_np.nbytes)
                rows = self._drain_rows(grid_np, range(K), keys=part.tile_ids)
                return _ShardRows(plan_key, rows, "full")
            if n == 0:
                return _ShardRows(plan_key, {}, "kernel")
            # compacted rows are front-packed in ascending order: fetch the
            # dirty rows, mapped to global ids via tile_ids.  The fetch
            # length rounds up to a power of two so the device-side slice
            # compiles O(log cap) distinct programs per device instead of
            # one per observed dirty count — fetched bytes stay within 2x
            # the exact delta
            n2 = min(cap, 1 << (n - 1).bit_length())
            data_np = jax.device_get(data[:n2])
            idx_np = jax.device_get(idx[:n2])
            sd.FETCH.note_fetch(part.device, data_np.nbytes + idx_np.nbytes)
            data_np = data_np[:n]
            idx_np = idx_np[:n]
            if use_fused and (sums is not None or block_path):
                faults.fire("kernels.fused")
                if self.fused_verify:
                    got = kops.chunk_checksums_host(data_np)
                    if sums is not None:
                        want = jax.device_get(sums[:n2])[:n]
                    else:
                        # block path: device lanes over only the fetched
                        # slice — O(fetched) integrity instead of O(capacity)
                        want = jax.device_get(
                            kops.chunk_checksums_device(data[:n2])
                        )[:n]
                    if not np.array_equal(got, want):
                        bad = np.flatnonzero(np.any(got != want, axis=1))
                        self.fused_checksum_mismatches += len(bad)
                        raise FaultError(
                            f"fused dump checksum mismatch on {tkey!r}: "
                            f"{len(bad)}/{n} fetched rows fail the "
                            f"device-computed lanes (attempt rolls back)"
                        )
            gids = part.tile_ids[np.asarray(idx_np, dtype=np.int64)]
            rows = self._drain_rows(data_np, range(n), keys=gids)
            return _ShardRows(plan_key, rows, "kernel")

        def commit(sr: _ShardRows) -> _ShardRows:
            return sr

        return _KeyTask(key=tkey, weight=weight, encode=encode, drain=drain, commit=commit)

    def _plan_shard_full(
        self, tkey: str, plan_key: str, part: Any, weight: int
    ) -> _KeyTask:
        from repro.kernels import ops as kops

        def encode():
            g = part.grid
            if not isinstance(g, np.ndarray):
                kops.start_host_fetch(g)
            return g

        def drain(g):
            import jax

            from repro.dist import shard_dump as sd

            if not isinstance(g, np.ndarray):
                g = jax.device_get(g)
                if part.device is not None:
                    sd.FETCH.note_fetch(part.device, g.nbytes)
                # device None = whole-array fallback part whose grid_fn
                # already recorded the gather in the ledger
            rows = self._drain_rows(g, range(part.n_local), keys=part.tile_ids)
            return _ShardRows(plan_key, rows, "full")

        def commit(sr: _ShardRows) -> _ShardRows:
            return sr

        return _KeyTask(key=tkey, weight=weight, encode=encode, drain=drain, commit=commit)

    def _commit_sharded_key(
        self,
        key: str,
        splan: _ShardedPlan,
        shard_rows: List[_ShardRows],
        res: EncodeResult,
    ) -> None:
        """Fold one sharded view's drained parts into the store.

        Walks global chunk ids 0..n-1 in order on the caller thread —
        store mutation stays single-threaded and the resulting metadata
        (ids, digests, tile layout) is bit-identical to what a
        single-device dump of the same tensor under the same TilePlan
        produces, which is the cross-mesh determinism invariant the
        differential tests pin."""
        view = splan.view
        pm = splan.pm
        store = self.store
        rows: Dict[int, Tuple[bytes, Optional[bytes]]] = {}
        kinds = set()
        for sr in shard_rows:
            rows.update(sr.rows)
            kinds.add(sr.kind)
        pm_digests_ok = pm is not None and len(pm.digests) == len(pm.chunk_ids)
        with_digests = store.dedupe and (pm is None or pm_digests_ok)
        ids: List[int] = []
        digests: List[bytes] = []
        dirtied = 0
        try:
            for i in range(view.n_chunks):
                pr = rows.get(i)
                if pr is None:  # clean under the per-part diff
                    if pm is None:
                        raise FaultError(
                            f"sharded dump of {key!r} missing chunk {i} "
                            f"with no parent metadata"
                        )
                    store.incref(pm.chunk_ids[i])
                    ids.append(pm.chunk_ids[i])
                    if with_digests:
                        digests.append(pm.digests[i])
                    continue
                payload, digest = pr
                same = False
                if pm is not None:
                    if digest is not None and pm_digests_ok:
                        same = pm.digests[i] == digest
                    elif digest is None:  # digest-less store: byte compare
                        same = store.get(pm.chunk_ids[i]) == payload
                if same:
                    store.incref(pm.chunk_ids[i])
                    ids.append(pm.chunk_ids[i])
                    if with_digests:
                        digests.append(digest)
                    continue
                if digest is not None:
                    ids.append(store.put_digested(payload, digest=digest, pad=0))
                else:
                    ids.append(store.put(payload, pad=0))
                if with_digests:
                    digests.append(digest)
                dirtied += 1
        except BaseException:
            # partial fold: refs taken so far belong to no entry yet —
            # return them so the dump's rollback leaves the store balanced
            store.decref_many(ids)
            raise
        res.entries[key] = TensorMeta(
            shape=tuple(view.shape),
            dtype=view.dtype,
            chunk_ids=tuple(ids),
            digests=tuple(digests) if with_digests else (),
            trailing_pad=0,
            tile_grid=tuple(view.plan.grid),
        )
        res.dirtied += dirtied
        res.shard_parts += len(splan.tasks)
        if splan.used_base and "full" not in kinds:
            res.kernel_keys += 1
        else:
            res.full_keys += 1

    # ---------------------------------------------------- encode: execution
    def _merge_task_result(
        self, res: EncodeResult, key: str, out: Tuple[TensorMeta, int, str]
    ) -> None:
        meta, n_dirty, kind = out
        res.entries[key] = meta
        res.dirtied += n_dirty
        if kind == "kernel":
            res.kernel_keys += 1
        else:
            res.full_keys += 1

    def _run_sync(
        self,
        tasks: List[_KeyTask],
        res: EncodeResult,
        cancel: Optional[threading.Event],
        shard_out: Dict[str, List[_ShardRows]],
    ) -> None:
        for task in tasks:
            if cancel is not None and cancel.is_set():
                raise StreamCancelled(
                    f"dump cancelled after {len(res.entries)} tensors (sync path)"
                )
            with spans.timed("dump.encode") as enc_t:
                enc = task.encode()
            with spans.timed("dump.drain") as drain_t:
                raw = task.drain(enc)
            with spans.timed("dump.commit") as commit_t:
                out = task.commit(raw)
            res.encode_ms += enc_t.ms
            res.drain_ms += drain_t.ms
            res.commit_ms += commit_t.ms
            if isinstance(out, _ShardRows):
                shard_out.setdefault(out.plan_key, []).append(out)
            else:
                self._merge_task_result(res, task.key, out)

    def _run_streamed(
        self,
        tasks: List[_KeyTask],
        items: List[WindowItem],
        res: EncodeResult,
        cancel: Optional[threading.Event],
        priority: str,
        shard_out: Dict[str, List[_ShardRows]],
    ) -> None:
        assert self.stream is not None
        out: Dict[str, Any] = {}
        try:
            stats = self.stream.stream(items, out, cancel=cancel, priority=priority)
        except BaseException:
            # roll back everything the drain thread completed; the caller's
            # handler then rolls back clean-key increfs via res.entries
            self._rollback(out)
            raise
        for task in tasks:                      # deterministic merge order
            o = out[task.key]
            if isinstance(o, _ShardRows):
                shard_out.setdefault(o.plan_key, []).append(o)
            else:
                self._merge_task_result(res, task.key, o)
        res.streamed = True
        res.windows = stats.windows
        res.window_bytes = stats.window_bytes
        res.encode_ms = stats.encode_ms
        res.drain_ms = stats.drain_ms
        res.commit_ms = stats.commit_ms
        res.stream_wall_ms = stats.wall_ms

    def _rollback(self, produced: Dict[str, Any]) -> None:
        """Drop every chunk reference held by already-produced entries,
        restoring the store to its pre-dump state (transactional dumps)."""
        ids: List[int] = []
        for val in produced.values():
            meta = val[0] if isinstance(val, tuple) else val
            ids.extend(meta.chunk_ids)
        if ids:
            self.store.decref_many(ids)

    def _capacity(self, n_chunks: int) -> int:
        """Fixed compaction capacity, pow2-rounded to bound jit recompiles."""
        target = max(1, int(np.ceil(n_chunks * self.capacity_frac)))
        return min(n_chunks, 1 << (target - 1).bit_length())

    def _commit_kernel_meta(
        self,
        view: ChunkedView,
        pm: TensorMeta,
        K: int,
        rows: Dict[int, Tuple[bytes, Optional[bytes]]],
    ) -> Tuple[TensorMeta, int]:
        """Fold drained dirty rows (index → (payload, digest)) into the
        store, re-referencing the parent's chunks for everything clean.

        Runs on the caller thread — all store mutation is single-threaded,
        so chunk ids come out identical to a synchronous dump.  Meta digests
        are recorded only when the parent entry also carries them (digests
        are all-or-nothing per entry)."""
        store = self.store
        with_digests = store.dedupe and len(pm.digests) == len(pm.chunk_ids)
        ids = []
        digests = []
        dirtied = 0
        try:
            for i in range(view.n_chunks):
                pr = rows.get(i)
                if pr is None:  # clean: re-reference the parent's chunk
                    store.incref(pm.chunk_ids[i])
                    ids.append(pm.chunk_ids[i])
                    if with_digests:
                        digests.append(pm.digests[i])
                    continue
                payload, digest = pr
                pad = view.trailing_pad if i == view.n_chunks - 1 else 0
                if digest is not None:   # rows are already padded: pad-0 hash
                    ids.append(store.put_digested(payload, digest=digest, pad=pad))
                else:
                    ids.append(store.put(payload, pad=pad))
                if with_digests:
                    digests.append(digest)
                dirtied += 1
        except BaseException:
            # a put/incref failure mid-fold: the refs taken so far are not in
            # any entry yet, so the outer rollback cannot see them — return
            # them here to keep the dump transactional
            store.decref_many(ids)
            raise
        return (
            TensorMeta(
                shape=view.shape,
                dtype=view.dtype,
                chunk_ids=tuple(ids),
                digests=tuple(digests) if with_digests else (),
                trailing_pad=view.trailing_pad,
            ),
            dirtied,
        )

    def _commit_full_grid(
        self,
        view: ChunkedView,
        pm: Optional[TensorMeta],
        rows: Dict[int, Tuple[bytes, Optional[bytes]]],
    ) -> Tuple[TensorMeta, int]:
        """Fold a fully-drained grid into the store, digest-deltaing every
        row against the parent entry (new tensors, shape changes, kernel
        capacity overflows)."""
        prev_ids = pm.chunk_ids if pm is not None and pm.shape == view.shape else ()
        prev_digests = pm.digests if pm is not None and pm.shape == view.shape else ()
        store = self.store
        with_digests = store.dedupe      # digests exist to key content dedupe
        ids = []
        digests = []
        dirtied = 0
        try:
            for i in range(view.n_chunks):
                payload, digest = rows[i]
                if i < len(prev_ids):
                    if digest is not None and i < len(prev_digests):
                        same = prev_digests[i] == digest
                    else:  # digest-less entry or store: full byte compare
                        same = store.get(prev_ids[i]) == payload
                    if same:
                        store.incref(prev_ids[i])
                        ids.append(prev_ids[i])
                        if digest is not None:
                            digests.append(digest)
                        continue
                pad = view.trailing_pad if i == view.n_chunks - 1 else 0
                if digest is not None:
                    ids.append(store.put_digested(payload, digest=digest, pad=pad))
                    digests.append(digest)
                else:
                    ids.append(store.put(payload, pad=pad))
                dirtied += 1
        except BaseException:
            # partial fold (a put fault or a corrupt parent read): the refs
            # taken so far belong to no entry yet — return them so the
            # dump's rollback leaves the store balanced
            store.decref_many(ids)
            raise
        return (
            TensorMeta(
                shape=view.shape,
                dtype=view.dtype,
                chunk_ids=tuple(ids),
                digests=tuple(digests) if with_digests else (),
                trailing_pad=view.trailing_pad,
            ),
            dirtied,
        )

    # --------------------------------------------------------------- decode
    def decode(
        self, image: Any, parent_image: Optional[Any]
    ) -> Dict[str, np.ndarray]:
        """Rebuild a dump image's payload.

        Tensors whose parent generation is still materialized are rebuilt as
        base grid + ``delta_apply`` scatter of only the chunks whose ids
        differ from the parent's; everything else is read from the store
        into a fresh grid, one copy.  The rebuilt generation is registered
        so subsequent restores (and dumps of its children) stay O(delta).
        A payload tensor that is a view of its registered grid is read-only.
        """
        parent_rec = self.record_for(parent_image.image_id) if parent_image is not None else None
        try:
            payload, new_views = self._decode_with_base(image, parent_image, parent_rec)
        finally:
            self.release_record(parent_rec)
        self.register(image.image_id, new_views, anchor=None)
        return payload

    def _decode_with_base(
        self, image: Any, parent_image: Optional[Any], parent_rec: Optional[_GenRecord]
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, ChunkedView]]:
        from repro.kernels import ops as kops
        import jax.numpy as jnp

        store = self.store
        payload: Dict[str, np.ndarray] = {}
        new_views: Dict[str, ChunkedView] = {}
        for name, meta in image.entries.items():
            grid_np: Optional[np.ndarray] = None
            base = parent_rec.views.get(name) if parent_rec is not None else None
            pm = parent_image.entries.get(name) if parent_image is not None else None
            if meta.tile_grid:
                # shard-native image: per-shard delta_apply when the base is
                # still sharded under the same plan, host tile scatter else
                val, view = self._decode_tiled(meta, pm, base)
                payload[name] = val
                if view is not None:
                    new_views[name] = view
                continue
            if (
                base is not None
                and pm is not None
                and not hasattr(base, "parts")
                and not getattr(base, "tile_grid", ())
                and not pm.tile_grid
                and len(pm.chunk_ids) == base.n_chunks
                and meta.dtype == pm.dtype
                and self._rows_match(meta, base.chunk_bytes)
            ):
                N = len(meta.chunk_ids)
                dirty = [
                    i
                    for i in range(N)
                    if i >= len(pm.chunk_ids) or meta.chunk_ids[i] != pm.chunk_ids[i]
                ]
                if isinstance(base.grid, np.ndarray) and not _on_tpu():
                    # host base off-TPU: a numpy scatter is the delta-apply
                    # kernel here (same cost argument as the encode path —
                    # the jax round-trip would copy the full base twice)
                    grid_np = np.zeros((N, base.chunk_bytes), np.uint8)
                    k = min(N, base.n_chunks)
                    grid_np[:k] = base.grid[:k]
                    for i in dirty:
                        grid_np[i] = np.frombuffer(store.get(meta.chunk_ids[i]), np.uint8)
                else:
                    if dirty:
                        # pow2-pad the scatter rows (idx -1 = no-op in the
                        # kernel) so delta_apply compiles per geometry, not
                        # per dirty count
                        M = 1 << (len(dirty) - 1).bit_length()
                        rows = np.zeros((M, base.chunk_bytes), np.uint8)
                        idx = np.full((M,), -1, np.int32)
                        for j, i in enumerate(dirty):
                            rows[j] = np.frombuffer(store.get(meta.chunk_ids[i]), np.uint8)
                            idx[j] = i
                    # the base grid up (when held on the host), the scatter,
                    # and the whole rebuilt grid back to the host
                    with spans.span("deltacr.apply", rows=len(dirty)):
                        base_grid = jnp.asarray(base.grid)
                        if base.n_chunks < N:
                            base_grid = jnp.zeros((N, base.chunk_bytes), jnp.uint8).at[
                                : base.n_chunks
                            ].set(base_grid)
                        elif base.n_chunks > N:
                            base_grid = base_grid[:N]
                        if dirty:
                            base_grid = kops.delta_apply(
                                base_grid, jnp.asarray(rows), jnp.asarray(idx)
                            )
                        grid_np = np.asarray(base_grid)
            else:
                # no base generation: every chunk from the store, straight
                # into the grid kept as the next base
                with spans.span("deltacr.read", rows=len(meta.chunk_ids)):
                    grid_np = self._read_grid(meta)
                    if grid_np is None:
                        payload[name] = store.get_array(
                            meta.chunk_ids, meta.shape, np.dtype(meta.dtype)
                        )
            if grid_np is None:
                continue
            # The payload and the next base share one grid.  Base grids are
            # never written, and a payload that aliases one is read-only, so
            # a restore_fn that writes copies first (CowArrayState counts a
            # read-only array as shared).  The grid owns its bytes: chunk
            # decrefs of a dropped checkpoint cannot reach it.
            payload[name] = self._grid_to_array(grid_np, meta)
            if meta.nbytes > 0:
                with spans.span("deltacr.keep_base"):
                    new_views[name] = ChunkedView(
                        shape=meta.shape,
                        dtype=meta.dtype,
                        nbytes=meta.nbytes,
                        chunk_bytes=grid_np.shape[1],
                        n_chunks=grid_np.shape[0],
                        trailing_pad=meta.trailing_pad,
                        grid_fn=lambda g=grid_np: g,
                    )
        return payload, new_views

    # ------------------------------------------------------- decode: tiled
    def _decode_tiled(
        self, meta: TensorMeta, pm: Optional[TensorMeta], base: Optional[Any]
    ) -> Tuple[Any, Optional[ChunkedView]]:
        """Rebuild a shard-native (tiled) tensor.

        Preferred path: the parent base is a ShardedView under the same
        TilePlan — each part scatters only its own dirty tiles with
        ``delta_apply`` on its own device and the global array reassembles
        via per-device blocks (no host round-trip of clean bytes).  Any
        asymmetry falls back to the host tile path, which is always
        correct: copy clean tiles from a host base (or fetch everything),
        then invert the tile layout."""
        from repro.dist import shard_dump as sd

        store = self.store
        plan = sd.TilePlan.from_meta(meta)
        pm_ok = (
            pm is not None
            and tuple(pm.tile_grid) == tuple(meta.tile_grid)
            and pm.dtype == meta.dtype
            and pm.shape == meta.shape
            and len(pm.chunk_ids) == len(meta.chunk_ids)
        )
        if (
            pm_ok
            and base is not None
            and hasattr(base, "parts")
            and tuple(base.plan.grid) == tuple(plan.grid)
            and base.sharding is not None
        ):
            try:
                return self._decode_tiled_sharded(meta, pm, base, plan)
            except Exception:
                pass   # device-path trouble: the host path below is always correct
        n = plan.n_tiles
        grid = np.empty((n, plan.tile_bytes), np.uint8)
        host_base = None
        if (
            pm_ok
            and base is not None
            and not hasattr(base, "parts")
            and tuple(getattr(base, "tile_grid", ())) == tuple(plan.grid)
            and base.chunk_bytes == plan.tile_bytes
            and isinstance(base.grid, np.ndarray)
        ):
            host_base = base.grid
        for i in range(n):
            if host_base is not None and meta.chunk_ids[i] == pm.chunk_ids[i]:
                grid[i] = host_base[i]
            else:
                grid[i] = np.frombuffer(store.get(meta.chunk_ids[i]), np.uint8)
        arr = sd.grid_to_array(grid, plan)
        view = ChunkedView(
            shape=tuple(meta.shape),
            dtype=meta.dtype,
            nbytes=int(arr.nbytes),
            chunk_bytes=plan.tile_bytes,
            n_chunks=n,
            trailing_pad=0,
            grid_fn=lambda g=grid: g,
            tile_grid=tuple(plan.grid),
        )
        return arr, view

    def _decode_tiled_sharded(
        self, meta: TensorMeta, pm: TensorMeta, base: Any, plan: Any
    ) -> Tuple[Any, Any]:
        import jax

        from repro.dist import shard_dump as sd
        from repro.kernels import ops as kops

        store = self.store
        tile_bytes = plan.tile_bytes
        out_parts = []
        block_by_off = {}
        for part in base.parts:
            if part.device is None:
                raise RuntimeError("gather-fallback base part: no device decode")
            gids = part.tile_ids
            dirty = [
                j
                for j in range(part.n_local)
                if meta.chunk_ids[int(gids[j])] != pm.chunk_ids[int(gids[j])]
            ]
            bgrid = part.grid
            if dirty:
                # pow2-pad the scatter rows (idx -1 = kernel no-op) so
                # delta_apply compiles per geometry, not per dirty count
                M = 1 << (len(dirty) - 1).bit_length()
                rows = np.zeros((M, tile_bytes), np.uint8)
                idx = np.full((M,), -1, np.int32)
                for j, lj in enumerate(dirty):
                    rows[j] = np.frombuffer(
                        store.get(meta.chunk_ids[int(gids[lj])]), np.uint8
                    )
                    idx[j] = lj
                new_grid = kops.delta_apply(
                    bgrid,
                    jax.device_put(rows, part.device),
                    jax.device_put(idx, part.device),
                )
            else:
                new_grid = bgrid
            block = sd.device_grid_to_block(
                new_grid, part.counts, plan.tile, meta.dtype
            )
            out_parts.append((part, new_grid))
            block_by_off[part.offsets] = block
        # scatter blocks onto every addressable device of the target
        # sharding — replicated axes receive the same block on each replica
        tile = plan.tile
        arrays = []
        imap = base.sharding.addressable_devices_indices_map(tuple(meta.shape))
        for dev, index in imap.items():
            offs = tuple((sl.start or 0) // t for sl, t in zip(index, tile))
            block = block_by_off[offs]
            if block.devices() != {dev}:
                block = jax.device_put(block, dev)
            arrays.append(block)
        arr = jax.make_array_from_single_device_arrays(
            tuple(meta.shape), base.sharding, arrays
        )
        new_view = sd.view_from_part_grids(plan, out_parts, base.sharding)
        return arr, new_view

    @staticmethod
    def _rows_match(meta: TensorMeta, row_bytes: int) -> bool:
        """Image chunking must align with the base grid's row layout."""
        n = len(meta.chunk_ids)
        return n > 0 and n * row_bytes == meta.nbytes + meta.trailing_pad

    def _read_grid(self, meta: TensorMeta) -> Optional[np.ndarray]:
        """The tensor's ``(n_chunks, row_bytes)`` grid filled from the store
        with one copy, its tail zero-padded; None when the image's chunks
        are not rows of one width."""
        n = len(meta.chunk_ids)
        row_bytes = self.store.size_of(meta.chunk_ids[0]) if n else 0
        if row_bytes == 0 or not self._rows_match(meta, row_bytes):
            return None
        grid = np.empty((n, row_bytes), np.uint8)
        flat = grid.reshape(-1)
        if self.store.read_into(meta.chunk_ids, flat[: meta.nbytes]) < meta.nbytes:
            raise ValueError(f"chunks hold fewer than the {meta.nbytes} bytes of the tensor")
        flat[meta.nbytes :] = 0
        return grid

    @staticmethod
    def _grid_to_array(grid: np.ndarray, meta: TensorMeta) -> np.ndarray:
        """The tensor as a read-only view of the first ``nbytes`` of ``grid``."""
        grid.flags.writeable = False
        return grid.reshape(-1)[: meta.nbytes].view(np.dtype(meta.dtype)).reshape(meta.shape)
