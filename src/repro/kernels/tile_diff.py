"""Pallas TPU kernel: per-tile dirtiness over a shard's native block layout.

The sharded dump path diffs each shard block against its parent block tile
by tile, where a tile is one chunk of the mesh-independent ``TilePlan``
(``dist.shard_dump``).  The grid walks the tile coordinates and each step
compares one tile in VMEM — a BlockSpec of the tile's shape over the
native block, so nothing is transposed into a tile grid first.  Tiles are
compared as int32 words (``words.as_words``) and each step folds its tile
to one lane vector.

The TPU block rule wants the tile's last two dims on the dtype's native
tile (``(8 * 4 // itemsize, 128)``); :func:`kernel_fits` says whether a
plan qualifies, and :func:`block_encode` diffs other plans with plain jnp.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import words

__all__ = ["block_encode", "kernel_fits", "tile_dirty"]


def kernel_fits(tile: Tuple[int, ...], dtype) -> bool:
    size = jnp.dtype(dtype).itemsize
    if len(tile) < 2 or size not in (1, 2, 4):
        return False
    sub = 8 * (4 // size)
    nbytes = int(np.prod(tile)) * size
    return tile[-1] % words.LANES == 0 and tile[-2] % sub == 0 and nbytes <= words.BLOCK_BYTES


def _step_rows(tile: Tuple[int, ...], size: int) -> int:
    """Rows of the second-minor dim per loop step: whole native tiles that
    divide ``tile[-2]``, about ``words.STEP_BYTES`` across the leading dims."""
    sub = 8 * (4 // size)
    per_row = int(np.prod(tile[:-2], dtype=np.int64)) * tile[-1] * size
    tiles = tile[-2] // sub
    d = max(1, min(tiles, words.STEP_BYTES // max(per_row * sub, 1)))
    while tiles % d:
        d -= 1
    return d * sub


def _tile_kernel(old_ref, new_ref, out_ref, *, ch: int):
    lead = old_ref.shape[:-2]
    lanes = old_ref.shape[-1]

    def body(c, acc):
        idx = (slice(None),) * len(lead) + (pl.ds(pl.multiple_of(c * ch, ch), ch), slice(None))
        o = words.as_words(old_ref[idx].reshape(-1, lanes))
        n = words.as_words(new_ref[idx].reshape(-1, lanes))
        return jnp.maximum(acc, jnp.max((o != n).astype(jnp.int32), axis=0, keepdims=True))

    acc = jax.lax.fori_loop(
        0, old_ref.shape[-2] // ch, body, jnp.zeros((1, lanes), jnp.int32)
    )
    folded = acc[:, : words.LANES]
    for j in range(1, lanes // words.LANES):
        folded = jnp.maximum(folded, acc[:, j * words.LANES : (j + 1) * words.LANES])
    out_ref[...] = folded.reshape(1, 1, words.LANES)


def tile_dirty(
    old: jax.Array,
    new: jax.Array,
    counts: Tuple[int, ...],
    tile: Tuple[int, ...],
    *,
    interpret: bool = False,
) -> jax.Array:
    """(prod(counts),) bool: tile ``t`` (row-major tile order) differs."""
    nd = len(counts)
    n = int(np.prod(counts, dtype=np.int64))
    strides = [int(np.prod(counts[d + 1 :], dtype=np.int64)) for d in range(nd)]

    def out_map(*ids):
        lin = ids[0] * strides[0]
        for d in range(1, nd):
            lin = lin + ids[d] * strides[d]
        return (lin, 0, 0)

    spec = pl.BlockSpec(tuple(tile), lambda *ids: ids)
    out = pl.pallas_call(
        functools.partial(_tile_kernel, ch=_step_rows(tuple(tile), jnp.dtype(old.dtype).itemsize)),
        grid=tuple(counts),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((1, 1, words.LANES), out_map),
        out_shape=jax.ShapeDtypeStruct((n, 1, words.LANES), jnp.int32),
        interpret=interpret,
    )(old, new)
    return jnp.any(out[:, 0] != 0, axis=-1)


def block_encode(
    old: jax.Array,
    new: jax.Array,
    counts: Tuple[int, ...],
    tile: Tuple[int, ...],
    max_changed: int,
    *,
    use_kernel: bool,
    interpret: bool = False,
):
    """Block-native diff + compact for one shard part: (data, idx, count).

    Same contract as ``ops.delta_encode``, but over the shard's NATIVE block
    layout: per-tile dirtiness is one read of old and new (the kernel above
    where the plan fits it), and only the ``max_changed`` dirty tiles'
    bytes are extracted — each as the row-major tile bitcast to uint8,
    bit-identical to the matching ``shard_dump._device_tile_grid`` row.
    """
    nd = len(counts)
    if use_kernel and kernel_fits(tuple(tile), old.dtype):
        dirty = tile_dirty(old, new, counts, tile, interpret=interpret)
    else:
        inter: list = []
        for c, t in zip(counts, tile):
            inter.extend((c, t))
        neq = (old != new).reshape(inter)
        dirty = jnp.any(neq, axis=tuple(2 * i + 1 for i in range(nd))).reshape(-1)
    count = jnp.sum(dirty.astype(jnp.int32))
    # ascending order, -1 padding at the tail, first-capacity overflow drop:
    # the exact delta_compact_ref slot contract
    idx = jnp.nonzero(dirty, size=max_changed, fill_value=-1)[0].astype(jnp.int32)
    # extract the selected tiles as a flat gather — work ∝ max_changed tiles,
    # never an O(block) tile-grid transpose.  Element offsets of one tile
    # (row-major over the tile, static) + the tile's base offset give each
    # row's exact element indices in the native block.
    block_shape = tuple(c * t for c, t in zip(counts, tile))
    estrides = np.ones(nd, np.int64)
    for i in range(nd - 2, -1, -1):
        estrides[i] = estrides[i + 1] * block_shape[i + 1]
    tcoords = np.indices(tile).reshape(nd, -1)
    t_off = (tcoords * estrides[:, None]).sum(0)             # (tile_elems,)
    ccoords = jnp.unravel_index(jnp.maximum(idx, 0), counts)
    # int32 offsets: fine below 2**31 elements per shard block (8 GiB f32)
    base = sum(
        c.astype(jnp.int32) * np.int32(t * s)
        for c, t, s in zip(ccoords, tile, estrides)
    )
    flat_idx = base[:, None] + jnp.asarray(t_off, jnp.int32)[None, :]
    rows = jnp.take(new.reshape(-1), flat_idx)               # (cap, tile_elems)
    u8 = jax.lax.bitcast_convert_type(rows, jnp.uint8).reshape(max_changed, -1)
    data = jnp.where((idx >= 0)[:, None], u8, jnp.uint8(0))
    return data, idx, count
