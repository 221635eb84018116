"""Row layout shared by the byte-grid kernels (diff, fused, apply).

The dump pipeline hands the kernels ``(N, C)`` grids — uint8 byte grids in
production — and the TPU compiler takes neither half of that as it is:
Mosaic does not compare or reduce 8-bit (or unsigned) values, and a
``(1, C)`` row block breaks the rule that a block's last two dims be
multiples of the native tile or the whole array's.  So the wrappers lay
every row out as ``(lines, 128)`` of its own dtype — ``(N, C)`` becomes
``(N, lines, 128)``, a reshape that moves no bytes on the chip — and the
kernels reinterpret each block as int32 words with ``pltpu.bitcast``:

* a block ``(b, r, 128)`` is legal for any row count ``b``, since ``r`` is
  all of the row or a multiple of the dtype's native tile;
* the bitcast packs ``4 // itemsize`` consecutive lines of ONE row into a
  word, so words of two rows are equal iff their bytes are;
* int32 adds and multiplies wrap exactly like uint32, so the checksum lanes
  of ``ref.chunk_checksums_ref`` come out bit for bit
  (:func:`word_byte_pos` names the byte each word byte holds).

A row is zero-padded to whole 4 KiB tiles of lines (and, for rows too large
for one block, to whole ``r``-line sub-blocks).  The padding is equal in
old and new, so it never reads as dirty.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "LANES",
    "BLOCK_BYTES",
    "STEP_BYTES",
    "as_words",
    "block_plan",
    "chunk_lines",
    "from_lines",
    "pad_rows",
    "to_lines",
    "word_byte_pos",
]

LANES = 128
#: Bytes of one native (sublane x lane) tile: 32x128 uint8, 16x128 bf16,
#: 8x128 f32 — rows pad to whole tiles so any line split stays aligned.
_TILE_BYTES = 4096
#: Bytes of one input block per grid step.  Two inputs, double-buffered,
#: keep the diff/fused kernels near 4x this — inside the 16 MiB of VMEM a
#: v5e kernel gets by default.
BLOCK_BYTES = 2 * 1024 * 1024
#: Bytes a kernel folds per inner loop step, so the word-level temporaries
#: of one step stay a few vregs deep instead of block-sized.
STEP_BYTES = 64 * 1024


def _itemsize(dtype) -> int:
    size = jnp.dtype(dtype).itemsize
    if size not in (1, 2, 4):
        raise TypeError(f"byte-grid kernels take 1/2/4-byte dtypes, not {dtype}")
    return size


def block_plan(
    n_rows: int, c: int, dtype, chunk_block: Optional[int] = None
) -> Tuple[int, int, int]:
    """``(rows per block, lines per sub-block, lines per padded row)`` for
    rows of ``c`` elements: whole rows per block while one fits in
    ``BLOCK_BYTES``, else one row split into ``r``-line sub-blocks."""
    size = _itemsize(dtype)
    tile_lines = _TILE_BYTES // (LANES * size)
    lines = max(1, -(-c // (LANES * tile_lines))) * tile_lines
    max_lines = BLOCK_BYTES // (LANES * size)
    if lines <= max_lines:
        b = max(1, min(n_rows, max_lines // lines))
        if chunk_block is not None:
            b = max(1, min(b, int(chunk_block)))
        return b, lines, lines
    return 1, max_lines, -(-lines // max_lines) * max_lines


def chunk_lines(r: int, dtype) -> int:
    """Lines per in-kernel step over an ``r``-line block: a divisor of ``r``
    of whole native tiles and at most ``STEP_BYTES``."""
    size = _itemsize(dtype)
    tile_lines = _TILE_BYTES // (LANES * size)
    tiles = r // tile_lines
    d = max(1, min(tiles, STEP_BYTES // _TILE_BYTES))
    while tiles % d:
        d -= 1
    return d * tile_lines


def to_lines(x: jax.Array, lines: int) -> jax.Array:
    """``(N, C)`` → ``(N, lines, 128)`` in ``x``'s dtype, zero-padded."""
    n, c = x.shape
    pad = lines * LANES - c
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(n, lines, LANES)


def from_lines(x: jax.Array, c: int) -> jax.Array:
    """Inverse of :func:`to_lines`."""
    return x.reshape(x.shape[0], -1)[:, :c]


def pad_rows(x: jax.Array, b: int) -> jax.Array:
    """Zero rows up to a multiple of ``b`` (never dirty: equal on both sides)."""
    extra = (-x.shape[0]) % b
    return jnp.pad(x, ((0, extra), (0, 0), (0, 0))) if extra else x


def as_words(x: jax.Array) -> jax.Array:
    """In-kernel view of a ``(b, r, 128)`` block as int32 words
    ``(b, r * itemsize // 4, 128)``."""
    if x.dtype == jnp.int32:
        return x
    return pltpu.bitcast(x, jnp.int32)


def word_byte_pos(word_line: jax.Array, lane: jax.Array, q: int, itemsize: int) -> jax.Array:
    """Row byte position of byte ``q`` (little-endian) of the word at
    ``(word_line, lane)``: the word packs lines ``p * word_line + t`` of
    the row, ``p = 4 // itemsize``, element ``t`` in its ``t``-th
    ``itemsize``-byte slot (the ``pltpu.bitcast`` packing)."""
    p = 4 // itemsize
    t, u = divmod(q, itemsize)
    return ((word_line * p + t) * LANES + lane) * itemsize + u
