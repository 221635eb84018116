"""Pallas TPU kernel: fused diff + checksum for delta dumps.

The pre-fusion dump path read every dirty byte three times: once in
``delta_diff`` (dirty bitmap), once in the compaction gather, and once for
the integrity checksums.  This kernel diffs old against new and computes
the 4-lane checksums of ``ref.chunk_checksums_ref`` in the same pass over
the grids, so the checksum costs no extra read; the compaction that
follows is a gather of only the dirty rows (``max_changed`` of them), so
the compaction buffer never has to live in VMEM.

Rows are lines read as int32 words (``words.py``).  Checksum lanes are
defined over byte values at byte positions, so each word contributes its
four bytes at the positions ``words.word_byte_pos`` names; int32
arithmetic wraps exactly like the oracle's uint32.  A block
with no dirty row skips the checksum math — its rows are never emitted.
``count`` totals ALL dirty rows, so ``count > max_changed`` signals
capacity overflow (the caller falls back to a full-grid dump).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import words
from .delta_diff import block_dirty
from .ref import _CS_ADD, _CS_MULT, _CS_XOR, CHECKSUM_LANES

__all__ = ["delta_fused", "compact_rows"]

# int32 views of the checksum constants (uint32 wraparound == int32 wrap)
_MULT, _ADD, _XOR = np.array([_CS_MULT, _CS_ADD, _CS_XOR], np.uint32).view(np.int32)


def _lane_sums(x, first_line, itemsize: int, rb: int):
    """Per-row lane-partial checksums of an int32 word block ``(b, r, 128)``
    whose first word line is row word line ``first_line``: four
    ``(b, 1, 128)`` int32."""
    shape = (1,) + x.shape[1:]
    line = first_line + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    s = [jnp.zeros(x.shape, jnp.int32) for _ in range(CHECKSUM_LANES)]
    for q in range(4):
        byte = jax.lax.shift_right_logical(x, jnp.int32(8 * q)) & 0xFF
        pos = words.word_byte_pos(line, lane, q, itemsize)
        w = pos * _MULT + _ADD
        s[0] = s[0] + byte
        s[1] = s[1] + byte * (pos + 1)
        s[2] = s[2] + byte * w
        s[3] = s[3] + jnp.where(pos < rb, (byte + 1) * (w ^ _XOR), 0)
    return [jnp.sum(v, axis=1, keepdims=True) for v in s]


def _fused_kernel(old_ref, new_ref, dirty_ref, sums_ref, *, rb: int, split: bool, ch: int):
    k = pl.program_id(1)
    itemsize = jnp.dtype(new_ref.dtype).itemsize
    b, r, _ = new_ref.shape
    cur = block_dirty(old_ref, new_ref, ch)                   # (b, 1, 128)

    @pl.when(k == 0)
    def _init():
        dirty_ref[...] = cur
        sums_ref[...] = jnp.zeros(sums_ref.shape, jnp.int32)

    @pl.when(k > 0)
    def _fold():
        dirty_ref[...] = jnp.maximum(dirty_ref[...], cur)

    def _sums():
        def body(c, acc):
            sl = pl.ds(pl.multiple_of(c * ch, ch), ch)
            x = words.as_words(new_ref[:, sl, :])
            first = (k * r + c * ch) * itemsize // 4
            return tuple(a + v for a, v in zip(acc, _lane_sums(x, first, itemsize, rb)))

        zero = jnp.zeros((b, 1, words.LANES), jnp.int32)
        lanes = jax.lax.fori_loop(0, r // ch, body, (zero,) * CHECKSUM_LANES)
        for t, v in enumerate(lanes):
            sums_ref[:, t : t + 1, :] = sums_ref[:, t : t + 1, :] + v

    # a split row may turn dirty in a later sub-block, so its checksum
    # parts are always summed; whole-row blocks skip clean blocks
    if split:
        _sums()
    else:
        pl.when(jnp.max(cur) > 0)(_sums)


def compact_rows(new: jax.Array, dirty: jax.Array, max_changed: int):
    """Dirty rows of ``new`` front-packed in ascending order: (data, idx,
    count), bit-identical to ``ref.delta_compact_ref`` but a gather of
    ``max_changed`` rows instead of a scatter of all ``N``."""
    count = jnp.sum(dirty.astype(jnp.int32))
    idx = jnp.nonzero(dirty, size=max_changed, fill_value=-1)[0].astype(jnp.int32)
    rows = jnp.take(new, jnp.maximum(idx, 0), axis=0)
    data = jnp.where((idx >= 0)[:, None], rows, jnp.zeros((), new.dtype))
    return data, idx, count


def delta_fused(
    old: jax.Array,     # (N, C)
    new: jax.Array,     # (N, C)
    *,
    max_changed: int,
    chunk_block: Optional[int] = None,
    interpret: bool = False,
):
    """Fused diff+compact+checksum: (data, idx, count, sums).

    Same contract as ``ref.fused_encode_ref`` — data (max_changed, C) with
    dirty rows in ascending order, idx (max_changed,) int32 (-1 padding),
    count () int32 over all dirty rows, sums (max_changed, CHECKSUM_LANES)
    uint32 zeroed on unused slots.
    """
    assert old.shape == new.shape and old.dtype == new.dtype
    N = old.shape[0]
    cap = int(max_changed)
    assert cap >= 1
    rb = old.shape[1] * jnp.dtype(old.dtype).itemsize
    b, r, lines = words.block_plan(N, old.shape[1], old.dtype, chunk_block)
    ol = words.pad_rows(words.to_lines(old, lines), b)
    nl = words.pad_rows(words.to_lines(new, lines), b)
    n_pad = ol.shape[0]
    spec = pl.BlockSpec((b, r, words.LANES), lambda i, k: (i, k, 0))
    dirty, sums = pl.pallas_call(
        functools.partial(
            _fused_kernel, rb=rb, split=lines > r, ch=words.chunk_lines(r, old.dtype)
        ),
        grid=(n_pad // b, lines // r),
        in_specs=[spec, spec],
        out_specs=[
            pl.BlockSpec((b, 1, words.LANES), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((b, CHECKSUM_LANES, words.LANES), lambda i, k: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1, words.LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, CHECKSUM_LANES, words.LANES), jnp.int32),
        ],
        interpret=interpret,
    )(ol, nl)
    dirty = jnp.any(dirty[:N, 0] != 0, axis=-1)
    row_sums = jnp.sum(
        jax.lax.bitcast_convert_type(sums[:N], jnp.uint32), axis=-1, dtype=jnp.uint32
    )
    data, idx, count = compact_rows(new, dirty, cap)
    valid = (idx >= 0)[:, None]
    sums = jnp.where(valid, jnp.take(row_sums, jnp.maximum(idx, 0), axis=0), jnp.uint32(0))
    return data, idx, count, sums
