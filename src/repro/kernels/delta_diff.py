"""Pallas TPU kernel: blockwise dirty-chunk detection for delta dumps.

The checkpoint-dump hot path: compare the current generation of a chunked
tensor against its parent and emit a per-chunk dirty bitmap.  The dump then
moves only dirty chunks device→host ("duplicate only the changes").  Rows
are laid out as lines and compared as int32 words (``words.py``): one grid
step compares a ``(rows, lines, 128)`` block in VMEM and folds it to one
lane vector per row; rows larger than a block are split over an inner grid
axis that accumulates into the same output block.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import words

__all__ = ["block_dirty", "delta_diff"]


def block_dirty(old_ref, new_ref, ch: int):
    """Per-row lane maxima of ``old != new`` over a ``(b, r, 128)`` block,
    folded ``ch`` lines at a time: ``(b, 1, 128)`` int32 (0 = clean)."""
    b, r, _ = old_ref.shape

    def body(c, acc):
        sl = pl.ds(pl.multiple_of(c * ch, ch), ch)
        neq = words.as_words(old_ref[:, sl, :]) != words.as_words(new_ref[:, sl, :])
        return jnp.maximum(acc, jnp.max(neq.astype(jnp.int32), axis=1, keepdims=True))

    return jax.lax.fori_loop(0, r // ch, body, jnp.zeros((b, 1, words.LANES), jnp.int32))


def _delta_diff_kernel(old_ref, new_ref, dirty_ref, *, ch: int):
    k = pl.program_id(1)
    cur = block_dirty(old_ref, new_ref, ch)

    @pl.when(k == 0)
    def _first():
        dirty_ref[...] = cur

    @pl.when(k > 0)
    def _rest():
        dirty_ref[...] = jnp.maximum(dirty_ref[...], cur)


def delta_diff(
    old: jax.Array,     # (N, C)
    new: jax.Array,     # (N, C)
    *,
    chunk_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Per-chunk dirty bitmap, (N,) bool."""
    assert old.shape == new.shape and old.dtype == new.dtype
    N, C = old.shape
    b, r, lines = words.block_plan(N, C, old.dtype, chunk_block)
    ol = words.pad_rows(words.to_lines(old, lines), b)
    nl = words.pad_rows(words.to_lines(new, lines), b)
    spec = pl.BlockSpec((b, r, words.LANES), lambda i, k: (i, k, 0))
    out = pl.pallas_call(
        functools.partial(_delta_diff_kernel, ch=words.chunk_lines(r, old.dtype)),
        grid=(ol.shape[0] // b, lines // r),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((b, 1, words.LANES), lambda i, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ol.shape[0], 1, words.LANES), jnp.int32),
        interpret=interpret,
    )(ol, nl)
    return jnp.any(out[:N, 0] != 0, axis=-1)
