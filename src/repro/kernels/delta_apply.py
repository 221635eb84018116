"""Pallas TPU kernel: scatter compacted dirty chunks into a base tensor.

The slow-path restore: a dump image arrives as (compacted dirty chunks,
chunk indices); this kernel scatters them into the parent-generation tensor
in place (aliased base).  Rows move as ``(lines, 128)`` blocks of their own
dtype (``words.py``), one row (or one sub-block of a large row) per grid
step.

Index rows with ``idx == -1`` are padding from the fixed-capacity
compaction and must not change the base.  A step writes its whole output
block, so a padding step re-writes the data of the last valid row before
it into that same row (the block stays resident, no extra traffic), and
padding with no valid row before it copies base row 0 onto itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import words

__all__ = ["delta_apply"]


def _delta_apply_kernel(src_ref, dst_ref, write_ref, data_ref, base_ref, out_ref):
    del src_ref, dst_ref
    j = pl.program_id(0)

    @pl.when(write_ref[j] > 0)
    def _data():
        out_ref[...] = data_ref[...]

    @pl.when(write_ref[j] == 0)
    def _keep():
        out_ref[...] = base_ref[...]


def delta_apply(
    base: jax.Array,     # (N, C) — aliased to the output
    data: jax.Array,     # (M, C)
    idx: jax.Array,      # (M,) int32, -1 padding
    *,
    interpret: bool = False,
) -> jax.Array:
    N, C = base.shape
    M = data.shape[0]
    _, r, lines = words.block_plan(1, C, base.dtype)
    bl = words.to_lines(base, lines)
    dl = words.to_lines(data, lines)

    idx = idx.astype(jnp.int32)
    pos = jnp.arange(M, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(idx >= 0, pos, -1), axis=0)
    write = (last >= 0).astype(jnp.int32)
    src = jnp.maximum(last, 0)
    dst = jnp.where(write > 0, idx[src], 0)

    blk = (1, r, words.LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(M, lines // r),
        in_specs=[
            pl.BlockSpec(blk, lambda j, k, s, d, w: (s[j], k, 0)),
            # base is read only before the first valid row, where dst is 0
            pl.BlockSpec(blk, lambda j, k, s, d, w: (0, k, 0)),
        ],
        out_specs=pl.BlockSpec(blk, lambda j, k, s, d, w: (d[j], k, 0)),
    )
    out = pl.pallas_call(
        _delta_apply_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(bl.shape, bl.dtype),
        input_output_aliases={4: 0},  # base (5th operand incl. scalars) -> out
        interpret=interpret,
    )(src, dst, write, dl, bl)
    return words.from_lines(out, C)
