"""Pallas TPU kernels for the C/R hot paths the paper optimizes.

* ``paged_attention`` — decode attention through a CoW page table
  (block-table indirection; what makes fork-shared KV pages readable in place).
* ``page_copy`` — batched CoW page privatization (fault / async-warm path).
* ``delta_diff`` / ``delta_fused`` / ``delta_apply`` — dirty-chunk
  detection (fused with the integrity checksums) and scatter-back (the
  delta-dump and slow-restore paths), over the row layout of ``words.py``.
* ``tile_diff`` — per-tile dirtiness over a shard's native block (the
  sharded dump path).

Each kernel ships as ``<name>.py`` (pl.pallas_call + BlockSpec), with the
jit'd wrappers in ``ops.py`` and pure-jnp oracles in ``ref.py``;
``tests/test_tpu_compile.py`` compiles each for a described TPU v5e.
"""
from . import ops, ref
from .ops import (
    chunk_checksums_host,
    delta_apply,
    delta_compact,
    delta_diff,
    delta_encode,
    fused_encode,
    page_copy,
    paged_attention,
)

__all__ = [
    "ops",
    "ref",
    "chunk_checksums_host",
    "delta_apply",
    "delta_compact",
    "delta_diff",
    "delta_encode",
    "fused_encode",
    "page_copy",
    "paged_attention",
]
