"""Pallas TPU kernel: decode attention through a CoW page table.

The serving hot path that makes template-fork restores free on TPU: forked
sessions *share* KV pages, so the decode step must read K/V through each
session's page table rather than a contiguous cache.  The page table and
sequence lengths are scalar-prefetch operands — the BlockSpec index maps
resolve the page indirection at DMA-issue time, so only the pages a session
actually references move HBM→VMEM (block-table indirection, the TPU analogue
of reading through CoW page tables).

Layout:
  q          (B, KVH, G, D)   — queries grouped under their kv head (GQA)
  k/v pages  (P, page_size, KVH, D)
  page_table (B, max_pages)   int32, entries beyond the active count must be
                              valid page ids (the pool keeps page 0 reserved)
  seq_lens   (B,)             int32

Grid (B, max_pages); the page axis iterates fastest and carries a
flash-style running (m, l, acc) in VMEM scratch.  One K/V block is a whole
page over every kv head, ``(1, page_size, KVH, D)``: its last two dims are
the array's own, which the TPU block rule accepts for any head count (a
one-head block is refused).  Scores and the weighted sum run per kv head on
the vector unit, ``G`` query rows at a time; decode attention reads each
page once and is bound by that read.  Grid steps past a session's last
page re-map to that page, so its K/V are not fetched again.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention"]

_NEG_INF = -1e30
_LANES = 128


def _paged_attention_kernel(
    # scalar prefetch
    seq_lens_ref,      # (B,)
    page_table_ref,    # (B, max_pages)
    # blocks
    q_ref,             # (1, G, KVH, D)
    k_ref,             # (1, page_size, KVH, D)
    v_ref,             # (1, page_size, KVH, D)
    o_ref,             # (1, G, KVH, D)
    # scratch
    m_scratch,         # (G, KVH, _LANES) f32
    l_scratch,         # (G, KVH, _LANES) f32
    acc_scratch,       # (G, KVH, D) f32
    *,
    page_size: int,
    num_page_steps: int,
    scale: float,
):
    b = pl.program_id(0)
    i = pl.program_id(1)
    G = q_ref.shape[1]

    @pl.when(i == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    seq_len = seq_lens_ref[b]
    page_start = i * page_size

    @pl.when(page_start < seq_len)  # skip fully-masked pages
    def _body():
        k = k_ref[0].astype(jnp.float32)                             # (psz, KVH, D)
        v = v_ref[0].astype(jnp.float32)
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, (page_size, 1, 1), 0)
        for g in range(G):
            q = q_ref[0, g].astype(jnp.float32) * scale              # (KVH, D)
            s = jnp.sum(k * q[None], axis=2, keepdims=True)          # (psz, KVH, 1)
            s = jnp.where(pos < seq_len, s, _NEG_INF)
            m_prev = m_scratch[g][:, :1]                             # (KVH, 1)
            l_prev = l_scratch[g][:, :1]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=0))         # (KVH, 1)
            alpha = jnp.exp(m_prev - m_next)
            p = jnp.exp(s - m_next[None])                            # (psz, KVH, 1)
            l_next = alpha * l_prev + jnp.sum(p, axis=0)
            acc_scratch[g] = acc_scratch[g] * alpha + jnp.sum(p * v, axis=0)
            m_scratch[g] = jnp.broadcast_to(m_next, m_scratch.shape[1:])
            l_scratch[g] = jnp.broadcast_to(l_next, l_scratch.shape[1:])

    @pl.when(i == num_page_steps - 1)
    def _finalize():
        for g in range(G):
            l = l_scratch[g][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)                          # seq_len == 0 guard
            o_ref[0, g] = (acc_scratch[g] / l).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    seq_lens: jax.Array,
    *,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """See module docstring.  Returns (B, KVH, G, D) in q.dtype."""
    B, KVH, G, D = q.shape
    P, page_size, KVH_k, D_k = k_pages.shape
    assert (KVH_k, D_k) == (KVH, D), (k_pages.shape, q.shape)
    assert v_pages.shape == k_pages.shape
    max_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _paged_attention_kernel,
        page_size=page_size,
        num_page_steps=max_pages,
        scale=float(scale),
    )

    def kv_map(b, i, sl, pt):
        last = jnp.maximum((sl[b] - 1) // page_size, 0)
        return (pt[b, jnp.minimum(i, last)], 0, 0, 0)

    q_spec = pl.BlockSpec((1, G, KVH, D), lambda b, i, sl, pt: (b, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, page_size, KVH, D), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((G, KVH, _LANES), jnp.float32),
            pltpu.VMEM((G, KVH, _LANES), jnp.float32),
            pltpu.VMEM((G, KVH, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, KVH, D), q.dtype),
        interpret=interpret,
    )(
        seq_lens.astype(jnp.int32),
        page_table.astype(jnp.int32),
        jnp.swapaxes(q, 1, 2),
        k_pages,
        v_pages,
    )
    return jnp.swapaxes(out, 1, 2)
