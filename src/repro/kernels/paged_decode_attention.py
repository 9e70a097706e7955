"""Pallas TPU paged decode attention — one query token vs a page-table
KV cache (DESIGN.md §10).

The paged engine stores all KV page-granular in one shared pool
``(n_pages, page_size, KV, hd)`` per layer; each decode row owns a
*page table* — the ordered page ids holding its context.  This kernel
reads the cache **through the page table** with no gather/copy into a
contiguous row: the grid is ``(batch, n_table_pages)`` with the table
slot minor, and the K/V BlockSpec index maps resolve the slot to a
physical pool page via a scalar-prefetched page table
(``pltpu.PrefetchScalarGridSpec``) — the indirection happens in the DMA
schedule, not in an HBM-materialized gather.

Each grid step fetches one whole pool page ``(page, KV, hd)`` — every KV
head at once, so the block's last two dims equal the pool's, as the TPU
lowering requires — and loops over the KV heads in VMEM.  As in
``decode_attention``, all ``G`` grouped query heads of one KV head ride
along in a single (G, hd) tile so each cache byte is read once per
group, and ragged lengths are masked per page from the per-row
``cache_len`` scalar — table slots entirely past the valid prefix are
skipped with ``pl.when`` (their index map clamps to page 0; the fetch is
never used).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, page, n_slots, KV):
    b = pl.program_id(0)
    si = pl.program_id(1)
    cache_len = len_ref[b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(si * page < cache_len)
    def _compute():
        for h in range(KV):
            q = q_ref[0, h]                        # (G, hd)
            k = k_ref[0, :, h, :]                  # (page, hd)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                              # (G, page)
            G, pk = s.shape
            pos = si * page + jax.lax.broadcasted_iota(jnp.int32, (G, pk), 1)
            s = jnp.where(pos < cache_len, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    @pl.when(si == n_slots - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,           # (B, 1, H, hd)
    k_pool: jax.Array,      # (n_pages, page, KV, hd) — shared page pool
    v_pool: jax.Array,      # (n_pages, page, KV, hd)
    page_table: jax.Array,  # (B, n_slots) int32 — pool page per table slot
    cache_len: jax.Array,   # (B,) int32 — valid context length per row
    *,
    interpret: bool = True,
) -> jax.Array:
    """Decode attention reading K/V through per-row page tables.

    Table slot ``i`` of row ``b`` holds positions
    ``[i·page, (i+1)·page)`` of that row's context in pool page
    ``page_table[b, i]``; slots at or past ``ceil(cache_len/page)`` may
    hold any in-range id (they are masked/skipped).
    """
    n_pages, page, KV, hd = k_pool.shape
    B, n_slots = page_table.shape
    H = q.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, KV, G, hd)
    kernel = functools.partial(_kernel, scale=scale, page=page,
                               n_slots=n_slots, KV=KV)
    # clamp: slots past the valid prefix still produce an in-bounds fetch
    # (skipped by pl.when); the table itself is engine-padded, this only
    # guards against garbage ids in the dead tail
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page table + cache_len drive the DMA
        grid=(B, n_slots),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd),
                         lambda b, si, table_ref, len_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, si, table_ref, len_ref:
                         (table_ref[b, si], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, si, table_ref, len_ref:
                         (table_ref[b, si], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd),
                               lambda b, si, table_ref, len_ref:
                               (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
    )(table, cache_len.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(B, 1, H, hd)
