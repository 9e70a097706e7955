"""Pallas TPU speculative-verification attention — K query tokens vs a
page-table KV cache (DESIGN.md §11).

Self-speculative decoding scores a whole draft window in ONE model call:
the engine appends the ``K`` speculative tokens' K/V into the row's
pages (positions ``cache_len .. cache_len+K-1``) and then asks, for each
window position ``j``, "what would greedy decode have sampled after
consuming tokens ``0..j``?".  That is attention with **causal masking
inside the speculative window**: query ``j`` of row ``b`` may attend to
context positions ``< cache_len[b] + j + 1`` — its own (just written)
position and everything before it, never the later draft positions.

The page indirection is exactly :mod:`repro.kernels.paged_decode_attention`:
grid ``(batch, n_table_slots)`` with the table slot minor, K/V BlockSpec
index maps resolving each slot to a whole physical pool page (all KV
heads) via the scalar-prefetched page table, and a loop over the KV
heads in VMEM.  The only generalization is the query tile: all
``K × G`` (window × grouped-heads) queries of one KV head ride in a
single ``(K·G, hd)`` VMEM tile — each cache byte is still read once per
(row, kv-head) — and the per-page mask adds the query's window offset
``j = row // G`` to the length bound.  With ``K == 1`` the tile,
the mask, and the accumulator update degenerate to the decode kernel's
(the K=1 parity test pins this).
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
            m_scr, l_scr, acc_scr, *, scale, page, n_slots, K, G, KV):
    b = pl.program_id(0)
    si = pl.program_id(1)
    cache_len = len_ref[b]

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the deepest query of the window reaches cache_len + K keys; pages
    # wholly past that bound are skipped (their index map clamps to page
    # 0; the fetch is never used)
    @pl.when(si * page < cache_len + K)
    def _compute():
        for h in range(KV):
            q = q_ref[0, h]                        # (K·G, hd)
            k = k_ref[0, :, h, :]                  # (page, hd)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                              # (K·G, page)
            KG, pk = s.shape
            pos = si * page + jax.lax.broadcasted_iota(jnp.int32, (KG, pk), 1)
            # query row r belongs to window position j = r // G and may
            # see positions < cache_len + j + 1 (causal inside the window)
            j = jax.lax.broadcasted_iota(jnp.int32, (KG, pk), 0) // G
            s = jnp.where(pos < cache_len + j + 1, s, NEG_INF)
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


def spec_verify_attention(
    q: jax.Array,           # (B, K, H, hd) — speculative-window queries
    k_pool: jax.Array,      # (n_pages, page, KV, hd) — shared page pool
    v_pool: jax.Array,      # (n_pages, page, KV, hd)
    page_table: jax.Array,  # (B, n_slots) int32 — pool page per table slot
    cache_len: jax.Array,   # (B,) int32 — context length BEFORE the window
    *,
    interpret: bool = True,
) -> jax.Array:
    """Multi-token verification attention through per-row page tables.

    The K/V of the window's tokens must already be written at positions
    ``cache_len .. cache_len+K-1`` of each row's pages.  Query ``j``
    attends to positions ``< cache_len + j + 1``; with ``K == 1`` this
    is exactly ``paged_decode_attention(q, ..., cache_len + 1)``.
    """
    n_pages, page, KV, hd = k_pool.shape
    B, n_slots = page_table.shape
    K, H = q.shape[1], q.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    # (B, K, KV, G, hd) → (B, KV, K·G, hd): window-major rows per KV head
    qg = q.reshape(B, K, KV, G, hd).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(B, KV, K * G, hd)
    kernel = functools.partial(_kernel, scale=scale, page=page,
                               n_slots=n_slots, K=K, G=G, KV=KV)
    # clamp: slots past the valid window still produce an in-bounds fetch
    # (skipped by pl.when); the table itself is engine-padded, this only
    # guards against garbage ids in the dead tail
    table = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page table + cache_len drive the DMA
        grid=(B, n_slots),
        in_specs=[
            pl.BlockSpec((1, KV, K * G, hd),
                         lambda b, si, table_ref, len_ref: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, si, table_ref, len_ref:
                         (table_ref[b, si], 0, 0, 0)),
            pl.BlockSpec((1, page, KV, hd),
                         lambda b, si, table_ref, len_ref:
                         (table_ref[b, si], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, K * G, hd),
                               lambda b, si, table_ref, len_ref:
                               (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, K * G, 1), jnp.float32),
            pltpu.VMEM((KV, K * G, 1), jnp.float32),
            pltpu.VMEM((KV, K * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, K * G, hd), q.dtype),
        interpret=interpret,
    )(table, cache_len.astype(jnp.int32), qg, k_pool, v_pool)
    out = out.reshape(B, KV, K, G, hd).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, K, H, hd)
