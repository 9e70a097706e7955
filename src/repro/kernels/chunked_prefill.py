"""Pallas TPU chunked-prefill attention — suffix queries over a cached
prefix plus their own causal window (the prefix-cache prefill path,
DESIGN.md §9).

A prompt whose first ``prefix_len`` tokens are served from the radix
prefix cache only computes Q/K/V for the *suffix*; attention must still
span the full context.  The kernel walks the KV axis in two phases on
the minor grid dimension:

* **prefix phase** (``ki < n_p``) — stream the cached K/V pages; every
  suffix query attends to every valid prefix position
  (``col < prefix_len``, a per-row scalar from SMEM).  Blocks entirely
  past the valid prefix are skipped with ``pl.when`` — ragged prefix
  lengths cost no dead HBM reads, mirroring ``decode_attention``.
* **suffix phase** (``ki >= n_p``) — standard causal flash attention in
  suffix-local coordinates (query ``i`` and key ``j`` sit at absolute
  positions ``prefix_len + i`` / ``prefix_len + j``, so the causal
  comparison is position-shift invariant).  Blocks strictly above the
  diagonal are skipped, as in ``flash_attention``.

The fp32 running-softmax accumulators live in VMEM scratch and persist
across both phases — one softmax over the concatenated context, never a
materialized (S, P+S) score matrix.  The wrapper moves heads ahead of
the sequence (``(B, heads, seq, hd)``) so every block's last two dims
are ``(block, hd)``, as the TPU lowering requires, and passes
``prefix_len`` as a scalar-prefetch operand (SMEM).  GQA rides the index
maps: K/V specs map query head ``h`` to ``h // G``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(plen_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, n_p, n_s, block_p, block_s):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    plen = plen_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate(s_blk, v):
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # ---- phase 1: cached prefix pages, masked by the per-row prefix_len
    @pl.when(jnp.logical_and(ki < n_p, ki * block_p < plen))
    def _prefix():
        q = q_ref[0, 0]                           # (cq, hd)
        k = kp_ref[0, 0]                          # (cp, hd)
        v = vp_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                  # (cq, cp)
        cq, cp = s.shape
        cols = ki * cp + jax.lax.broadcasted_iota(jnp.int32, (cq, cp), 1)
        _accumulate(jnp.where(cols < plen, s, NEG_INF), v)

    # ---- phase 2: causal suffix (suffix-local coordinates)
    si = ki - n_p
    q_len = q_ref.shape[2]

    @pl.when(jnp.logical_and(ki >= n_p,
                             si * block_s <= qi * q_len + q_len - 1))
    def _suffix():
        q = q_ref[0, 0]
        k = ks_ref[0, 0]
        v = vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                  # (cq, cs)
        cq, cs = s.shape
        rows = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, cs), 0)
        cols = si * cs + jax.lax.broadcasted_iota(jnp.int32, (cq, cs), 1)
        _accumulate(jnp.where(rows >= cols, s, NEG_INF), v)

    @pl.when(ki == n_p + n_s - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)


def _divisor_block(n: int, target: int) -> int:
    b = min(target, n)
    while n % b:
        b -= 1
    return b


def chunked_prefill_attention(
    q: jax.Array,           # (B, S, H, hd) — suffix queries
    k_suffix: jax.Array,    # (B, S, KV, hd)
    v_suffix: jax.Array,    # (B, S, KV, hd)
    k_prefix: jax.Array,    # (B, P, KV, hd) — cached pages (may be ragged)
    v_prefix: jax.Array,    # (B, P, KV, hd)
    prefix_len: jax.Array,  # (B,) int32 — valid cached tokens per row
    *,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    B, S, H, hd = q.shape
    KV = k_suffix.shape[2]
    P = k_prefix.shape[1]
    if P == 0:
        raise ValueError("P == 0: use flash_attention for the no-prefix case")
    G = H // KV
    block_q = _divisor_block(S, block_q)
    block_s = _divisor_block(S, block_k)
    block_p = _divisor_block(P, block_k)
    n_q, n_s, n_p = S // block_q, S // block_s, P // block_p
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _kernel, scale=scale, n_p=n_p, n_s=n_s,
        block_p=block_p, block_s=block_s,
    )
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)  # (B,S,h,hd) → (B,h,S,hd)
    prefix_blk = lambda b, h, qi, ki, plen: (b, h // G,
                                             jnp.minimum(ki, n_p - 1), 0)
    suffix_blk = lambda b, h, qi, ki, plen: (b, h // G,
                                             jnp.maximum(ki - n_p, 0), 0)
    # the minor dim covers prefix pages then suffix blocks; each spec
    # clamps its index so the "other" phase re-fetches a resident block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # prefix_len
        grid=(B, H, n_q, n_p + n_s),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki, plen: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_p, hd), prefix_blk),
            pl.BlockSpec((1, 1, block_p, hd), prefix_blk),
            pl.BlockSpec((1, 1, block_s, hd), suffix_blk),
            pl.BlockSpec((1, 1, block_s, hd), suffix_blk),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki, plen: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        interpret=interpret,
    )(prefix_len.astype(jnp.int32), heads_major(q), heads_major(k_prefix),
      heads_major(v_prefix), heads_major(k_suffix), heads_major(v_suffix))
    return heads_major(out)
