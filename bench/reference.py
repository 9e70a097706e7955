"""Plain float32 reference of a dense GQA decoder (Llama-style layer).

Written from the published description, in straightforward ``jax.numpy``
at ``highest`` matmul precision, with no cache, no paging and no
batching tricks; it imports nothing of the program.  Per layer:

    h = x + Wo · attn(rope(Wq · n1(x)), rope(Wk · n1(x)), Wv · n1(x))
    x' = h + Wdown · (silu(Wgate · n2(h)) * (Wup · n2(h)))

with RMS norms ``n(x) = x / sqrt(mean(x^2) + eps) * w``, rotary
embeddings on the two halves of each head (theta from the config),
causal softmax attention scaled by 1/sqrt(head_dim), and key/value head
``h // (heads / kv_heads)`` serving query head ``h``.  Logits are the
final-normed state times the (tied or separate) output table.

It runs layer by layer, drawing each layer's weights again from the
seed (``weights.draw_slice``), so it never holds more than one layer's
float32 weights beside the activations.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
Q_CHUNK = 512


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, n, hd); pos: (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _row_layer(x, w, *, KV, G, hd, eps, theta):
    """One layer over one row ``x`` (S, D)."""
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, w["attn_norm"], eps)
    q = _rope(jnp.einsum("sd,dhk->shk", h, w["wq"], precision=HIGHEST),
              pos, theta)
    k = _rope(jnp.einsum("sd,dhk->shk", h, w["wk"], precision=HIGHEST),
              pos, theta)
    v = jnp.einsum("sd,dhk->shk", h, w["wv"], precision=HIGHEST)
    q = q.reshape(S // Q_CHUNK, Q_CHUNK, KV, G, hd)
    outs = []
    for c in range(S // Q_CHUNK):  # queries c*Q..: keys up to their end
        end = (c + 1) * Q_CHUNK
        s = jnp.einsum("qkgd,skd->kgqs", q[c], k[:end], precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(hd))
        qpos = c * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.where(pos[None, None, None, :end] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v[:end],
                               precision=HIGHEST))
    o = jnp.concatenate(outs).reshape(S, KV * G, hd)
    x = x + jnp.einsum("shk,hkd->sd", o, w["wo"], precision=HIGHEST)
    h = _rms(x, w["mlp_norm"], eps)
    g = jnp.einsum("sd,df->sf", h, w["w_gate"], precision=HIGHEST)
    u = jnp.einsum("sd,df->sf", h, w["w_up"], precision=HIGHEST)
    return x + jnp.einsum("sf,fd->sd", jax.nn.silu(g) * u, w["w_down"],
                          precision=HIGHEST)


_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
         "num_key_value_heads", "head_dim", "intermediate_size",
         "vocab_size", "tie_word_embeddings", "rms_norm_eps", "rope_theta")


@functools.lru_cache(maxsize=None)
def _programs(frozen: tuple):
    """Jitted pieces for one configuration (keyed by its sizes)."""
    cfg = dict(zip(_KEYS, frozen))
    s = W.sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=(1,))
    def leaf(key, name, index):
        return W.draw_slice(cfg, key, name, index, jnp.bfloat16).astype(f32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def table(key, name):
        return W._table(cfg, key, name, jnp.bfloat16, s["V"]).astype(f32)

    row = functools.partial(_row_layer, KV=s["KV"], G=s["H"] // s["KV"],
                            hd=s["hd"], eps=eps, theta=theta)

    @jax.jit
    def layer(x, w):
        return jax.lax.map(lambda xr: row(xr, w), x)

    @jax.jit
    def head(x, rows, cols, norm, table):
        h = _rms(x[rows, cols], norm, eps)
        return jnp.einsum("nd,vd->nv", h, table, precision=HIGHEST)

    return leaf, table, layer, head


def logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
           want: Sequence[Sequence[int]], length: int, batch: int
           ) -> List[np.ndarray]:
    """Float32 logits of each sequence at its ``want`` positions.

    ``seqs`` are token ids; they are padded to ``length`` positions
    (a multiple of 512) and to ``batch`` rows, so that one compiled
    program serves every run of a cell."""
    if len(seqs) > batch or any(len(s) > length for s in seqs):
        raise ValueError("sequences exceed the reference's padded shape")
    s = W.sizes(cfg)
    leaf, table, layer, head = _programs(tuple(cfg.get(k) for k in _KEYS))
    key = W.root_key(seed)
    toks = np.zeros((batch, length), np.int32)
    for r, ids in enumerate(seqs):
        toks[r, :len(ids)] = ids
    emb = table(key, "embed")
    x = emb[jnp.asarray(toks)]
    if s["tied"]:
        out_table = emb
    else:
        del emb
        out_table = table(key, "unembed")
    for l in range(s["L"]):
        w = {n: leaf(key, n, l) for n in W.LAYER_LEAVES}
        x = layer(x, w)
    rows = np.concatenate([np.full(len(p), r) for r, p in enumerate(want)])
    cols = np.concatenate([np.asarray(p) for p in want])
    lg = np.asarray(head(x, jnp.asarray(rows), jnp.asarray(cols),
                         leaf(key, "final_norm", 0), out_table))
    splits = np.cumsum([len(p) for p in want])[:-1]
    return np.split(lg, splits)
