"""A dense GQA decoder (Llama-style layer): its weights, its plain
reference and its work counts.

Weights.  Layout (names are the benchmark's; ``to_program`` maps them
onto the program's tree): ``embed`` (V, D), ``final_norm`` (D,),
``unembed`` (V, D) when the head is untied, and ``layers`` with stacked
``attn_norm`` (L, D), ``wq`` (L, D, H, hd), ``wk``/``wv`` (L, D, KV,
hd), ``wo`` (L, H, hd, D), ``mlp_norm`` (L, D), ``w_gate``/``w_up``
(L, D, F), ``w_down`` (L, F, D); each drawn slice by slice from the
seed (``bench/weights.py``), leaf ``name`` under the key folded with its
place in ``LAYER_LEAVES + TOP_LEAVES``.

Reference.  Written from the published description, in straightforward
``jax.numpy`` at ``highest`` matmul precision, with no cache, no paging
and no batching tricks; it imports nothing of the program.  Per layer:

    h = x + Wo · attn(rope(Wq · n1(x)), rope(Wk · n1(x)), Wv · n1(x))
    x' = h + Wdown · (silu(Wgate · n2(h)) * (Wup · n2(h)))

with RMS norms ``n(x) = x / sqrt(mean(x^2) + eps) * w``, rotary
embeddings on the two halves of each head (theta from the config),
causal softmax attention scaled by 1/sqrt(head_dim), and key/value head
``h // (heads / kv_heads)`` serving query head ``h``.  Logits are the
final-normed state times the (tied or separate) output table.  It runs
layer by layer, drawing each layer's weights again from the seed, so it
never holds more than one layer's float32 weights beside the
activations.

Work counts.  What the algorithm needs, not what an implementation
happens to execute (padding, recomputation and dead rows do not count).
A matmul of (m x k) by (k x n) is 2mkn FLOPs.  One query position
attending ``ctx`` keys costs 4 * heads * head_dim * ctx per layer
(scores and the weighted sum of values).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Iterable, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

# ------------------------------------------------------------------ sizes

#: ``ModelConfig`` attribute -> configuration-file key, for every width
#: and constant the program must share with the file
FIELDS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "n_kv_heads": "num_key_value_heads", "resolved_head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
          "tie_embeddings": "tie_word_embeddings"}

#: program presets whose smoke sizes the reference test runs (3 layers)
SMOKE_ARCHS = {"granite-3-2b": {"n_layers": 3}, "yi-9b": {"n_layers": 3}}


def program_fields(cfg: dict) -> dict:
    """The depth and the choice of the Pallas kernels, from the file."""
    return {"n_layers": cfg["num_hidden_layers"],
            "use_pallas": cfg["serving"]["use_pallas"]}


def checked_fields(cfg: dict) -> dict:
    return {attr: cfg[key] for attr, key in FIELDS.items()}


def file_config(pc) -> dict:
    """The configuration-file keys of the program config ``pc``."""
    out = {key: getattr(pc, attr) for attr, key in FIELDS.items()}
    out["num_hidden_layers"] = pc.n_layers
    return out


def sizes(cfg: dict) -> dict:
    """The widths a dense GQA decoder needs, from a configuration file."""
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return {
        "L": cfg["num_hidden_layers"], "D": D, "H": H,
        "KV": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or D // H,
        "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
        "tied": bool(cfg["tie_word_embeddings"]),
    }


def vocab(cfg: dict) -> int:
    return cfg["vocab_size"]


# ---------------------------------------------------------------- weights

#: standard deviation of a head's attention scores ``q.k / sqrt(hd)``
#: over random keys.  At 1 (both projections N(0, 1/D)) a head spreads
#: its weight over ~40 % of 4096 random keys, which averages away what
#: the KV cache's rounding does to any one key: the program's fp8 cache
#: read 1.3x the sound runs' KL divergence on the chip, and 2.6x at 2.
#: At 3 a head puts its weight on ~0.5 % of them, as trained heads put
#: theirs on few tokens.  ``wq`` and ``wk`` are each drawn sqrt(3) wider.
ATTN_SCORE_STD = 3.0

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo",
                "mlp_norm", "w_gate", "w_up", "w_down")
TOP_LEAVES = ("embed", "final_norm", "unembed")


def slice_spec(cfg: dict, name: str) -> Tuple[Tuple[int, ...], str, float]:
    """(shape of one slice, init kind, scale) of leaf ``name``."""
    s = sizes(cfg)
    D, H, KV, hd, F = s["D"], s["H"], s["KV"], s["hd"], s["F"]
    table = {
        "attn_norm": ((D,), "norm", 0.1),
        "mlp_norm": ((D,), "norm", 0.1),
        "final_norm": ((D,), "norm", 0.1),
        "wq": ((D, H, hd), "normal", math.sqrt(ATTN_SCORE_STD / D)),
        "wk": ((D, KV, hd), "normal", math.sqrt(ATTN_SCORE_STD / D)),
        "wv": ((D, KV, hd), "normal", 1 / math.sqrt(D)),
        "wo": ((H, hd, D), "normal", 1 / math.sqrt(H * hd)),
        "w_gate": ((D, F), "normal", 1 / math.sqrt(D)),
        "w_up": ((D, F), "normal", 1 / math.sqrt(D)),
        "w_down": ((F, D), "normal", 1 / math.sqrt(F)),
        "embed": ((W.ROW_BLOCK, D), "normal", 0.02),
        "unembed": ((W.ROW_BLOCK, D), "normal", 0.02),
    }
    return table[name]


def draw_slice(cfg: dict, key: jax.Array, name: str, index, dtype):
    """Slice ``index`` of leaf ``name`` in ``dtype`` (traceable)."""
    leaf_key = jax.random.fold_in(key, (LAYER_LEAVES + TOP_LEAVES).index(name))
    return W.draw(leaf_key, index, slice_spec(cfg, name), dtype)


def _table(cfg: dict, key, name: str, dtype, rows: int):
    return W.table(lambda i: draw_slice(cfg, key, name, i, dtype),
                   sizes(cfg)["V"], rows, dtype)


def draw_params(cfg: dict, seed: int, dtype=jnp.bfloat16,
                vocab_rows: int = 0) -> Dict:
    """The whole weight tree, in one jitted call on the default device.

    ``vocab_rows`` (>= vocab_size) pads the embedding tables with zero
    rows, for a program that serves a padded vocabulary."""
    s = sizes(cfg)
    rows = max(vocab_rows, s["V"])

    @jax.jit
    def build(key):
        layers = {
            name: jax.lax.map(
                lambda i, name=name: draw_slice(cfg, key, name, i, dtype),
                jnp.arange(s["L"]))
            for name in LAYER_LEAVES
        }
        out = {"embed": _table(cfg, key, "embed", dtype, rows),
               "final_norm": draw_slice(cfg, key, "final_norm", 0, dtype),
               "layers": layers}
        if not s["tied"]:
            out["unembed"] = _table(cfg, key, "unembed", dtype, rows)
        return out

    return build(W.root_key(seed))


def to_program(params: Dict, program_shapes) -> Dict:
    """Map the benchmark's tree onto the program's dense-decoder tree
    and check every shape against ``program_shapes`` (the program's own
    parameter shapes, as a tree of ``.shape``-bearing leaves)."""
    lay = params["layers"]
    tree = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "blocks": {
            "attn": {"norm": lay["attn_norm"], "wq": lay["wq"],
                     "wk": lay["wk"], "wv": lay["wv"], "wo": lay["wo"]},
            "mlp": {"norm": lay["mlp_norm"], "w_gate": lay["w_gate"],
                    "w_up": lay["w_up"], "w_down": lay["w_down"]},
        },
    }
    if "unembed" in params:
        tree["unembed"] = params["unembed"]
    got = jax.tree.map(lambda a: tuple(a.shape), tree)
    want = jax.tree.map(lambda a: tuple(a.shape), program_shapes)
    if got != want:
        raise ValueError(f"weight layout differs from the program's: "
                         f"{got} vs {want}")
    return tree


# -------------------------------------------------------------- reference

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
    s = sizes(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = jnp.float32

    @functools.partial(jax.jit, static_argnums=(1,))
    def leaf(key, name, index):
        return draw_slice(cfg, key, name, index, jnp.bfloat16).astype(f32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def table(key, name):
        return _table(cfg, key, name, jnp.bfloat16, s["V"]).astype(f32)

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


def reference_logits(cfg: dict, seed: int, seqs: Sequence[Sequence[int]],
                     want: Sequence[Sequence[int]], length: int, batch: int
                     ) -> List[np.ndarray]:
    """Float32 logits of each sequence at its ``want`` positions.

    ``seqs`` are token ids; they are padded to ``length`` positions
    (a multiple of 512) and to ``batch`` rows, so that one compiled
    program serves every run of a cell."""
    if len(seqs) > batch or any(len(s) > length for s in seqs):
        raise ValueError("sequences exceed the reference's padded shape")
    s = sizes(cfg)
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
        w = {n: leaf(key, n, l) for n in LAYER_LEAVES}
        x = layer(x, w)
    rows = np.concatenate([np.full(len(p), r) for r, p in enumerate(want)])
    cols = np.concatenate([np.asarray(p) for p in want])
    lg = np.asarray(head(x, jnp.asarray(rows), jnp.asarray(cols),
                         leaf(key, "final_norm", 0), out_table))
    splits = np.cumsum([len(p) for p in want])[:-1]
    return np.split(lg, splits)


# ------------------------------------------------------------ work counts

def linear_flops_per_token(cfg: dict) -> int:
    """Projections and MLP of every layer, for one position."""
    s = sizes(cfg)
    D, H, KV, hd, F = s["D"], s["H"], s["KV"], s["hd"], s["F"]
    per_layer = (2 * D * H * hd          # q
                 + 2 * 2 * D * KV * hd   # k, v
                 + 2 * H * hd * D        # o
                 + 3 * 2 * D * F)        # gate, up, down
    return s["L"] * per_layer


def attention_flops(cfg: dict, ctx: int) -> int:
    """One position attending ``ctx`` keys, over every layer."""
    s = sizes(cfg)
    return s["L"] * 4 * s["H"] * s["hd"] * ctx


def unembed_flops(cfg: dict) -> int:
    """Logits over the vocabulary for one position."""
    s = sizes(cfg)
    return 2 * s["D"] * s["V"]


def span_flops(cfg: dict, first: int, last: int) -> int:
    """Positions ``first .. last-1`` of one row computed in one step
    (prefill of an uncached suffix, or one decode position), with the
    logits of the last position.  Position p attends p + 1 keys."""
    n = last - first
    if n <= 0:
        return 0
    ctx = (first + 1 + last) * n // 2   # sum of p + 1 over the span
    return (n * linear_flops_per_token(cfg) + attention_flops(cfg, ctx)
            + unembed_flops(cfg))


def step_flops(cfg: dict, spans: Iterable[Tuple[int, int]]) -> int:
    return sum(span_flops(cfg, a, b) for a, b in spans)


def param_count(cfg: dict) -> int:
    s = sizes(cfg)
    D, H, KV, hd, F, V = s["D"], s["H"], s["KV"], s["hd"], s["F"], s["V"]
    per_layer = D * H * hd * 2 + 2 * D * KV * hd + 3 * D * F + 2 * D
    return s["L"] * per_layer + V * D * (1 if s["tied"] else 2) + D


def kv_bytes_per_token(cfg: dict, bytes_per: int = 2) -> int:
    s = sizes(cfg)
    return s["L"] * 2 * s["KV"] * s["hd"] * bytes_per


def decode_step_bytes(cfg: dict, ctxs: Iterable[int],
                      bytes_per: int = 2) -> int:
    """HBM bytes one decode step must read at least: every weight once,
    plus each row's cached keys and values (``ctxs`` positions)."""
    return (param_count(cfg) * bytes_per
            + sum(ctxs) * kv_bytes_per_token(cfg, bytes_per))
