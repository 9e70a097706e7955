"""Seeded random weights for a dense GQA decoder, drawn on the device.

The benchmark owns the weights: it draws them here from ``--seed`` and
hands them to the program, and the plain reference (``reference.py``)
draws the same slices again after the program has been freed.  Every
leaf is drawn slice by slice along its first axis (one layer of a
stacked leaf, or ``ROW_BLOCK`` rows of an embedding table) from a key
that depends only on the seed, the leaf's name and the slice index, so
a slice drawn alone equals the same slice drawn inside the whole tree,
bit for bit.

Layout (names are the benchmark's; ``to_program`` maps them onto the
program's tree): ``embed`` (V, D), ``final_norm`` (D,), ``unembed``
(V, D) when the head is untied, and ``layers`` with stacked
``attn_norm`` (L, D), ``wq`` (L, D, H, hd), ``wk``/``wv`` (L, D, KV,
hd), ``wo`` (L, H, hd, D), ``mlp_norm`` (L, D), ``w_gate``/``w_up``
(L, D, F), ``w_down`` (L, F, D).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

#: rows of an embedding table drawn per slice (bounds the float32
#: temporary of a draw to ROW_BLOCK x d_model)
ROW_BLOCK = 8192

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
        "embed": ((ROW_BLOCK, D), "normal", 0.02),
        "unembed": ((ROW_BLOCK, D), "normal", 0.02),
    }
    return table[name]


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed (more than 32 bits allowed)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, (LAYER_LEAVES + TOP_LEAVES).index(name))


def draw_slice(cfg: dict, key: jax.Array, name: str, index, dtype):
    """Slice ``index`` of leaf ``name`` in ``dtype`` (traceable)."""
    shape, kind, scale = slice_spec(cfg, name)
    k = jax.random.fold_in(_leaf_key(key, name), index)
    z = jax.random.normal(k, shape, jnp.float32)
    w = 1.0 + scale * z if kind == "norm" else scale * z
    return w.astype(dtype)


def _table(cfg: dict, key, name: str, dtype, rows: int):
    """An embedding table of ``rows`` rows: the first ``vocab_size``
    drawn in ROW_BLOCK slices, any rows past them zero."""
    V = sizes(cfg)["V"]
    n = -(-V // ROW_BLOCK)
    blocks = jax.lax.map(lambda i: draw_slice(cfg, key, name, i, dtype),
                         jnp.arange(n))
    t = blocks.reshape(n * ROW_BLOCK, -1)[:V]
    if rows > V:
        t = jnp.concatenate([t, jnp.zeros((rows - V, t.shape[1]), dtype)])
    return t


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

    return build(root_key(seed))


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
