"""Model FLOPs and bytes of a dense GQA decoder, computed from shapes.

These are the yardstick's counts: what the algorithm needs, not what an
implementation happens to execute (padding, recomputation and dead rows
do not count).  A matmul of (m x k) by (k x n) is 2mkn FLOPs.  One query
position attending ``ctx`` keys costs 4 * heads * head_dim * ctx per
layer (scores and the weighted sum of values).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench.weights import sizes


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
