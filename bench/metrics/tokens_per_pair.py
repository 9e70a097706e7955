"""Prompt plus completion tokens per row pair settled (the paper's cost),
over the calls answered in the traced window.  Layer: join operators
(``core/``); source: the program's per-call token usage."""

from bench.traffic import pairs_settled


def read(ctx):
    w = ctx["window"]
    pairs = pairs_settled(ctx["memos"], w.t0, w.t1)
    if not pairs:
        return None
    tokens = sum(c.prompt_tokens + c.completion_tokens for c in ctx["calls"]
                 if w.t0 <= c.done <= w.t1)
    return tokens / pairs
