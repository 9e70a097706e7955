"""Rows decoded per decode step in the traced window:
``ExecutorStats.generated_tokens / decode_steps`` (deltas).  Layer:
executor (``serve/executor.py``); source: the program's counters."""


def read(ctx):
    w = ctx["window"]
    steps = w.stats1["decode_steps"] - w.stats0["decode_steps"]
    if steps <= 0:
        return None
    return (w.stats1["generated_tokens"] - w.stats0["generated_tokens"]) / steps
