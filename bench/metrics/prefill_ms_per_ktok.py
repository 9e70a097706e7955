"""Device time of everything but the decode program (the prefill
programs, the page scatter and reshape of their K/V, the logits insert)
per 1000 prompt tokens computed (``ExecutorStats.prefill_tokens_computed``
in the traced window), in ms.  Layer: engine step (``serve/engine.py``);
source: the profiler trace."""

from bench.trace import decode_program


def read(ctx):
    w = ctx["window"]
    tokens = (w.stats1["prefill_tokens_computed"]
              - w.stats0["prefill_tokens_computed"])
    progs = ctx["trace"].get("programs", {})
    if tokens <= 0 or not progs:
        return None
    decode = decode_program(progs, w.stats1["decode_steps"]
                            - w.stats0["decode_steps"])
    seconds = sum(t for name, (_, t) in progs.items() if name != decode)
    return 1e3 * seconds / (tokens / 1000.0)
