"""Device time of the engine's decode program per decode step, in ms.

The decode program is the one whose executions in the traced window
match the executor's decode steps (``trace.decode_program``); XLA names
it ``jit__lambda(<fingerprint>)`` today, like every engine program.
Layer: engine step (``serve/engine.py``); source: the profiler trace."""

from bench.trace import decode_program


def read(ctx):
    w = ctx["window"]
    progs = ctx["trace"].get("programs", {})
    name = decode_program(progs, w.stats1["decode_steps"]
                          - w.stats0["decode_steps"])
    if name is None:
        return None
    n, seconds = progs[name]
    return 1e3 * seconds / n
