"""Share of the positions the prefill programs were launched over that
were prompt tokens computed, in the traced window, in %:
100 × ``ExecutorStats.prefill_tokens_computed`` /
``ExecutorStats.prefill_positions_run`` (deltas; the engine counts rows ×
bucket at each launch, padding rows included).  Layer: engine step
(``serve/engine.py``); source: the program's counters.  None where the
program keeps no ``prefill_positions_run`` or launched no prefill."""


def read(ctx):
    w = ctx["window"]
    if "prefill_positions_run" not in w.stats1:
        return None
    run = w.stats1["prefill_positions_run"] - w.stats0["prefill_positions_run"]
    if run <= 0:
        return None
    tokens = (w.stats1["prefill_tokens_computed"]
              - w.stats0["prefill_tokens_computed"])
    return 100.0 * tokens / run
