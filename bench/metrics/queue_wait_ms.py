"""Mean time from a request's submission to its admission into a decode
slot, over the admissions of the traced window, in ms:
1e3 × ``ExecutorStats.queued_s`` / ``ExecutorStats.refills`` (deltas, on
the executor's clock).  Layer: executor (``serve/executor.py``); source:
the program's counters.  None where the program keeps no ``queued_s`` or
admitted nothing."""


def read(ctx):
    w = ctx["window"]
    if "queued_s" not in w.stats1:
        return None
    refills = w.stats1["refills"] - w.stats0["refills"]
    if refills <= 0:
        return None
    return 1e3 * (w.stats1["queued_s"] - w.stats0["queued_s"]) / refills
