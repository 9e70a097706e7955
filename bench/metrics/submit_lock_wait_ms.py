"""Mean wait of a submit or cancel for its replica's lock, which the
replica's worker holds through each engine step, in the traced window,
in ms: 1e3 × ``ExecutorStats.submit_lock_wait_s`` /
``ExecutorStats.submit_lock_waits`` (deltas; the cluster times each
acquisition with ``perf_counter``).  Layer: client / cluster
(``serve/cluster.py``); source: the program's counters.  None where the
program keeps no such counter or nothing was submitted or cancelled."""


def read(ctx):
    w = ctx["window"]
    if "submit_lock_waits" not in w.stats1:
        return None
    waits = w.stats1["submit_lock_waits"] - w.stats0["submit_lock_waits"]
    if waits <= 0:
        return None
    return 1e3 * (w.stats1["submit_lock_wait_s"]
                  - w.stats0["submit_lock_wait_s"]) / waits
