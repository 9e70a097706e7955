"""Share of the traced window in which no program ran on the device:
1 - (union of device-busy intervals) / window, in percent.  Layer:
device (TPU v5e); source: the profiler trace."""


def read(ctx):
    t = ctx["trace"]
    if not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
