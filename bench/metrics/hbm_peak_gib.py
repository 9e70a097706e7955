"""Peak HBM in use on the chip over the run (``memory_stats()``'s
``peak_bytes_in_use``, read after the window), in GiB.  Layer: device."""


def read(ctx):
    peak = ctx.get("memory_peak")
    return None if peak is None else peak / 2**30
