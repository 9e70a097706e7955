"""XLA programs compiled, or loaded from the persistent cache, inside the
traced window (should be 0: set-up warms up every shape the mix uses).
Layer: model step (``models/``); source: ``jax.monitoring`` events."""


def read(ctx):
    w = ctx["window"]
    return float(w.compiles1 - w.compiles0)
