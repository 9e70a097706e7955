"""Model FLOPs of the positions the engine computed in the traced
window (prefilled suffixes and decode rows, with attention at their
lengths and the logits each step produced; ``bench/flops.py``) over the
window times the chip's bf16 peak, in percent.  Layer: model step
(``models/``).  Padding, dead rows and recomputation do not count."""

from bench.flops import step_flops


def read(ctx):
    w = ctx["window"]
    flops = sum(step_flops(ctx["config"], s.spans) for s in ctx["steps"]
                if w.t0 <= s.t <= w.t1)
    if not flops:
        return None
    return 100.0 * flops / ((w.t1 - w.t0) * ctx["peaks"]["bf16_flops_per_s"])
