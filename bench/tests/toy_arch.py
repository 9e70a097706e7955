"""A toy architecture module, which only the contract test loads (by
``"bench_arch": "bench.tests.toy_arch"``): the dense GQA decoder with
its final norm's weight negated, one scalar of the draw changed.  Its
logits are then exactly the dense decoder's negated, so its reference is
the dense reference negated, and a run checked against either module's
reference alone fails.  It counts one multiply more per position and
width, the negation, so that a reader shows whose counts it took."""

from bench.arch import dense_gqa
from bench.arch.dense_gqa import (checked_fields, decode_step_bytes,  # noqa: F401
                                  param_count, program_fields, to_program,
                                  vocab)


def draw_params(cfg, seed, dtype, vocab_rows=0):
    params = dense_gqa.draw_params(cfg, seed, dtype, vocab_rows)
    params["final_norm"] = -params["final_norm"]
    return params


def reference_logits(cfg, seed, seqs, want, length, batch):
    return [-x for x in dense_gqa.reference_logits(cfg, seed, seqs, want,
                                                   length, batch)]


def step_flops(cfg, spans):
    return (dense_gqa.step_flops(cfg, spans)
            + cfg["hidden_size"] * sum(b - a for a, b in spans))
