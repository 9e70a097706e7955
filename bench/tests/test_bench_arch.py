"""The room for a new architecture: a module that lives outside
``bench/arch/`` (``toy_arch.py``, beside this file), named by a
configuration's ``bench_arch``, drives a whole run and its readers with
no harness file changed."""

import importlib

import pytest

from bench import run
from bench.arch import dense_gqa
from bench.capture import StepLog
from bench.tests import toy_arch
from bench.tests.test_bench_run import LIMITS, MIX, TINY, tiny_program  # noqa: F401

TOY = dict(TINY, bench_arch="bench.tests.toy_arch")


@pytest.mark.parametrize("reference", ["toy", "dense"])
def test_a_toy_module_drives_a_whole_run(monkeypatch, reference):
    """Drawn, served, traced and checked through the toy module: correct
    against its own reference, and not against the dense one, which the
    toy's negated head must fail."""
    if reference == "dense":
        monkeypatch.setattr(toy_arch, "reference_logits",
                            dense_gqa.reference_logits)
    res = run.run_cell(TOY, MIX, 2**32 + 33, 3.0, True, limits=LIMITS,
                       compile_cache=False,
                       peaks={"bf16_flops_per_s": 1e12})
    failed = {k for k, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed == (set() if reference == "toy"
                      else {"logit_kl_max", "logit_kl_mean"}), res["checks"]
    assert res["correct"] == (reference == "toy")
    assert res["readings"]["compared_positions"] > 20
    assert res["metrics"]["step_mfu"]["value"] > 0


def test_step_mfu_takes_the_modules_counts():
    step_mfu = importlib.import_module("bench.metrics.step_mfu")
    window = run.Window(10.0, 12.0, {}, {}, 0, 0)
    steps = [StepLog(10.5, "prefill", [(0, 300), (0, 41)]),
             StepLog(11.0, "decode", [(300, 301), (41, 42)]),
             StepLog(12.5, "decode", [(301, 302)])]  # after the window
    spans = [s for st in steps[:2] for s in st.spans]
    ctx = {"config": TOY, "window": window, "steps": steps,
           "peaks": {"bf16_flops_per_s": 1e12}}
    want = 100.0 * toy_arch.step_flops(TOY, spans) / (2.0 * 1e12)
    assert step_mfu.read(ctx) == pytest.approx(want, rel=1e-12)
    assert toy_arch.step_flops(TOY, spans) > dense_gqa.step_flops(TOY, spans)
    assert step_mfu.read(dict(ctx, config=TINY)) == pytest.approx(
        100.0 * dense_gqa.step_flops(TINY, spans) / 2e12, rel=1e-12)
