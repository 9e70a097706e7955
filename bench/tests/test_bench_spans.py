"""The program's spans and counters as the benchmark reads them: a
``--trace 1`` run reports the three counter metrics, which the program
keeps whether or not its recorder is live; ``REPRO_TRACE=1`` arms the
live recorder, so its spans enter the profiler's trace, with no change to
the benchmark; a ``--trace 0`` run keeps the program's no-op recorder.
The readers leave a metric out where the program keeps no such counter or
the window holds nothing to divide by.

Same toy configuration and mix as ``test_bench_run.py``.
"""

import importlib
import types

import pytest

from bench import run
from bench.tests.test_bench_run import LIMITS, MIX, TINY, tiny_program  # noqa: F401

NEW = ("prefill_useful_share", "submit_lock_wait_ms", "queue_wait_ms")


@pytest.fixture
def clusters(monkeypatch):
    """Every Cluster that run_cell builds, in order."""
    import repro.serve as serve

    built = []

    class Recorded(serve.Cluster):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            built.append(self)

    monkeypatch.setattr(serve, "Cluster", Recorded)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    return built


@pytest.mark.parametrize("armed", [False, True])
def test_traced_run_reports_the_counter_metrics(clusters, monkeypatch,
                                                armed):
    from repro.obs import NULL_TRACE, TraceRecorder

    if armed:
        monkeypatch.setenv("REPRO_TRACE", "1")
    res = run.run_cell(TINY, MIX, 2**32 + 21, 3.0, True, limits=LIMITS,
                       compile_cache=False,
                       peaks={"bf16_flops_per_s": 1e12})
    assert res["correct"], res["checks"]
    (cl,) = clusters
    if armed:
        assert isinstance(cl.trace, TraceRecorder)
        assert {e[1] for e in cl.trace.events()} >= {
            "cluster.step", "cluster.lock_wait", "executor.prefill",
            "engine.prefill"}
    else:
        assert cl.trace is NULL_TRACE
    m = res["metrics"]
    assert 0 < m["prefill_useful_share"]["value"] <= 100
    assert m["prefill_useful_share"]["unit"] == "%"
    assert m["submit_lock_wait_ms"]["value"] >= 0
    assert m["queue_wait_ms"]["value"] >= 0
    # the accepted metrics the CPU can read are still there
    assert {"rows_per_decode_step", "tokens_per_pair",
            "window_compiles"} <= set(m)


def test_untraced_run_keeps_the_null_recorder(clusters):
    from repro.obs import NULL_TRACE

    res = run.run_cell(TINY, MIX, 2**31 + 8, 1.5, False, limits=LIMITS,
                       compile_cache=False,
                       peaks={"bf16_flops_per_s": 1e12})
    assert res["correct"], res["checks"]
    (cl,) = clusters
    assert cl.trace is NULL_TRACE
    assert not set(NEW) & set(res["metrics"])


def window(stats0, stats1):
    return {"window": types.SimpleNamespace(stats0=stats0, stats1=stats1)}


PARENT = {"decode_steps": 10, "generated_tokens": 50, "refills": 4,
          "prefill_tokens_computed": 900}


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_counter(name):
    """A program that keeps none of the new counters (the parent's) gives
    no reading, and no error."""
    mod = importlib.import_module(f"bench.metrics.{name}")
    assert mod.read(window(dict(PARENT), dict(PARENT, refills=6))) is None


@pytest.mark.parametrize("name, zero", [
    ("prefill_useful_share", "prefill_positions_run"),
    ("submit_lock_wait_ms", "submit_lock_waits"),
    ("queue_wait_ms", "refills")])
def test_reader_gives_none_when_its_denominator_did_not_move(name, zero):
    s = dict(PARENT, prefill_positions_run=4096, submit_lock_waits=3,
             submit_lock_wait_s=0.5, queued_s=0.25)
    moved = {k: v * 2 for k, v in s.items()}
    moved[zero] = s[zero]
    mod = importlib.import_module(f"bench.metrics.{name}")
    assert mod.read(window(s, moved)) is None


@pytest.mark.parametrize("name, expect", [
    ("prefill_useful_share", 100.0 * 900 / 4096),
    ("submit_lock_wait_ms", 1e3 * 0.5 / 3),
    ("queue_wait_ms", 1e3 * 0.25 / 4)])
def test_reader_divides_the_window_deltas(name, expect):
    s = dict(PARENT, prefill_positions_run=4096, submit_lock_waits=3,
             submit_lock_wait_s=0.5, queued_s=0.25)
    moved = {k: v * 2 for k, v in s.items()}
    mod = importlib.import_module(f"bench.metrics.{name}")
    assert mod.read(window(s, moved)) == pytest.approx(expect)
