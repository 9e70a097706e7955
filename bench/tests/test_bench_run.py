"""CPU runs of the harness at a tiny size: a sound run comes out correct,
and each fault planted under the timed path makes ``correct`` false.

The configuration keeps the granite layer at toy widths (2 layers,
d_model 64) and the mix two closed-loop pipelines on small tables, so a
run takes seconds; the chip's cells use the files under ``bench/``.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import run, scenarios

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]

TINY = {"name": "tiny", "program_arch": "granite-3-2b",
        "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 515, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "torch_dtype": "bfloat16",
        "serving": {"max_seq": 1024, "slots": 4, "page_size": 16,
                    "pool_pages": 256, "use_pallas": False}}
MIX = {"name": "toy", "operator": "block_join", "prefix_cache": False,
       "reserve_tokens": 64, "pipelines": [
    {"scenario": "ads", "rows": [4, 4], "warmup_rows": [4, 4],
     "track": 1, "track_from": 1},
    {"scenario": "emails", "rows": [8, 3], "warmup_rows": [8, 3],
     "track": 3, "track_from": 6}]}
#: logit limits for this toy size, set like a cell's (CPU, 8 seeds of the
#: program: KL max <= 1.2e-4, mean <= 6.7e-6; int8 weights on 3 seeds:
#: max >= 6.7e-5, mean >= 2.0e-5; fp8 KV on 3: max >= 3.7e-4,
#: mean >= 8.3e-5).  At these widths the KL max of sound runs swings
#: past int8's, so int8 is caught by the mean alone.
LIMITS = {"wrong_pairs": 0, "query_errors": 0, "unanswered_calls": 0,
          "unchecked_requests": 0,
          "logit_kl_max": 3e-4, "logit_kl_mean": 1.2e-5}


@pytest.fixture(autouse=True)
def tiny_program(monkeypatch):
    """The program's granite config at the toy widths of ``TINY``; a
    planted fault that stalls a call is given up on after 3 s."""
    import repro.configs as rc

    def get_config(arch):
        return dataclasses.replace(rc.get_smoke_config(arch), d_ff=128)

    monkeypatch.setattr(rc, "get_config", get_config)
    monkeypatch.setattr(run, "DRAIN_SECONDS", 3.0)


def cell(seed, **kw):
    return run.run_cell(TINY, MIX, seed, 1.5, False, limits=LIMITS,
                        compile_cache=False,
                        peaks={"bf16_flops_per_s": 1e12}, **kw)


class Wrap:
    """Engine proxy for a planted fault; delegates everything else."""

    def __init__(self, engine):
        self._engine = engine

    def __getattr__(self, name):
        return getattr(self._engine, name)


class StaleState(Wrap):
    """A decode step that returns its state unchanged."""

    def decode_active(self, state, tokens, active):
        return None


class AlteredToken(Wrap):
    """Every token fed to decode altered where it is produced."""

    def decode_active(self, state, tokens, active):
        return self._engine.decode_active(state, (np.asarray(tokens) + 1)
                                          % 256 + 4, active)


class SlowDecode(Wrap):
    """Every decode step 50 ms longer, as on a slower device."""

    def decode_active(self, state, tokens, active):
        import time

        time.sleep(0.05)
        return self._engine.decode_active(state, tokens, active)


class HalfBatch(Wrap):
    """Half of the batch's rows left out of each decode step."""

    def decode_active(self, state, tokens, active):
        act = np.array(active, bool)
        act[::2] = False
        return self._engine.decode_active(state, tokens, act)


def wrong_teacher(t1, t2):
    """An answer altered where it is produced: one pair flipped."""
    flip = t1.startswith("Offering table that is made of solid oak wood "
                         "and painted blue")
    return scenarios.oracle_predicate(t1, t2) != flip


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_sound_run_is_correct(prefix_cache):
    """Sound runs pass, with the radix prefix cache off (the chip cells'
    path) and on (prefix-hit chunked prefill, the ``block`` mix's path)."""
    mix = dict(MIX, prefix_cache=prefix_cache)
    res = run.run_cell(TINY, mix, 2**33 + 5, 3.0, False, limits=LIMITS,
                       compile_cache=False,
                       peaks={"bf16_flops_per_s": 1e12})
    r = res["readings"]
    assert res["correct"], res["checks"]
    assert r["compared_positions"] > 20
    assert res["metrics"]["pairs_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", [StaleState, AlteredToken, HalfBatch])
def test_fault_under_the_timed_path_is_caught(fault):
    res = cell(41, wrap_engine=fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("control, seed", [("fp8_kv", 200), ("fp8_kv", 202),
                                           ("int8", 201)])
def test_lower_precision_control_is_caught(control, seed):
    """The program's own paths one precision below the configured bf16
    (int8 weights, fp8 KV cache) fail the logit comparison."""
    res = cell(seed, control=control)
    assert not res["correct"]
    failed = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert set(failed) & {"logit_kl_max", "logit_kl_mean"}, res["checks"]


def test_a_slower_device_shows_in_both_end_to_end_metrics():
    """The same seed with every decode step 50 ms longer (several toy
    steps): fewer pairs a second and a longer call tail, each well past
    run-to-run noise on a loaded host."""
    fast = run.run_cell(TINY, MIX, 77, 3.0, False, limits=LIMITS,
                        compile_cache=False, peaks={"bf16_flops_per_s": 1e12})
    slow = run.run_cell(TINY, MIX, 77, 3.0, False, limits=LIMITS,
                        compile_cache=False, peaks={"bf16_flops_per_s": 1e12},
                        wrap_engine=SlowDecode)
    f, s = fast["metrics"], slow["metrics"]
    assert fast["correct"] and slow["correct"], slow["checks"]
    assert s["pairs_per_s"]["value"] < 0.8 * f["pairs_per_s"]["value"]
    assert s["call_p95_ms"]["value"] > 1.25 * f["call_p95_ms"]["value"]


def test_a_mix_naming_tuple_join_runs_tuple_join(monkeypatch):
    """The operator comes from the mix: a ``tuple_join`` mix never calls
    the block join, makes one call per pair, and comes out correct."""
    import importlib

    bj = importlib.import_module("repro.core.block_join")

    def refuse(*a, **k):
        raise AssertionError("block_join called for a tuple_join mix")

    monkeypatch.setattr(bj, "block_join", refuse)
    # "Yes" is three tokens of the served byte tokenizer
    mix = dict(MIX, operator="tuple_join", max_answer_tokens=4, pipelines=[
        {"scenario": "ads", "rows": [3, 3], "warmup_rows": [3, 3],
         "track": 2, "track_from": 9}])
    res = run.run_cell(TINY, mix, 2**31 + 77, 1.5, False, limits=LIMITS,
                       compile_cache=False, peaks={"bf16_flops_per_s": 1e12})
    assert res["correct"], res["checks"]
    assert res["readings"]["compared_requests"] == 2
    assert res["metrics"]["pairs_per_s"]["value"] > 0


def test_traced_run_reports_the_layers_it_can_read():
    """The ``--trace 1`` path end to end on the CPU: the counters' and the
    host's metrics are there; the device's are left out (no TPU plane)."""
    res = run.run_cell(TINY, MIX, 2**32 + 9, 3.0, True, limits=LIMITS,
                       compile_cache=False,
                       peaks={"bf16_flops_per_s": 1e12})
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["rows_per_decode_step"]["value"] >= 1
    assert m["tokens_per_pair"]["value"] > 0
    assert "device_idle_share" not in m and "decode_step_ms" not in m
    assert res["device"]["busy_s"] is None
    assert res["device"]["window_s"] > 0


def test_altered_answer_is_caught():
    res = cell(43, predicate=wrong_teacher)
    assert not res["correct"]
    assert res["checks"]["wrong_pairs"]["value"] > 0


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELL, "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELL, "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
