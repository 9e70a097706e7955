"""End-to-end behaviour tests for the paper's system.

Covers: the full paper pipeline (scenarios → joins → cost/quality), the
claims of §7 at test scale, and a subprocess mini dry-run that exercises
the production sharding/lowering machinery on an 8-device host mesh
(pytest's own process must keep seeing 1 device).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import (
    GPT4_PRICING,
    OracleLLM,
    adaptive_join,
    block_join,
    embedding_join,
    generate_statistics,
    lotus_join,
    optimal_batch_sizes,
    tuple_join,
)
from repro.data import all_scenarios

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# the paper's headline claims, end to end
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def scenarios():
    return {sc.name: sc for sc in all_scenarios()}


def test_block_join_beats_tuple_join_by_orders_of_magnitude(scenarios):
    sc = scenarios["emails"]
    mk = lambda: OracleLLM(sc.predicate, context_limit=2000)
    res_t = tuple_join(sc.r1, sc.r2, sc.condition, mk())
    res_a = adaptive_join(sc.r1, sc.r2, sc.condition, mk(),
                          initial_estimate=1e-4)
    assert res_t.f1(sc.truth) == res_a.f1(sc.truth) == 1.0
    assert res_t.cost(GPT4_PRICING) > 10 * res_a.cost(GPT4_PRICING)
    assert res_t.ledger.calls > 100 * res_a.ledger.calls


def test_adaptive_handles_skew_where_informed_overflows(scenarios):
    """The paper's §6.1 data-skew point, observed live: on Reviews
    (σ=0.5, sentiments cluster), some batch pairs match at σ_eff≈1, so a
    block join tuned for the *global* selectivity overflows — this is
    exactly why the paper's real-data experiments (Fig. 6) only run
    Block-C (σ=1), and why Algorithm 3 only ever *increases* estimates."""
    from repro.core import Overflow

    sc = scenarios["reviews"]
    stats = generate_statistics(sc.r1, sc.r2, sc.condition)
    t = 2000 - stats.p
    b1, b2 = optimal_batch_sizes(stats, sc.selectivity, t,
                                 headroom=stats.s3 + 1)
    with pytest.raises(Overflow):
        block_join(sc.r1, sc.r2, sc.condition,
                   OracleLLM(sc.predicate, context_limit=2000), b1, b2)

    # Block-C (conservative σ=1) and Adaptive both complete; adaptive pays
    # only a bounded retry premium (paper: <3%; ours ~10% at this scale).
    bc1, bc2 = optimal_batch_sizes(stats, 1.0, t)
    conservative = block_join(sc.r1, sc.r2, sc.condition,
                              OracleLLM(sc.predicate, context_limit=2000),
                              bc1, bc2)
    adaptive = adaptive_join(sc.r1, sc.r2, sc.condition,
                             OracleLLM(sc.predicate, context_limit=2000),
                             initial_estimate=1e-4, alpha=4.0)
    assert adaptive.pairs == conservative.pairs == sc.truth
    assert adaptive.cost() <= 1.25 * conservative.cost()


def test_embedding_join_signature(scenarios):
    """F1 ≈ 0 where the condition is contradiction, 1.0 where similarity."""
    emails, ads = scenarios["emails"], scenarios["ads"]
    assert embedding_join(emails.r1, emails.r2, "").f1(emails.truth) < 0.5
    assert embedding_join(ads.r1, ads.r2, "").f1(ads.truth) == 1.0


def test_lotus_profile(scenarios):
    """LOTUS: tuple-join token counts, parallel (lower simulated latency)."""
    sc = scenarios["ads"]
    c1 = OracleLLM(sc.predicate, context_limit=2000)
    res_t = tuple_join(sc.r1, sc.r2, sc.condition, c1)
    c2 = OracleLLM(sc.predicate, context_limit=2000)
    res_l = lotus_join(sc.r1, sc.r2, sc.condition, c2, parallel=64)
    assert res_l.ledger.usage.total_tokens == res_t.ledger.usage.total_tokens
    assert c2.sim_clock_s < c1.sim_clock_s / 5


# ---------------------------------------------------------------------------
# repo hygiene
# ---------------------------------------------------------------------------


def test_no_smoke_benchmark_artifact_is_tracked():
    """Smoke benchmark runs (CI legs) write gitignored ``*.smoke.json``
    precisely so they can never clobber the committed full-run evidence
    (``benchmarks/BENCH_*.json``).  A tracked smoke artifact would
    silently *become* the evidence — guard the invariant at git level."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=ROOT, text=True,
                             capture_output=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("git unavailable")
    if out.returncode != 0:
        pytest.skip("not a git checkout")
    offenders = [f for f in out.stdout.splitlines()
                 if f.endswith(".smoke.json")]
    assert offenders == [], (
        f"smoke benchmark artifacts must stay untracked: {offenders}")


# ---------------------------------------------------------------------------
# mini dry-run in a subprocess (8 fake devices, reduced configs)
# ---------------------------------------------------------------------------

MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config, InputShape
    from repro.launch.dryrun import lower_cell
    from repro.utils.hlo_analysis import collective_bytes

    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    out = {}
    for arch in ["yi-9b", "grok-1-314b", "mamba2-130m", "jamba-1.5-large-398b"]:
        cfg = get_smoke_config(arch)
        for shape in [InputShape("train", 32, 8, "train"),
                      InputShape("prefill", 64, 4, "prefill"),
                      InputShape("decode", 64, 8, "decode")]:
            lowered = lower_cell(cfg, shape, mesh,
                                 accum_steps=2 if shape.kind == "train" else 1)
            compiled = lowered.compile()
            coll = collective_bytes(compiled.as_text())
            mem = compiled.memory_analysis()
            out[f"{arch}:{shape.name}"] = {
                "coll_total": coll["total"],
                "temp": mem.temp_size_in_bytes,
            }
    print(json.dumps(out))
""")


def test_mini_multipod_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out) == 12
    # sharded training must communicate (grad reduction at minimum)
    assert out["yi-9b:train"]["coll_total"] > 0


def test_full_dryrun_artifacts_if_present():
    """Validate any artifacts the real 512-device dry-run has produced."""
    art = os.path.join(ROOT, "artifacts", "dryrun")
    if not os.path.isdir(art) or not os.listdir(art):
        pytest.skip("no dry-run artifacts yet")
    for name in sorted(os.listdir(art)):
        with open(os.path.join(art, name)) as f:
            rec = json.load(f)
        assert rec["chips"] in (256, 512)
        assert rec["memory"]["peak_device_bytes"] > 0
        if "roofline" in rec:
            assert rec["roofline"]["dominant"] in ("compute", "memory",
                                                   "collective")
