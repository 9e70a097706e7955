"""Serving engine: ragged batched prefill, slot-refill continuous
batching (executor), stop strings, EngineClient-backed joins."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import adaptive_join, block_join
from repro.core.accounting import Ledger
from repro.core.oracle import OracleLLM
from repro.data.tokenizer import ByteTokenizer, HashWordTokenizer
from repro.models import init_params, model_specs
from repro.serve import Engine, EngineClient

KEY = jax.random.PRNGKey(3)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("granite-3-2b")
    params = init_params(model_specs(cfg), KEY, jnp.float32)
    tok = ByteTokenizer(cfg.vocab_size)
    return Engine(cfg, params, tok, max_seq=512, slots=4)


def test_ragged_batch_equals_solo(engine):
    """A prompt decoded in a ragged batch must equal its solo decode."""
    prompts = ["short one", "a rather much longer prompt with more tokens",
               "mid size text"]
    batch = engine.generate(prompts, max_tokens=8)
    solo = [engine.generate([p], max_tokens=8)[0] for p in prompts]
    for b, s in zip(batch, solo):
        assert b.text == s.text
        assert b.prompt_tokens == s.prompt_tokens


def test_teacher_forced_stop_and_accounting(engine):
    res = engine.generate(
        ["Q: match?\nA:"], max_tokens=32, stop="Finished",
        expected=["1,2; Finished"],
    )[0]
    assert res.text.rstrip().endswith("Finished")
    assert res.finish_reason == "stop"
    assert res.completion_tokens == len(engine.tokenizer.encode(
        "1,2; Finished", bos=False))


def test_max_tokens_truncation(engine):
    res = engine.generate(
        ["Q:"], max_tokens=5, expected=["averyveryverylongforcedanswer"],
    )[0]
    assert res.completion_tokens == 5
    assert res.finish_reason == "length"


def test_executor_admission_and_completion(engine):
    """More requests than slots: admission carves them into refills and
    every request still completes (the old Scheduler facade's run(),
    now the executor's submit + drain directly)."""
    ex = engine.executor()
    handles = [ex.submit(f"prompt number {i}", max_tokens=4,
                         expected=f"ans{i}") for i in range(9)]
    ex.drain()
    for h in handles:
        assert h.status == "finished"
        assert h.result.completion_tokens > 0


def test_engine_client_block_join(engine):
    r1 = [f"item {c}" for c in ["red", "blue", "green", "teal"]]
    r2 = [f"want {c}" for c in ["blue", "red", "teal", "green"]]
    pred = lambda a, b: a.split()[-1] == b.split()[-1]
    truth = {(i, k) for i, a in enumerate(r1) for k, b in enumerate(r2)
             if pred(a, b)}
    client = EngineClient(engine, oracle=OracleLLM(pred, context_limit=512))
    res = block_join(r1, r2, "colors match", client, 2, 2)
    assert res.pairs == truth
    assert res.ledger.prompt_tokens > 0 and res.ledger.completion_tokens > 0


def test_mixed_wave_respects_per_request_max_tokens(engine):
    """Regression (old Scheduler widened every request to the wave max):
    a request batched with longer-budget peers must stop at ITS OWN
    ``max_tokens``."""
    ex = engine.executor()
    short = ex.submit("Q1:", max_tokens=2, expected="aaaaaaaaaaaaaaaa")
    long_ = ex.submit("Q2:", max_tokens=10, expected="bbbbbbbbbbbbbbbb")
    ex.drain()
    assert short.result.completion_tokens == 2
    assert short.result.finish_reason == "length"
    assert long_.result.completion_tokens == 10


def test_mixed_wave_honors_heterogeneous_stops(engine):
    """Regression (the pre-executor scheduler passed stop=None when a
    wave mixed stop strings): each request's own stop string terminates
    it even when batched with different-stop peers."""
    ex = engine.executor()
    done = [
        ex.submit("Q1:", max_tokens=32, stop="DONE", expected="xy DONE zz"),
        ex.submit("Q2:", max_tokens=32, stop="END", expected="pq END rr"),
        ex.submit("Q3:", max_tokens=32, stop=None, expected="kk"),
    ]
    ex.drain()
    assert done[0].result.finish_reason == "stop"
    assert done[0].result.text.rstrip().endswith("DONE")
    assert done[1].result.finish_reason == "stop"
    assert done[1].result.text.rstrip().endswith("END")
    assert done[2].result.finish_reason == "stop"  # EOS after forced text


def test_admission_control_token_budget(engine):
    """Eq. (1): reserved prompt+completion tokens of concurrently active
    requests never exceed slots × max_seq, even with free slots left."""
    ex = engine.executor()
    budget = engine.slots * engine.max_seq  # 4 × 512
    handles = [ex.submit(f"req {i}:", max_tokens=900, expected="x")
               for i in range(4)]
    ex.step()
    active = [h for h in handles if h.status == "active"]
    reserved = sum(h.prompt_tokens + h.max_tokens for h in active)
    assert reserved <= budget
    assert 0 < len(active) < 4  # admission bound below the slot count
    ex.drain()
    assert all(h.result is not None for h in handles)


def test_slot_refill_beats_barrier_waves_on_skewed_lengths(engine):
    """Acceptance: continuous batching must spend fewer decode steps than
    barrier waves when completion lengths are skewed — freed slots are
    refilled mid-decode instead of idling until the wave's slowest row."""
    skew = ["a" * 40 if i % engine.slots == 0 else "b" * 3
            for i in range(2 * engine.slots)]
    prompts = [f"req {i}:" for i in range(len(skew))]

    barrier = engine.executor()
    for lo in range(0, len(prompts), engine.slots):  # barrier: drain per wave
        for p, e in zip(prompts[lo:lo + engine.slots],
                        skew[lo:lo + engine.slots]):
            barrier.submit(p, max_tokens=64, expected=e)
        barrier.drain()

    refill = engine.executor()
    handles = [refill.submit(p, max_tokens=64, expected=e)
               for p, e in zip(prompts, skew)]
    refill.drain()

    assert refill.stats.decode_steps < barrier.stats.decode_steps
    assert refill.stats.generated_tokens == barrier.stats.generated_tokens
    for h, e in zip(handles, skew):
        assert h.result.text == e  # outputs identical to the barrier run
    # fully idle executors release their slots × max_seq cache
    assert refill._state is None and barrier._state is None


def test_executor_requeues_on_engine_failure(engine, monkeypatch):
    """An engine exception re-queues in-flight requests (idempotent
    prompts) and the next step retries them on a fresh decode state."""
    ex = engine.executor(max_retries=2)
    handles = [ex.submit(f"rq {i}:", max_tokens=4, expected="ok")
               for i in range(3)]
    failures = iter([True])

    def make_flaky(real):
        def flaky(*args, **kw):
            if next(failures, False):
                raise RuntimeError("injected engine failure")
            return real(*args, **kw)
        return flaky

    # a spec-decode engine steps through verify_active instead of
    # decode_active — inject into whichever the env selects
    monkeypatch.setattr(engine, "decode_active",
                        make_flaky(engine.decode_active))
    monkeypatch.setattr(engine, "verify_active",
                        make_flaky(engine.verify_active))
    ex.drain()
    assert all(h.result is not None and h.result.completion_tokens > 0
               for h in handles)
    assert max(h.retries for h in handles) == 1

    ex2 = engine.executor(max_retries=1)
    h = ex2.submit("rq:", max_tokens=4, expected="ok")
    down = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("always down"))
    monkeypatch.setattr(engine, "decode_active", down)
    monkeypatch.setattr(engine, "verify_active", down)
    with pytest.raises(RuntimeError):
        ex2.drain()
    assert h.status == "queued" and h.retries > 1


def test_prefill_failure_keeps_prefill_stats_exact(engine, monkeypatch):
    """Regression: a prefill_rows failure after handles went ACTIVE must
    not back out prefill-token stats that were never added (the counters
    fed the benchmark's computed-prefill ratio — a retry used to zero or
    negate them)."""
    ex = engine.executor(max_retries=2)
    handles = [ex.submit(f"stat rq {i}:", max_tokens=3, expected="ok")
               for i in range(2)]
    real = engine.prefill_rows
    failures = iter([True])

    def flaky(prompts):
        if next(failures, False):
            raise RuntimeError("injected prefill failure")
        return real(prompts)

    monkeypatch.setattr(engine, "prefill_rows", flaky)
    ex.drain()
    assert all(h.result is not None for h in handles)
    total = sum(h.prompt_tokens for h in handles)
    assert (ex.stats.prefill_tokens_computed
            + ex.stats.prefill_tokens_cached == total)
    assert ex.stats.prefill_tokens_computed > 0


def test_block_join_resume_out_of_order(engine):
    """block_join(completed=...) must not re-pay finished blocks even when
    completions arrive out of order through the executor (skewed per-block
    answer lengths make completion order differ from submission order)."""
    r1 = [f"item {i % 2}" for i in range(8)]  # item 0 matches 4×4 pairs
    r2 = [f"item {i % 2}" for i in range(8)]
    pred = lambda a, b: a == b
    truth = {(i, k) for i, a in enumerate(r1) for k, b in enumerate(r2)
             if pred(a, b)}

    def client():
        return EngineClient(engine, oracle=OracleLLM(pred, context_limit=512))

    memo = {}
    full_ledger = Ledger()
    full = block_join(r1, r2, "equal", client(), 4, 4,
                      completed=memo, ledger=full_ledger)
    assert full.pairs == truth
    n_blocks = len(memo)

    partial = {k: memo[k] for k in list(memo)[:2]}
    replay_ledger = Ledger()
    replay = block_join(r1, r2, "equal", client(), 4, 4,
                        completed=partial, ledger=replay_ledger)
    assert replay.pairs == truth
    assert replay_ledger.calls == full_ledger.calls - 2 == n_blocks - 2


def test_overflow_accounts_for_in_flight_blocks(engine):
    """The overflow path must keep honest accounting: blocks already in
    flight when the first overflow lands keep running — their tokens are
    recorded in the ledger and their completions feed the resume memo.
    Only still-queued (unpaid) blocks are cancelled."""
    from repro.core.join_types import Overflow

    r1 = ["same"] * 6 + [f"ua{i}" for i in range(6)]
    r2 = ["same"] * 6 + [f"ub{i}" for i in range(6)]
    pred = lambda a, b: a == b
    client = EngineClient(engine, oracle=OracleLLM(pred, context_limit=400))
    client.context_limit = 400  # dense 6×6 block's answer cannot fit
    ledger, memo = Ledger(), {}
    with pytest.raises(Overflow):
        block_join(r1, r2, "equal", client, 6, 6,
                   completed=memo, ledger=ledger)
    assert ledger.calls == 4          # all four in-flight blocks recorded
    assert ledger.overflows == 1      # exactly the dense block overflowed
    assert len(memo) == 3             # the three complete blocks memoized


def test_foreign_handle_raises_instead_of_hanging(engine):
    """Waiting on a handle owned by a different executor must raise, not
    busy-loop forever."""
    ex_a = engine.executor()
    ex_b = engine.executor()
    h = ex_a.submit("Q:", max_tokens=4, expected="ok")
    with pytest.raises(ValueError):
        ex_b.result(h)
    with pytest.raises(ValueError):
        list(ex_b.as_completed([h]))
    assert ex_a.result(h).completion_tokens > 0


def test_adaptive_resume_through_executor(engine):
    """adaptive_join(resume=True) keeps blocks solved before an overflow:
    skewed data makes sparse (short-answer) blocks complete *before* the
    dense block overflows the round, out of submission order — those
    blocks must not be re-paid by later, smaller-batched rounds."""
    r1 = ["same entry text"] * 3 + [f"uniq a{i} text" for i in range(6)]
    r2 = ["same entry text"] * 3 + [f"uniq b{i} text" for i in range(6)]
    pred = lambda a, b: a == b
    truth = {(i, k) for i, a in enumerate(r1) for k, b in enumerate(r2)
             if pred(a, b)}

    def client(limit):
        c = EngineClient(engine, oracle=OracleLLM(pred, context_limit=limit))
        c.context_limit = limit  # tighten Definition 2.2's budget
        return c

    res_full = adaptive_join(r1, r2, "equal", client(430),
                             initial_estimate=1e-4, resume=False)
    res_resume = adaptive_join(r1, r2, "equal", client(430),
                               initial_estimate=1e-4, resume=True)
    assert res_full.pairs == res_resume.pairs == truth
    assert res_resume.meta["rounds"] >= 2  # the overflow path was exercised
    assert res_resume.ledger.calls < res_full.ledger.calls


def test_hashword_tokenizer_roundtrip():
    tok = HashWordTokenizer(4096)
    text = "Find indexes x,y such that 3,4; Finished"
    ids = tok.encode(text, bos=False)
    assert tok.decode(ids) == text


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_is_env_or_one_fixed_checkout_path(
        monkeypatch, tmp_path, from_env):
    """``enable_compile_cache`` keeps ``$JAX_COMPILATION_CACHE_DIR`` as
    it is, else sets ``<repo>/.jax_cache`` (the same path every call),
    and caches every program whatever its compile time.  JAX's config is
    recorded, not changed: tests never turn the cache on."""
    import pathlib

    from repro.launch.serve import enable_compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    assert enable_compile_cache() == enable_compile_cache() == want
    set_dirs = {v for k, v in updates if k == "jax_compilation_cache_dir"}
    assert set_dirs == (set() if from_env else {want})
    assert ("jax_persistent_cache_min_compile_time_secs", 0) in updates
