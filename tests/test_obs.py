"""Serving-tier observability (DESIGN.md §17): trace recorder, metrics
registry, exporters, and their instrumentation through the executor,
cluster, and join operators.

The two core invariants pinned here:

* **Zero observation effect** — with a live recorder attached (or
  ``REPRO_TRACE=1``), every join is token-identical to the untraced run
  across the ``paged × prefix × spec`` engine matrix and under
  ``REPRO_CHAOS`` fault injection.  Tracing may never change what the
  engine computes.
* **Exact conservation** — latency histogram counts reconcile exactly
  with ``ExecutorStats`` request totals (``ttft.count + score_e2e.count
  == requests_finished``), including merged across replica incarnations
  after a kill + resurrection; histogram merge is associative and
  count-conserving.

Plus: ring-buffer bounded memory, and VirtualClock-deterministic replay
(two identical runs serialize to byte-identical Perfetto JSON).
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import OracleLLM, block_join, tuple_join
from repro.core.cascade import cascade_tuple_join
from repro.core.oracle import VirtualClock
from repro.data.tokenizer import ByteTokenizer
from repro.models import init_params, model_specs
from repro.obs import (
    NULL_TRACE,
    MetricsRegistry,
    NullRecorder,
    TraceRecorder,
    chrome_trace_json,
    queue_depth_timeline,
    recorder_from_env,
    registry_of,
    trace_of,
    write_chrome_trace,
)
from repro.obs.metrics import COUNT_BOUNDS, Histogram
from repro.obs.trace import adopt_clock
from repro.serve import (
    Cluster,
    ClusterClient,
    ContinuousBatchingExecutor,
    Engine,
    EngineClient,
    make_router,
)

KEY = jax.random.PRNGKey(7)


def make_tables(n1=8, n2=16):
    colours = ["red", "blue"]
    left = [f"item {i} in {colours[i % 2]}" for i in range(n1)]
    right = [f"want {k} {colours[k % 2]}" for k in range(n2)]
    pred = lambda a, b: a.split()[-1] == b.split()[-1]
    truth = {(i, k) for i, a in enumerate(left)
             for k, b in enumerate(right) if pred(a, b)}
    return left, right, pred, truth


@pytest.fixture(scope="module")
def params():
    cfg = get_smoke_config("granite-3-2b")
    return cfg, init_params(model_specs(cfg), KEY, jnp.float32)


def fresh_engine(params, **kw):
    """A brand-new engine per run: traced-vs-untraced comparisons must
    not share a radix prefix cache (the second run would see different
    cached_prompt_tokens regardless of tracing)."""
    cfg, p = params
    kw.setdefault("max_seq", 512)
    kw.setdefault("slots", 4)
    return Engine(cfg, p, ByteTokenizer(cfg.vocab_size), **kw)


# ---------------------------------------------------------------------------
# recorder: no-op default, ring buffer, env arming
# ---------------------------------------------------------------------------


def test_null_recorder_is_falsy_and_free():
    assert not NULL_TRACE
    assert isinstance(NULL_TRACE, NullRecorder)
    NULL_TRACE.instant("x", "cat", foo=1)
    NULL_TRACE.complete("x", "cat", 0.0)
    NULL_TRACE.counter("x", 3)
    assert len(NULL_TRACE) == 0
    assert NULL_TRACE.events() == []
    assert NULL_TRACE.dropped == 0


def test_recorder_from_env(monkeypatch):
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert recorder_from_env() is NULL_TRACE
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert recorder_from_env() is NULL_TRACE
    monkeypatch.setenv("REPRO_TRACE", "1")
    rec = recorder_from_env()
    assert isinstance(rec, TraceRecorder) and rec
    monkeypatch.setenv("REPRO_TRACE_CAPACITY", "17")
    assert recorder_from_env().capacity == 17


def test_ring_buffer_bounded_memory():
    rec = TraceRecorder(capacity=64)
    for i in range(10_000):
        rec.instant("e", "t", i=i)
    assert len(rec) == 64
    assert rec.total == 10_000
    assert rec.dropped == 10_000 - 64
    # the ring keeps the NEWEST events
    kept = [args["i"] for *_rest, args in rec.events()]
    assert kept == list(range(10_000 - 64, 10_000))
    rec.clear()
    assert len(rec) == 0


def test_adopt_clock_only_replaces_fallback():
    clk = VirtualClock()
    rec = TraceRecorder()
    adopt_clock(rec, clk)
    assert rec.clock is clk           # fallback replaced by owner clock
    other = VirtualClock()
    adopt_clock(rec, other)
    assert rec.clock is clk           # explicit clock never overridden


def test_trace_of_and_registry_of():
    class Bare:
        pass

    class Carrier:
        trace = TraceRecorder()
        metrics = MetricsRegistry()

    assert trace_of(Bare()) is NULL_TRACE
    assert registry_of(Bare()) is None
    c = Carrier()
    assert trace_of(c) is Carrier.trace
    assert registry_of(c) is Carrier.metrics

    class WrongKind:
        metrics = {"not": "a registry"}

    assert registry_of(WrongKind()) is None


# ---------------------------------------------------------------------------
# metrics: histogram merge associativity + conservation
# ---------------------------------------------------------------------------


def _filled(values):
    h = Histogram()
    for v in values:
        h.record(v)
    return h


def test_histogram_basic_percentiles():
    h = _filled([0.001] * 50 + [0.1] * 45 + [5.0] * 5)
    assert h.count == 100
    # percentiles are bucket upper edges clamped to observed extremes
    assert h.percentile(0.5) <= 0.1 * 10 ** 0.25
    assert h.percentile(0.99) >= 1.0
    assert h.vmin == 0.001 and h.vmax == 5.0
    assert h.mean == pytest.approx((0.05 + 4.5 + 25.0) / 100)


def test_histogram_merge_associative_and_conserving():
    import random

    rng = random.Random(3)
    parts = [[rng.uniform(1e-6, 50.0) for _ in range(n)]
             for n in (17, 5, 42)]
    a, b, c = (_filled(p) for p in parts)
    # merge via fresh copies both ways: (a+b)+c vs a+(b+c)
    left = _filled(parts[0]); left.merge(_filled(parts[1]))
    left.merge(_filled(parts[2]))
    bc = _filled(parts[1]); bc.merge(_filled(parts[2]))
    right = _filled(parts[0]); right.merge(bc)
    assert left.counts == right.counts
    assert left.count == right.count == sum(len(p) for p in parts)
    assert left.total == pytest.approx(right.total)
    # conservation: merged count is exactly the sum of the parts
    assert left.count == a.count + b.count + c.count
    with pytest.raises(ValueError):
        _filled(parts[0]).merge(Histogram(bounds=COUNT_BOUNDS))


def test_registry_merge_and_kind_collision():
    r1 = MetricsRegistry()
    r1.counter("calls").inc(3)
    r1.gauge("depth").set(5)
    r1.histogram("lat").record(0.5)
    r2 = MetricsRegistry()
    r2.counter("calls").inc(4)
    r2.gauge("depth").set(2)
    r2.histogram("lat").record(1.5)
    merged = r1 + r2
    assert merged.counter("calls").value == 7
    assert merged.gauge("depth").value == 7      # gauges sum replica-wise
    assert merged.histogram("lat").count == 2
    # originals untouched (merge copies)
    assert r1.counter("calls").value == 3
    with pytest.raises(TypeError):
        r1.gauge("calls")
    snap = merged.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["calls"] == 7


# ---------------------------------------------------------------------------
# export: Chrome/Perfetto shapes + timeline extraction
# ---------------------------------------------------------------------------


def test_chrome_trace_shapes(tmp_path):
    rec = TraceRecorder(clock=VirtualClock())
    rec.instant("submit", "request", request=1)
    t0 = rec.now()
    rec.complete("prefill", "executor", t0, rows=2)
    rec.counter("queue_depth", 3)
    doc = chrome_trace_json(rec.events(), pid_names={0: "exec"})
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert metas and metas[0]["args"]["name"] == "exec"
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["args"]["request"] == 1
    span = next(e for e in evs if e["ph"] == "X")
    assert "dur" in span
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"] == {"queue_depth": 3}
    path = tmp_path / "t.json"
    n = write_chrome_trace(str(path), rec)
    assert n == 3
    json.load(open(path))  # well-formed


def test_queue_depth_timeline_downsamples():
    rec = TraceRecorder(clock=VirtualClock())
    for i in range(1000):
        rec.counter("queue_depth", i % 7)
        rec.instant("noise", "x")
    pts = queue_depth_timeline(rec.events(), max_points=50)
    assert len(pts) == 50
    assert all(0 <= v <= 6 for _, v in pts)


# ---------------------------------------------------------------------------
# zero observation effect: traced ≡ untraced across the engine matrix
# ---------------------------------------------------------------------------

MATRIX = [
    dict(paged=False, prefix_cache=False, spec_decode=False),
    dict(paged=True, prefix_cache=False, spec_decode=False),
    dict(paged=True, prefix_cache=True, spec_decode=False),
    dict(paged=True, prefix_cache=True, spec_decode=True),
]


def run_block(params, trace, **engine_kw):
    left, right, pred, truth = make_tables()
    client = EngineClient(fresh_engine(params, **engine_kw),
                          oracle=OracleLLM(pred, context_limit=512),
                          trace=trace)
    res = block_join(left, right, "the colours match", client, 4, 2)
    return res, client.executor.stats, truth


@pytest.mark.parametrize("engine_kw", MATRIX, ids=lambda d: "-".join(
    k for k, v in d.items() if v) or "dense")
def test_traced_join_token_identical(params, engine_kw):
    ref, ref_stats, truth = run_block(params, None, **engine_kw)
    rec = TraceRecorder()
    res, stats, _ = run_block(params, rec, **engine_kw)
    assert res.pairs == ref.pairs == truth
    assert res.ledger.prompt_tokens == ref.ledger.prompt_tokens
    assert res.ledger.completion_tokens == ref.ledger.completion_tokens
    # generated tokens are conserved even when ambient REPRO_CHAOS fires
    # (retries back partial attempts out); step counts and cache hits
    # are only comparable fault-free — standalone executors draw
    # auto-assigned replica ids, so two runs see different (all
    # token-identical) fault schedules under an ambient plan, and a
    # retried request re-rolls its radix-cache luck
    assert stats.generated_tokens == ref_stats.generated_tokens
    if not os.environ.get("REPRO_CHAOS"):
        assert (res.ledger.cached_prompt_tokens
                == ref.ledger.cached_prompt_tokens)
        assert stats.decode_steps == ref_stats.decode_steps
    # and the trace actually saw the join: lifecycle + join spans present
    names = {e[1] for e in rec.events()}
    assert {"submit", "admit", "request", "join.block",
            "block_done"} <= names


def test_traced_join_token_identical_under_chaos(params, monkeypatch):
    monkeypatch.setenv("REPRO_CHAOS", "7")
    ref, _, truth = run_block(params, None, paged=True, prefix_cache=True)
    rec = TraceRecorder()
    res, stats, _ = run_block(params, rec, paged=True, prefix_cache=True)
    assert res.pairs == ref.pairs == truth
    assert res.ledger.prompt_tokens == ref.ledger.prompt_tokens
    assert res.ledger.completion_tokens == ref.ledger.completion_tokens
    # chaos backoffs surface in the trace when retries fired
    if stats.retries:
        assert "backoff" in {e[1] for e in rec.events()}


def test_env_armed_trace_token_identical(params, monkeypatch):
    ref, _, truth = run_block(params, None, prefix_cache=True)
    monkeypatch.setenv("REPRO_TRACE", "1")
    client = EngineClient(fresh_engine(params, prefix_cache=True),
                          oracle=OracleLLM(
                              make_tables()[2], context_limit=512))
    assert client.trace  # env arming reached the executor
    left, right, _, _ = make_tables()
    res = block_join(left, right, "the colours match", client, 4, 2)
    assert res.pairs == ref.pairs == truth
    assert res.ledger.completion_tokens == ref.ledger.completion_tokens


# ---------------------------------------------------------------------------
# conservation: histograms ≡ ExecutorStats request totals
# ---------------------------------------------------------------------------


def test_executor_conservation_decode_and_score(params):
    left, right, pred, truth = make_tables()
    client = EngineClient(fresh_engine(params, prefix_cache=True),
                          oracle=OracleLLM(pred, context_limit=512),
                          trace=TraceRecorder())
    res = block_join(left, right, "the colours match", client, 4, 2)
    assert res.pairs == truth
    sres = tuple_join(left[:2], right[:2], "the colours match", client,
                      scoring=True)
    m = client.metrics
    stats = client.executor.stats
    ttft = m.get("ttft_s")
    score = m.get("score_e2e_s")
    e2e = m.get("e2e_s")
    assert ttft.count + score.count == stats.requests_finished
    assert e2e.count == ttft.count
    assert score.count == stats.score_requests
    assert sres.pairs == {(i, k) for i, k in truth if i < 2 and k < 2}
    # snapshot carries the conservation anchor
    snap = stats.snapshot()
    assert snap["requests_finished"] == stats.requests_finished
    assert snap["model_passes"] == stats.model_passes
    # per-operator counters booked through the client conduit
    assert m.counter("join_block_runs").value == 1
    assert m.counter("join_block_model_passes").value == res.ledger.calls
    assert m.counter("join_tuple_scored_runs").value == 1


def test_cluster_conservation_across_incarnations(params):
    """Kill a replica mid-life, resurrect it, run again: merged metrics
    must still reconcile with merged stats — the incarnation carry-over
    mirrors ExecutorStats.merge."""
    cfg, p = params
    left, right, pred, truth = make_tables()
    trace = TraceRecorder()
    with Cluster.replicate(cfg, p, ByteTokenizer(cfg.vocab_size), 2,
                           router=make_router("round_robin"),
                           max_seq=512, slots=4, trace=trace) as cl:
        client = ClusterClient(cl, oracle=OracleLLM(pred, context_limit=512))
        cl.hold()
        r1 = block_join(left, right, "the colours match", client, 4, 2)
        cl.drain()
        assert r1.pairs == truth
        before = cl.metrics()
        stats_before = cl.stats()
        assert (before.get("ttft_s").count
                == stats_before.requests_finished)

        cl.fail_replica(1)
        deadline = time.time() + 60
        while cl.replicas_alive == 2 and time.time() < deadline:
            time.sleep(0.01)
        assert cl.replicas_alive == 1
        assert cl.check_health() == 1  # resurrected at generation+1

        cl.hold()
        r2 = block_join(left, right, "the colours match", client, 4, 2)
        cl.drain()
        assert r2.pairs == truth
        merged = cl.metrics()
        stats = cl.stats()
        ttft = merged.get("ttft_s")
        score = merged.get("score_e2e_s")
        score_n = score.count if score is not None else 0
        # both incarnations' requests are in both the stats AND the
        # histograms — nothing was lost in the engine rebuild
        assert ttft.count + score_n == stats.requests_finished
        assert ttft.count > before.get("ttft_s").count
        summ = cl.summary()
        assert summ["metrics"]["histograms"]["ttft_s"]["count"] == ttft.count
        assert summ["trace"]["events"] == len(trace)
        # cluster-scope routing + the resurrection left their marks
        names = {e[1] for e in trace.events()}
        assert {"route", "resurrect"} <= names


# ---------------------------------------------------------------------------
# deterministic replay: two VirtualClock runs → byte-identical export
# ---------------------------------------------------------------------------


def _virtual_run(params, path):
    clock = VirtualClock()
    rec = TraceRecorder()
    engine = fresh_engine(params, prefix_cache=True)
    ex = ContinuousBatchingExecutor(engine, clock=clock, trace=rec)
    assert rec.clock is clock  # executor clock adopted
    handles = [ex.submit(f"Text: colour probe {i}\nAnswer:", max_tokens=6)
               for i in range(6)]
    for _ in ex.as_completed(handles):
        pass
    texts = [h.result.text for h in handles]
    write_chrome_trace(path, rec)
    return texts


def test_virtualclock_trace_replay_byte_identical(params, tmp_path,
                                                  monkeypatch):
    # ambient chaos would hand the two executors different auto-assigned
    # replica ids (different backoff events) — the byte-identity claim
    # is about the recorder/export, so pin the fault-free schedule
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    p1, p2 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    t1 = _virtual_run(params, p1)
    t2 = _virtual_run(params, p2)
    assert t1 == t2
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    assert len(b1) > 100


# ---------------------------------------------------------------------------
# join-operator conduits on non-serving clients stay free
# ---------------------------------------------------------------------------


def test_oracle_client_joins_have_noop_conduits():
    left, right, pred, truth = make_tables(4, 4)
    client = OracleLLM(pred, context_limit=512)
    assert trace_of(client) is NULL_TRACE
    assert registry_of(client) is None
    res = block_join(left, right, "the colours match", client, 2, 2)
    assert res.pairs == truth
    cres = cascade_tuple_join(left, right, "the colours match",
                              client, client, threshold=0.5)
    assert cres.pairs == truth
