"""The serving tier's spans on the profiler's clock, the engine's program
names, and the always-on counters booked beside them (DESIGN.md §17).

A live recorder's synchronous spans enter ``jax.profiler`` annotations of
the same name, so a profiler capture holds ``cluster.step`` ⊃
``executor.*`` ⊃ ``engine.*`` on each worker thread and
``cluster.lock_wait`` on the submitter's; the engine's jitted programs
carry ``engine_*`` names.  The counters (``prefill_positions_run``,
``submit_lock_waits`` / ``submit_lock_wait_s``, ``queued_s``) are exact
and live in ``ExecutorStats``.
"""

import glob
import os
import subprocess
import sys
import threading
import time
import tracemalloc
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.data.tokenizer import ByteTokenizer
from repro.models import init_params, model_specs
from repro.obs import NULL_TRACE, TraceRecorder
from repro.serve import Cluster, ContinuousBatchingExecutor, Engine

ROOT = Path(__file__).resolve().parents[1]
KEY = jax.random.PRNGKey(11)


@pytest.fixture(scope="module")
def params():
    cfg = get_smoke_config("granite-3-2b")
    return cfg, init_params(model_specs(cfg), KEY, jnp.float32)


def engine(params, **kw):
    cfg, p = params
    kw.setdefault("max_seq", 256)
    kw.setdefault("slots", 2)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("spec_decode", False)
    return Engine(cfg, p, ByteTokenizer(cfg.vocab_size), **kw)


def prompt(n_tokens: int) -> str:
    """A prompt of exactly ``n_tokens`` byte-tokenizer tokens (BOS
    included)."""
    return "x" * (n_tokens - 1)


def host_events(tdir):
    """(line id, start_ns, end_ns, name) of every host-plane event."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    assert files, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host"):
            for i, line in enumerate(plane.lines):
                out += [(i, e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
    return out


def inside(ev, outer, names):
    """Whether an event named in ``names`` on ``ev``'s line encloses it."""
    line, s, e, _ = ev
    return any(o[0] == line and o[3] in names and o[1] <= s and e <= o[2]
               for o in outer)


# ---------------------------------------------------------------------------
# the profiled window
# ---------------------------------------------------------------------------


def test_profiled_window_holds_the_program_spans_nested(params, tmp_path,
                                                        monkeypatch):
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    rec = TraceRecorder()
    with Cluster([engine(params)], trace=rec) as cl:
        # compiles first, outside the capture
        cl.result(cl.submit(prompt(40), max_tokens=3))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            cl.hold()
            handles = [cl.submit(prompt(n), max_tokens=3) for n in (30, 50)]
            for h in handles:
                cl.result(h)
        finally:
            jax.profiler.stop_trace()
    evs = host_events(tmp_path)
    names = {e[3] for e in evs}
    assert {"cluster.step", "cluster.lock_wait", "executor.refill",
            "executor.prefill", "executor.sample", "executor.decode_step",
            "engine.prefill"} <= names
    steps = [e for e in evs if e[3] == "cluster.step"]
    execs = [e for e in evs if e[3].startswith("executor.")]
    engine_prefills = [e for e in evs if e[3] == "engine.prefill"]
    # step ⊃ executor ⊃ engine, on the worker's thread
    assert all(inside(e, execs, {"executor.prefill"})
               for e in engine_prefills)
    assert all(inside(e, steps, {"cluster.step"}) for e in execs)
    # the submits waited for the lock on their own (the test's) thread
    waits = [e for e in evs if e[3] == "cluster.lock_wait"]
    assert len(waits) >= 2
    assert not any(inside(w, steps, {"cluster.step"}) for w in waits)
    # the engine's programs carry their names on the host
    assert "PjitFunction(engine_decode_paged)" in names
    assert "PjitFunction(engine_prefill_bucket)" in names
    assert not any(n.startswith("PjitFunction(<lambda>") for n in names)
    # the same spans are in the ring, on the recorder's clock
    ring = {e[1] for e in rec.events()}
    assert {"cluster.step", "cluster.lock_wait", "executor.decode_step",
            "engine.prefill", "request"} <= ring


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_prefill_positions_run_is_rows_times_bucket(params, paged,
                                                    monkeypatch):
    """row bucket × bucket per prefill launch, pad rows included; the
    dense engine also launches its warm all-pad prefill (slots rows) at
    each ``init_state``; a score batch launches one prefill of its own,
    at slots rows."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    eng = engine(params, paged=paged, slots=4)
    assert eng.prefill_buckets == [128, 256]
    ex = ContinuousBatchingExecutor(eng)
    batches = [(20, 100), (130,), (200, 30, 40)]
    for lens in batches:
        for n in lens:
            ex.submit(prompt(n), max_tokens=2)
        ex.drain()  # idle: the dense decode state is released
    ex.submit_score(prompt(60), "Yes")
    ex.drain()
    buckets = [128, 256, 256, 128]   # by the longest row; the score batch
    rows = [2, 1, 4, 4]   # row buckets of 2, 1 and 3 rows; the score: slots
    warm = 0 if paged else 3 * 4 * 128   # one init_state per drained batch
    assert ex.stats.prefill_positions_run == (
        sum(r * b for r, b in zip(rows, buckets)) + warm)
    assert ex.stats.prefill_batches == 4
    assert ex.stats.prefill_tokens_computed == sum(map(sum, batches)) + 60 + 3


def test_cancel_does_not_back_out_prefill_positions(params, monkeypatch):
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    ex = ContinuousBatchingExecutor(engine(params))
    h = ex.submit(prompt(90), max_tokens=50)
    ex.step()                       # admitted, prefilled, one decode step
    assert ex.stats.prefill_tokens_computed == 90
    ex.cancel(h)
    assert ex.stats.prefill_tokens_computed == 0
    assert ex.stats.prefill_positions_run == 1 * 128   # one row of 2 slots


def test_cluster_stats_never_read_a_prefill_half_booked(params, monkeypatch):
    """Every launch here is one full 128-token row at the 128 bucket, so
    prompt tokens equal launched positions in every snapshot: the
    executor books the two together, and ``Cluster.stats`` copies them
    together, even while the worker steps."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    snaps, stop = [], threading.Event()
    interval = sys.getswitchinterval()
    with Cluster([engine(params, slots=1)]) as cl:
        def poll():
            while not stop.is_set():
                s = cl.stats()
                snaps.append((s.prefill_tokens_computed,
                              s.prefill_positions_run))
                time.sleep(0)

        pollers = [threading.Thread(target=poll) for _ in range(4)]
        sys.setswitchinterval(1e-5)
        try:
            for t in pollers:
                t.start()
            handles = [cl.submit(prompt(128), max_tokens=6)
                       for _ in range(4)]
            for h in handles:
                cl.result(h)
        finally:
            stop.set()
            sys.setswitchinterval(interval)
            for t in pollers:
                t.join(timeout=30)
    assert not any(t.is_alive() for t in pollers)
    assert snaps[-1] == (4 * 128, 4 * 128)
    assert all(tok == pos for tok, pos in snaps)


class SignallingLock:
    """A replica lock that says when some thread starts to acquire it."""

    def __init__(self, lock):
        self._lock = lock
        self.acquiring = threading.Event()

    def acquire(self):
        self.acquiring.set()
        return self._lock.acquire()

    def release(self):
        self._lock.release()

    def __enter__(self):
        return self.acquire()

    def __exit__(self, *exc):
        self.release()


def test_submit_books_one_wait_of_at_least_the_hold(params, monkeypatch):
    """The test holds the replica's lock (as the worker does through a
    step) while a submit runs on another thread: exactly one wait is
    booked, of at least the time held; a cancel books one more."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    hold_s = 0.3
    with Cluster([engine(params)]) as cl:
        assert cl.trace is NULL_TRACE
        cl.hold()                   # the worker stays parked
        rep = cl._replicas[0]
        held = rep.lock
        held.acquire()
        rep.lock = SignallingLock(held)
        out = {}
        t = threading.Thread(target=lambda: out.update(
            h=cl.submit(prompt(30), max_tokens=2)))
        t.start()
        assert rep.lock.acquiring.wait(timeout=30)
        t0 = time.perf_counter()
        time.sleep(hold_s)
        held_s = time.perf_counter() - t0
        held.release()
        t.join(timeout=30)
        st = cl.stats()
        assert st.submit_lock_waits == 1
        assert held_s <= st.submit_lock_wait_s < held_s + 10
        assert cl.cancel(out["h"])
        st = cl.stats()
        assert st.submit_lock_waits == 2
        assert st.snapshot()["submit_lock_wait_s"] == st.submit_lock_wait_s


def test_queued_s_is_the_queue_wait_histogram_sum(params, monkeypatch):
    """Five requests on two slots: the later ones queue behind the first
    refill.  ``queued_s`` sums the same waits, in the same order, as the
    ``queue_wait_s`` histogram, over the admissions ``refills`` counts."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    ex = ContinuousBatchingExecutor(engine(params))
    for i in range(5):
        ex.submit(prompt(20 + 7 * i), max_tokens=4)
    ex.drain()
    hist = ex.metrics.get("queue_wait_s")
    assert ex.stats.refills == hist.count == 5
    assert ex.stats.queued_s == hist.total
    assert ex.stats.queued_s > 0


# ---------------------------------------------------------------------------
# the recorder itself
# ---------------------------------------------------------------------------


def test_null_span_is_one_shared_context_that_allocates_nothing():
    first = NULL_TRACE.span("a", "cat")
    assert NULL_TRACE.span("b", "other", pid=3, rows=2) is first
    with NULL_TRACE.span("c") as sp:
        assert sp is None
    tracemalloc.start()
    try:
        for _ in range(100):        # settle any one-off caches
            with NULL_TRACE.span("x", "cat"):
                pass
        before = tracemalloc.take_snapshot()
        for _ in range(10_000):
            with NULL_TRACE.span("x", "cat"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.traceback[0].filename.endswith("trace.py"))
    assert grown <= 0


def test_live_span_records_the_args_set_in_its_body():
    rec = TraceRecorder()
    with rec.span("engine.prefill", "engine", pid=2, rows=3) as sp:
        sp["cached"] = 16
    with pytest.raises(RuntimeError):
        with rec.span("engine.score", "engine"):
            raise RuntimeError("boom")
    (ph, name, cat, _ts, dur, pid, _tid, args), second = rec.events()
    assert (ph, name, cat, pid) == ("X", "engine.prefill", "engine", 2)
    assert args == {"rows": 3, "cached": 16} and dur >= 0.0
    assert second[1] == "engine.score"  # recorded though the body raised


def test_importing_obs_imports_no_jax():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    code = ("import sys, repro.obs\n"
            "rec = repro.obs.TraceRecorder()\n"
            "rec.instant('x', 'y')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
