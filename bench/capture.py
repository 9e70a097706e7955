"""What the benchmark observes on the served path, without changing it.

``CapturingEngine`` stands between the executor and the engine at the
engine's slot API (``prefill_rows`` / ``insert_row`` / ``decode_active``
/ ``release_slot``), the seam the executor drives and the program's own
fault injector wraps.  It delegates every call untouched and records:

* for each *tracked* request (chosen up front from the seed), the logits
  row the engine produced at each position it served — the prefill's
  last position, then every decode step — and the token fed at each
  step; these are what ``check.py`` compares with the plain reference;
* for every step, the positions it computed, from which ``flops.py``
  counts the model FLOPs of the window.

``TimedClient`` is the join operators' client: it times every model call
on the client's clock and credits it to the query that made it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.serve.cluster import ClusterClient


@dataclasses.dataclass
class Tracked:
    prompt: str
    logits: list = dataclasses.field(default_factory=list)  # device rows
    fed: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepLog:
    """One engine step as the host issued it: ``kind`` is "prefill" or
    "decode"; ``spans`` lists (first, last+1) positions computed per row
    (a decode row computes one position)."""
    t: float
    kind: str
    spans: List[Tuple[int, int]]


class CapturingEngine:
    def __init__(self, engine):
        self._engine = engine
        self._mu = threading.Lock()
        self._tracked: Dict[str, Tracked] = {}
        self._batch: Dict[int, Tracked] = {}   # prefill row -> tracked
        self._slots: Dict[int, Tracked] = {}   # decode slot -> tracked
        self.steps: List[StepLog] = []
        #: while True, each slot-API call is a span in the profiler's
        #: trace (``bench.<method>``), so device idle gaps can be put
        #: against what the host was doing
        self.annotate = False

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def track(self, prompt: str) -> Tracked:
        with self._mu:
            return self._tracked.setdefault(prompt, Tracked(prompt))

    @property
    def tracked(self) -> List[Tracked]:
        with self._mu:
            return list(self._tracked.values())

    def clear_tracked(self) -> None:
        with self._mu:
            self._tracked.clear()

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")

    # --- the slot API ---------------------------------------------------
    def prefill_rows(self, prompts):
        with self._span("prefill_rows"):
            out = self._engine.prefill_rows(prompts)
        _, logits, lens, cached = out
        self.steps.append(StepLog(time.perf_counter(), "prefill",
                                  list(zip(cached, lens))))
        with self._mu:
            self._batch = {r: self._tracked[p] for r, p in enumerate(prompts)
                           if p in self._tracked
                           and not self._tracked[p].logits}
        for r, rec in self._batch.items():
            rec.logits.append(logits[r])
        return out

    def insert_row(self, state, cache, logits, row, slot):
        with self._span("insert_row"):
            self._engine.insert_row(state, cache, logits, row, slot)
        rec = self._batch.pop(row, None)
        if rec is not None:
            self._slots[slot] = rec

    def decode_active(self, state, tokens, active):
        with self._span("decode_active"):
            self._engine.decode_active(state, tokens, active)
        act = np.asarray(active, bool)
        lens = np.asarray(state.lens)[act]  # after the step: position + 1
        self.steps.append(StepLog(time.perf_counter(), "decode",
                                  [(int(n) - 1, int(n)) for n in lens]))
        for slot, rec in self._slots.items():
            if act[slot]:
                rec.fed.append(int(tokens[slot]))
                rec.logits.append(state.logits[slot])

    def release_slot(self, state, slot):
        self._slots.pop(slot, None)
        return self._engine.release_slot(state, slot)

    def release_state(self, state):
        self._slots.clear()
        return self._engine.release_state(state)


@dataclasses.dataclass
class Call:
    pipeline: int
    query: int
    index: int
    submitted: float
    done: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0
    text: str = ""
    tracked: Optional[Tracked] = None
    cancelled: bool = False


class TimedClient(ClusterClient):
    """``ClusterClient`` that times each call from its submission to
    the moment the join operator receives its answer."""

    def __init__(self, cluster, *, oracle, engine: CapturingEngine,
                 track: Optional[set] = None):
        super().__init__(cluster, oracle=oracle)
        self._engine = engine
        self._track = track or set()
        self._local = threading.local()
        self._mu = threading.Lock()
        self.calls: List[Call] = []
        self._by_handle: Dict[int, Call] = {}
        self.handles: list = []
        #: set by ``close`` or ``cancel``: no query begins afterwards, and
        #: a call still submitted is cancelled
        self.closed = False
        #: cleared while the pipelines submit their first queries into a
        #: held cluster; the operators wait on it before consuming
        self.go = threading.Event()
        self.go.set()

    def begin(self, query) -> bool:
        """The calls this thread submits next belong to ``query``; False,
        and nothing bound, once the client is closed."""
        with self._mu:
            if self.closed:
                return False
        self._local.query = query
        self._local.n = 0
        return True

    def close(self) -> None:
        """No query begins after this; calls still made are cancelled."""
        with self._mu:
            self.closed = True

    def submit(self, prompt, *, max_tokens, stop=None, deadline=None):
        q = self._local.query
        k = self._local.n
        self._local.n += 1
        call = Call(q.pipeline, q.index, k, time.perf_counter())
        if (q.pipeline, q.index, k) in self._track:
            call.tracked = self._engine.track(prompt)
        h = super().submit(prompt, max_tokens=max_tokens, stop=stop,
                           deadline=deadline)
        with self._mu:
            self.calls.append(call)
            self._by_handle[id(h)] = call
            self.handles.append(h)
            closed = self.closed
        if closed and h.cancel():  # made after the window's close
            call.cancelled = True
        return h

    def as_completed(self, handles):
        handles = list(handles)
        self._local.query.submitted.set()
        self.go.wait()
        for h in super().as_completed(handles):
            with self._mu:
                call = self._by_handle[id(h)]
            call.done = time.perf_counter()
            u = h._response.usage
            call.prompt_tokens = u.prompt_tokens
            call.completion_tokens = u.completion_tokens
            call.text = h._response.text
            yield h

    def cancel(self, queued_only: bool = False) -> None:
        """Cancel every call not yet answered, or only those no engine
        has started on; a cancelled call counts neither as answered nor
        as failed."""
        with self._mu:
            self.closed = True
            pending = [(h, self._by_handle[id(h)]) for h in self.handles
                       if not h.done()]
        for h, call in pending:
            if queued_only and h.started():
                continue
            if h.cancel():
                call.cancelled = True
