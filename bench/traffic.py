"""The general traffic generator: closed-loop join pipelines from a mix file.

A traffic mix (``traffic/<name>.json``) names its pipelines, each one
scenario at its table sizes, and the join operator they repeat, one of
``OPERATORS``.  Every query draws fresh tables from ``(seed, pipeline,
query)``; the block join plans its batch sizes from the query's own
statistics with the paper's optimizer.  Nothing else about a mix is
code.  ``Pipeline`` runs one closed loop on a thread.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import random
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from bench import scenarios

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def query_rng(seed: int, pipeline: int, query: int, tag: str = "run"
              ) -> random.Random:
    """A generator that depends only on the run's seed, the pipeline's
    index and the query's index (and the phase ``tag``)."""
    h = hashlib.sha256(f"{tag}/{seed}/{pipeline}/{query}".encode()).digest()
    return random.Random(int.from_bytes(h[:8], "little"))


def byte_tokens(text: str) -> int:
    """Tokens of ``text`` under the served byte tokenizer (BOS + bytes)."""
    return 1 + len(text.encode("utf-8"))


class SettledMemo(dict):
    """The pairs a query's answered calls settled, one rectangle
    ``(lo1, hi1, lo2, hi2)`` per call (the ``completed`` memo a block
    join fills itself), with the pairs found in it.  Records when each
    rectangle was settled, on the client's clock."""

    def __init__(self):
        super().__init__()
        self.settled_at: Dict[Tuple[int, int, int, int], float] = {}

    def __setitem__(self, rect, pairs):
        self.settled_at.setdefault(rect, time.perf_counter())
        super().__setitem__(rect, pairs)

    def settle(self, rect, pairs, at: float) -> None:
        """A rectangle settled by a call answered at ``at``."""
        self.settled_at.setdefault(rect, at)
        super().__setitem__(rect, pairs)


def rect_pairs(rect: Tuple[int, int, int, int]) -> int:
    lo1, hi1, lo2, hi2 = rect
    return (hi1 - lo1) * (hi2 - lo2)


def pairs_settled(memos: List[SettledMemo], t0: float, t1: float) -> int:
    """Row pairs settled by calls answered inside ``[t0, t1]``."""
    return sum(rect_pairs(r) for m in memos
               for r, t in m.settled_at.items() if t0 <= t <= t1)


@dataclasses.dataclass
class Query:
    pipeline: int
    index: int
    tables: scenarios.Tables
    b1: int
    b2: int
    memo: SettledMemo
    tag: str = "run"
    mix: dict = dataclasses.field(default_factory=dict)
    pairs: Optional[Set[Tuple[int, int]]] = None
    error: Optional[str] = None
    submitted: threading.Event = dataclasses.field(
        default_factory=threading.Event)


def plan_query(tables: scenarios.Tables, context: int, reserve: int,
               counter, prefix_cached: bool) -> Tuple[int, int]:
    """Batch sizes from the query's own statistics and selectivity: the
    paper's optimizer (with the prefix-cache objective where the served
    tier caches prefixes) over the served window less ``reserve`` tokens
    held back for rows longer than the mean."""
    from repro.core.adaptive_join import generate_statistics
    from repro.core.batch_opt import plan

    stats = generate_statistics(tables.r1, tables.r2, tables.condition,
                                counter=counter)
    p = plan(stats, tables.selectivity, context - stats.p - reserve,
             prefix_cached=prefix_cached)
    return p.b1, p.b2


class BlockJoin:
    """Algorithm 2 (``core/block_join``): one call per block of b1 x b2
    rows, planned per query; a call settles its block's rectangle, which
    the operator itself writes into the query's memo."""

    def plan(self, mix: dict, tables, context: int, counter):
        return plan_query(tables, context, mix["reserve_tokens"], counter,
                          mix["prefix_cache"])

    def calls(self, q: "Query") -> int:
        return (math.ceil(len(q.tables.r1) / q.b1)
                * math.ceil(len(q.tables.r2) / q.b2))

    def run(self, mix: dict, q: "Query", client):
        from repro.core.block_join import block_join

        return block_join(q.tables.r1, q.tables.r2, q.tables.condition,
                          client, q.b1, q.b2, completed=q.memo)

    def credit(self, q: "Query", calls) -> None:
        """Nothing to add: the block join filled the memo as it ran."""


class TupleJoin:
    """Algorithm 1 (``core/tuple_join``), decoded: one call per pair, in
    the operator's row-major order, each settling its own pair.  The mix
    may set ``max_answer_tokens`` (1, the paper's, cannot hold "Yes"
    under a byte tokenizer) and ``window`` (the operator's in-flight
    bound).  Scoring (``scoring=True``) is refused: the
    capture does not read the score path's log-probs yet."""

    def plan(self, mix: dict, tables, context: int, counter):
        if mix.get("scoring"):
            raise ValueError("tuple_join with scoring is not measured by "
                             "this harness yet (no score-path capture)")
        return 1, 1

    def calls(self, q: "Query") -> int:
        return len(q.tables.r1) * len(q.tables.r2)

    def run(self, mix: dict, q: "Query", client):
        from repro.core.tuple_join import tuple_join

        return tuple_join(q.tables.r1, q.tables.r2, q.tables.condition,
                          client, scoring=False,
                          max_answer_tokens=mix.get("max_answer_tokens", 1),
                          window=mix.get("window", 256))

    def credit(self, q: "Query", calls) -> None:
        """The k-th call of a query asked about the k-th pair; each
        answered call settles that pair, with the operator's own
        reading of the answer."""
        from repro.core.prompts import parse_yes_no

        n2 = len(q.tables.r2)
        for c in calls:
            if c.done:
                i, k = divmod(c.index, n2)
                q.memo.settle((i, i + 1, k, k + 1),
                              {(i, k)} if parse_yes_no(c.text) else set(),
                              c.done)


#: the join operators a mix may name under ``"operator"``
OPERATORS = {"block_join": BlockJoin(), "tuple_join": TupleJoin()}


def operator(mix: dict):
    """The adapter of the operator a mix names; an unknown one is an
    error, never a silent fallback."""
    name = mix.get("operator")
    if name not in OPERATORS:
        raise ValueError(f"mix {mix.get('name')!r} names operator {name!r};"
                         f" known: {sorted(OPERATORS)}")
    return OPERATORS[name]


def make_query(mix: dict, seed: int, pipeline: int, index: int,
               context: int, counter, tag: str = "run") -> Query:
    spec = mix["pipelines"][pipeline]
    rows = spec["warmup_rows"] if tag == "warmup" else spec["rows"]
    tables = scenarios.make(spec["scenario"],
                            query_rng(seed, pipeline, index, tag), rows)
    b1, b2 = operator(mix).plan(mix, tables, context, counter)
    return Query(pipeline, index, tables, b1, b2, SettledMemo(), tag, mix)


def credit(queries: List[Query], calls) -> None:
    """Let each query's operator credit the calls it answered to the
    query's memo (after the window, before anything reads the memos)."""
    by_query: Dict[Tuple[int, int], list] = {}
    for c in calls:
        by_query.setdefault((c.pipeline, c.query), []).append(c)
    for q in queries:
        operator(q.mix).credit(q, by_query.get((q.pipeline, q.index), []))


class Pipeline(threading.Thread):
    """One closed loop: a join query, then the next, until ``stop``.

    ``client`` is the benchmark's timed client; the pipeline tells it
    which query is submitting (``client.begin``) so that every call can
    be credited to its query, and stops once the client is closed."""

    def __init__(self, mix: dict, seed: int, index: int, client,
                 context: int, stop: threading.Event, tag: str = "run",
                 max_queries: Optional[int] = None):
        super().__init__(name=f"bench-pipeline-{index}", daemon=True)
        self.mix, self.seed, self.index = mix, seed, index
        self.client, self.context, self.stop = client, context, stop
        self.tag, self.max_queries = tag, max_queries
        self.queries: List[Query] = []
        self.first_submitted = threading.Event()

    def run(self) -> None:
        op = operator(self.mix)
        q = 0
        while not self.stop.is_set() and (self.max_queries is None
                                          or q < self.max_queries):
            query = make_query(self.mix, self.seed, self.index, q,
                               self.context, self.client.count_tokens,
                               self.tag)
            if q == 0:
                query.submitted = self.first_submitted
            self.queries.append(query)
            if not self.client.begin(query):  # closed: the window is over
                self.queries.pop()
                query.submitted.set()
                return
            try:
                res = op.run(self.mix, query, self.client)
                query.pairs = set(res.pairs)
                if res.meta.get("degraded"):
                    query.error = f"degraded: {res.meta.get('error')}"
            except Exception as exc:  # recorded; the run is then not correct
                query.error = f"{type(exc).__name__}: {exc}"
            query.submitted.set()  # also when the join raised early
            if query.error is not None:
                return
            q += 1
