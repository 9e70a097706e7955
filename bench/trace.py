"""Reduce a profiler trace of the window to the numbers the readers need.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  On a TPU the device plane is
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per
program execution and its ``XLA Ops`` line one per operation.  The host
plane holds the benchmark's own spans around the engine's slot-API calls
(``bench.prefill_rows``, ``bench.decode_active``, ``bench.insert_row``;
see ``capture.py``).  Host and device events share one clock.

XLA names the engine's programs ``jit__lambda(<fingerprint>)`` today
(each is a ``jax.jit`` of a lambda; the fingerprint changes with the
program), and the eager reshape of prefill's K/V before the page scatter
``jit_reshape(<fingerprint>)``.  So the decode step's program is told
apart by how often it ran (``decode_program``), not by its name.
"""

from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[int, int]

#: the host span that marks the traced window (see ``summarize``)
WINDOW_SPAN = "bench.window"


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval]) -> List[Interval]:
    """Idle intervals between consecutive merged busy intervals."""
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]


def label_gaps(idle: Sequence[Interval],
               host: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Idle nanoseconds by the host span that overlaps each gap most
    (``idle`` when the host was in none of the benchmark's spans)."""
    out: Dict[str, int] = defaultdict(int)
    host = sorted(host)
    starts = [h[0] for h in host]
    for a, b in idle:
        best, best_ov = "no engine call on the host", 0
        i = max(0, bisect.bisect_left(starts, a) - 1)
        while i < len(host) and host[i][0] < b:
            s, e, name = host[i]
            ov = min(b, e) - max(a, s)
            if ov > best_ov:
                best, best_ov = name, ov
            i += 1
        out[best] += b - a
    return dict(out)


def programs(modules: Sequence[Tuple[int, int, str]]) -> Dict[str, List]:
    """``{module name: [executions, device seconds]}`` of the window."""
    out: Dict[str, List] = defaultdict(lambda: [0, 0.0])
    for s, e, name in modules:
        out[name][0] += 1
        out[name][1] += (e - s) / 1e9
    return dict(out)


def decode_program(progs: Dict[str, List], decode_steps: int):
    """The decode step's program: of the programs that run at least a
    millisecond per execution, the one whose executions come closest to
    the executor's count of decode steps in the window (the larger
    device time breaks a tie).  None when the window holds no step."""
    cands = [(abs(n - decode_steps), -t, name)
             for name, (n, t) in progs.items() if n and t / n >= 1e-3]
    if decode_steps <= 0 or not cands:
        return None
    return min(cands)[2]


def short_name(name: str, limit: int = 200) -> str:
    """An XLA op's event name (its whole HLO instruction) without the
    operands' layouts, cut to ``limit`` characters."""
    prev = None
    while prev != name:  # layouts nest: {1,0:T(8,128)(2,1)}
        prev, name = name, re.sub(r"\{[^{}]*\}", "", name)
    return name[:limit]


def read_events(path: str):
    """(device module events, device op events, host span events) of the
    first TPU, each as (start_ns, end_ns, name)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, host = [], [], []
    chips = sorted(p.name for p in pd.planes
                   if p.name.startswith("/device:TPU:"))
    for plane in pd.planes:
        if chips and plane.name == chips[0]:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name.startswith("bench.")]
    return modules, ops, host


def clip(events: Sequence[Tuple[int, int, str]], lo: int, hi: int):
    """The parts of ``events`` that lie inside [lo, hi)."""
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if a < hi and b > lo]


def summarize(tdir, window_s: float) -> dict:
    """Busy and idle time, device time by program, and the breakdown the
    result line carries, for the traced window.

    The window is the host span ``WINDOW_SPAN`` that the run opens right
    after the profiler starts and closes right before it stops; device
    time is clipped to it, and a program counts once for each execution
    that started inside it.  Without that span the window is taken to be
    ``window_s`` seconds and nothing is clipped."""
    files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
    if not files:
        return {"busy_s": None, "window_s": window_s, "programs": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    modules, ops, host = read_events(files[0])
    span = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    host = [h for h in host if h[2] != WINDOW_SPAN]
    started = modules
    if span:
        lo, hi = span[0]
        window_s = (hi - lo) / 1e9
        started = [m for m in modules if lo <= m[0] < hi]
        modules, ops = clip(modules, lo, hi), clip(ops, lo, hi)
    busy = union([(a, b) for a, b, _ in modules])
    by_op: Dict[str, int] = defaultdict(int)
    for a, b, name in (ops or modules):
        by_op[short_name(name)] += b - a
    idle = label_gaps(gaps(busy), host)
    top = lambda d: [[k, v / 1e9] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "busy_s": total(busy) / 1e9 if busy else None,
        "window_s": window_s,
        "programs": programs(started),
        "breakdown": {"device_ops": top(by_op), "idle_gaps": top(idle)},
    }
