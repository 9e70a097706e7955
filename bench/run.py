"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload granite-3-2b-nomult.block_nocache --seed 7 \
        --seconds 30 --trace 0

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a model
configuration (``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  The run draws the weights from the
seed, builds the served stack (engine, executor, one-replica cluster,
the join operators' client), warms up the mix's shapes, starts the
mix's closed-loop pipelines, which submit their first queries into the
held cluster, and opens the window as it lets the cluster serve them.
At ``--seconds`` it cancels the calls still queued, waits for those an
engine has started on, reads the device's peak memory, frees the
program and checks the outputs against the plain reference.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the first seconds of the window with the profiler and reports
the per-layer metrics (each read by ``bench/metrics/<name>.py``).  The
last line of standard output is one JSON object; the numbers compared
for ``correct`` are printed beside their limits as the last lines of
standard error and under the result's last key, ``checks``.

The run needs a TPU and as many chips as the cell asks for; without
them it exits with code 3 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import json
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: seconds of the window that a ``--trace 1`` run records
TRACE_SECONDS = 8.0
#: how long the run waits, past the window's close, for the calls an
#: engine had started on (calls still queued are cancelled at the close)
DRAIN_SECONDS = 60.0


def log(msg: str) -> None:
    """A progress line on standard error, stamped with seconds since the
    process started."""
    print(f"bench {time.time() - _START_WALL:8.1f}s {msg}", file=sys.stderr,
          flush=True)


def process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                    .split()[19]) / ticks
        uptime = float(Path("/proc/uptime").read_text().split()[0])
        return time.time() - (uptime - start)
    except (OSError, ValueError, IndexError):
        return time.time()


_START_WALL = process_start_wall()


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_workload(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}")


def load_config(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise SystemExit(f"unknown config {name!r}")


def load_peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """XLA programs compiled, and programs loaded from the persistent
    cache, in this process, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def total(self) -> int:
        return self.programs + self.cache_hits


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the fields its
    architecture module sets from the file, then every field the module
    names checked against the file's number."""
    from bench import arch
    from repro.configs import get_config

    model = arch.load(cfg)
    pc = dataclasses.replace(get_config(cfg["program_arch"]),
                             **model.program_fields(cfg))
    for k, v in model.checked_fields(cfg).items():
        if getattr(pc, k) != v:
            raise ValueError(f"program config {k}={getattr(pc, k)} differs "
                             f"from the configuration file's {v}")
    return pc


def draw_track(mix: dict, seed: int, context: int, counter) -> set:
    """The requests whose logits are compared: per pipeline, calls of its
    first measured query drawn from the seed (always its first call)."""
    from bench import traffic

    op = traffic.operator(mix)
    out = set()
    for p, spec in enumerate(mix["pipelines"]):
        q = traffic.make_query(mix, seed, p, 0, context, counter)
        pool = range(1, min(op.calls(q), spec["track_from"]))
        rng = traffic.query_rng(seed, p, 0, "track")
        picks = rng.sample(list(pool), min(spec["track"] - 1, len(pool)))
        out |= {(p, 0, k) for k in [0] + picks}
    return out


def call_latencies_ms(calls, t0: float, t1: float) -> List[float]:
    """Submission to answer, in ms, of every call submitted before the
    window's close and not answered before its opening.  A call still
    unanswered at the close (queued, and cancelled there, or still
    decoding) counts as ``t1 - submitted``: the least it took."""
    return [((min(c.done, t1) if c.done else t1) - c.submitted) * 1e3
            for c in calls
            if c.submitted < t1 and (c.done == 0.0 or c.done >= t0)]


def percentile(values: List[float], q: int) -> float:
    """The q-th percentile (statistics.quantiles, inclusive method)."""
    if len(values) < 2:
        return values[0] if values else float("nan")
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    stats0: dict
    stats1: dict
    compiles0: int
    compiles1: int


def stats_snapshot(cluster) -> dict:
    return cluster.stats().snapshot()


def run_cell(config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, *, limits: dict, control: Optional[str] = None,
             wrap_engine=None,
             compile_cache: bool = True, peaks: Optional[dict] = None,
             predicate=None) -> dict:
    """One run of a cell; returns the result object (see module doc).

    ``control`` switches on one of the program's own lower-precision
    paths ("int8" weights or "fp8_kv" cache) for the limit readings;
    ``wrap_engine`` wraps the engine under the capture (fault tests);
    ``peaks`` stands in for the peak table's row (CPU rehearsals);
    ``predicate`` replaces the teacher's predicate (fault tests)."""
    import jax
    import jax.numpy as jnp

    from bench import arch, capture, check, scenarios, traffic
    from bench import trace as btrace
    from repro.core.oracle import OracleLLM
    from repro.data.tokenizer import ByteTokenizer
    from repro.models import model_specs
    from repro.models.params import is_spec
    from repro.serve import Cluster, Engine

    if compile_cache:
        from repro.launch.serve import enable_compile_cache

        enable_compile_cache()
    counter = CompileCounter()
    dev = jax.devices()[0]
    serving = config["serving"]
    context = serving["max_seq"]
    pcfg = program_config(config)
    if control == "fp8_kv":
        pcfg = dataclasses.replace(pcfg, kv_cache_dtype="float8_e4m3fn")
    dtype = jnp.dtype(config["torch_dtype"])
    model = arch.load(config)

    params = model.draw_params(config, seed, dtype,
                               vocab_rows=pcfg.padded_vocab)
    shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
                          model_specs(pcfg), is_leaf=is_spec)
    params = model.to_program(params, shapes)
    if control == "int8":
        params = quantize_leaves(params, model_specs(pcfg))
    jax.block_until_ready(params)
    log("weights drawn")
    engine = Engine(pcfg, params, ByteTokenizer(pcfg.vocab_size),
                    max_seq=context, slots=serving["slots"],
                    page_size=serving["page_size"],
                    pool_pages=serving["pool_pages"],
                    prefix_cache=mix["prefix_cache"],
                    quant=(control == "int8"))
    del params
    inner = wrap_engine(engine) if wrap_engine is not None else engine
    eng = capture.CapturingEngine(inner)
    cluster = Cluster([eng])
    oracle = OracleLLM(predicate or scenarios.oracle_predicate,
                       context_limit=context)
    track = draw_track(mix, seed, context, traffic.byte_tokens)
    client = capture.TimedClient(cluster, oracle=oracle, engine=eng,
                                 track=track)
    result: Dict = {"correct": False, "attempted": 0, "failed": 0,
                    "metrics": {}, "device": {}}
    pipes: List[traffic.Pipeline] = []
    stop = threading.Event()
    try:
        # warm-up: one query of every pipeline on tables cut to the mix's
        # warm-up sizes, started in the same order as the measured ones
        warm_stop = threading.Event()
        warm = [traffic.Pipeline(mix, seed, p, client, context, warm_stop,
                                 tag="warmup", max_queries=1)
                for p in range(len(mix["pipelines"]))]
        for w in warm:
            w.start()
            w.first_submitted.wait(timeout=600)
        for w in warm:
            w.join(timeout=900)
        # the warm-up's tracked calls only compiled the capture's slicing
        eng.clear_tracked()
        log("warm-up done")
        errors = [q.error for w in warm for q in w.queries if q.error]
        if errors:
            raise RuntimeError(f"warm-up query failed: {errors[0]}"
                               f"{replica_errors(cluster)}")

        # the first queries are submitted into a held cluster, in the
        # mix's order, and served from the moment the window opens
        pipes = [traffic.Pipeline(mix, seed, p, client, context, stop)
                 for p in range(len(mix["pipelines"]))]
        client.go.clear()
        cluster.hold()
        for p in pipes:
            p.start()
            p.first_submitted.wait(timeout=600)
        t_open = time.perf_counter()
        client.go.set()
        setup_s = time.time() - _START_WALL
        log("window open")
        win = Window(t_open, 0.0, stats_snapshot(cluster), {},
                     counter.total, 0)
        traced = None
        if trace:
            tdir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
            eng.annotate = True
            jax.profiler.start_trace(str(tdir))
            with jax.profiler.TraceAnnotation(btrace.WINDOW_SPAN):
                t_tr0 = time.perf_counter()
                tr_stats0, tr_c0 = stats_snapshot(cluster), counter.total
                time.sleep(max(0.0, t_tr0 + min(seconds, TRACE_SECONDS)
                               - time.perf_counter()))
                t_tr1 = time.perf_counter()
                tr_stats1, tr_c1 = stats_snapshot(cluster), counter.total
            jax.profiler.stop_trace()
            eng.annotate = False
            traced = (Window(t_tr0, t_tr1, tr_stats0, tr_stats1, tr_c0,
                             tr_c1), tdir)
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        win.t1 = time.perf_counter()
        win.stats1 = stats_snapshot(cluster)
        win.compiles1 = counter.total
        close_window(cluster, client, pipes, stop)
        log("window closed, queued calls cancelled")
        deadline = time.perf_counter() + DRAIN_SECONDS
        for p in pipes:
            p.join(timeout=max(0.0, deadline - time.perf_counter()))
        unanswered = sum(1 for c in client.calls
                         if not c.done and not c.cancelled)
        if any(p.is_alive() for p in pipes):
            client.cancel()
            for p in pipes:
                p.join(timeout=30)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        log("calls in flight answered")

        calls = [c for c in client.calls if c.done or c.submitted < win.t1]
        queries = [q for p in pipes for q in p.queries]
        traffic.credit(queries, client.calls)
        memos = [q.memo for q in queries]
        pairs = traffic.pairs_settled(memos, win.t0, win.t1)
        attempted = [c for c in calls if not c.cancelled
                     and (c.done == 0.0 or c.done >= win.t0)]
        result["attempted"] = len(attempted)
        result["failed"] = unanswered
        result["device"] = {"platform": dev.platform,
                            "kind": dev.device_kind,
                            "count": len(jax.devices()),
                            "memory_peak_bytes": peak}
        span = win.t1 - win.t0
        lat = call_latencies_ms(client.calls, win.t0, win.t1)
        if not trace:
            result["metrics"] = {
                "pairs_per_s": {"value": pairs / span, "unit": "pairs/s"},
                "call_p95_ms": {"value": percentile(lat, 95), "unit": "ms"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
        else:
            tw, tdir = traced
            summary = btrace.summarize(tdir, tw.t1 - tw.t0)
            shutil.rmtree(tdir, ignore_errors=True)
            ctx = dict(config=config,
                       peaks=peaks or load_peaks(dev.device_kind),
                       window=tw, calls=calls, memos=memos,
                       steps=eng.steps, trace=summary, memory_peak=peak)
            result["metrics"] = read_layer_metrics(ctx)
            result["device"]["busy_s"] = summary["busy_s"]
            result["device"]["window_s"] = summary["window_s"]
            result["breakdown"] = summary["breakdown"]
        tracked = [(t, [c for c in client.calls if c.tracked is t])
                   for t in eng.tracked]
        host_logits = [(t, [jax.device_get(r) for r in t.logits], cs)
                       for t, cs in tracked]
    finally:
        stop.set()
        client.cancel()
        cluster.shutdown()
    # free the program before the reference runs
    del engine, eng, inner, cluster, client
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
    batch = sum(spec["track"] for spec in mix["pipelines"])
    log("program freed; reference running")
    checks, readings = check.run_checks(config, seed, context, queries,
                                        host_logits, limits, batch,
                                        unanswered)
    log("reference compared")
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["readings"] = readings
    result["checks"] = checks
    return result


def quantize_leaves(params, specs):
    """The program's int8 weight quantization (``models.quant``), one
    leaf at a time under ``jit``, each bf16 leaf freed once quantized.

    ``Engine(quant=True)`` quantizes the whole tree at once, with every
    leaf's float32 temporaries beside the bf16 weights, which does not
    fit one chip beside 9.35 GB of yi-9b-24l; given quantized leaves it
    keeps them as they are."""
    import jax

    from repro.models import quant
    from repro.models.params import is_spec

    leaves, treedef = jax.tree.flatten(params)
    out = []
    for leaf, spec in zip(leaves, jax.tree.leaves(specs, is_leaf=is_spec)):
        if quant._quantizable(spec):
            q = jax.jit(quant.quantize, static_argnames="keep_leading")(
                leaf, keep_leading=spec.axes[0] == "layers")
            jax.block_until_ready(q)
            leaf.delete()
            leaf = q
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def close_window(cluster, client, pipes, stop) -> None:
    """End the pipelines' loops and cancel the calls still queued.

    The cluster is held while the calls are cancelled: a cancel takes
    the replica's lock, which its worker holds through every step, so
    hundreds of cancels against a serving worker would each wait for
    steps.  Held, the worker parks after its current step; the
    pipelines finish submitting the queries they had begun (the
    operators wait on ``client.go`` before consuming, which would end
    the hold), then everything queued is cancelled at once and the
    calls an engine has started on are served to their end."""
    stop.set()
    client.go.clear()
    cluster.hold()
    client.close()
    for p in pipes:
        if p.queries:
            p.queries[-1].submitted.wait(timeout=DRAIN_SECONDS)
    client.cancel(queued_only=True)
    client.go.set()
    cluster.release()


def replica_errors(cluster) -> str:
    """The exception that killed each dead replica, for the run's error."""
    import traceback

    out = ""
    for rep in getattr(cluster, "_replicas", []):
        if rep.error is not None:
            out += "\n" + "".join(traceback.format_exception(rep.error))
    return out


def read_layer_metrics(ctx: dict) -> dict:
    """Each per-layer metric of the manifest, read by its own file under
    ``bench/metrics``; a reader that finds nothing returns None and the
    metric is left out."""
    out = {}
    for m in manifest()["per_layer"]:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest()
    wl = find_workload(man, args.workload)
    config = load_config(man, wl["config"])
    from bench import traffic

    mix = traffic.load_mix(wl["traffic"])
    limits = load_json(HERE / "limits" / f"{wl['name']}.json")

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["chips"]:
        print(f"bench: needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    load_peaks(devs[0].device_kind)

    res = run_cell(config, mix, args.seed, args.seconds, bool(args.trace),
                   limits=limits["limits"])
    res["checks"] = res.pop("checks")  # the last key of the line
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
