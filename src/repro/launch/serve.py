"""Serving launcher — host an architecture and run semantic joins on it.

  python -m repro.launch.serve --arch granite-3-2b --smoke \
      --scenario ads --operator adaptive

  # data-parallel cluster: N engine replicas behind the prefix-affinity
  # router (DESIGN.md §12); also via REPRO_REPLICAS=N
  python -m repro.launch.serve --arch granite-3-2b --smoke --replicas 2

  # DP x TP: each replica tensor-parallel over its own contiguous slice
  # of tp devices, optionally int8-weight-resident (DESIGN.md §15); also
  # via REPRO_TP=N / REPRO_QUANT=1
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.serve --arch granite-3-2b --smoke \
      --replicas 2 --tp 2

Production notes: on a TPU slice the engine compiles per prefill bucket
once at startup; the executor's token-budget admission (paper Eq. 1)
bounds in-flight HBM while freed cache slots are refilled mid-decode
(slot-refill continuous batching, DESIGN.md §8); engine failures re-queue
idempotent block prompts.  With ``--replicas N`` each replica is a full
engine (own page pool, prefix cache, executor; Eq. (1) admission stays
per replica) on its own worker thread — pin replicas to distinct
accelerators (or, on CPU, force multiple host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and the router
keeps every left block's prompts on one replica so cache hit rates stay
at single-engine levels; a dead replica's work fails over to survivors.
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core import adaptive_join, block_join, tuple_join
from repro.core.oracle import OracleLLM
from repro.data import all_scenarios
from repro.data.tokenizer import ByteTokenizer
from repro.obs import TraceRecorder, write_chrome_trace
from repro.serve import Cluster, ClusterClient, Engine, EngineClient, make_router
from repro.models import init_params, model_specs


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself).  Otherwise the cache sits at one fixed path in the
    checkout, ``<repo>/.jax_cache`` (gitignored): the path is part of
    the cache key, so it must not move between runs.  Every program is
    cached, however fast it compiled: JAX's default skips those under a
    second, most of an engine's.  Returns the directory in use.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def build_server(
    cfg, params, tok, oracle: OracleLLM, *,
    max_seq: int, slots: int, replicas: int = 1, tp: int = 1,
    router: str = "affinity", trace=None,
) -> Tuple[Any, Optional[Cluster]]:
    """The serving stack joins talk to: one :class:`Engine` (tensor
    -parallel over the first ``tp`` devices when ``tp > 1``) behind an
    :class:`EngineClient`, or ``replicas`` engines behind the router in a
    :class:`Cluster` and a :class:`ClusterClient`.  Engines keep their
    defaults (paged KV, radix prefix cache).  Returns ``(client,
    cluster)``; ``cluster`` is None for a single engine."""
    if replicas > 1:
        cluster = Cluster.replicate(
            cfg, params, tok, replicas, router=make_router(router),
            tp=tp, max_seq=max_seq, slots=slots, trace=trace)
        return ClusterClient(cluster, oracle=oracle), cluster
    mesh = None
    if tp > 1:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(tp=tp)
    engine = Engine(cfg, params, tok, max_seq=max_seq, slots=slots, mesh=mesh)
    return EngineClient(engine, oracle=oracle, trace=trace), None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scenario", default="ads",
                    choices=["ads", "emails", "reviews"])
    ap.add_argument("--operator", default="adaptive",
                    choices=["tuple", "block", "adaptive"])
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--replicas", type=int,
                    default=int(os.environ.get("REPRO_REPLICAS", "1")),
                    help="data-parallel engine replicas (DESIGN.md §12; "
                         "default from REPRO_REPLICAS, 1 = single engine)")
    ap.add_argument("--router", default="affinity",
                    choices=["affinity", "round_robin"],
                    help="cluster routing policy (replicas > 1)")
    ap.add_argument("--tp", type=int,
                    default=int(os.environ.get("REPRO_TP", "1")),
                    help="tensor-parallel degree per replica (DESIGN.md "
                         "§15; default from REPRO_TP, 1 = no mesh)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a request-lifecycle trace and write it "
                         "as Perfetto/Chrome trace_event JSON to PATH "
                         "(DESIGN.md §17; equivalent to REPRO_TRACE=1 "
                         "plus an export)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    # published widths serve in bf16; the CPU smoke presets stay float32
    dtype = jnp.float32 if args.smoke else jnp.bfloat16
    params = init_params(model_specs(cfg), jax.random.PRNGKey(0), dtype)
    tok = ByteTokenizer(cfg.vocab_size)

    sc = {s.name: s for s in all_scenarios()}[args.scenario]
    oracle = OracleLLM(sc.predicate, context_limit=args.max_seq)

    trace = TraceRecorder() if args.trace_out else None

    client, cluster = build_server(
        cfg, params, tok, oracle, max_seq=args.max_seq, slots=args.slots,
        replicas=args.replicas, tp=args.tp, router=args.router, trace=trace)

    try:
        if args.operator == "tuple":
            res = tuple_join(sc.r1, sc.r2, sc.condition, client)
        elif args.operator == "block":
            res = block_join(sc.r1, sc.r2, sc.condition, client, 4, 4)
        else:
            res = adaptive_join(sc.r1, sc.r2, sc.condition, client,
                                initial_estimate=1e-3)

        q = res.quality(sc.truth)
        backend = (f"{cfg.name} x{args.replicas} ({args.router})"
                   if cluster is not None else cfg.name)
        print(f"{args.operator} join on {sc.name} via {backend}: "
              f"calls={res.ledger.calls} tokens={res.ledger.usage.total_tokens} "
              f"P={q['precision']:.2f} R={q['recall']:.2f} F1={q['f1']:.2f} "
              f"wall={res.wall_time_s:.1f}s")
        if cluster is not None:
            cluster.drain()
            summ = cluster.summary()
            print(f"cluster: critical_path_passes={summ['critical_path_passes']} "
                  f"router={summ['router']} "
                  f"per_replica_calls="
                  f"{[r['ledger']['calls'] for r in summ['per_replica']]}")
        if trace is not None:
            n = write_chrome_trace(args.trace_out, trace)
            print(f"trace: {n} events -> {args.trace_out} "
                  f"(dropped={trace.dropped}; open in ui.perfetto.dev)")
    finally:
        if cluster is not None:
            cluster.shutdown()


if __name__ == "__main__":
    main()
