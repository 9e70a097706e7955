#!/usr/bin/env python3
"""Bring-up check: the granite-3-2b semantic-join server on a TPU.

    python3 chip_smoke.py              # one chip: the serving main path
    python3 chip_smoke.py --chips 4    # four chips: DP replicas and TP=4

The one-chip run hosts granite-3-2b at its published widths (40 layers,
d_model 2048, 32/8 heads, d_ff 8192, vocab 49155) in bf16 with seeded
random weights, built by the same ``build_server`` as
``python -m repro.launch.serve``: Engine (paged KV, radix prefix cache)
-> continuous-batching executor -> EngineClient -> the join operators,
teacher-forced by the scenario oracle.  Phases, in order:

  kernels         paged decode, spec verify, chunked prefill and top-k,
                  compiled for the chip (interpret=False) at granite
                  widths, each against its kernels/ref.py oracle
  block, adaptive block and adaptive joins on the ads scenario: F1 = 1.0
  scored_tuple    prefill-only tuple join on the same scenario: F1 = 1.0
  prefilter       EngineEmbedder + the top-k kernel, then scored
                  verification: exact on its candidate set
  logits_engine   engine prefill logits (fresh and prefix-hit paths)
                  against models.forward on the same bf16 weights
  logits_depth    the same in bf16 on engines at granite widths cut
                  to 4 and 8 layers: how the 40-layer gap grows with depth
  logits_f32      the same in float32 at "highest" precision, on an
                  engine at granite widths cut to 4 layers
  logits_devices  the smoke config's float32 logits on the chip against
                  the host CPU, both at "highest" matmul precision

Every serving phase fails the run on a retried step, an expired
deadline, unresolved or undecided pairs, or a dead replica.  With
``--chips 4`` only the multi-chip paths run: a 4-replica cluster (one
replica per chip) and one TP=4 engine, each against the one-chip
engine's block join in the same process.

Each phase prints one JSON line (wall, compile seconds, programs
compiled, HBM in use and peak).  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  A failed
phase is reported on its line and the later phases still run; the run
then exits 1 without the last line, and so does a run that finds no
TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "granite-3-2b"
SEED = 0         # random weights and kernel inputs
MAX_SEQ = 1024   # serve.py's default window; ads block prompts fit it
SLOTS = 8        # 5 GB of weights + a 8 x 1024-token pool (640 MiB)
PAGE = 16        # the engine's page size

#: Logits checks, as the relative L2 error ||a - b|| / ||b|| per row
#: over the vocabulary.  An unrelated row (garbled pages, wrong prompt)
#: reads about 1.4.
#: - bf16, engine vs forward on the same weights: the two programs
#:   round differently, and random-init layers amplify it with depth.
#:   At 40 layers a v5e read 0.091 fresh and 0.113 prefix-hit; the
#:   limit is about twice that, and the same at DEPTH_LAYERS.
#: - float32 at "highest" precision, granite widths cut to F32_LAYERS
#:   layers (a float32 copy of all 40 would not fit next to the bf16
#:   weights): rounding stays small there, so this is the tight check.
BF16_LOGITS_TOL = 0.25
DEPTH_LAYERS = (4, 8)
F32_LAYERS = 4
F32_LOGITS_TOL = 1e-3
DEVICE_LOGITS_TOL = 1e-4   # float32 "highest" on the chip vs the CPU
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)   # bf16, as tests/test_kernels.py
TOPK_TOL = 1e-2            # f32 similarities of unit vectors


class SmokeFailure(Exception):
    """A phase produced a wrong or degraded result."""


def need(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """XLA programs compiled (or loaded from the persistent cache) in
    this process, and the seconds spent on them."""

    def __init__(self):
        import jax

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"hbm_in_use_bytes": stats.get("bytes_in_use"),
            "hbm_peak_bytes": stats.get("peak_bytes_in_use")}


class Phases:
    """Runs the phases in order and prints one JSON line for each.  A
    phase that fails is reported (its line carries ``failed``, the
    traceback goes to stderr) and the later phases still run, so one
    chip run shows every fault; the run then exits 1."""

    def __init__(self, dev):
        self.dev = dev
        self.counter = CompileCounter()
        self.failed = []

    def run(self, name, fn) -> dict:
        c = self.counter
        p0, s0 = c.programs, c.seconds
        t0 = time.perf_counter()
        try:
            out = fn() or {}
        except Exception as e:
            traceback.print_exc()
            self.failed.append(name)
            out = {"failed": f"{type(e).__name__}: {e}"[:500]}
        # keys starting with "_" carry objects back to the caller, unprinted
        line = {"phase": name, "wall_s": time.perf_counter() - t0,
                "compile_s": c.seconds - s0, "programs": c.programs - p0,
                **{k: v for k, v in out.items() if not k.startswith("_")},
                **hbm(self.dev)}
        print(json.dumps(line), flush=True)
        return out


# ---------------------------------------------------------------------------
# health of the serving path
# ---------------------------------------------------------------------------


def check_healthy(client, cluster=None) -> dict:
    """No retried step, no expired deadline, no dead replica."""
    stats = cluster.stats() if cluster is not None else client.executor.stats
    need(stats.retries == 0, f"{stats.retries} engine steps were retried")
    need(stats.deadline_expired == 0,
         f"{stats.deadline_expired} requests passed their deadline")
    if cluster is not None:
        need(cluster.replicas_alive == len(cluster.engines),
             f"only {cluster.replicas_alive} of {len(cluster.engines)} "
             "replicas alive")
    return stats.snapshot()


def check_complete(res, name: str) -> None:
    for key in ("degraded", "unresolved", "undecided"):
        need(not res.meta.get(key), f"{name} join {key}: {res.meta.get(key)}")


def join_line(res, sc) -> dict:
    led = res.ledger
    return {"join_wall_s": res.wall_time_s, "f1": res.f1(sc.truth),
            "calls": led.calls, "prompt_tokens": led.prompt_tokens,
            "cached_prompt_tokens": led.cached_prompt_tokens,
            "completion_tokens": led.completion_tokens,
            "scored_tokens": led.scored_tokens}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def check_kernels() -> dict:
    """The four served kernels, compiled for the chip, at granite-3-2b's
    serving shapes against ``ref.py``: 32 query / 8 KV heads of 64, the
    engine's page pool and draft length, and the prefilter's top-k over
    10^3 x 10^4 d_model-wide embeddings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.chunked_prefill import chunked_prefill_attention
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.spec_verify_attention import spec_verify_attention
    from repro.kernels.topk_sim import topk_similarity

    B, H, KV, hd, page, spec_k = SLOTS, 32, 8, 64, PAGE, 8
    suffix, prefix = 256, 512
    topk_m, topk_n, dim, k = 1000, 10000, 2048, 8
    rng = np.random.default_rng(SEED)
    bf = jnp.bfloat16
    normal = lambda *s: jnp.asarray(rng.standard_normal(s), bf)
    n_slots = MAX_SEQ // page
    n_pages = B * n_slots + 1
    pool_k, pool_v = normal(n_pages, page, KV, hd), normal(n_pages, page, KV, hd)
    table = jnp.asarray(rng.permutation(n_pages)[: B * n_slots]
                        .reshape(B, n_slots), jnp.int32)
    out = {}

    def close(name, got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        out[f"{name}_max_abs_err"] = err
        need(np.all(np.isfinite(got)), f"{name}: non-finite output")
        need(np.allclose(got, want, **KERNEL_TOL),
             f"{name}: max abs error {err} vs ref.py")

    lens = jnp.asarray(rng.integers(1, MAX_SEQ - spec_k, B), jnp.int32)
    q = normal(B, 1, H, hd)
    close("paged_decode",
          jax.jit(lambda *a: paged_decode_attention(*a, interpret=False))(
              q, pool_k, pool_v, table, lens),
          ref.paged_decode_attention_ref(q, pool_k, pool_v, table, lens))

    q = normal(B, spec_k + 1, H, hd)
    close("spec_verify",
          jax.jit(lambda *a: spec_verify_attention(*a, interpret=False))(
              q, pool_k, pool_v, table, lens),
          ref.spec_verify_attention_ref(q, pool_k, pool_v, table, lens))

    q, ks, vs = (normal(B, suffix, H, hd), normal(B, suffix, KV, hd),
                 normal(B, suffix, KV, hd))
    kp, vp = normal(B, prefix, KV, hd), normal(B, prefix, KV, hd)
    plen = jnp.asarray(rng.integers(0, prefix + 1, B), jnp.int32)
    close("chunked_prefill",
          jax.jit(lambda *a: chunked_prefill_attention(
              *a, interpret=False))(q, ks, vs, kp, vp, plen),
          ref.chunked_prefill_attention_ref(q, ks, vs, kp, vp, plen))

    def unit(m):
        e = rng.standard_normal((m, dim)).astype(np.float32)
        return jnp.asarray(e / np.linalg.norm(e, axis=1, keepdims=True))

    e1, e2 = unit(topk_m), unit(topk_n)
    idx, sim = jax.jit(lambda a, b: topk_similarity(
        a, b, k, interpret=False))(e1, e2)
    with jax.default_matmul_precision("highest"):
        want_idx, want = ref.topk_sim_ref(e1, e2, k)
        full = jnp.einsum("md,nd->mn", e1, e2)
    idx, sim, want = np.asarray(idx), np.asarray(sim), np.asarray(want)
    picked = np.take_along_axis(np.asarray(full), idx, axis=1)
    # near-ties may order differently at another matmul precision, so
    # the kernel's picks are held to the true top-k similarities and
    # its reported similarities to its own picks
    err = float(max(np.max(np.abs(picked - want)),
                    np.max(np.abs(sim - picked))))
    out["topk_max_abs_err"] = err
    out["topk_index_agreement"] = float(np.mean(idx == np.asarray(want_idx)))
    need(err <= TOPK_TOL, f"topk: similarity error {err} vs ref.py")
    return out


def block_phase(client, sc, cluster=None) -> dict:
    from repro.core import block_join

    res = block_join(sc.r1, sc.r2, sc.condition, client, 4, 4)
    check_complete(res, "block")
    need(res.f1(sc.truth) == 1.0, f"block join F1 {res.f1(sc.truth)}")
    return {**join_line(res, sc), "stats": check_healthy(client, cluster),
            "_result": res}


def adaptive_phase(client, sc) -> dict:
    from repro.core import adaptive_join

    res = adaptive_join(sc.r1, sc.r2, sc.condition, client,
                        initial_estimate=1e-3)
    check_complete(res, "adaptive")
    need(res.f1(sc.truth) == 1.0, f"adaptive join F1 {res.f1(sc.truth)}")
    return {**join_line(res, sc), "rounds": res.meta["rounds"],
            "stats": check_healthy(client)}


def scored_tuple_phase(client, sc) -> dict:
    from repro.core import tuple_join

    steps = client.executor.stats.decode_steps
    res = tuple_join(sc.r1, sc.r2, sc.condition, client, scoring=True)
    check_complete(res, "scored tuple")
    need(res.f1(sc.truth) == 1.0, f"scored tuple join F1 {res.f1(sc.truth)}")
    need(client.executor.stats.decode_steps == steps,
         "the scored tuple join ran decode steps")
    need(res.ledger.scored_tokens > 0, "no tokens were scored")
    return {**join_line(res, sc), "stats": check_healthy(client)}


def prefilter_phase(client, sc, k: int = 4) -> dict:
    from repro.core import prefilter_join
    from repro.serve import EngineEmbedder

    emb = EngineEmbedder(client)
    res = prefilter_join(sc.r1, sc.r2, sc.condition, client, emb, k=k,
                         use_kernel=True)
    check_complete(res, "prefilter")
    cands = set(res.meta["candidate_pairs"])
    # random weights embed at random; verification is exact on what
    # the top-k kernel proposed
    need(res.pairs == sc.truth & cands,
         "prefilter join disagrees with the truth on its candidates")
    need(res.ledger.calls == 2 + len(cands), "prefilter ledger miscounted")
    return {**join_line(res, sc), "candidates": len(cands),
            "candidate_recall": len(sc.truth & cands) / len(sc.truth),
            "stats": check_healthy(client)}


def engine_logits_phase(engine, cfg, params, tol: float) -> dict:
    """Engine prefill logits vs ``models.forward`` on the same weights,
    for fresh prompts (paged prefill) and prompts that hit the prefix
    cache (chunked prefill over shared pages)."""
    import jax
    import numpy as np

    from repro.models import forward

    fresh = [f"Probe {i}: logits of a fresh prompt, row {i}, checked "
             "against a plain forward pass over the same weights."
             for i in range(2)]
    hits = [p + f" Then a suffix past the cached pages, variant {i}."
            for i, p in enumerate(fresh)]
    tok = engine.tokenizer
    out = {}
    rows, got = [], []
    for path, prompts in (("fresh", fresh), ("prefix_hit", hits)):
        (tables, _), logits, lens, cached = engine.prefill_rows(prompts)
        for t in tables:   # hand the rows' pages back, as score_rows does
            engine.pool.decref(t)
        hit = path == "prefix_hit"
        need(all(cached) if hit else not any(cached),
             f"{path} prompts had cached tokens {cached}")
        out[f"{path}_cached_tokens"] = int(sum(cached))
        got.extend(np.asarray(logits[: len(prompts)], np.float32))
        rows.extend(tok.encode(p) for p in prompts)
    L = -(-max(map(len, rows)) // PAGE) * PAGE
    toks = np.zeros((len(rows), L), np.int32)
    for r, ids in enumerate(rows):
        toks[r, : len(ids)] = ids
    ref_all = np.asarray(jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})[0])(
        params, toks), np.float32)
    want = [ref_all[r, len(ids) - 1] for r, ids in enumerate(rows)]
    need(all(np.all(np.isfinite(g)) for g in got), "non-finite engine logits")
    errs = [float(np.linalg.norm(g - w) / np.linalg.norm(w))
            for g, w in zip(got, want)]
    out["fresh_rel_err"] = max(errs[:2])
    out["prefix_hit_rel_err"] = max(errs[2:])
    out["argmax_agree"] = int(sum(int(np.argmax(g)) == int(np.argmax(w))
                                  for g, w in zip(got, want)))
    for path in ("fresh", "prefix_hit"):
        err = out[f"{path}_rel_err"]
        need(err <= tol, f"{path} prefill logits off by {err} > {tol}")
    return out


def cut_logits(tok, oracle, layers: int, dtype, tol: float) -> dict:
    """:func:`engine_logits_phase` on an engine built like the served
    one at granite widths and ``layers`` layers, with ``dtype`` weights;
    float32 runs at "highest" matmul precision."""
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.serve import build_server
    from repro.models import init_params, model_specs

    cfg = dataclasses.replace(get_config(ARCH), n_layers=layers)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(SEED), dtype)
    f32 = dtype == jnp.float32
    with (jax.default_matmul_precision("highest") if f32 else
          contextlib.nullcontext()):
        client, _ = build_server(cfg, params, tok, oracle, max_seq=MAX_SEQ,
                                 slots=SLOTS)
        return engine_logits_phase(client.engine, cfg, params, tol=tol)


def depth_logits_phase(tok, oracle) -> dict:
    """The bf16 engine-vs-forward gap at ``DEPTH_LAYERS`` layers."""
    import jax.numpy as jnp

    out = {}
    for layers in DEPTH_LAYERS:
        got = cut_logits(tok, oracle, layers, jnp.bfloat16, BF16_LOGITS_TOL)
        out.update({f"l{layers}_{k}": v for k, v in got.items()})
    return out


def device_logits_phase(dev, *, tol: float) -> dict:
    """The smoke config's float32 logits on ``dev`` vs the host CPU."""
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models import forward, init_params, model_specs

    cfg = get_smoke_config(ARCH)
    cpu = jax.devices("cpu")[0]
    params = init_params(model_specs(cfg), jax.random.PRNGKey(SEED))
    toks = np.random.default_rng(SEED).integers(
        1, cfg.vocab_size, (4, 64)).astype(np.int32)
    fwd = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})[0])
    with jax.default_matmul_precision("highest"):
        on = {d: np.asarray(fwd(jax.device_put(params, d),
                                jax.device_put(toks, d)))
              for d in (dev, cpu)}
    err = float(np.max(np.abs(on[dev] - on[cpu])) / np.max(np.abs(on[cpu])))
    need(err <= tol, f"{dev.platform} vs cpu logits off by {err} > {tol}")
    return {"rel_err": err}


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------


def same_join(res, ref, name: str) -> None:
    need(res.pairs == ref.pairs, f"{name}: pairs differ from one chip")
    for f in ("calls", "prompt_tokens", "completion_tokens"):
        need(getattr(res.ledger, f) == getattr(ref.ledger, f),
             f"{name}: {f} {getattr(res.ledger, f)} vs one chip "
             f"{getattr(ref.ledger, f)}")


def one_chip(ph, cfg, params, tok, sc, oracle) -> None:
    import jax.numpy as jnp

    from repro.launch.serve import build_server

    client, _ = build_server(cfg, params, tok, oracle, max_seq=MAX_SEQ,
                             slots=SLOTS)
    ph.run("kernels", check_kernels)
    ph.run("block", lambda: block_phase(client, sc))
    ph.run("adaptive", lambda: adaptive_phase(client, sc))
    ph.run("scored_tuple", lambda: scored_tuple_phase(client, sc))
    ph.run("prefilter", lambda: prefilter_phase(client, sc))
    ph.run("logits_engine", lambda: engine_logits_phase(
        client.engine, cfg, params, tol=BF16_LOGITS_TOL))
    ph.run("logits_depth", lambda: depth_logits_phase(tok, oracle))
    ph.run("logits_f32", lambda: cut_logits(
        tok, oracle, F32_LAYERS, jnp.float32, F32_LOGITS_TOL))
    ph.run("logits_devices", lambda: device_logits_phase(
        ph.dev, tol=DEVICE_LOGITS_TOL))


def four_chips(ph, cfg, params, tok, sc, oracle) -> None:
    import jax

    from repro.launch.serve import build_server

    devs = jax.devices()
    need(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    ref = ph.run("one_chip_block", lambda: block_phase(build_server(
        cfg, params, tok, oracle, max_seq=MAX_SEQ, slots=SLOTS)[0], sc)
    ).get("_result")

    def replicas():
        client, cluster = build_server(cfg, params, tok, oracle,
                                       max_seq=MAX_SEQ, slots=SLOTS,
                                       replicas=4)
        try:
            out = block_phase(client, sc, cluster)
            same_join(out["_result"], ref, "4 replicas")
            homes = []
            for i, eng in enumerate(cluster.engines):
                held = {d for leaf in jax.tree.leaves(eng.params)
                        for d in leaf.devices()}
                need(held == eng.pool.k.devices() == {devs[i]},
                     f"replica {i}: params on {held}, pool on "
                     f"{eng.pool.k.devices()}, expected {devs[i]}")
                homes.append(devs[i].id)
            calls = [r["ledger"]["calls"]
                     for r in cluster.summary()["per_replica"]]
            need(all(calls), f"a replica got no work: calls {calls}")
            return {**out, "replica_devices": homes,
                    "per_replica_calls": calls}
        finally:
            cluster.shutdown()

    def tensor_parallel():
        client, _ = build_server(cfg, params, tok, oracle, max_seq=MAX_SEQ,
                                 slots=SLOTS, tp=4)
        out = block_phase(client, sc)
        same_join(out["_result"], ref, "tp=4")
        pool_devs = client.engine.pool.k.devices()
        need(pool_devs == set(devs[:4]), f"tp=4 pool on {pool_devs}")
        return {**out, "pool_devices": sorted(d.id for d in pool_devs)}

    ph.run("replicas_4", replicas)
    ph.run("tp_4", tensor_parallel)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path (default); 4: only the "
                         "4-replica and TP=4 paths against one chip")
    args = ap.parse_args(argv)

    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from repro.launch.serve import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    ph = Phases(dev)

    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.oracle import OracleLLM
    from repro.data import ads_scenario
    from repro.data.tokenizer import ByteTokenizer
    from repro.models import init_params, model_specs

    cfg = get_config(ARCH)
    sc = ads_scenario()
    oracle = OracleLLM(sc.predicate, context_limit=MAX_SEQ)
    tok = ByteTokenizer(cfg.vocab_size)
    params = ph.run("setup_weights", lambda: {"_params": init_params(
        model_specs(cfg), jax.random.PRNGKey(SEED), jnp.bfloat16)}
    ).get("_params")
    if params is not None:
        if args.chips == 1:
            one_chip(ph, cfg, params, tok, sc, oracle)
        else:
            four_chips(ph, cfg, params, tok, sc, oracle)
    c = ph.counter
    print(json.dumps({"summary": True, "total_s": time.perf_counter() - t0,
                      "programs": c.programs, "compile_s": c.seconds,
                      "cache_hits": c.cache_hits, "cache_dir": cache_dir,
                      "failed": ph.failed, **hbm(dev)}), flush=True)
    if ph.failed:
        print(f"chip_smoke: FAILED: {', '.join(ph.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
