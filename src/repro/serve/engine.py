"""The serving engine: batched prefill + an incremental slot API for
continuous batching with KV caches.

The paper's block-join prompts run through *this* (via
:class:`repro.serve.client.EngineClient`) when an architecture is hosted:

* **Ragged batched prefill** — prompts right-padded to a bucket length,
  the batch to a row bucket (a power of two below ``slots``, or
  ``slots``); causality + per-row ``valid_len`` make padding exact (see
  model.prefill).
* **Slot-refill continuous batching** — the engine exposes an incremental
  slot API (:meth:`init_state` / :meth:`prefill_rows` / :meth:`insert_row`
  / :meth:`decode_active`) driven by
  :class:`repro.serve.executor.ContinuousBatchingExecutor`: each of the
  ``slots`` decode rows hosts one request; the moment a row finishes it is
  retired and a queued prompt is prefilled into the freed slot mid-decode —
  no barrier between "waves" (DESIGN.md §8).
* **Paged KV** (default for KV-only families, ``REPRO_PAGED_KV=0/1``) —
  all KV lives page-granular in **one shared refcounted page pool**
  (DESIGN.md §10): each slot owns a *page table* instead of a dense
  ``max_seq`` cache row, decode attention reads through the table
  (:mod:`repro.kernels.paged_decode_attention` / the XLA gather
  fallback) and appends new tokens into pages in place, and prefix-cache
  hits are **zero-copy** — the matched pages are refcount-shared into
  the new row's table, read-only, with copy-on-write guarding the (never
  shared in practice) partial tail page.  HBM is bounded by *live
  tokens* (plus sharing), not ``slots × max_seq`` over-reservation.
* **Per-row termination** — greedy sampling; per-row stop-string / EOS /
  ``max_tokens`` termination with O(1) incremental stop-string suffix
  matching (:class:`StopMatcher`) — stop strings are the ``Finished``
  sentinel mechanism of Algorithm 2.
* **Radix-tree KV prefix cache** — prompt token-ID prefixes are interned
  page-granular in :class:`repro.serve.prefix_cache.RadixPrefixCache`;
  ``prefill_rows`` looks up the longest cached prefix and
  **chunked-prefills only the uncached suffix**
  (:func:`repro.models.chunked_prefill`) — block-join prompts sharing
  their header + left block skip recomputing it (DESIGN.md §9).  On the
  dense path the hit is copied into the slot row; on the paged path it
  is shared by reference (§10).
* **Token accounting** — real tokenizer counts, the same interface the
  cost model prices (prompt vs completion tokens, split into cached
  vs computed prompt tokens).
* **Teacher-forcing mode** — ``expected`` answers can be fed so the full
  serving stack (prefill, cache writes, decode steps, stop handling, token
  accounting) is exercised end-to-end even with untrained demo weights; the
  engine still runs every forward pass and reports real token flows.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.llm_client import cancel_unfinished
from repro.models import chunked_prefill, decode_step, encode, prefill, verify_step
from repro.models.model import KV_ONLY_FAMILIES, cache_specs, model_specs
from repro.models.params import Spec, is_spec
from repro.models.quant import quantize_params, serving_param_shardings
from repro.obs.trace import NULL_TRACE
from repro.serve.prefix_cache import PagedKVPool, RadixPrefixCache
from repro.sharding.logical import use_mesh

_ID_BYTES = 4  # int32 token ids in the packed speculative context


def pack_ids(ids: Sequence[int]) -> bytearray:
    """Pack token ids into the byte buffer :func:`propose_draft` scans."""
    return bytearray(np.asarray(list(ids), np.int32).tobytes())


def pack_id(tok: int) -> bytes:
    """One token id, appended to a packed context per emitted token."""
    return int(tok).to_bytes(_ID_BYTES, "little", signed=True)


def propose_draft(ctx: bytes, k: int, *, max_ngram: int = 3,
                  min_ngram: int = 1) -> List[int]:
    """Reference-free n-gram drafting (prompt lookup, DESIGN.md §11).

    ``ctx`` is the packed (``pack_ids``) token-id stream of one slot:
    prompt + everything generated so far.  The longest suffix n-gram
    (``max_ngram`` down to ``min_ngram`` tokens) that re-occurs earlier
    in the stream selects a draft: the up-to-``k`` tokens that followed
    its most recent earlier occurrence.  The block join's answers are
    near-verbatim copies of prompt substrings (row ids, separators, the
    ``Finished`` sentinel), which is exactly what this finds.

    Host-side and model-free: the scan is ``bytes.rfind`` over the
    packed buffer (C speed), with an alignment check rejecting matches
    that straddle id boundaries.  A draft is only ever a *proposal* —
    verification accepts the longest greedy-matching prefix, so a bad
    draft costs wasted FLOPs, never a wrong token.
    """
    isz = _ID_BYTES
    L = len(ctx) // isz
    if k <= 0 or L < min_ngram + 1:
        return []
    buf = bytes(ctx)
    for n in range(min(max_ngram, L - 1), min_ngram - 1, -1):
        pat = buf[(L - n) * isz:]
        # an earlier occurrence must start at token <= L-n-1, i.e. end
        # by byte (L-1)*isz
        end = (L - 1) * isz
        pos = buf.rfind(pat, 0, end)
        while pos >= 0 and pos % isz:
            pos = buf.rfind(pat, 0, pos + n * isz - 1)
        if pos < 0:
            continue
        start = pos // isz + n
        stop = min(start + k, L)
        return [int(t) for t in
                np.frombuffer(buf[start * isz:stop * isz], np.int32)]
    return []


@dataclasses.dataclass
class GenResult:
    text: str
    prompt_tokens: int
    completion_tokens: int
    finish_reason: str  # "stop" | "length" | "eos"
    #: prompt tokens served from the radix prefix cache (never recomputed);
    #: always <= prompt_tokens, 0 when the cache is off or missed
    cached_prompt_tokens: int = 0
    #: speculative decoding (DESIGN.md §11): draft tokens proposed for /
    #: accepted by this request.  Accepted drafts are ordinary completion
    #: tokens (already counted there); rejected drafts cost only wasted
    #: verification FLOPs, never tokens — Eq. (1) budgets are untouched
    drafted_tokens: int = 0
    accepted_draft_tokens: int = 0
    #: prefill-only scoring (DESIGN.md §13): candidate-continuation tokens
    #: whose log-probs were read from prefill logits (subset of
    #: prompt_tokens; completion_tokens stays 0 for score requests)
    scored_tokens: int = 0
    #: total log-prob of the scored continuation (None for generation)
    score_logprob: Optional[float] = None


@dataclasses.dataclass
class ScoreRow:
    """One scored (prompt, continuation) pair from :meth:`Engine.score_rows`.

    ``logprob`` is the sum of per-token log-probs of the continuation under
    teacher forcing after the prompt — read from per-position prefill
    logits, zero decode steps.  ``cached_tokens`` of the sequence were
    served by the radix prefix cache instead of recomputed.
    """

    logprob: float
    token_logprobs: List[float]
    prompt_tokens: int
    cont_tokens: int
    cached_tokens: int


class StopMatcher:
    """Incremental ``text.rstrip().endswith(stop)`` in O(1) per token.

    The old decode loop re-decoded the *entire* completion every step to
    test the stop condition — O(n²) over a generation of n tokens.  This
    matcher keeps only the last ``len(stop)`` characters of the
    right-stripped text plus any still-trailing whitespace run, so each
    :meth:`push` costs O(|piece| + |stop|) regardless of how much text has
    been generated.

    Pieces are per-token decodes; both shipped tokenizers decode
    concatenatively, so the incremental stream equals the full decode
    (stop strings are ASCII — the ``Finished`` sentinel convention of
    DESIGN.md §8).
    """

    def __init__(self, stop: Optional[str]):
        self.stop = stop
        self._tail = ""     # last len(stop) chars of the rstripped text
        self._pending = ""  # trailing whitespace, not yet made interior

    def push(self, piece: str) -> bool:
        """Append one decoded token; return True iff the stop now matches."""
        if not self.stop:
            return False
        buf = self._tail + self._pending + piece
        stripped = buf.rstrip()
        # Only the last len(stop) chars of the whitespace run can ever be
        # reached by a future suffix window — truncating keeps push() O(1)
        # even through degenerate all-whitespace generations.
        self._pending = buf[len(stripped):][-len(self.stop):]
        self._tail = stripped[-len(self.stop):]
        return self._tail == self.stop


@dataclasses.dataclass
class DecodeState:
    """Device-side state of the ``slots``-wide continuous batch (dense
    KV layout).

    ``cache``  — batched KV/SSM cache tree (batch dim = engine.slots),
    allocated once at ``max_seq`` capacity; rows are overwritten in place
    as requests retire and new prompts are prefilled into freed slots.
    ``logits`` — (slots, vocab) next-token logits per row (from prefill for
    freshly inserted rows, from the last decode step otherwise).
    """

    cache: Any
    logits: jax.Array


@dataclasses.dataclass
class PagedDecodeState:
    """State of the ``slots``-wide continuous batch in paged-KV mode
    (DESIGN.md §10).

    There is **no per-slot cache row**: K/V live in the engine's shared
    page pool, and each slot carries only its page table (host-side list
    of pool page ids, in context order) and its valid length.
    ``table_np`` is the dense ``(slots, max_pages)`` mirror of
    ``tables`` that the decode/verify device calls consume — maintained
    *incrementally* (insert/release touch one row; append/CoW/rollback
    touch single cells), never rebuilt from the lists per decoded token.
    Cells past a row's pages hold the engine's dump page, so budget
    -padded window positions route their writes harmlessly.
    """

    logits: jax.Array          # (slots, vocab)
    lens: np.ndarray           # (slots,) int32 — valid context length
    tables: List[List[int]]    # per-slot pool page ids, context order
    table_np: np.ndarray       # (slots, max_pages) int32 mirror, dump-padded


def _slots_wide(out: Tuple[Any, jax.Array],
                slots: int) -> Tuple[Any, jax.Array]:
    """A generation prefill's ``(cache, logits)`` with the logits padded
    to ``slots`` rows: the decode batch's layout, whatever the row bucket
    the prefill ran at."""
    cache, logits = out
    return cache, jnp.pad(logits, ((0, slots - logits.shape[0]), (0, 0)))


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    # never silently clamp: a clamped bucket would truncate the prompt
    # downstream (the old behavior) — fail loudly instead
    raise ValueError(
        f"sequence of {n} tokens exceeds the largest prefill bucket "
        f"{buckets[-1]} — prompt longer than max_seq?"
    )


class Engine:
    #: request-lifecycle tracing (DESIGN.md §17) — class attributes so an
    #: untraced engine pays nothing per instance; an executor or cluster
    #: installs a live recorder via :meth:`set_trace` (which resolves
    #: through FaultyEngine's ``__getattr__`` delegation, so the chaos
    #: proxy needs no changes)
    trace = NULL_TRACE
    trace_pid = 0

    def set_trace(self, recorder, pid: int = 0) -> None:
        """Attach a :class:`~repro.obs.trace.TraceRecorder` for engine
        -level spans (radix lookups, page alloc/CoW, bucketed prefill)."""
        self.trace = recorder
        self.trace_pid = pid

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        tokenizer: Any,
        *,
        max_seq: int = 1024,
        slots: int = 8,
        prefill_buckets: Sequence[int] = (128, 256, 512, 1024),
        prefix_cache: Optional[bool] = None,
        prefix_page_size: Optional[int] = None,
        prefix_pool_pages: Optional[int] = None,
        paged: Optional[bool] = None,
        page_size: int = 16,
        pool_pages: Optional[int] = None,
        spec_decode: Optional[bool] = None,
        spec_k: int = 8,
        spec_ngram: Tuple[int, int] = (3, 1),
        mesh: Any = None,
        rules: Any = None,
        quant: Optional[bool] = None,
    ):
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_seq = max_seq
        self.slots = slots

        # Tensor parallelism + int8 residency (DESIGN.md §15).  ``mesh``
        # is this replica's serving mesh (make_serving_mesh over its
        # contiguous device slice); ``rules`` merge over the config's own
        # sharding_overrides (which merge over DEFAULT_RULES inside
        # use_mesh).  No mesh → the exact single-device engine as before.
        self.mesh = mesh
        merged_rules = dict(cfg.rules())
        if rules:
            merged_rules.update(rules)
        self.rules = merged_rules
        if quant is None:
            quant = os.environ.get("REPRO_QUANT", "0") == "1"
        self.quant = bool(quant)
        if self.quant:
            # idempotent: a cluster may pass an already-quantized tree
            params = quantize_params(params, model_specs(cfg))
        if mesh is not None:
            # Commit every weight to its TP-resident sharding up front.
            # The jitted entry points then see *committed* operands, so
            # GSPMD propagates from them plus the model code's shard()
            # constraints — no per-closure in_shardings needed, and the
            # serving mesh has no "data" axis so there are no FSDP
            # all-gathers on the prefill/decode path.
            params = jax.device_put(
                params,
                serving_param_shardings(params, model_specs(cfg), mesh,
                                        self.rules),
            )
        self.params = params

        # Self-speculative decoding (DESIGN.md §11): greedy-parity prompt
        # n-gram drafting + multi-token verification.  Off by default
        # (REPRO_SPEC_DECODE=0/1; the CI matrix crosses it with the paged
        # -KV legs) — it is a pure perf feature whose outputs are token
        # -identical by construction.  KV-only families only: SSM/hybrid
        # state advances irreversibly per token and cannot roll back.
        if spec_decode is None:
            spec_decode = os.environ.get("REPRO_SPEC_DECODE", "0") == "1"
        self.spec_decode = bool(spec_decode) and cfg.family in KV_ONLY_FAMILIES
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram

        # Paged KV (DESIGN.md §10): default-on for KV-only families,
        # overridable per engine or via REPRO_PAGED_KV=0/1 (the CI matrix
        # runs both).  SSM/hybrid state is not page-granular — dense rows.
        if paged is None:
            paged = os.environ.get("REPRO_PAGED_KV", "1") != "0"
        self.paged = bool(paged) and cfg.family in KV_ONLY_FAMILIES
        # ONE page size everywhere: the paged pool and the prefix cache
        # (dense engines may override the latter via prefix_page_size) —
        # cached-token accounting is only comparable across engines that
        # match at the same page granularity
        if self.paged and prefix_page_size not in (None, page_size):
            raise ValueError(
                "a paged engine has ONE page granularity: the prefix cache "
                f"shares the pool's page_size={page_size}; got "
                f"prefix_page_size={prefix_page_size}")
        self.page_size = (prefix_page_size if not self.paged
                          and prefix_page_size is not None else page_size)
        pg = self.page_size

        buckets = sorted({b for b in prefill_buckets if b <= max_seq} | {max_seq})
        if self.paged:
            # page-scatter needs page-aligned buckets
            buckets = sorted({min(-(-b // pg) * pg, -(-max_seq // pg) * pg)
                              for b in buckets})
        self.prefill_buckets = buckets
        # a refill's prefill batch is padded to a row bucket (the powers
        # of two below slots, then slots), not to slots: a one-row refill
        # computes one row
        self.row_buckets = sorted({1 << i for i in range(slots.bit_length())
                                   if 1 << i < slots} | {slots})
        #: sequence buckets whose row buckets are compiled (_rehearse)
        self._rehearsed: set = set()
        self._maxp = -(-max_seq // pg)  # page-table width per row

        # Radix-tree KV prefix cache (DESIGN.md §9): default-on for KV-only
        # families, overridable per engine or via REPRO_PREFIX_CACHE=0/1
        # (the CI matrix runs both).  SSM/hybrid families are gated off:
        # their states cannot be re-anchored mid-sequence.
        if prefix_cache is None:
            prefix_cache = os.environ.get("REPRO_PREFIX_CACHE", "1") != "0"
        self.prefix_cache: Optional[RadixPrefixCache] = None
        self.pool: Optional[PagedKVPool] = None
        self._dump = -1  # scratch page for inactive rows' decode writes
        #: high-water mark of *distinct* pages referenced by live decode
        #: rows (shared prefix pages count once — the zero-copy win); the
        #: required working set, as opposed to pool.peak_pages which also
        #: counts elastic (evictable) prefix-cache retention
        self._peak_live_pages = 0
        #: positions the prefill programs were launched over — row
        #: bucket × bucket, pad rows included (init_state's warm prefill,
        #: score batches and radix-hit launches, all slots rows, too),
        #: counted at each launch and never backed out (the compile
        #: rehearsal's launches are not counted); the executor books its
        #: deltas into ExecutorStats
        self.prefill_positions_run = 0

        if self.paged:
            # ONE pool backs live decode state and the prefix cache; +1
            # for the dump page.  Sized by pool_pages (benchmarks shrink
            # it to show the footprint win) or the dense-equivalent
            # capacity by default.
            n_pages = (pool_pages if pool_pages is not None
                       else prefix_pool_pages if prefix_pool_pages is not None
                       else slots * self._maxp)
            self.pool = PagedKVPool(n_pages + 1, pg)
            self._dump = self.pool.alloc(1)[0]  # pinned forever
            if prefix_cache and cfg.family in KV_ONLY_FAMILIES:
                self.prefix_cache = RadixPrefixCache(
                    self.pool.n_pages, pg, pool=self.pool)
        elif prefix_cache and cfg.family in KV_ONLY_FAMILIES:
            n_pages = (prefix_pool_pages if prefix_pool_pages is not None
                       else 2 * slots * max_seq // pg)
            self.prefix_cache = RadixPrefixCache(n_pages, pg)

        # page-aligned buckets for the gathered-prefix length
        self._prefix_buckets = sorted({
            b for b in [4 * pg, *self.prefill_buckets,
                        max_seq // pg * pg]
            if 0 < b <= max_seq and b % pg == 0
        }) or [max_seq]

        # generation prefills run at a row bucket and return slots-wide
        # logits (_slots_wide), so installing a row is one program
        self._prefill = self._mjit(
            lambda p, toks, vlen: _slots_wide(prefill(
                cfg, p, {"tokens": toks}, max_seq=self.max_seq, valid_len=vlen
            ), slots),
            name="engine_prefill",
        )
        # paged prefill: no max_seq padding — K/V come back bucket-length
        # and are page-scattered into the pool (shape-specialized per
        # bucket, exactly like the dense prefill)
        self._prefill_bucket = self._mjit(
            lambda p, toks, vlen: _slots_wide(prefill(
                cfg, p, {"tokens": toks}, max_seq=toks.shape[1], valid_len=vlen
            ), slots),
            name="engine_prefill_bucket",
        )
        self._chunked_prefill = self._mjit(
            lambda p, toks, vlen, kp, vp, plen: _slots_wide(chunked_prefill(
                cfg, p, {"tokens": toks}, max_seq=self.max_seq,
                valid_len=vlen, prefix_k=kp, prefix_v=vp, prefix_len=plen,
            ), slots),
            name="engine_chunked_prefill",
        )
        self._chunked_prefill_paged = self._mjit(
            lambda p, toks, vlen, kp, vp, plen: _slots_wide(chunked_prefill(
                cfg, p, {"tokens": toks}, max_seq=self.max_seq,
                valid_len=vlen, prefix_k=kp, prefix_v=vp, prefix_len=plen,
                paged=True,
            ), slots),
            name="engine_chunked_prefill_paged",
        )
        # scoring variants (DESIGN.md §13): identical passes that unembed
        # every position — score_rows reads teacher-forced continuation
        # log-probs straight out of the prefill, zero decode steps.  The
        # plain variant is bucket-length (score rows never join the decode
        # batch, so no max_seq padding) and serves dense, paged, and SSM
        # engines alike.
        self._prefill_bucket_all = self._mjit(
            lambda p, toks, vlen: prefill(
                cfg, p, {"tokens": toks}, max_seq=toks.shape[1],
                valid_len=vlen, all_logits=True,
            ),
            name="engine_prefill_bucket_all",
        )
        self._chunked_prefill_all = self._mjit(
            lambda p, toks, vlen, kp, vp, plen: chunked_prefill(
                cfg, p, {"tokens": toks}, max_seq=self.max_seq,
                valid_len=vlen, prefix_k=kp, prefix_v=vp, prefix_len=plen,
                all_logits=True,
            ),
            name="engine_chunked_prefill_all",
        )
        self._chunked_prefill_all_paged = self._mjit(
            lambda p, toks, vlen, kp, vp, plen: chunked_prefill(
                cfg, p, {"tokens": toks}, max_seq=self.max_seq,
                valid_len=vlen, prefix_k=kp, prefix_v=vp, prefix_len=plen,
                paged=True, all_logits=True,
            ),
            name="engine_chunked_prefill_all_paged",
        )
        # per-position log-prob gather: select each row's continuation
        # -predicting positions, log-softmax, take the target token ids
        self._score_gather = self._mjit(
            lambda lg, idx, tgt: jnp.take_along_axis(
                jax.nn.log_softmax(
                    jnp.take_along_axis(lg, idx[:, :, None], axis=1),
                    axis=-1),
                tgt[:, :, None], axis=2)[..., 0],
            name="engine_score_gather")
        # embedding surface (DESIGN.md §14): the same bucketed ragged
        # batch shape as prefill, but no KV cache and no unembed — the
        # backbone's final-norm hidden states come back mean-pooled per
        # row.  Shape-specialized per (slots, bucket) like every other
        # closure here.
        self._encode = self._mjit(
            lambda p, toks, vlen: encode(
                cfg, p, {"tokens": toks}, valid_len=vlen
            ),
            name="engine_encode",
        )
        self._decode = self._mjit(
            lambda p, cache, toks, act: decode_step(cfg, p, cache, toks,
                                                    active=act),
            name="engine_decode",
        )
        # paged decode donates the cache tree: the page pool (GiB-scale
        # at real configs) must be appended to in place, not copied per
        # token — the engine rebinds pool.k/v from the outputs
        self._decode_paged = self._mjit(
            lambda p, cache, toks, act: decode_step(cfg, p, cache, toks,
                                                    active=act),
            donate_argnums=(1,),
            name="engine_decode_paged",
        )
        # speculative verification (DESIGN.md §11): one model call scores
        # a spec_k+1-token window per slot; the paged variant donates the
        # pool exactly like _decode_paged
        self._verify = self._mjit(
            lambda p, cache, toks: verify_step(cfg, p, cache, toks),
            name="engine_verify")
        self._verify_paged = self._mjit(
            lambda p, cache, toks: verify_step(cfg, p, cache, toks),
            donate_argnums=(1,),
            name="engine_verify_paged",
        )
        # post-verify logits select: row r keeps the logits of its last
        # accepted window position (counts[r]-1)
        self._select_logits = self._mjit(
            lambda lg, sel: jnp.take_along_axis(
                lg, sel[:, None, None], axis=1)[:, 0],
            name="engine_select_logits")
        # Per-leaf batch axis of the cache tree, derived from the logical
        # axis names in cache_specs — k/v carry batch at axis 1, the hybrid
        # conv/ssm states at axis 2, "len" at axis 0.
        self._batch_axes = jax.tree.map(
            lambda s: s.axes.index("batch") if "batch" in s.axes else 0,
            cache_specs(cfg, slots, max_seq),
            is_leaf=is_spec,
        )
        self._insert = self._mjit(self._insert_impl, donate_argnums=(0, 1),
                                  name="engine_insert")
        self._insert_logits = self._mjit(
            lambda dst, src, row, slot: dst.at[slot].set(src[row]),
            donate_argnums=(0,),
            name="engine_insert_logits",
        )
        self._default_executor = None  # lazy, for the generate() facade

    # ------------------------------------------------------------------
    def _mjit(self, fn, *, name: str, **jit_kwargs):
        """``jax.jit`` of ``fn`` under the program name ``name`` + this
        replica's mesh context.

        ``name`` is what profiles show: ``jit_<name>`` on the TPU's
        ``XLA Modules`` line, ``PjitFunction(<name>)`` on the host — the
        engine's programs read ``engine_*`` instead of ``jit__lambda``.
        It changes the program's name only, not its code.

        Without a mesh this IS ``jax.jit`` — byte-for-byte the old
        engine.  With one, every call runs under ``use_mesh(self.mesh,
        self.rules)`` so (a) the model code's ``shard()`` constraints
        resolve against this replica's mesh at trace time and (b) the
        Pallas gates in the model blocks see ``mesh_active()`` and take
        the XLA fallbacks.  The context is thread-local, and cluster
        worker threads make the first (tracing) call — which is exactly
        why the wrapper re-enters per call instead of tracing eagerly
        here.  Weights were committed by ``device_put`` at load, so no
        explicit in/out shardings are needed: GSPMD propagates from
        committed operands (donated caches keep their layout).
        """
        named = functools.partial(fn)
        named.__name__ = named.__qualname__ = name
        jf = jax.jit(named, **jit_kwargs)
        if self.mesh is None:
            return jf
        mesh, rules = self.mesh, self.rules

        def call(*args):
            with use_mesh(mesh, rules):
                return jf(*args)

        return call

    # ------------------------------------------------------------------
    def count_tokens(self, text: str) -> int:
        return len(self.tokenizer.encode(text))

    def prefix_cache_stats(self) -> Optional[dict]:
        """Hit/miss/eviction counters of the radix prefix cache (or None)."""
        if self.prefix_cache is None:
            return None
        return self.prefix_cache.stats.summary()

    # ------------------------------------------------------------------
    # Paged-KV bookkeeping (DESIGN.md §10)
    # ------------------------------------------------------------------
    @property
    def total_kv_pages(self) -> int:
        """Pages available to requests (excludes the pinned dump page)."""
        return self.pool.n_pages - 1 if self.paged else 0

    def request_pages(self, prompt_tokens: int, max_tokens: int) -> int:
        """Worst-case page reservation of one request: every position the
        request can ever occupy (prompt + clamped completion), rounded up
        to whole pages.  Shared-prefix hits only reduce *actual*
        allocation — the reservation stays conservative so a mid-decode
        append can never find the pool empty (tree-only pages are always
        evictable)."""
        if not self.paged:
            return 0
        need = prompt_tokens + min(max_tokens, self.max_seq - prompt_tokens)
        return -(-need // self.page_size)

    def kv_stats(self) -> Optional[dict]:
        """Page-pool occupancy counters (None on the dense engine)."""
        if not self.paged:
            return None
        return {
            "page_size": self.page_size,
            "pool_pages": self.total_kv_pages,
            "pages_in_use": self.pool.allocated_pages - 1,   # sans dump
            "peak_pages": self.pool.peak_pages - 1,          # sans dump
            "peak_tokens": (self.pool.peak_pages - 1) * self.page_size,
            # the required working set: live rows only, sharing deduped
            "peak_live_pages": self._peak_live_pages,
            "peak_live_tokens": self._peak_live_pages * self.page_size,
        }

    def _note_live_pages(self, state: Any) -> None:
        live = set()
        for t in state.tables:
            live.update(t)
        self._peak_live_pages = max(self._peak_live_pages, len(live))

    def _alloc_pages(self, n: int) -> List[int]:
        """Allocate ``n`` exclusive pages, evicting unreferenced prefix
        -cache leaves under pressure.  Raises when the pool genuinely
        cannot serve (executor admission makes this unreachable)."""
        if n == 0:
            return []
        pages = self.pool.alloc(n)
        evicted = 0
        while pages is None:
            if self.prefix_cache is None or not self.prefix_cache._evict_one():
                raise RuntimeError(
                    f"KV page pool exhausted: need {n} pages, "
                    f"{self.pool.free_pages} free and nothing evictable"
                )
            evicted += 1
            pages = self.pool.alloc(n)
        if self.trace:
            self.trace.instant("page_alloc", "engine", pid=self.trace_pid,
                               pages=n, evicted=evicted,
                               free=int(self.pool.free_pages))
        return pages

    def _cow_page(self, page: int) -> int:
        """Copy-on-write a shared page into a fresh exclusive one."""
        new = self.pool.copy_page(page)
        while new is None:
            if self.prefix_cache is None or not self.prefix_cache._evict_one():
                raise RuntimeError("KV page pool exhausted during copy-on-write")
            new = self.pool.copy_page(page)
        if self.trace:
            self.trace.instant("cow", "engine", pid=self.trace_pid,
                               page=int(page), new=int(new))
        return new

    def release_slot(self, state: Any, slot: int) -> None:
        """Drop a retired slot's page references (paged mode; dense rows
        are simply overwritten on the next refill)."""
        if not self.paged or state is None:
            return
        if state.tables[slot]:
            self.pool.decref(state.tables[slot])
        state.tables[slot] = []
        state.lens[slot] = 0
        state.table_np[slot, :] = self._dump

    def release_state(self, state: Any) -> None:
        """Release every slot of a decode state about to be dropped."""
        if not self.paged or state is None:
            return
        for slot in range(self.slots):
            self.release_slot(state, slot)

    # ------------------------------------------------------------------
    # Incremental slot API (driven by the executor — DESIGN.md §8)
    # ------------------------------------------------------------------
    def init_state(self):
        """Allocate the ``slots``-wide decode state.

        Dense: run the real (jitted) prefill on an all-pad batch of
        ``slots`` rows — a cache with exactly the dtypes/shapes later row
        inserts will scatter into, sharing its compilation with every
        later ``slots``-row refill prefill at the first bucket.
        Paged: no cache rows exist at all — just empty page tables and a
        zero logits buffer (DESIGN.md §10).
        """
        if self.paged:
            return PagedDecodeState(
                logits=jnp.zeros((self.slots, self.cfg.padded_vocab),
                                 jnp.float32),
                lens=np.zeros(self.slots, np.int32),
                tables=[[] for _ in range(self.slots)],
                table_np=np.full((self.slots, self._maxp), self._dump,
                                 np.int32),
            )
        B, L = self.slots, self.prefill_buckets[0]
        toks = jnp.zeros((B, L), jnp.int32)
        vlen = jnp.ones((B,), jnp.int32)
        cache, logits = self._prefill(self.params, toks, vlen)
        self.prefill_positions_run += B * L
        return DecodeState(cache=cache, logits=logits)

    def prefill_rows(
        self, prompts: Sequence[str]
    ) -> Tuple[Any, jax.Array, List[int], List[int]]:
        """Prefill up to ``slots`` prompts as one ragged batch.

        The batch is padded to a row bucket (``row_buckets``: the
        smallest power of two, or ``slots``, that holds the prompts), so
        a one-row refill computes one row; a batch with a radix hit keeps
        ``slots`` rows.  The first launch of whole prompts at a bucket
        compiles every row bucket of it (:meth:`_rehearse`): later such
        refills of any row count compile nothing.  Returns ``(cache,
        logits, prompt_lens, cached_lens)``; row ``r`` of the cache and
        of the slots-wide logits belongs to ``prompts[r]`` and is meant
        to be scattered into a free slot with :meth:`insert_row`;
        ``cached_lens[r]`` prompt tokens were served from the prefix
        cache instead of being computed.

        With the prefix cache on, each prompt's token IDs are looked up in
        the radix tree first; the longest page-aligned cached prefix
        (capped at ``len - 1`` so at least one token is computed — its
        logits seed decoding) skips the prefill compute and only the
        uncached suffix runs through :func:`repro.models.chunked_prefill`.
        Dense engines *gather* the matched pages into the slot row and
        copy-intern new pages afterwards (§9); paged engines share the
        matched pages by reference into the row's page table and intern
        the row's own pages zero-copy (§10).
        """
        if not 0 < len(prompts) <= self.slots:
            raise ValueError(f"prefill_rows takes 1..{self.slots} prompts")
        ids = [self.tokenizer.encode(p) for p in prompts]
        lens = [len(seq) for seq in ids]
        if max(lens) > self.max_seq - 1:
            raise ValueError(
                f"prompt of {max(lens)} tokens exceeds engine max_seq {self.max_seq}"
            )
        with self.trace.span("engine.prefill", "engine", pid=self.trace_pid,
                             rows=len(prompts)) as sp:
            if self.paged:
                out = self._prefill_rows_paged(ids, lens)
            else:
                out = self._prefill_rows_dense(ids, lens)
            if sp is not None:
                sp["row_bucket"] = self._row_bucket(len(ids), any(out[3]))
                sp["bucket"] = int(_bucket(max(lens), self.prefill_buckets))
                sp["cached"] = int(sum(out[3]))
        return out

    def score_rows(
        self, pairs: Sequence[Tuple[str, str]]
    ) -> List[ScoreRow]:
        """Score up to ``slots`` (prompt, continuation) pairs in ONE
        prefill pass with zero decode steps (DESIGN.md §13).

        Each row teacher-forces ``prompt + continuation`` through prefill
        with per-position logits: the logit at position ``i`` predicts
        token ``i + 1``, so the continuation's log-prob is read directly
        — no decode step, no sampling, no decode slot.

        The full serving machinery is reused: the radix prefix cache
        serves any cached prefix (capped at ``len(prompt_ids) - 1`` so
        the position predicting the first continuation token is always
        computed), the uncached suffix runs through chunked prefill over
        the gathered prefix, and on the paged engine the rows' pages are
        allocated/deduped/interned exactly like a generation prefill —
        then **released immediately** after the gather: a score request
        never holds pages beyond its own prefill batch (the radix tree
        keeps interned pages elastically, evictable under pressure).
        """
        if not 0 < len(pairs) <= self.slots:
            raise ValueError(f"score_rows takes 1..{self.slots} pairs")
        prompt_ids = [self.tokenizer.encode(p) for p, _ in pairs]
        cont_ids = [self.tokenizer.encode(c, bos=False) for _, c in pairs]
        if any(not ci for ci in cont_ids):
            raise ValueError("cannot score an empty continuation")
        seqs = [p + c for p, c in zip(prompt_ids, cont_ids)]
        lens = [len(s) for s in seqs]
        if max(lens) > self.max_seq:
            raise ValueError(
                f"prompt+continuation of {max(lens)} tokens exceeds "
                f"engine max_seq {self.max_seq}")
        limits = [len(p) - 1 for p in prompt_ids]
        with self.trace.span("engine.score", "engine", pid=self.trace_pid,
                             rows=len(pairs)) as sp:
            if self.paged:
                cache, logits, _, cached = self._prefill_rows_paged(
                    seqs, lens, limits=limits, all_logits=True)
            else:
                cache, logits, _, cached = self._prefill_rows_dense(
                    seqs, lens, limits=limits, all_logits=True)
            # logits: (slots, L, vocab) over each row's *computed*
            # suffix — continuation token i lives at suffix-relative
            # position len(prompt_ids) - 1 + i - cached[r]
            M = max(len(ci) for ci in cont_ids)
            idx = np.zeros((self.slots, M), np.int32)
            tgt = np.zeros((self.slots, M), np.int32)
            for r, (pi, ci) in enumerate(zip(prompt_ids, cont_ids)):
                base = len(pi) - 1 - cached[r]
                for i, t in enumerate(ci):
                    idx[r, i] = base + i
                    tgt[r, i] = t
            lp = np.asarray(self._score_gather(
                logits, jnp.asarray(idx), jnp.asarray(tgt)))
            rows = []
            for r, (pi, ci) in enumerate(zip(prompt_ids, cont_ids)):
                token_lps = [float(lp[r, i]) for i in range(len(ci))]
                rows.append(ScoreRow(
                    logprob=float(sum(token_lps)), token_logprobs=token_lps,
                    prompt_tokens=len(pi), cont_tokens=len(ci),
                    cached_tokens=cached[r]))
            if self.paged:
                # release immediately: score rows never own pages past
                # their batch — only the radix tree's own (evictable)
                # refs remain
                tables, _ = cache
                for t in tables:
                    if t:
                        self.pool.decref(t)
            if sp is not None:
                sp["cached"] = int(sum(cached))
        return rows

    def embed_rows(
        self, texts: Sequence[str]
    ) -> Tuple[np.ndarray, List[int]]:
        """Embed up to ``slots`` texts in ONE bucketed encode pass.

        Each text runs the full backbone as a ragged right-padded row
        (same bucketing as prefill); the fp32 mean-pooled final-norm
        hidden states come back as a ``(len(texts), d_model)`` array
        together with each row's prompt-token count — the serving tier's
        embedding surface (DESIGN.md §14), consumed by
        :class:`repro.serve.client.EngineEmbedder`.

        No KV cache or decode slot is touched: embeddings never join the
        decode batch, so the pass is cache-free and releases nothing.
        The batch is padded to ``slots`` rows so the jitted encode
        compiles once per prefill bucket.
        """
        if not 0 < len(texts) <= self.slots:
            raise ValueError(f"embed_rows takes 1..{self.slots} texts")
        ids = [self.tokenizer.encode(t) for t in texts]
        lens = [len(i) for i in ids]
        if max(lens) > self.max_seq:
            raise ValueError(
                f"text of {max(lens)} tokens exceeds engine max_seq "
                f"{self.max_seq}")
        L = _bucket(max(lens), self.prefill_buckets)
        with self.trace.span("engine.embed", "engine", pid=self.trace_pid,
                             rows=len(texts), bucket=int(L)):
            toks = np.zeros((self.slots, L), np.int32)
            vlen = np.zeros((self.slots,), np.int32)
            for r, seq in enumerate(ids):
                toks[r, :len(seq)] = seq
                vlen[r] = len(seq)
            vecs = np.asarray(self._encode(
                self.params, jnp.asarray(toks), jnp.asarray(vlen)))
        return vecs[:len(texts)], lens

    def _prefill_rows_dense(self, ids: List[List[int]], lens: List[int],
                            limits: Optional[List[int]] = None,
                            all_logits: bool = False):
        pc = self.prefix_cache
        matches = []
        cached = [0] * len(ids)
        if pc is not None and pc.pool.bound:
            # cap at len-1 (decode: the last token's logits seed the decode
            # loop) or at the caller's limit (scoring: prompt_len-1, so the
            # position predicting the first continuation token is computed)
            caps = limits or [len(seq) - 1 for seq in ids]
            matches = [pc.match(seq, limit=cap)
                       for seq, cap in zip(ids, caps)]
            cached = [m.length for m in matches]
            if self.trace:
                self.trace.instant(
                    "radix_lookup", "engine", pid=self.trace_pid,
                    rows=len(ids), hit_tokens=int(sum(cached)),
                    total_tokens=int(sum(lens)))

        try:
            cache, logits = self._launch(ids, matches, cached, all_logits)
            if pc is not None:
                if not pc.pool.bound:
                    pc.pool.bind(cache["k"], cache["v"])
                for r, seq in enumerate(ids):
                    pc.insert(
                        seq,
                        lambda start, stop, r=r: cache["k"][:, r, start:stop],
                        lambda start, stop, r=r: cache["v"][:, r, start:stop],
                    )
        finally:
            # locks held through gather AND insert: insert's eviction
            # pressure must never free the pages a match is using
            for m in matches:
                m.release()
        return cache, logits, lens, cached

    def _row_bucket(self, n: int, hit: bool) -> int:
        """The rows a generation launch of ``n`` prompts runs at: the
        smallest row bucket that holds them, or ``slots`` over a radix
        hit, whose prefix bucket follows the cache's contents — row
        buckets there would multiply the programs it compiles on
        demand."""
        return self.slots if hit else _bucket(n, self.row_buckets)

    def _launch(self, ids: List[List[int]], matches: List[Any],
                cached: List[int], all_logits: bool = False):
        """The batch's one prefill launch: whole prompts at a row bucket,
        or with a radix hit the uncached suffixes by chunked prefill over
        the gathered cached pages (:meth:`_row_bucket`); scoring
        launches (``all_logits``) keep ``slots`` rows.  Counted in
        ``prefill_positions_run``.  The first generation launch of whole
        prompts at a bucket first compiles the other row buckets
        (:meth:`_rehearse`)."""
        seqs = [seq[c:] for seq, c in zip(ids, cached)]
        L = _bucket(max(map(len, seqs)), self.prefill_buckets)
        P = (_bucket(max(cached), self._prefix_buckets) if any(cached)
             else None)
        rows = (self.slots if all_logits
                else self._row_bucket(len(ids), P is not None))
        if not all_logits and P is None and L not in self._rehearsed:
            self._rehearsed.add(L)
            self._rehearse([r for r in self.row_buckets if r != rows], L)
        self.prefill_positions_run += rows * L
        return self._run(seqs, matches if P is not None else [], rows, L, P,
                         all_logits)

    def _run(self, seqs: List[List[int]], matches: List[Any], rows: int,
             L: int, P: Optional[int], all_logits: bool):
        """Run the prefill program over ``rows`` rows at bucket ``L``:
        ``seqs`` fill the first rows, the rest are pad rows of one dummy
        token.  With a prefix bucket ``P``, row ``r``'s cached prefix
        (``matches[r]``'s pages) is gathered and ``seqs[r]`` is its
        suffix: dense keeps the returned contiguous rows (prefix copied
        in), paged takes the suffix-only K/V and page-scatters it (the
        gathered prefix is a transient activation input — the suffix must
        attend to it — never per-row storage)."""
        toks = np.zeros((rows, L), np.int32)
        vlen = np.ones((rows,), np.int32)  # pad rows: 1 dummy
        for r, seq in enumerate(seqs):
            toks[r, : len(seq)] = seq
            vlen[r] = len(seq)
        toks, vlen = jnp.asarray(toks), jnp.asarray(vlen)
        if P is None:
            if all_logits:
                fn = self._prefill_bucket_all
            else:
                fn = self._prefill_bucket if self.paged else self._prefill
            return fn(self.params, toks, vlen)
        pc = self.prefix_cache
        page_ids = np.zeros((rows, P // pc.page_size), np.int32)
        plen = np.zeros((rows,), np.int32)
        for r, m in enumerate(matches):
            plen[r] = m.length
            page_ids[r, : len(m.pages)] = m.pages
        kp, vp = pc.pool.gather(page_ids)
        if self.paged:
            fn = (self._chunked_prefill_all_paged if all_logits
                  else self._chunked_prefill_paged)
        else:
            fn = (self._chunked_prefill_all if all_logits
                  else self._chunked_prefill)
        return fn(self.params, toks, vlen, kp, vp, jnp.asarray(plen))

    def _rehearse(self, row_buckets: List[int], L: int) -> None:
        """Compile rehearsal: launch pad rows at each of ``row_buckets``
        × ``L`` and compile what a refill runs after the prefill — the
        page scatter, run to the dump page, or the dense row insert,
        compiled ahead of time for the decode state's shapes (no second
        ``slots`` × ``max_seq`` cache; on a mesh it compiles on demand)
        — so that no refill of whole prompts at this bucket compiles.  Runs before the real launch,
        with nothing of it alive, and waits for each launch before the
        next, so no two launches' buffers are alive at once.  Not
        counted in ``prefill_positions_run``."""
        if not row_buckets:
            return
        aot = not self.paged and self.mesh is None
        if aot:
            # the decode state's shapes: init_state's all-pad prefill
            i32 = jax.ShapeDtypeStruct((), jnp.int32)
            dst = jax.eval_shape(
                self._prefill, self.params,
                jax.ShapeDtypeStruct((self.slots, self.prefill_buckets[0]),
                                     jnp.int32),
                jax.ShapeDtypeStruct((self.slots,), jnp.int32))
        for rows in row_buckets:
            cache, logits = self._run([], [], rows, L, None, False)
            done = cache
            if self.paged:
                if not self.pool.bound:
                    self.pool.bind(cache["k"], cache["v"])
                self._scatter_rows(cache, [])
                done = (self.pool.k, self.pool.v)
            elif aot:
                # the state is placed as the prefill's outputs are: a
                # committed placement is part of the compiled program
                like = jax.tree.map(
                    lambda d, x: jax.ShapeDtypeStruct(
                        d.shape, d.dtype,
                        sharding=x.sharding if x.committed else None),
                    dst, (cache, logits))
                self._insert.lower(*like, cache, logits, i32, i32).compile()
            jax.block_until_ready(done)
            del cache, logits, done

    # ---------------------------- paged path --------------------------
    def _prefill_rows_paged(self, ids: List[List[int]], lens: List[int],
                            limits: Optional[List[int]] = None,
                            all_logits: bool = False):
        """Prefill into freshly allocated pool pages; share matched
        prefixes by reference (zero-copy, DESIGN.md §10).

        Per row: the matched prefix (page-aligned, capped at ``len-1``)
        is *referenced* into the row's page table (incref — the payload
        never moves); the suffix is computed via chunked prefill and
        page-scattered into newly allocated exclusive pages; finally the
        row's own full pages are interned back into the radix tree by
        reference, so the next prompt sharing the prefix pays nothing.

        **In-batch dedup**: rows of one refill batch routinely share a
        page-aligned prefix that is not in the tree yet (a cold left
        block admitted across several slots at once).  Such rows map the
        common full pages to the *same* freshly allocated pages — keyed
        by the entire token prefix up to the page, since KV content
        depends on all preceding tokens — and the duplicate rows'
        scatter chunks are routed to the dump page.  Computation is
        unchanged (each row still prefills its copy, exactly like the
        dense engine — accounting parity); only the *storage* is
        deduplicated, so a cold burst of one left block costs one copy
        of the shared prefix, not ``slots`` copies.
        """
        pg = self.page_size
        pc = self.prefix_cache
        matches: List[Any] = [None] * len(ids)
        cached = [0] * len(ids)
        if pc is not None and self.pool.bound:
            caps = limits or [len(seq) - 1 for seq in ids]
            matches = [pc.match(seq, limit=cap)
                       for seq, cap in zip(ids, caps)]
            cached = [m.length for m in matches]
            if self.trace:
                self.trace.instant(
                    "radix_lookup", "engine", pid=self.trace_pid,
                    rows=len(ids), hit_tokens=int(sum(cached)),
                    total_tokens=int(sum(lens)))

        row_own: List[List[int]] = []     # pages this row allocated (writer)
        row_reuse: List[List[int]] = []   # in-batch deduped pages, in order
        chunks: List[List[Optional[int]]] = []  # scatter target per chunk
        refs_taken: List[int] = []        # incref'd pages, for error backout
        providers: dict = {}              # full-prefix tuple → page id
        try:
            for r, seq in enumerate(ids):
                own, reuse, plan = [], [], []
                # registered before filling: a mid-row allocation failure
                # must still back these pages out in the except handler
                row_own.append(own)
                row_reuse.append(reuse)
                chunks.append(plan)
                # dedup keys chain incrementally: (previous page id,
                # this page's tokens) identifies the full prefix — page
                # content depends on all preceding tokens, and within
                # one batch a page id maps to exactly one token prefix —
                # at O(page) per key instead of O(L) full-prefix tuples
                start = cached[r] // pg
                parent = matches[r].pages[start - 1] if start else -1
                for j in range(start, len(seq) // pg):
                    key = (parent, tuple(seq[j * pg : (j + 1) * pg]))
                    page = providers.get(key)
                    if page is None:
                        page = self._alloc_pages(1)[0]
                        providers[key] = page
                        own.append(page)
                        plan.append(page)
                    else:
                        reuse.append(page)
                        plan.append(None)  # duplicate chunk → dump
                    parent = page
                if len(seq) % pg:  # partial tail page: always exclusive
                    page = self._alloc_pages(1)[0]
                    own.append(page)
                    plan.append(page)
            cache, logits = self._launch(ids, matches, cached, all_logits)
            if not self.pool.bound:
                self.pool.bind(cache["k"], cache["v"])
            self._scatter_rows(cache, chunks)
            # references are taken only after the single scatter write, so
            # a page is never written while shared:
            # (1) the rows' refs on in-batch deduped pages,
            for reuse in row_reuse:
                self.pool.incref(reuse)
                refs_taken.extend(reuse)
            # (2) the rows' refs on tree-matched pages — while the match
            # lock still pins them against eviction
            shared_taken: List[List[int]] = []
            for r, m in enumerate(matches):
                shared = list(m.pages[: cached[r] // pg]) if m else []
                self.pool.incref(shared)
                refs_taken.extend(shared)
                shared_taken.append(shared)
            tables = []
            for r in range(len(ids)):
                reuse_iter = iter(row_reuse[r])
                body = [p if p is not None else next(reuse_iter)
                        for p in chunks[r]]
                tables.append(shared_taken[r] + body)
            if pc is not None:
                for r, seq in enumerate(ids):
                    pc.insert_refs(seq, tables[r][: len(seq) // pg])
        except Exception:
            for pages in row_own:
                self.pool.decref(pages)
            self.pool.decref(refs_taken)
            raise
        finally:
            for m in matches:
                if m is not None:
                    m.release()
        return (tables, list(lens)), logits, lens, cached

    def _scatter_rows(self, cache: Any,
                      chunks: List[List[Optional[int]]]) -> None:
        """Page-scatter prefilled K/V ``(layers, rows, L, KV, hd)`` into
        each row's target pages.  ``chunks[r][c]`` is the pool page for
        row ``r``'s ``c``-th computed page-chunk, or None for chunks
        whose page is written by another row of this batch (in-batch
        dedup); those — and pad rows — are routed to the dump page."""
        k, v = cache["k"], cache["v"]
        layers, B, L, KV, hd = k.shape
        npg = L // self.page_size
        ids = np.full(B * npg, self._dump, np.int32)
        for r, plan in enumerate(chunks):
            for c, page in enumerate(plan):
                if page is not None:
                    ids[r * npg + c] = page
        self.pool.write(
            ids,
            k.reshape(layers, B * npg, self.page_size, KV, hd),
            v.reshape(layers, B * npg, self.page_size, KV, hd),
        )

    # ------------------------------------------------------------------
    def insert_row(
        self, state: Any, cache: Any, logits: jax.Array,
        row: int, slot: int,
    ) -> None:
        """Install row ``row`` of a prefill result into ``slot`` in place.

        Dense: scatter the cache row + logits.  Paged: the slot takes
        ownership of the row's page table (the pages were allocated /
        refcounted by ``prefill_rows``); only logits move on device.
        """
        if self.paged:
            tables, lens = cache
            state.tables[slot] = tables[row]
            state.lens[slot] = lens[row]
            state.table_np[slot, :] = self._dump
            state.table_np[slot, : len(tables[row])] = tables[row]
            self._note_live_pages(state)
            state.logits = self._insert_logits(
                state.logits, logits, jnp.int32(row), jnp.int32(slot))
            return
        state.cache, state.logits = self._insert(
            state.cache, state.logits, cache, logits,
            jnp.int32(row), jnp.int32(slot),
        )

    def _insert_impl(self, dst_cache, dst_logits, src_cache, src_logits,
                     row, slot):
        def put(dst, src, axis):
            piece = jax.lax.dynamic_index_in_dim(src, row, axis, keepdims=True)
            return jax.lax.dynamic_update_slice_in_dim(
                dst, piece.astype(dst.dtype), slot, axis)

        new_cache = jax.tree.map(put, dst_cache, src_cache, self._batch_axes)
        new_logits = put(dst_logits, src_logits, 0)
        return new_cache, new_logits

    def decode_active(
        self, state: Any, tokens: np.ndarray, active: np.ndarray
    ) -> None:
        """One decode step over the batch; inactive rows are frozen.

        Dense: inactive rows keep a frozen ``len`` (their writes are
        overwritten on the next refill).  Paged: inactive rows' table is
        pointed at the dump page with ``len = 0`` — a retired slot can
        never scribble on a page already recycled to another request —
        and a fresh page is allocated host-side whenever an active row's
        next position crosses a page boundary (with a copy-on-write
        guard should the tail page ever be shared).  The device-side
        table/lens arguments come straight from the *incrementally*
        maintained ``state.table_np``/``state.lens`` (inactive slots were
        reset by :meth:`release_slot`): only slots whose tables actually
        changed this step (page append, CoW) touch the host arrays."""
        if not self.paged:
            state.cache, state.logits = self._decode(
                self.params, state.cache,
                jnp.asarray(tokens, jnp.int32)[:, None],
                jnp.asarray(active, bool),
            )
            return
        for s in np.nonzero(active)[0]:
            self._extend_tail(state, int(s), 1)
        self._note_live_pages(state)
        cache = self._device_table_args(state)
        new_cache, logits = self._decode_paged(
            self.params, cache,
            jnp.asarray(tokens, jnp.int32)[:, None],
            jnp.asarray(active, bool),
        )
        self.pool.k, self.pool.v = new_cache["k"], new_cache["v"]
        state.logits = logits
        state.lens[np.asarray(active, bool)] += 1

    # ------------------------------------------------------------------
    # Self-speculative decoding (DESIGN.md §11)
    # ------------------------------------------------------------------
    def propose(self, ctx: bytes, k: int) -> List[int]:
        """N-gram draft for one slot's packed token-id context."""
        max_n, min_n = self.spec_ngram
        return propose_draft(ctx, min(k, self.spec_k),
                             max_ngram=max_n, min_ngram=min_n)

    def _device_table_args(self, state: Any) -> dict:
        """Paged decode/verify cache arguments from the incremental host
        state.  ``lens``/``table_np`` are **copied** on handoff:
        ``jnp.asarray`` may alias numpy memory on CPU, and the host
        mutates these arrays (append, CoW, rollback, slot release) while
        the async dispatch is still reading — the copy is what makes the
        incremental mirror race-free."""
        return {
            "len": jnp.asarray(state.lens.copy()),
            "pages": jnp.asarray(state.table_np.copy()),
            "k": self.pool.k, "v": self.pool.v,
        }

    def _extend_tail(self, state: Any, s: int, n_tok: int) -> None:
        """Make slot ``s``'s pages cover the next ``n_tok`` write
        positions ``lens[s] .. lens[s]+n_tok-1``: copy-on-write the
        partial tail page if it is shared (page-aligned matching never
        produces one, but the invariant is enforced, not assumed) and
        allocate fresh pages across boundaries.  Updates ``tables[s]``
        and the ``table_np`` mirror cell-by-cell."""
        pg = self.page_size
        pos = int(state.lens[s])
        t = state.tables[s]
        if pos % pg and not self.pool.writable(t[pos // pg]):
            t[pos // pg] = self._cow_page(t[pos // pg])
            state.table_np[s, pos // pg] = t[pos // pg]
        need = -(-(pos + n_tok) // pg)  # pages covering [0, pos+n_tok)
        while len(t) < need:
            t.append(self._alloc_pages(1)[0])
            state.table_np[s, len(t) - 1] = t[-1]

    def verify_active(
        self, state: Any, tokens: np.ndarray, n_tokens: np.ndarray,
        active: np.ndarray,
    ) -> jax.Array:
        """Score each active row's speculative window in ONE model call.

        ``tokens`` (slots, spec_k+1): the greedy token plus the n-gram
        draft, budget-padded; ``n_tokens`` (slots,): the real window
        length per row (padded positions' writes land in masked garbage
        or are dropped).  Returns the (slots, spec_k+1, vocab) logits —
        ``logits[s, j]`` is the next-token distribution after row ``s``
        consumed window tokens ``0..j``.  Nothing is committed:
        :meth:`commit_spec` advances lengths by the *accepted* counts
        and rolls back speculative pages.
        """
        toks = jnp.asarray(tokens, jnp.int32)
        if not self.paged:
            state.cache, logits = self._verify(self.params, state.cache, toks)
            return logits
        for s in np.nonzero(active)[0]:
            self._extend_tail(state, int(s), int(n_tokens[s]))
        self._note_live_pages(state)
        cache = self._device_table_args(state)
        new_cache, logits = self._verify_paged(self.params, cache, toks)
        self.pool.k, self.pool.v = new_cache["k"], new_cache["v"]
        return logits

    def commit_spec(
        self, state: Any, logits: jax.Array, counts: np.ndarray,
        alive: np.ndarray,
    ) -> None:
        """Commit a verification's accepted prefixes (DESIGN.md §11).

        ``counts`` (slots,): tokens actually consumed into each row's
        context this step (1 + accepted drafts; 0 for rows that were
        inactive or retired mid-window — their slot release already
        dropped all pages).  Each surviving row keeps the logits of its
        last accepted window position, its length advances by its count,
        and pages allocated for the rejected tail are **rolled back**
        (decref'd, table cells reset to the dump page) so a rejected
        draft can never pin pool capacity.
        """
        sel = jnp.asarray(np.maximum(counts - 1, 0), jnp.int32)
        state.logits = self._select_logits(logits, sel)
        if not self.paged:
            state.cache["len"] = (state.cache["len"]
                                  + jnp.asarray(counts, jnp.int32))
            return
        pg = self.page_size
        for s in np.nonzero(alive)[0]:
            state.lens[s] += counts[s]
            t = state.tables[s]
            keep = -(-int(state.lens[s]) // pg)  # pages holding valid tokens
            if len(t) > keep:
                dropped = t[keep:]
                del t[keep:]
                state.table_np[s, keep:keep + len(dropped)] = self._dump
                self.pool.decref(dropped)

    # ------------------------------------------------------------------
    # Convenience facade
    # ------------------------------------------------------------------
    def executor(self, **kwargs):
        """A fresh :class:`ContinuousBatchingExecutor` over this engine."""
        from repro.serve.executor import ContinuousBatchingExecutor

        return ContinuousBatchingExecutor(self, **kwargs)

    def generate(
        self,
        prompts: Sequence[str],
        *,
        max_tokens: int,
        stop: Optional[str] = None,
        expected: Optional[Sequence[str]] = None,
    ) -> List[GenResult]:
        """Synchronous batch API, now a facade over the executor: all
        prompts are enqueued at once and decode with slot refill instead of
        barrier waves (a request's budget/stop handling is per-row either
        way)."""
        if self._default_executor is None:
            self._default_executor = self.executor()
        ex = self._default_executor
        handles = []
        try:
            for i, p in enumerate(prompts):
                handles.append(ex.submit(
                    p, max_tokens=max_tokens, stop=stop,
                    expected=expected[i] if expected is not None else None,
                ))
        except Exception:
            cancel_unfinished(ex, handles)
            raise
        try:
            return [ex.result(h) for h in handles]
        except Exception:
            cancel_unfinished(ex, handles)
            raise
