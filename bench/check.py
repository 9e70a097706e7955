"""The comparison that decides ``correct``.

Three kinds of number, each held to a limit from ``limits/<cell>.json``:

* the join results: every rectangle a call settled is compared with the
  scenario's truth (``wrong_pairs``, limit 0), every query must end
  without an error (``query_errors``, limit 0), and every call made
  before the window's close must be answered within the wait after it
  (``unanswered_calls``, limit 0);
* the tracked requests: each one served must have finished, with one
  logits row per served token and the tokens fed to decode equal to the
  answer it returned (``unchecked_requests``, limit 0); one still queued
  at the window's close, and so cancelled, is not compared;
* their logits against the plain float32 reference of the
  configuration's architecture module (``bench/arch/``,
  ``reference_logits``), run over the same prompt and served tokens:
  ``logit_kl_max`` and ``logit_kl_mean`` are the largest and the mean,
  over every compared position, of the KL
  divergence of the engine's next-token distribution from the
  reference's.  The readings also carry ``logit_gap`` (the widest gap
  between the reference's best logit and its logit of the token the
  engine ranked first) and the L2 relative error of a position's
  logits, which are not compared: on random weights neither tells the
  program's int8 path from a sound run (PERF.md).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import arch

BOS, EOS, OFFSET = 1, 2, 4  # the served byte tokenizer: byte + 4


def byte_ids(text: str, bos: bool) -> List[int]:
    return ([BOS] if bos else []) + [b + OFFSET for b in text.encode("utf-8")]


def join_numbers(queries: Sequence) -> Dict[str, int]:
    wrong = errors = 0
    for q in queries:
        if q.error is not None:
            errors += 1
        truth = q.tables.truth()
        settled = set()
        for (lo1, hi1, lo2, hi2), found in q.memo.items():
            want = {(i, k) for (i, k) in truth
                    if lo1 <= i < hi1 and lo2 <= k < hi2}
            wrong += len(set(found) ^ want)
            settled |= set(found)
        if q.pairs is not None and q.pairs != settled:
            wrong += len(q.pairs ^ settled)
    return {"wrong_pairs": wrong, "query_errors": errors}


def logit_numbers(config: dict, seed: int, context: int,
                  tracked: Sequence, batch: int) -> Dict[str, float]:
    """``tracked``: (record, host logits rows, calls) per tracked request."""
    model = arch.load(config)
    V = model.vocab(config)
    seqs, want, rows = [], [], []
    unchecked = 0
    for rec, logits, calls in tracked:
        call = calls[0] if calls else None
        if call is not None and call.cancelled:
            continue  # still queued at the window's close: never served
        if call is None or call.done == 0.0:
            unchecked += 1
            continue
        served = byte_ids(call.text, bos=False)
        if len(logits) == len(served) + 1:
            served.append(EOS)  # an answer with no stop string ends on EOS
        n = len(served)
        if n == 0 or len(logits) != n or rec.fed != served[:n - 1]:
            unchecked += 1
            continue
        prompt = byte_ids(rec.prompt, bos=True)
        seqs.append(prompt + served[:n - 1])
        want.append(list(range(len(prompt) - 1, len(prompt) + n - 1)))
        rows.append(np.stack(logits)[:, :V].astype(np.float32))
    out = {"unchecked_requests": unchecked, "compared_requests": len(seqs),
           "compared_positions": 0,
           "logit_gap": float("inf"), "logit_rel_err": float("inf"),
           "logit_rel_err_median": float("inf"),
           "logit_kl_max": float("inf"), "logit_kl_mean": float("inf")}
    if not seqs:
        return out
    refs = model.reference_logits(config, seed, seqs, want, length=context,
                                  batch=batch)
    gaps, rels, kls = [], [], []
    for eng, ref in zip(rows, refs):
        top = eng.argmax(axis=1)
        gaps.append(ref.max(axis=1) - ref[np.arange(len(top)), top])
        rels.append(np.linalg.norm(eng - ref, axis=1)
                    / np.linalg.norm(ref, axis=1))
        kls.append(kl_divergence(ref, eng))
    gaps, rels, kls = (np.concatenate(x) for x in (gaps, rels, kls))
    out.update(compared_positions=len(gaps),
               logit_gap=float(gaps.max()),
               logit_rel_err=float(rels.max()),
               logit_rel_err_median=float(np.median(rels)),
               logit_kl_max=float(kls.max()),
               logit_kl_mean=float(kls.mean()))
    return out


def kl_divergence(ref: np.ndarray, eng: np.ndarray) -> np.ndarray:
    """KL(softmax(ref) || softmax(eng)) of each row, in nats."""
    def log_softmax(x):
        x = x.astype(np.float64)
        m = x.max(axis=1, keepdims=True)
        return x - m - np.log(np.exp(x - m).sum(axis=1, keepdims=True))

    lp, lq = log_softmax(ref), log_softmax(eng)
    return (np.exp(lp) * (lp - lq)).sum(axis=1)


CHECKS = ("wrong_pairs", "query_errors", "unanswered_calls",
          "unchecked_requests", "logit_kl_max", "logit_kl_mean")


def run_checks(config: dict, seed: int, context: int, queries: Sequence,
               tracked: Sequence, limits: dict, batch: int,
               unanswered: int) -> tuple:
    """Every compared number with its limit, in a fixed order, and all
    the readings (the compared numbers and their counts)."""
    values = dict(join_numbers(queries), unanswered_calls=unanswered)
    values.update(logit_numbers(config, seed, context, tracked, batch))
    return {name: {"value": values[name], "limit": limits[name]}
            for name in CHECKS}, values
