"""How ``correct`` is decided, and ``recall_k``.

Four numbers, each beside its limit from the configuration file:

* ``unanswered``: requests of the window that never got an answer (limit 0);
* ``bad_answers``: answers that are not a top-k list: a pid outside the
  corpus, a repeated pid, a real pid after a pad, a score that is not finite
  or not in descending order (limit 0);
* ``score_gap``: over a sample of answers drawn from the seed, the widest
  gap between a returned score and the reference's float32 MaxSim of the
  returned passage.  Stage 4 rescores every finalist exactly, so this gap is
  rounding; a lower precision, an altered pid or score, or a lane answered
  with another query's list reads far above it;
* ``plaid_miss``: over a smaller sample drawn from the seed, the share of
  returned passages that PLAID's own four stages, run plainly
  (``reference.plaid_topk``), do not return: stages 1 to 3 (the probe,
  the candidate union and its cap, the pruned and the full centroid
  interaction, the ``ndocs`` cuts) decide which passages reach stage 4, so
  a dropped candidate, a wrong threshold or a cut in another precision
  reads here, where ``score_gap`` cannot see it.

``recall_k`` is the mean over the same sample of |returned top-k ∩
exhaustive top-k| / k (an end-to-end metric, not a check).
"""
from __future__ import annotations

import numpy as np


def bad_answers(scores: list, pids: list, k: int, n_passages: int) -> int:
    bad = 0
    for s, p in zip(scores, pids):
        if p is None:
            continue
        s, p = np.asarray(s, np.float64), np.asarray(p, np.int64)
        real = p >= 0
        ok = p.shape == (k,) and s.shape == (k,)
        ok = ok and bool((p >= -1).all() and (p < n_passages).all())
        ok = ok and bool(real[: real.sum()].all())  # pads only at the tail
        ok = ok and len(np.unique(p[real])) == real.sum()
        ok = ok and bool(np.isfinite(s[real]).all())
        ok = ok and bool((np.diff(s[real]) <= 0).all())
        bad += not ok
    return int(bad)


def score_gap(served_scores: np.ndarray, served_pids: np.ndarray,
              ref_scores: np.ndarray) -> float:
    """Widest |served - reference| over the real pids of the sample; a real
    pid that the reference does not know reads as an infinite gap."""
    real = served_pids >= 0
    gap = np.abs(served_scores.astype(np.float64) - ref_scores)
    gap = np.where(np.isnan(gap), np.inf, gap)
    return float(gap[real].max()) if real.any() else float("inf")


def plaid_miss(served_pids: np.ndarray, plain_pids: np.ndarray) -> float:
    """Share of the returned real pids that are not in the plain PLAID
    top-k of the same query (pooled over the sample); 1.0 where nothing
    real was returned."""
    n = missed = 0
    for s, e in zip(served_pids, plain_pids):
        s = s[s >= 0]
        n += len(s)
        missed += len(np.setdiff1d(s, e[e >= 0]))
    return float(missed / n) if n else 1.0


def recall(served_pids: np.ndarray, exact_pids: np.ndarray, k: int) -> float:
    hits = [
        len(np.intersect1d(s[s >= 0], e[e >= 0])) / k
        for s, e in zip(served_pids, exact_pids)
    ]
    return float(np.mean(hits))


def sample(answered: np.ndarray, n: int, seed: int, stream: int = 3) -> np.ndarray:
    """Up to ``n`` answered request indices drawn from ``seed``."""
    idx = np.flatnonzero(answered)
    rng = np.random.default_rng((int(seed), stream))
    return np.sort(rng.choice(idx, size=min(n, len(idx)), replace=False))


def verdict(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct iff none is above it."""
    checks = {
        name: {"value": values[name], "limit": limits[name]} for name in limits
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
