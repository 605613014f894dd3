"""What decides ``correct``: each solve's answer against the reference.

A solve returns alpha and certifies, by its own in-graph primal, that
the suboptimality (P - p*) / (P(0) - p*) is at most eps. The reference
evaluates P at the returned alpha in float64 (``reference.primal64``)
against its own p*, and two numbers are compared, each the worst over
the solves checked:

  * ``primal_gap``: |P reported by the solve - P64(alpha)| / (P(0) - p*).
    The solve reports P from the shared residual w it carried through
    the rounds, the reference from alpha alone, so a gather, local
    solve, exchange or apply that lets w and alpha part, or a metric
    computed wrongly, shows here. Its limit is the configuration's
    ``limits.primal_gap``, set from readings (see PERF.md).
  * ``subopt``: (P64(alpha) - p*) / (P(0) - p*), the certificate
    itself. Its limit is the mix's eps plus the primal_gap limit: the
    certificate holds to within the precision its metric is held to.

and a third is counted: ``failed_solves``, the solves that raised or
reached the mix's ``max_rounds`` without the certificate (limit 0).
"""
from __future__ import annotations

import hashlib

import numpy as np

from chipbench.reference import primal64

# distinct answers evaluated at most (a deterministic program returns
# one alpha for every solve of a run; the cap bounds the check's time)
MAX_DISTINCT = 8


def compare(solves: list, failed: int, *, A: np.ndarray, b: np.ndarray,
            lam: float, p_star: float, p_zero: float, eps: float,
            gap_limit: float, seed_word: int) -> dict:
    """``solves`` holds ``(alpha, reported_primal)`` of every solve of
    the window that certified. Returns ``{name: (value, limit)}``."""
    by_answer: dict[str, list] = {}
    for alpha, reported in solves:
        key = hashlib.sha1(np.ascontiguousarray(alpha).tobytes()).hexdigest()
        by_answer.setdefault(key, [alpha, []])[1].append(reported)
    answers = list(by_answer.values())
    if len(answers) > MAX_DISTINCT:
        rng = np.random.default_rng(seed_word)
        answers = [answers[i] for i in rng.choice(len(answers), MAX_DISTINCT,
                                                  replace=False)]
    gap, sub = 0.0, 0.0
    denom = p_zero - p_star
    for alpha, reported in answers:
        p64 = primal64(A, b, alpha, lam)
        sub = max(sub, (p64 - p_star) / denom)
        gap = max(gap, max(abs(r - p64) for r in reported) / denom)
    if not answers:
        gap = sub = None
    return {"failed_solves": (failed, 0),
            "primal_gap": (gap, gap_limit),
            "subopt": (sub, eps + gap_limit)}


def passed(numbers: dict) -> bool:
    """Every number read and within its limit (no answer to check
    reads None, and fails)."""
    return all(v is not None and v <= lim for v, lim in numbers.values())


def lines(numbers: dict) -> list[str]:
    return [f"check {k} {v!r} limit {lim!r}" for k, (v, lim) in
            numbers.items()]
