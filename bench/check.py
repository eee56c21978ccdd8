"""The comparison that decides ``correct``.

Numbers compared, each against the limit the configuration file states:

  blocked_diff  size of the symmetric difference between the checked job's
                blocked pair set and the reference's (exact: limit 0)
  match_gap     widest distance from the threshold of the reference score
                of a pair on which the checked job's matched set and the
                reference's disagree; 0 when they agree.  A disagreement
                that float32 rounding explains sits within about 1e-6 of
                the threshold; bfloat16 arithmetic moves scores by up to
                half a bfloat16 step there, about 2e-3
  overflow      capacity drops summed over every job of the window
                (overflow + cand_overflow + pair_overflow; limit 0)
  jobs_differ   jobs of the window whose pair sets differ from the checked
                job's, by length and set hash (limit 0)
"""
from __future__ import annotations

import itertools

import numpy as np

from bench import reference as R


def packed(pairs) -> np.ndarray:
    """Sorted packed uint64 array of a set of (lo, hi) tuples."""
    flat = np.fromiter(itertools.chain.from_iterable(pairs), np.int64,
                       count=2 * len(pairs)).reshape(-1, 2)
    return np.sort(R.pack(flat[:, 0], flat[:, 1]))


def compare(rec: dict, matcher: dict, ref: tuple, blocked: np.ndarray,
            matched: np.ndarray) -> dict:
    """``blocked_diff`` and ``match_gap`` of a packed answer against the
    reference ``ref = (blocked, matched, survivors)``."""
    rb, rm, _ = ref
    dis = np.setxor1d(matched, rm, assume_unique=True)
    gap = 0.0
    if dis.size:
        gap = float(np.abs(R.pair_scores(rec, dis, matcher)
                           - matcher["threshold"]).max())
    return {"blocked_diff": int(np.setxor1d(blocked, rb,
                                            assume_unique=True).size),
            "match_gap": gap}


def verdict(numbers: dict, limits: dict):
    """(``{name: {"value", "limit"}}``, whether every value is within its
    limit)."""
    out = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return out, all(v <= limits[k] for k, v in numbers.items())
