"""Plain reference for the batch resolve: sorted-neighborhood blocking and
the weighted matcher cascade, in numpy, independent of the system under
test.

Semantics (arXiv:1010.3053 §3-§4, the configuration's ``matcher``):

  * blocked: sort the records by (key, eid); every two records at sorted
    distance 1 .. w-1 form a pair (lo eid, hi eid).  RepSN and JobSN both
    promise exactly this set, whatever the partitioning.
  * matched: a blocked pair whose weighted mean of matcher scores is at or
    above the threshold.  cosine = clip((1 + a.b) / 2, 0, 1); jaccard =
    |a & b| / |a | b| over signature bits (1 when both are empty); edit =
    1 - levenshtein(a, b) / max(len a, len b, 1), lengths counting non-zero
    bytes.

Pairs are of record ids (eids, a permutation of 0 .. n-1, not row
numbers) and travel as packed uint64 ``(lo << 32) | hi``.  Scores are computed in float64, or in bfloat16
for the precision control (``dtype=BF16``): every product, sum and ratio
then rounds to bfloat16, the precision one step below the float32 the
configuration states.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)
BLOCK = 1 << 18          # rows scored at a time (bounds host memory)


def pack(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    return (lo.astype(np.uint64) << np.uint64(32)) | hi.astype(np.uint64)


def unpack(p: np.ndarray):
    p = np.asarray(p, np.uint64)
    return ((p >> np.uint64(32)).astype(np.int64),
            (p & np.uint64(0xFFFFFFFF)).astype(np.int64))


def _cosine(a, b, dt):
    if dt == np.float64:
        s = np.einsum("ij,ij->i", a, b, dtype=np.float64)
    else:
        s = np.sum(a.astype(dt) * b.astype(dt), axis=-1, dtype=dt)
    return np.clip((s + dt.type(1)) * dt.type(0.5), 0, 1).astype(dt)


def _jaccard(a, b, dt):
    inter = np.bitwise_count(a & b).sum(axis=-1).astype(dt)
    union = np.bitwise_count(a | b).sum(axis=-1).astype(dt)
    return np.where(union > 0, inter / np.maximum(union, dt.type(1)),
                    dt.type(1)).astype(dt)


def levenshtein(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Edit distance of each row pair of (m, L) uint8 arrays; zero bytes
    are padding and the strings are their non-zero prefix."""
    m, L = a.shape
    la = (a > 0).sum(axis=1)
    lb = (b > 0).sum(axis=1)
    at, bt = np.ascontiguousarray(a.T), np.ascontiguousarray(b.T)
    rows = np.arange(m)
    # prev[j] = distance of a[:i-1] to b[:j], one row of m pairs per j
    prev = np.repeat(np.arange(L + 1, dtype=np.int32)[:, None], m, axis=1)
    out = np.where(la == 0, lb, 0).astype(np.int32)
    for i in range(1, L + 1):
        cur = np.empty_like(prev)
        cur[0] = i
        for j in range(1, L + 1):
            sub = prev[j - 1] + (at[i - 1] != bt[j - 1])
            cur[j] = np.minimum(np.minimum(prev[j], cur[j - 1]) + 1, sub)
        done = la == i
        out[done] = cur[lb[done], rows[done]]
        prev = cur
    return out


def _edit(a, b, dt):
    d = levenshtein(a, b).astype(dt)
    mx = np.maximum(np.maximum((a > 0).sum(1), (b > 0).sum(1)), 1).astype(dt)
    return np.clip(dt.type(1) - d / mx, 0, 1).astype(dt)


SIMS = {"cosine": _cosine, "jaccard": _jaccard, "edit": _edit}


def cascade(pa: dict, pb: dict, matcher: dict, *, dtype=np.float64,
            skip: bool = True):
    """Weighted cascade score of row pairs (``pa[f][i]``, ``pb[f][i]``).

    Returns ``(score, exact)``.  With ``skip``, a pair whose score cannot
    reach the threshold even if every later matcher scored 1 stops there
    (the paper's skip rule, cheap to expensive); its ``score`` is then that
    upper bound, below the threshold, and ``exact`` is False."""
    dt = np.dtype(dtype)
    ms = sorted(matcher["matchers"], key=lambda m: m["cost"])
    remaining = sum(m["weight"] for m in ms)
    wsum = dt.type(remaining)
    thr = dt.type(matcher["threshold"])
    m0 = len(next(iter(pa.values())))
    acc = np.zeros(m0, dt)
    bound = np.zeros(m0, dt)
    alive = np.ones(m0, bool)
    for m in ms:
        idx = np.nonzero(alive)[0]
        a, b = pa[m["field"]], pb[m["field"]]
        sim = SIMS[m["kind"]](a[idx], b[idx], dt)
        acc[idx] = (acc[idx] + dt.type(m["weight"]) * sim).astype(dt)
        remaining -= m["weight"]
        if skip and remaining > 0:
            ub = ((acc + dt.type(remaining)) / wsum).astype(dt)
            died = alive & (ub < thr)
            bound[died] = ub[died]
            alive &= ~died
    return np.where(alive, (acc / wsum).astype(dt), bound), alive


def resolve(rec: dict, w: int, matcher: dict, *, dtype=np.float64):
    """The reference answer: ``(blocked, matched, survivors)``, the sorted
    packed blocked and matched pair sets and the number of blocked pairs
    the skip rule cannot drop before the last matcher."""
    dt = np.dtype(dtype)
    thr = dt.type(matcher["threshold"])
    order = np.lexsort((rec["eid"], rec["key"]))
    se = rec["eid"][order].astype(np.int64)
    fields = {m["field"] for m in matcher["matchers"]}
    sf = {f: rec[f][order] for f in fields}
    blocked, matched, survivors = [], [], 0
    for d in range(1, min(w, se.size)):
        for s in range(0, se.size - d, BLOCK):
            e = min(s + BLOCK, se.size - d)
            a, b = se[s:e], se[s + d:e + d]
            p = pack(np.minimum(a, b), np.maximum(a, b))
            score, exact = cascade({f: v[s:e] for f, v in sf.items()},
                                   {f: v[s + d:e + d] for f, v in sf.items()},
                                   matcher, dtype=dt)
            blocked.append(p)
            matched.append(p[score >= thr])
            survivors += int(exact.sum())
    cat = lambda xs: np.sort(np.concatenate(xs)) if xs \
        else np.zeros(0, np.uint64)
    return cat(blocked), cat(matched), survivors


def pair_scores(rec: dict, pairs: np.ndarray, matcher: dict) -> np.ndarray:
    """Exact float64 scores of arbitrary packed pairs (every matcher
    evaluated)."""
    row = np.empty(rec["eid"].size, np.int64)
    row[rec["eid"]] = np.arange(rec["eid"].size)
    lo, hi = (row[x] for x in unpack(pairs))
    fields = {m["field"] for m in matcher["matchers"]}
    out = np.zeros(pairs.size)
    for s in range(0, pairs.size, BLOCK):
        ia, ib = lo[s:s + BLOCK], hi[s:s + BLOCK]
        out[s:s + BLOCK], _ = cascade({f: rec[f][ia] for f in fields},
                                      {f: rec[f][ib] for f in fields},
                                      matcher, skip=False)
    return out
