"""Match strategies (paper §3, §5.1).

The paper's matcher: edit distance on title + TriGram similarity on abstract,
weighted average, threshold 0.75, with an internal optimization that SKIPS the
second matcher when the first one's score can no longer reach the threshold.

TPU adaptation (DESIGN.md §2): entities carry
  * "feat": unit-norm embeddings  -> cosine similarity  (cheap matcher)
  * "sig":  bit-packed trigram sets -> Jaccard via popcount (TriGram matcher)
  * "text": padded byte strings  -> exact edit distance (expensive matcher)

``CascadeMatcher`` reproduces the skip optimization: the cheap similarity
gates the expensive one.  Vectorized, ``combined`` applies the rule as a
mask; the band engines (core/window.py) turn it into real FLOP savings by
compacting the gate's survivors and running the last matcher on them only
(the Pallas engine also runs the cheap stage as a fused kernel).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.scopes import BAND_CHEAP, BAND_EXPENSIVE


# -- primitive similarities (operate on payload slices of paired entities) ----------

def cosine_sim(a: jax.Array, b: jax.Array) -> jax.Array:
    """a, b: (..., F) unit-ish vectors -> (...,) in [0, 1]."""
    s = jnp.sum(a.astype(jnp.float32) * b.astype(jnp.float32), axis=-1)
    return jnp.clip(0.5 * (s + 1.0), 0.0, 1.0)


def jaccard_sig(a: jax.Array, b: jax.Array) -> jax.Array:
    """a, b: (..., W) uint32 bit-packed sets -> Jaccard |a&b|/|a|b|."""
    inter = jax.lax.population_count(a & b).sum(axis=-1).astype(jnp.float32)
    union = jax.lax.population_count(a | b).sum(axis=-1).astype(jnp.float32)
    return jnp.where(union > 0, inter / jnp.maximum(union, 1.0), 1.0)


def _edit_distance_scan(a32, b32, L, la, lb):
    BIG = jnp.int32(2 * L + 7)
    rows = jnp.arange(L + 1, dtype=jnp.int32)
    shape = a32.shape[:-1] + (L + 1,)
    ones = jnp.ones(shape, jnp.int32)
    prev2 = jnp.where(rows == 0, 0, BIG) * ones
    prev = jnp.where(rows <= 1, 1, BIG) * ones
    target_d = la + lb                                  # (...,)
    # capture dp[la, lb]: on diagonal d == la+lb at row i == la.  Diagonals
    # 0 and 1 are the scan init, so their answers are captured here.
    ans0 = jnp.where(target_d == 0, 0,
                     jnp.where(target_d == 1, 1, BIG))

    def step(carry, d):
        prev2, prev, ans = carry
        i = rows
        j = d - i
        on = (j >= 0) & (j <= L)
        up = jnp.concatenate(
            [jnp.full(shape[:-1] + (1,), BIG), prev[..., :-1]], axis=-1)
        left = prev
        diag = jnp.concatenate(
            [jnp.full(shape[:-1] + (1,), BIG), prev2[..., :-1]], axis=-1)
        ca = jnp.take(a32, jnp.clip(i - 1, 0, L - 1), axis=-1)
        cb_idx = jnp.clip(j - 1, 0, L - 1)
        cb = jnp.take(b32, cb_idx, axis=-1)
        sub = diag + jnp.where(ca == cb, 0, 1)
        cur = jnp.minimum(jnp.minimum(up + 1, left + 1), sub)
        cur = jnp.where(i == 0, jnp.minimum(d, BIG), cur)
        cur = jnp.where(j == 0, i, cur)
        cur = jnp.where(on, cur, BIG)
        hit = (d == target_d)[..., None] & (i == la[..., None])
        ans = jnp.where(jnp.any(hit, -1),
                        jnp.sum(jnp.where(hit, cur, 0), axis=-1), ans)
        return (prev, cur, ans), None

    (_, _, ans), _ = jax.lax.scan(
        step, (prev2, prev, ans0),
        jnp.arange(2, 2 * L + 1, dtype=jnp.int32))
    return ans


def edit_distance_impl(a: jax.Array, b: jax.Array) -> jax.Array:
    L = a.shape[-1]
    a32 = a.astype(jnp.int32)
    b32 = b.astype(jnp.int32)
    la = jnp.sum((a32 > 0).astype(jnp.int32), axis=-1)
    lb = jnp.sum((b32 > 0).astype(jnp.int32), axis=-1)
    return _edit_distance_scan(a32, b32, L, la, lb)


def edit_sim(a: jax.Array, b: jax.Array) -> jax.Array:
    """1 - dist / max(len) in [0,1]."""
    a32 = a.astype(jnp.int32)
    b32 = b.astype(jnp.int32)
    la = jnp.sum((a32 > 0).astype(jnp.int32), axis=-1)
    lb = jnp.sum((b32 > 0).astype(jnp.int32), axis=-1)
    d = _edit_distance_scan(a32, b32, a.shape[-1], la, lb)
    mx = jnp.maximum(jnp.maximum(la, lb), 1)
    return jnp.clip(1.0 - d.astype(jnp.float32) / mx.astype(jnp.float32),
                    0.0, 1.0)


def edit_distance_ref(a: np.ndarray, b: np.ndarray) -> int:
    """Host oracle for tests."""
    sa = bytes(a[a > 0].tolist())
    sb = bytes(b[b > 0].tolist())
    m, n = len(sa), len(sb)
    dp = list(range(n + 1))
    for i in range(1, m + 1):
        prev = dp[0]
        dp[0] = i
        for j in range(1, n + 1):
            cur = dp[j]
            dp[j] = min(dp[j] + 1, dp[j - 1] + 1,
                        prev + (sa[i - 1] != sb[j - 1]))
            prev = cur
    return dp[n]


# -- matcher strategy objects -----------------------------------------------------

@dataclass(frozen=True)
class Matcher:
    """One similarity over a payload field."""
    field: str
    kind: str            # "cosine" | "jaccard" | "edit"
    weight: float = 1.0
    cost: float = 1.0    # relative cost (cascade ordering)

    def __call__(self, pa: Dict[str, jax.Array],
                 pb: Dict[str, jax.Array]) -> jax.Array:
        a, b = pa[self.field], pb[self.field]
        if self.kind == "cosine":
            return cosine_sim(a, b)
        if self.kind == "jaccard":
            return jaccard_sig(a, b)
        if self.kind == "edit":
            return edit_sim(a, b)
        raise ValueError(self.kind)


@dataclass(frozen=True)
class CascadeMatcher:
    """Weighted-average match strategy with the paper's skip optimization:
    matchers are evaluated cheap-to-expensive; if the best still-achievable
    combined score drops below the threshold, later matchers are skipped.

    ``combined(pa, pb)`` returns (score, evaluated_mask) vectorized over any
    leading shape.  Each matcher runs inside a device stage scope: the most
    expensive of two or more in ``band/expensive``, the others in
    ``band/cheap`` (``repro.obs.scopes``)."""
    matchers: Tuple[Matcher, ...]
    threshold: float = 0.75

    def ordered(self):
        return tuple(sorted(self.matchers, key=lambda m: m.cost))

    @property
    def weight_sum(self) -> float:
        """The matchers' total weight, which normalises the score."""
        return sum(m.weight for m in self.ordered())

    def _skip_rule(self, acc, alive, remaining: float, skip: bool):
        """The pairs still alive once the best achievable combined score,
        every remaining matcher scoring 1.0, is known: ``alive`` and
        ``(acc + remaining) / wsum >= threshold`` (unchanged without
        ``skip``)."""
        if not skip:
            return alive
        return alive & ((acc + remaining) / self.weight_sum
                        >= self.threshold)

    def prefix(self, pa, pb, *, skip: bool = True):
        """Every matcher but the last, cheap to expensive, in ``band/cheap``.

        Returns ``(acc, alive, evaluated)``: the weighted partial sum (None
        for a one-matcher cascade), the skip rule's verdict on the last
        matcher (the pairs it could still lift to the threshold; all True
        without ``skip``) and the evaluations counted so far.  ``combined``
        is ``prefix`` then ``finish``; the scan band engine runs the last
        matcher only where ``alive`` holds (``core/window.py``)."""
        ms = self.ordered()
        acc = alive = None
        remaining = self.weight_sum
        evaluated = 0.0
        for m in ms[:-1]:
            if acc is not None:
                alive = self._skip_rule(acc, alive, remaining, skip)
            with jax.named_scope(BAND_CHEAP):
                s = m(pa, pb)
            if acc is None:
                acc = m.weight * s
                alive = jnp.ones_like(s, bool)
            else:
                acc = acc + m.weight * jnp.where(alive, s, 0.0)
            evaluated = evaluated + alive.astype(jnp.float32)
            remaining -= m.weight
        if acc is not None:
            alive = self._skip_rule(acc, alive, remaining, skip)
        return acc, alive, evaluated

    def finish(self, acc, alive, s):
        """The combined score from ``prefix``'s ``acc`` and the last
        matcher's score ``s``, which counts only where ``alive``."""
        last = self.ordered()[-1]
        if acc is None:
            return last.weight * s / self.weight_sum
        return (acc + last.weight * jnp.where(alive, s, 0.0)) \
            / self.weight_sum

    def combined(self, pa, pb, *, skip: bool = True):
        acc, alive, evaluated = self.prefix(pa, pb, skip=skip)
        stage = BAND_CHEAP if acc is None else BAND_EXPENSIVE
        with jax.named_scope(stage):
            s = self.ordered()[-1](pa, pb)
        if alive is None:
            alive = jnp.ones_like(s, bool)
        return self.finish(acc, alive, s), \
            evaluated + alive.astype(jnp.float32)

    def matches(self, pa, pb, *, skip: bool = True):
        score, _ = self.combined(pa, pb, skip=skip)
        return score >= self.threshold


def default_matcher() -> CascadeMatcher:
    """The paper's strategy: cheap trigram-style sim gates the edit distance;
    weighted average, threshold 0.75 (§5.1)."""
    return CascadeMatcher(
        matchers=(
            Matcher(field="feat", kind="cosine", weight=0.5, cost=1.0),
            Matcher(field="sig", kind="jaccard", weight=0.5, cost=2.0),
        ),
        threshold=0.75)
