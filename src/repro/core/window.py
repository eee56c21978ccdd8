"""Sliding-window matching over a sorted shard (the paper's reduce step).

The window is evaluated as a BAND: for a sorted array of M slots,
``band[d-1, i] = score(E[i], E[i+d])`` for distance d in 1..w-1.  Validity
masking + the slot conventions (valid entities contiguous in key order, halo
entities occupying the first ``halo_len`` slots) make slot distance equal
rank distance, so the band is exactly the paper's sliding window.

Band evaluation is a pluggable seam — a **BandEngine** selected by
``ERConfig.band_engine`` and used by every variant's ``_band`` hook:

  * ``scan``    (ScanBandEngine) pure-JAX scan over distances: w-1 shifted
                payload passes score every slot with every matcher but
                the last (``CascadeMatcher.prefix``); the §5.1 skip rule's
                survivors among the blocked slots are compacted into a
                band-sized buffer (never overflows) and the last matcher
                scores them in chunks (``score_survivors``), so the
                expensive matcher runs only where the threshold is still
                reachable.  No capacity, O(M * F) live payload.
  * ``pallas``  (PallasBandEngine) the paper's §5.1 two-stage cascade with
                REAL FLOP savings: a fused Pallas kernel
                (kernels/fused_band.py) evaluates the cheap matchers for the
                whole band at MXU rate, cumsum-based compaction
                (``compact_candidates``) packs gate survivors into a
                ``cand_cap`` buffer (overflow counted, never silent), and
                the expensive matcher (``score_candidates``) runs ONLY on
                survivors.  Decisions match the scan engine exactly: the
                gate keeps every pair whose best-achievable combined score
                can still reach the threshold (plus an epsilon guard for
                kernel-vs-jnp rounding), and survivors are rescored with
                the full jnp cascade.

Engines register with ``@register_band_engine("name")``; both return the
same part dict (``mask``/``match``/``matcher_evals``/``cand_count``/
``cand_overflow``), so variants and runners never branch on the engine.

Device stages (``repro.obs.scopes``): the variants run the band inside
``band/select``; here the rolls and gathers that put each row beside its
partner open ``band/align``, the fused kernel ``band/cheap``, and the
matchers their own stage (``CascadeMatcher.combined``).

The halo/seam convention generalizes beyond shard boundaries: the same
``[halo | native]`` layout that closes partition seams (RepSN) closes the
CHUNK seams of out-of-core streaming — ``repro.stream`` prepends the w-1
preceding global entities to every chunk and the band emits each SN pair
at its true sorted distance (the pair-ownership rule of the cost model
below is also why per-chunk pair unions dedup cleanly).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.match import CascadeMatcher
from repro.obs.scopes import BAND_ALIGN, BAND_CHEAP, BAND_EXPENSIVE

# epsilon guard on the cascade gate: the fused kernel's cheap scores can
# differ from the jnp oracle by reduction-order ulps; widening the gate by
# GATE_EPS (in normalized-score units) keeps every pair the scan engine
# could accept, and extra survivors are exactly rescored anyway.
GATE_EPS = 1e-5

# gate survivors per shard that the scan engine's last matcher scores in
# one pass of its loop (``score_survivors``); chosen on a v5e (PERF.md)
SURVIVOR_CHUNK = 4096


def _pair_mask(valid: jax.Array, d: jax.Array, *, halo_len: int,
               mode: str, weff: Optional[jax.Array] = None) -> jax.Array:
    """Mask for pairs (i, i+d) of a combined [halo | native] array.

    mode:
      "all"      every valid pair (plain SRP shard)
      "native"   at least the LATER element is native (RepSN rule: halo-halo
                 pairs were already emitted by the predecessor shard)
      "cross"    earlier element in the first half, later in the second half
                 (JobSN boundary job: only cross-partition pairs; same-side
                 pairs were emitted in phase 1)

    ``weff`` (adaptive windows, DESIGN.md §14) is a per-slot effective
    window: pair (i, i+d) additionally requires d < weff[i+d] — the LATER
    element owns the comparison, the same ownership rule as the cost model,
    so per-entity windows compose with every mode/halo convention.
    """
    m = valid.shape[0]
    i = jnp.arange(m, dtype=jnp.int32)
    j = i + d
    ok = (j < m) & valid & jnp.roll(valid, -d)
    if weff is not None:
        ok &= d < jnp.roll(weff, -d)
    if mode == "native":
        ok &= j >= halo_len
    elif mode == "cross":
        ok &= (i < halo_len) & (j >= halo_len)
    return ok


def cross_source_rows(src: jax.Array, w: int) -> jax.Array:
    """(w-1, M) linkage mask: row d-1 true where src[i] != src[i+d] — THE
    one implementation of the cross-source rule (api.linkage and both band
    engines delegate here)."""
    def step(_, d):
        return None, src != jnp.roll(src, -d)
    _, rows = jax.lax.scan(step, None, jnp.arange(1, w, dtype=jnp.int32))
    return rows


def band_mask(valid: jax.Array, w: int, *, halo_len: int = 0,
              mode: str = "all", src: Optional[jax.Array] = None,
              weff: Optional[jax.Array] = None) -> jax.Array:
    """(w-1, M) validity band: row d-1 masks distance-d pairs.  ``src``
    (linkage mode) additionally restricts to cross-source pairs via
    ``cross_source_rows``; ``weff`` restricts each pair to the later
    element's effective window (adaptive policy)."""
    def step(_, d):
        return None, _pair_mask(valid, d, halo_len=halo_len, mode=mode,
                                weff=weff)
    _, rows = jax.lax.scan(step, None, jnp.arange(1, w, dtype=jnp.int32))
    if src is not None:
        rows = rows & cross_source_rows(src, w)
    return rows


def band_scores(ents: dict, w: int, matcher: CascadeMatcher, *,
                halo_len: int = 0, mode: str = "all",
                skip: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Returns (scores, mask), each (w-1, M): row d-1 holds distance-d pairs.

    Scans over distances; each step scores M pairs via a rolled payload view —
    O(M * F) live memory regardless of w."""
    payload = ents["payload"]
    valid = ents["valid"]
    weff = payload.get("_weff")      # adaptive per-entity windows, if riding

    def step(_, d):
        with jax.named_scope(BAND_ALIGN):
            rolled = {k: jnp.roll(v, -d, axis=0) for k, v in payload.items()}
        score, _ = matcher.combined(payload, rolled, skip=skip)
        ok = _pair_mask(valid, d, halo_len=halo_len, mode=mode, weff=weff)
        return None, (jnp.where(ok, score, 0.0), ok)

    _, (scores, mask) = jax.lax.scan(
        step, None, jnp.arange(1, w, dtype=jnp.int32))
    return scores, mask


def band_matches(ents: dict, w: int, matcher: CascadeMatcher, *,
                 halo_len: int = 0, mode: str = "all") -> jax.Array:
    scores, mask = band_scores(ents, w, matcher, halo_len=halo_len, mode=mode)
    return (scores >= matcher.threshold) & mask


def compact_flat(band: jax.Array, cap: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Pack the True positions of a boolean band (w-1, M) into a fixed-
    capacity buffer of FLAT indices ``(d-1)*M + i``, in band order.

    Cumsum-based: each survivor's slot is its exclusive prefix count — O(wM)
    work and one scatter, vs a full-band argsort's O(wM log wM).

    Returns (flat_idx (cap,) int32, n_true () int32, overflow () int32);
    positions past ``cap`` are dropped but counted in ``overflow`` (never
    silent).  Buffer slots beyond ``min(n_true, cap)`` are zero-filled."""
    flat = band.reshape(-1)
    n = flat.shape[0]
    rank = jnp.cumsum(flat.astype(jnp.int32)) - 1          # survivor rank
    n_true = jnp.sum(flat.astype(jnp.int32))
    target = jnp.where(flat & (rank < cap), rank, cap)     # cap -> dump slot
    buf = jnp.zeros((cap + 1,), jnp.int32).at[target].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    overflow = jnp.maximum(n_true - cap, 0)
    return buf[:cap], n_true, overflow


def compact_candidates(gate: jax.Array, cap: int
                       ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                  jax.Array, jax.Array]:
    """Stage-2 of the cascade: pack the True (d, i) band positions of
    ``gate`` (w-1, M) into a fixed-capacity candidate list, in band order
    (``compact_flat`` split back into (i, d) coordinates).

    Returns (cand_i, cand_d, cand_valid, n_cand, overflow); candidates past
    ``cap`` are dropped but counted in ``overflow`` (never silent)."""
    m = gate.shape[1]
    cand_flat, n_cand, overflow = compact_flat(gate, cap)
    kept = jnp.minimum(n_cand, cap)
    cand_valid = jnp.arange(cap, dtype=jnp.int32) < kept
    cand_d = cand_flat // m + 1
    cand_i = cand_flat % m
    return (cand_i.astype(jnp.int32), cand_d.astype(jnp.int32), cand_valid,
            n_cand, overflow)


def emit_band_indices(band: jax.Array, cap: int) -> dict:
    """Device-side pair emission (ISSUE 4): compact a boolean band (w-1, M)
    into a packed flat-index buffer so the host transfers ``cap`` int32
    slots + a count instead of the whole O(w*M) band.  The same capacity /
    overflow contract as the SRP shuffle and cand_cap: drops are counted,
    never silent.  Consumed by ``results.packed_pairs_from_idx`` (host eid
    translation is vectorized there)."""
    idx, n_true, overflow = compact_flat(band, cap)
    return {"idx": idx, "n": jnp.minimum(n_true, cap).astype(jnp.int32),
            "overflow": overflow.astype(jnp.int32)}


def cheap_band_jnp(payload: dict, split: "CascadeSplit",
                   w: int) -> jax.Array:
    """Band-shaped jnp evaluation of the cascade's cheap prefix: (w-1, M)
    unnormalized partial scores ``w_cos*cosine + w_jac*jaccard`` — the same
    math as the fused Pallas kernel, but computing only the w-1 band scores
    per row instead of the kernel's 2*block_i-wide tile.

    The tile shape is what the TPU MXU wants; off-TPU it is pure waste
    (~2*block_i/(w-1) extra cheap evaluations), so the pallas engine uses
    this path when the interpreter would otherwise run the tile kernel
    (band_interpret=None off-TPU).  Numerically this matches the scan
    oracle's per-matcher scores exactly (same jnp ops), so the GATE_EPS
    guard is strictly slack here."""
    from repro.core.match import cosine_sim, jaccard_sig

    feat = payload.get(split.feat_field) if split.feat_field else None
    sig = payload.get(split.sig_field) if split.sig_field else None

    def step(_, d):
        part = jnp.float32(0.0)
        if feat is not None:
            with jax.named_scope(BAND_ALIGN):
                partner = jnp.roll(feat, -d, axis=0)
            with jax.named_scope(BAND_CHEAP):
                part = part + split.w_cos * cosine_sim(feat, partner)
        if sig is not None:
            with jax.named_scope(BAND_ALIGN):
                partner = jnp.roll(sig, -d, axis=0)
            with jax.named_scope(BAND_CHEAP):
                part = part + split.w_jac * jaccard_sig(sig, partner)
        return None, part

    _, rows = jax.lax.scan(step, None, jnp.arange(1, w, dtype=jnp.int32))
    return rows


def score_candidates(ents: dict, cand_i, cand_d, cand_valid,
                     matcher: CascadeMatcher) -> jax.Array:
    """Run the full (expensive) matcher on compacted candidate pairs only —
    the real-FLOP realization of the paper's skip optimization."""
    j = cand_i + cand_d
    j = jnp.minimum(j, ents["valid"].shape[0] - 1)
    with jax.named_scope(BAND_ALIGN):
        pa = {k: v[cand_i] for k, v in ents["payload"].items()}
        pb = {k: v[j] for k, v in ents["payload"].items()}
    score, _ = matcher.combined(pa, pb, skip=False)
    return jnp.where(cand_valid, score, 0.0)


def prefix_band(payload: dict, matcher: CascadeMatcher, w: int
                ) -> Tuple[jax.Array, jax.Array]:
    """(acc, alive), each (w-1, M): ``matcher.prefix`` (every matcher but
    the last, and the skip rule) of the distance-d pairs (i, i+d) in row
    d-1, by a scan over distances of rolled payload views."""
    fields = {m.field for m in matcher.ordered()[:-1]}

    def step(_, d):
        with jax.named_scope(BAND_ALIGN):
            rolled = {f: jnp.roll(payload[f], -d, axis=0) for f in fields}
        acc, alive, _ = matcher.prefix(payload, rolled)
        return None, (acc, alive)

    _, rows = jax.lax.scan(step, None, jnp.arange(1, w, dtype=jnp.int32))
    return rows


def score_survivors(payload: dict, matcher: CascadeMatcher, acc: jax.Array,
                    gate: jax.Array) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """Run the cascade's last matcher on the True slots of ``gate`` only.

    ``acc`` (w-1, M) is ``prefix_band``'s partial sum.  The gate's slots
    are packed in band order into a buffer the size of the band
    (``compact_flat``, so nothing overflows), and a ``while_loop`` scores
    them ``SURVIVOR_CHUNK`` at a time while any remain, scattering each
    chunk's scores into the band; under vmap it runs until the busiest
    shard is done.  Returns (scores (w-1, M), survivors, evals): a
    survivor's score is ``finish(acc, True, s)``, every other slot's
    ``finish(acc, False, .)``, what ``combined`` gives a pair the skip
    rule dropped; ``evals`` counts the buffer positions scored."""
    rows, m = gate.shape
    slots = rows * m
    c = min(SURVIVOR_CHUNK, slots)
    idx, n, _ = compact_flat(gate, slots)
    last = matcher.ordered()[-1]
    field = payload[last.field]
    acc_flat = acc.reshape(-1)

    def body(carry):
        k, scores = carry
        # the last chunk is clamped to end at the buffer's end, so it stays
        # in bounds; the survivors it scores again get the same result
        start = jnp.minimum(k * c, slots - c)
        flat = jax.lax.dynamic_slice(idx, (start,), (c,))
        i = flat % m
        j = jnp.minimum(i + flat // m + 1, m - 1)
        with jax.named_scope(BAND_ALIGN):
            pa, pb = {last.field: field[i]}, {last.field: field[j]}
            part = acc_flat[flat]
        with jax.named_scope(BAND_EXPENSIVE):
            s = last(pa, pb)
        live = start + jnp.arange(c, dtype=jnp.int32) < n
        return k + 1, scores.at[jnp.where(live, flat, slots)].set(
            matcher.finish(part, True, s), mode="drop")

    k, scores = jax.lax.while_loop(
        lambda carry: carry[0] * c < n, body,
        (jnp.int32(0), matcher.finish(acc_flat, False, 0.0)))
    return scores.reshape(rows, m), n, jnp.minimum(k * c, slots)


def prune_low_evidence(payload: dict, matcher: CascadeMatcher, w: int,
                       mask: jax.Array, threshold: float
                       ) -> Tuple[jax.Array, jax.Array]:
    """Meta-blocking comparison pruning (DESIGN.md §14): shrink the blocked
    band to pairs whose CHEAP cascade evidence clears ``threshold`` (a
    fraction of the cheap prefix's weight), BEFORE the expensive matcher.

    The evidence is always ``cheap_band_jnp`` — the identical jnp math both
    engines' gates use — so prune decisions are bit-identical between scan
    and pallas, and the GATE_EPS slack guarantees a pair exactly at the bar
    is kept (invariant 14: no gold pair at/above the bar is ever pruned).

    Returns (kept_mask, pruned_count).  Raises when the matcher has no
    kernel-supported cheap prefix — there is no evidence to prune on."""
    split = split_cascade(matcher, payload)
    if split is None:
        raise ValueError(
            "prune_policy='evidence' needs a matcher whose cascade starts "
            "with a kernel-supported cheap stage (cosine/jaccard on a "
            "present payload field); split_cascade found none")
    cheap = cheap_band_jnp(payload, split, w)               # (w-1, M)
    bar = threshold * (split.w_cos + split.w_jac) - GATE_EPS
    kept = mask & (cheap >= bar)
    pruned = band_pair_count(mask) - band_pair_count(kept)
    return kept, pruned.astype(jnp.int32)


def band_pair_count(mask: jax.Array) -> jax.Array:
    """Number of True slots in a boolean band — the device-side pair count
    (blocked or matched, depending on which band is passed)."""
    return jnp.sum(mask.astype(jnp.int32))


# -- window comparison cost model (host-side; the balance subsystem's oracle) -------
#
# Under the band layout every SN pair (i-d, i) is OWNED by its later element
# i (RepSN mode="native": pairs whose later element is native to the shard),
# so the entity at global sorted rank i contributes exactly min(i, w-1)
# comparisons to whichever shard it lands on.  Contiguous rank ranges then
# have a closed-form comparison count — the cost model `repro.balance` plans
# against.

def rank_prefix_comparisons(rank, w: int) -> np.ndarray:
    """Closed-form sum of the per-rank marginal cost min(i, w-1) over ranks
    i < rank: the total SN pairs among the first ``rank`` sorted entities.
    Vectorized; equals ``sn.expected_pair_count(rank, w)``."""
    r = np.asarray(rank, np.int64)
    ramp = np.minimum(r, w - 1)
    return ramp * (ramp - 1) // 2 + np.maximum(r - (w - 1), 0) * (w - 1)


def rank_for_prefix_comparisons(target: float, w: int) -> int:
    """Inverse of ``rank_prefix_comparisons``: the smallest rank whose prefix
    comparison count reaches ``target`` (the pair-space -> rank-space map the
    pairrange planner and blocksplit's mid-block splits use)."""
    wm1 = w - 1
    if target <= 0:
        return 0
    tri = wm1 * (wm1 - 1) // 2                 # prefix at rank w-1
    if target <= tri:
        e = int(np.ceil((1.0 + np.sqrt(1.0 + 8.0 * float(target))) / 2.0))
        while e * (e - 1) // 2 < target:       # guard float rounding
            e += 1
        while e > 0 and (e - 1) * (e - 2) // 2 >= target:
            e -= 1
        return e
    return wm1 + int(np.ceil((float(target) - tri) / wm1))


# -- band engines -------------------------------------------------------------------

_BAND_ENGINES: Dict[str, Type["BandEngine"]] = {}


def register_band_engine(name: str):
    """Class decorator: ``@register_band_engine("pallas")``."""
    def deco(cls):
        cls.name = name
        _BAND_ENGINES[name] = cls
        return cls
    return deco


def get_band_engine(name: str) -> "BandEngine":
    try:
        return _BAND_ENGINES[name]()
    except KeyError:
        raise ValueError(
            f"unknown band engine {name!r}; registered: "
            f"{available_band_engines()}") from None


def available_band_engines() -> Tuple[str, ...]:
    return tuple(sorted(_BAND_ENGINES))


class BandEngine:
    """One way to evaluate the sliding-window band of a sorted shard.

    ``band(ents, cfg, halo_len=..., mode=...)`` returns the per-part dict
    consumed by variants/runners/collectors:

      mask           (w-1, M) bool   blocked (candidate) pairs
      match          (w-1, M) bool   matcher-accepted pairs
      matcher_evals  ()       int32  evaluations of the cascade's last
                                     (most expensive) matcher ACTUALLY run
                                     (static-shape honest: scan scores its
                                     survivors in whole chunks; the pallas
                                     engine scores its whole cand_cap
                                     buffer, so there a finite cand_cap is
                                     what buys the FLOP cut)
      cand_count     ()       int32  cascade-gate survivors kept (0 for a
                                     one-matcher cascade — no gate)
      cand_overflow  ()       int32  gate survivors dropped by cand_cap
      scores         (w-1, M) f32    only when cfg.return_scores
    """

    name = "?"

    def band(self, ents: dict, cfg, *, halo_len: int, mode: str) -> dict:
        raise NotImplementedError

    def match_bound(self, ents: dict, cfg) -> Optional[int]:
        """Static upper bound on True entries in this engine's MATCH band,
        beyond the band size itself, or None.  Device-side pair emission
        uses it to shrink the match index buffer (the match band is orders
        of magnitude sparser than the blocked mask)."""
        return None

    @staticmethod
    def _src(ents: dict, cfg) -> Optional[jax.Array]:
        if getattr(cfg, "linkage", False) and "src" in ents["payload"]:
            return ents["payload"]["src"]
        return None


@register_band_engine("scan")
class ScanBandEngine(BandEngine):
    """Pure-JAX engine and the default: a scan over the w-1 distances
    scores every slot with every matcher but the last (``prefix_band``);
    the last matcher then runs only on the blocked slots the skip rule
    keeps (``score_survivors``), so every blocked pair gets exactly the
    score ``CascadeMatcher.combined`` gives it.  A one-matcher cascade
    cannot skip: it scores every slot in the scan."""

    def band(self, ents: dict, cfg, *, halo_len: int, mode: str) -> dict:
        w, matcher = cfg.window, cfg.matcher
        payload = ents["payload"]
        m = ents["valid"].shape[0]
        mask = band_mask(ents["valid"], w, halo_len=halo_len, mode=mode,
                         src=self._src(ents, cfg),
                         weff=payload.get("_weff"))
        pruned = jnp.int32(0)
        if getattr(cfg, "prune_policy", "off") == "evidence":
            mask, pruned = prune_low_evidence(payload, matcher, w, mask,
                                              cfg.prune_threshold)
        if len(matcher.matchers) < 2:
            scores, _ = band_scores(ents, w, matcher, halo_len=halo_len,
                                    mode=mode)
            survivors, evals = jnp.int32(0), jnp.int32((w - 1) * m)
        else:
            acc, alive = prefix_band(payload, matcher, w)
            scores, survivors, evals = score_survivors(
                payload, matcher, acc, alive & mask)
        scores = jnp.where(mask, scores, 0.0)
        out = {"mask": mask,
               "match": (scores >= matcher.threshold) & mask,
               "matcher_evals": evals.astype(jnp.int32),
               "cand_count": survivors.astype(jnp.int32),
               "cand_overflow": jnp.int32(0),
               "pruned": pruned}
        if cfg.return_scores:
            out["scores"] = scores
        return out


@dataclass(frozen=True)
class CascadeSplit:
    """How the matcher cascade maps onto the fused kernel: the cheap prefix
    (cosine and/or jaccard, kernel-supported) and the gate threshold for the
    UNNORMALIZED partial score the kernel emits."""
    feat_field: Optional[str]
    sig_field: Optional[str]
    w_cos: float
    w_jac: float
    tau_partial: float       # gate: cheap_partial >= tau_partial


def split_cascade(matcher: CascadeMatcher,
                  payload: dict) -> Optional[CascadeSplit]:
    """Split the cost-ordered cascade into a kernel-supported cheap prefix
    (one cosine field + one jaccard field, in cost order) and the remainder.
    Returns None when the FIRST matcher is unsupported (no cheap stage — the
    pallas engine then falls back to the scan engine)."""
    w_cos = w_jac = 0.0
    feat_field = sig_field = None
    prefix_w = 0.0
    for m in matcher.ordered():
        if m.kind == "cosine" and feat_field is None and m.field in payload:
            feat_field, w_cos = m.field, m.weight
        elif m.kind == "jaccard" and sig_field is None and m.field in payload:
            sig_field, w_jac = m.field, m.weight
        else:
            break
        prefix_w += m.weight
    if feat_field is None and sig_field is None:
        return None
    wsum = sum(m.weight for m in matcher.matchers)
    remaining = wsum - prefix_w
    # gate passes iff (cheap + remaining)/wsum >= threshold - GATE_EPS
    tau = (matcher.threshold - GATE_EPS) * wsum - remaining
    return CascadeSplit(feat_field=feat_field, sig_field=sig_field,
                        w_cos=w_cos, w_jac=w_jac, tau_partial=tau)


@register_band_engine("pallas")
class PallasBandEngine(BandEngine):
    """The §5.1 cascade end-to-end on device: fused cheap-band kernel ->
    cumsum compaction -> exact matcher on survivors only.

    cand_cap (cfg.cand_cap; 0 = full band, never overflows) bounds the
    survivor buffer exactly like SRP's cap_link bounds the shuffle:
    candidates past the cap are dropped and counted in ``cand_overflow``.
    Dropped candidates can only LOSE matches (blocked pairs come from the
    pre-compaction mask), mirroring the paper's capacity/overflow
    accounting.

    Because XLA shapes are static, the expensive stage scores the WHOLE
    cand_cap buffer — cand_cap is therefore the FLOP *and memory* lever:
    cand_cap=0 (parity-safe default) keeps a full-band buffer, saving
    nothing on the expensive stage and gathering O(w*M*F) payload slices
    (vs the scan engine's O(M*F) live set — large w*M needs a finite cap);
    a finite cap sized above the survivor count (see DESIGN.md §6) gets
    the cascade cut with zero overflow."""

    def match_bound(self, ents: dict, cfg) -> Optional[int]:
        """Accepted matches are scattered from the cand_cap buffer, so a
        finite cand_cap bounds the match band's True count exactly — the
        emitted match index buffer never needs more slots (unless the
        cascade falls back to the scan engine, where no such bound holds)."""
        cand_cap = cfg.cand_cap or 0   # None (unresolved auto) acts like 0
        if cand_cap > 0 and \
                split_cascade(cfg.matcher, ents["payload"]) is not None:
            return cand_cap
        return None

    def band(self, ents: dict, cfg, *, halo_len: int, mode: str) -> dict:
        from repro.kernels import ops

        split = split_cascade(cfg.matcher, ents["payload"])
        if split is None:     # no kernel-supported cheap stage
            return ScanBandEngine().band(ents, cfg, halo_len=halo_len,
                                         mode=mode)
        w = cfg.window
        valid = ents["valid"]
        m = valid.shape[0]
        payload = ents["payload"]
        mask = band_mask(valid, w, halo_len=halo_len, mode=mode,
                         src=self._src(ents, cfg),
                         weff=payload.get("_weff"))
        pruned = jnp.int32(0)
        if getattr(cfg, "prune_policy", "off") == "evidence":
            # prune BEFORE the gate: the blocked set itself shrinks (the
            # reduction-ratio lever), and the gate then only sees survivors
            mask, pruned = prune_low_evidence(payload, cfg.matcher, w, mask,
                                              cfg.prune_threshold)

        if cfg.band_interpret is None and ops.default_interpret():
            # auto mode off-TPU: band-shaped jnp cheap stage (the tile
            # kernel's 2*block_i scores per row only pay off on the MXU;
            # band_interpret=True still forces the interpreted kernel —
            # the kernel-validation path the parity tests exercise)
            cheap_rows = cheap_band_jnp(payload, split, w)  # (w-1, M)
        else:
            feat = payload[split.feat_field] if split.feat_field else \
                jnp.zeros((m, 1), jnp.float32)
            sig = payload[split.sig_field] if split.sig_field else \
                jnp.zeros((m, 1), jnp.uint32)
            with jax.named_scope(BAND_CHEAP):
                cheap = ops.fused_cheap_band(
                    feat, sig, window=w - 1, w_cos=split.w_cos,
                    w_jac=split.w_jac, block_i=cfg.band_block,
                    interpret=cfg.band_interpret)
                cheap_rows = cheap.T
        gate = (cheap_rows >= split.tau_partial) & mask     # (w-1, M)

        cand_cap = cfg.cand_cap or 0   # None (unresolved auto) acts like 0
        cap = cand_cap if cand_cap > 0 else (w - 1) * m
        cand_i, cand_d, cand_valid, n_cand, overflow = \
            compact_candidates(gate, cap)
        score = score_candidates(ents, cand_i, cand_d, cand_valid,
                                 cfg.matcher)
        accept = cand_valid & (score >= cfg.matcher.threshold)

        flat_idx = (cand_d - 1) * m + cand_i
        safe = jnp.where(cand_valid, flat_idx, (w - 1) * m)  # OOB -> dropped
        match = jnp.zeros(((w - 1) * m,), bool).at[safe].set(
            accept, mode="drop").reshape(w - 1, m)
        out = {"mask": mask, "match": match,
               # static shapes mean the expensive stage scores the whole
               # cand_cap buffer (invalid slots included) — report THAT,
               # not the survivor count: with cand_cap=0 the buffer is the
               # full band and there is no expensive-stage saving
               "matcher_evals": jnp.int32(cap),
               "cand_count": jnp.minimum(n_cand, cap).astype(jnp.int32),
               "cand_overflow": overflow.astype(jnp.int32),
               "pruned": pruned}
        if cfg.return_scores:
            # survivors carry their exact rescored value; gated-out slots are
            # 0 (they are sub-threshold by construction)
            out["scores"] = jnp.zeros(((w - 1) * m,), jnp.float32).at[
                safe].set(jnp.where(cand_valid, score, 0.0),
                          mode="drop").reshape(w - 1, m)
        return out
