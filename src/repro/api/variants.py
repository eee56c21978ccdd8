"""Variant registry — the SN strategies behind ``repro.api.resolve``.

Each variant owns three hooks:

  * ``shard_program(ents, bounds, r, axis, cfg, cap_link=None)``  the
    per-shard collective program (runs under vmap-with-axis-name or
    shard_map); returns a dict of per-shard outputs with at least
    ``overflow``, ``load`` and one or more band parts (``main``, optionally
    ``boundary``).  ``cap_link`` is the planner-provided shuffle capacity
    (repro.balance ShardPlan); None derives it from ``cfg.cap_factor``.
  * ``collect(out)``  turn the stacked runner output into host pair sets
    (blocked + matched), one slot map per band part, unioned across parts
  * ``sequential_pairs(keys, eids, bounds, w, part=None)``  the HOST oracle
    with this variant's semantics (SRP: per-partition windows — boundary
    pairs are missed by design; RepSN/JobSN: the complete sequential SN
    pair set).  ``part`` carries per-entity shard ids from a rank-granular
    ShardPlan; the sequential runner always passes it.

New variants register with ``@register_variant("name")`` — no dispatch code
anywhere else changes (this replaces the old if/elif in pipeline.sn_shard).
"""
from __future__ import annotations

from typing import Dict, Set, Tuple, Type

import jax
import numpy as np

from repro.core import jobsn as J
from repro.core import repsn as R
from repro.core import sn
from repro.core import srp as S
from repro.core import window as W
from repro.api import results as RES
from repro.obs.scopes import (BAND_SELECT, SHUFFLE, SHUFFLE_EXCHANGE,
                              SHUFFLE_HALO)

_REGISTRY: Dict[str, Type["VariantBase"]] = {}


def register_variant(name: str):
    """Class decorator: ``@register_variant("repsn")``."""
    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_variant(name: str) -> "VariantBase":
    """Instantiate the registered variant named ``name`` (raises
    ``ValueError`` listing the registry when unknown)."""
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(f"unknown SN variant {name!r}; registered: "
                         f"{available_variants()}") from None


def available_variants() -> Tuple[str, ...]:
    """Sorted names of every registered SN variant."""
    return tuple(sorted(_REGISTRY))


def link_capacity(cap0: int, r: int, cfg, cap_link: int = None) -> int:
    """The SRP shuffle's per-(mapper, destination) bucket capacity for
    mapper shards of ``cap0`` rows.  Precedence: the planner-provided
    ``cap_link`` (exact, from the ShardPlan) > ``cfg.cap_factor`` > full
    capacity (never overflows)."""
    if cap_link is not None:
        return cap_link
    return cap0 if cfg.cap_factor <= 0 else \
        max(1, int(np.ceil(cap0 * cfg.cap_factor / r)))


class VariantBase:
    """Shared SRP front-end + band evaluation; subclasses add the variant's
    boundary-handling step."""

    name = "?"
    parts: Tuple[str, ...] = ("main",)
    halo_slices = False        # True: slices w-1 boundary slots per shard
    boundary_complete = True   # sequential_pairs == full SN oracle

    # -- device side ---------------------------------------------------------

    def shard_program(self, ents: dict, bounds: jax.Array, r: int,
                      axis: str, cfg, cap_link: int = None) -> dict:
        """The per-shard collective program (runs under vmap-with-axis-name
        or shard_map): SRP shuffle + this variant's ``_windows`` step.
        Returns the per-shard output dict (``overflow``, ``load``, one band
        part per ``self.parts``).  ``cap_link`` is the planner-provided
        shuffle capacity; None derives it from ``cfg.cap_factor``.

        Device stages (``repro.obs.scopes``): the SRP shuffle and the
        variant's halo or boundary exchange run in ``shuffle`` (its
        sub-stages ``shuffle/route``, ``shuffle/exchange`` and
        ``shuffle/sort`` in ``srp.srp_shard``, the load ``all_gather`` in
        ``shuffle/exchange``, the halo or boundary group in
        ``shuffle/halo``), each band in ``band/select`` (``_band``)."""
        cap_link = link_capacity(ents["key"].shape[0], r, cfg, cap_link)
        if self.halo_slices and cfg.window - 1 > r * cap_link:
            raise ValueError(
                f"variant {self.name!r} slices w-1 boundary slots per "
                f"shard, but window={cfg.window} exceeds the per-shard "
                f"buffer of {r * cap_link} slots; reduce window or "
                f"num_shards, raise cap_factor, or use runner='sequential'")
        with jax.named_scope(SHUFFLE):
            sorted_ents, overflow = S.srp_shard(ents, bounds, r, axis,
                                                cap_link)
            with jax.named_scope(SHUFFLE_EXCHANGE):
                load = S.local_load(sorted_ents, axis)
        out = {"overflow": overflow, "load": load}
        out.update(self._windows(sorted_ents, r, axis, cfg))
        return out

    def _windows(self, sorted_ents: dict, r: int, axis: str, cfg) -> dict:
        raise NotImplementedError

    def halo_rows(self, r: int, cfg) -> int:
        """Rows the variant's halo or boundary exchange sends between
        shards in one run (the wrapped ring edge, invalidated, not
        counted)."""
        return 0

    def _band(self, e: dict, halo_len: int, mode: str, cfg) -> dict:
        """Evaluate this part's window band with the configured BandEngine
        (scan oracle or the Pallas cascade — see core/window.py); the engine
        owns masking (incl. the linkage cross-source rule), matching, and
        the cascade's candidate/overflow accounting.

        With ``cfg.emit == "pairs"`` the boolean bands never leave the
        device: each is compacted into a packed flat-index buffer
        (``window.emit_band_indices`` — capacity ``cfg.pair_cap``, overflow
        counted) and the part carries only those buffers plus the (M,) eid
        vector for host translation, instead of O(w*M) bands + full payload
        slices.

        The band runs inside the ``band/select`` device scope, and the
        engine opens ``band/align``, ``band/cheap`` and ``band/expensive``
        inside it (``repro.obs.scopes``)."""
        engine = W.get_band_engine(getattr(cfg, "band_engine", "scan"))
        with jax.named_scope(BAND_SELECT):
            out = engine.band(e, cfg, halo_len=halo_len, mode=mode)
        if getattr(cfg, "emit", "band") == "pairs":
            m = e["valid"].shape[0]
            full = (cfg.window - 1) * m
            pair_cap = cfg.pair_cap or 0   # None (unresolved auto) -> full
            cap = min(pair_cap, full) if pair_cap > 0 else full
            bound = engine.match_bound(e, cfg)     # match band is sparser:
            caps = {"mask": cap,                   # engines with a provable
                    "match": cap if bound is None  # bound (pallas cand_cap)
                    else min(cap, bound)}          # shrink its buffer
            for field in ("mask", "match"):
                with jax.named_scope(BAND_SELECT):
                    emitted = W.emit_band_indices(out.pop(field),
                                                  caps[field])
                out.update({f"{field}_idx": emitted["idx"],
                            f"{field}_n": emitted["n"],
                            f"{field}_overflow": emitted["overflow"]})
            out["eid"] = e["eid"]
        else:
            out["ents"] = e
        out["halo_len"] = halo_len
        return out

    # -- host side -----------------------------------------------------------

    def collect(self, out: dict) -> RES.CollectedPairs:
        """Stacked runner output -> deduplicated PACKED pair arrays (uint64
        ``(lo << 32) | hi``); the RunnerOutcome boundary wraps them in
        ``results.PairSet``s.  Each part gives both sets at once
        (``results.packed_pair_sets_from_part``: one slot map for its
        bands, or its device-emitted index buffers under emit="pairs").  A
        variant with one part returns that part's arrays as they are;
        several parts are unioned (``results.union_packed``), so a pair
        emitted by several parts counts once."""
        sets = [RES.packed_pair_sets_from_part(out[p])
                for p in self.parts if p in out]
        return RES.CollectedPairs(
            blocked=RES.union_packed([b for b, _ in sets]),
            matched=RES.union_packed([m for _, m in sets]))

    def sequential_pairs(self, keys: np.ndarray, eids: np.ndarray,
                         bounds: np.ndarray, w: int,
                         part: np.ndarray = None,
                         weff: np.ndarray = None) -> Set[Tuple[int, int]]:
        """Host oracle with this variant's semantics (boundary-complete
        variants return the full sequential SN pair set).  ``part``: per-
        entity shard ids from a rank-granular ShardPlan — overrides the
        key->shard map for variants whose pair set depends on the
        partitioning (SRP).  ``weff``: per-entity effective windows
        (adaptive policy) — the later sorted element's weff bounds each
        pair's distance, overriding the constant ``w``."""
        if weff is not None:
            return sn.adaptive_sn_pairs(keys, eids, weff)
        return sn.sequential_sn_pairs(keys, eids, w)


@register_variant("srp")
class SrpVariant(VariantBase):
    """Plain Sorted Reduce Partitions (paper §4.1): window within each
    partition only; misses (r-1)*w*(w-1)/2 boundary pairs by design."""

    boundary_complete = False

    def _windows(self, sorted_ents, r, axis, cfg):
        return {"main": self._band(sorted_ents, 0, "all", cfg)}

    def sequential_pairs(self, keys, eids, bounds, w, part=None, weff=None):
        """SRP's host oracle: SN pairs WITHIN each partition only (``part``
        per-entity ids win over the ``bounds`` key map) — boundary pairs
        are missed by design, exactly like the device program."""
        if part is None:
            part = np.searchsorted(np.asarray(bounds), keys, side="left")
        pairs: Set[Tuple[int, int]] = set()
        for p in np.unique(part):
            sel = part == p
            if weff is not None:
                pairs |= sn.adaptive_sn_pairs(keys[sel], eids[sel],
                                              np.asarray(weff)[sel])
            else:
                pairs |= sn.sequential_sn_pairs(keys[sel], eids[sel], w)
        return pairs


@register_variant("repsn")
class RepSNVariant(VariantBase):
    """SN with replication (paper §4.3): halo-prepend the predecessor's last
    w-1 entities, then window with mode="native"."""

    halo_slices = True

    def _windows(self, sorted_ents, r, axis, cfg):
        with jax.named_scope(SHUFFLE), jax.named_scope(SHUFFLE_HALO):
            combined, hl = R.repsn_combine(sorted_ents, cfg.window, r, axis,
                                           hops=cfg.hops)
        return {"main": self._band(combined, hl, "native", cfg)}

    def halo_rows(self, r, cfg):
        """w-1 rows per hop over each of the r-1 forward ring edges."""
        return (r - 1) * (cfg.window - 1) * cfg.hops


@register_variant("jobsn")
class JobSNVariant(VariantBase):
    """SN with an additional phase (paper §4.2): plain SRP window plus a
    boundary-group pass restricted to cross-boundary pairs."""

    parts = ("main", "boundary")
    halo_slices = True

    def _windows(self, sorted_ents, r, axis, cfg):
        with jax.named_scope(SHUFFLE), jax.named_scope(SHUFFLE_HALO):
            group, hl = J.boundary_group(sorted_ents, cfg.window, r, axis)
        return {"main": self._band(sorted_ents, 0, "all", cfg),
                "boundary": self._band(group, hl, "cross", cfg)}

    def halo_rows(self, r, cfg):
        """Each successor's first w-1 rows, over the r-1 backward edges."""
        return (r - 1) * (cfg.window - 1)
