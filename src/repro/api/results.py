"""Typed result objects + host-side pair extraction.

Replaces the raw nested dicts the old pipeline returned: results carry the
pair sets, per-shard load, overflow accounting, and (optionally) blocking
quality metrics computed against the sequential oracle.

Internally pairs travel as PACKED uint64 arrays — ``(lo << 32) | hi`` with
``lo < hi`` eids — sorted and deduplicated.  Collection of a band part
packs every band slot once from the eid vector (the slot map), takes each
pair set by one boolean compress of that map, and sorts it in place
(linear passes plus one sort) instead of building millions of Python
tuples.  The public ``RunnerOutcome``/``BlockingResult``
boundary wraps the arrays in ``PairSet``s, which equal and hash like the
frozensets of (lo, hi) tuples and make a tuple only when iterated.
"""
from __future__ import annotations

import itertools
from collections import abc
from dataclasses import dataclass
from typing import AbstractSet, FrozenSet, NamedTuple, Optional, Set, Tuple

import numpy as np

from repro import obs as OBS
from repro.resilience.retry import ResilienceStats

Pair = Tuple[int, int]

PACKED_DTYPE = np.uint64


def pack_pairs(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Elementwise (a, b) eid pairs -> canonical packed uint64
    ``(min << 32) | max``.  Eids must be non-negative and < 2^32."""
    a = np.asarray(a, PACKED_DTYPE)
    b = np.asarray(b, PACKED_DTYPE)
    lo = np.minimum(a, b)
    hi = np.maximum(a, b)
    return (lo << PACKED_DTYPE(32)) | hi


def unpack_pairs(packed: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Packed uint64 -> (lo, hi) int64 arrays."""
    packed = np.asarray(packed, PACKED_DTYPE)
    lo = (packed >> PACKED_DTYPE(32)).astype(np.int64)
    hi = (packed & PACKED_DTYPE(0xFFFFFFFF)).astype(np.int64)
    return lo, hi


def pack_pair_set(pairs: Set[Pair]) -> np.ndarray:
    """Host pair set -> sorted deduplicated packed array (a ``PairSet``'s
    own array, without materializing its pairs)."""
    if isinstance(pairs, PairSet):
        return pairs.packed
    if not pairs:
        return np.empty((0,), PACKED_DTYPE)
    flat = np.fromiter((c for p in pairs for c in p), np.int64,
                       2 * len(pairs)).reshape(-1, 2)
    return np.unique(pack_pairs(flat[:, 0], flat[:, 1]))


# CPython's (3.8+, 64-bit) hash constants: the xxHash-style tuple hash and
# the frozenset hash of Objects/setobject.c
_U64 = (1 << 64) - 1
_XXPRIME_1 = np.uint64(11400714785074694791)
_XXPRIME_2 = np.uint64(14029467366897019727)
_XXPRIME_5 = np.uint64(2870177450012600261)
_TUPLE2_LEN = np.uint64(2 ^ (2870177450012600261 ^ 3527539))
_CHUNK = 1 << 16        # pairs per step of the hash and of iteration
_MATERIALIZED = "pairs.materialized"
_SLOTS = "collect.slots"
_DUPLICATES = "collect.duplicates"


def _rotl31(acc: np.ndarray, tmp: np.ndarray) -> None:
    np.left_shift(acc, np.uint64(31), out=tmp)
    np.right_shift(acc, np.uint64(33), out=acc)
    np.bitwise_or(acc, tmp, out=acc)


def _tuple_hashes(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """``hash((lo, hi))`` elementwise, as uint64 bits, for eids whose int
    hash is the eid itself (0 <= eid < 2**61 - 1)."""
    acc = np.multiply(lo, _XXPRIME_2, dtype=np.uint64)
    tmp = np.empty_like(acc)
    np.add(acc, _XXPRIME_5, out=acc)
    _rotl31(acc, tmp)
    np.multiply(acc, _XXPRIME_1, out=acc)
    np.multiply(hi, _XXPRIME_2, out=tmp)
    np.add(acc, tmp, out=acc)
    _rotl31(acc, tmp)
    np.multiply(acc, _XXPRIME_1, out=acc)
    np.add(acc, _TUPLE2_LEN, out=acc)
    acc[acc == np.uint64(_U64)] = 1546275796    # -1 is CPython's error code
    return acc


def _shuffled_xor(h: np.ndarray) -> int:
    """XOR over ``_shuffle_bits`` of each element hash in ``h`` (clobbered)."""
    tmp = np.left_shift(h, np.uint64(16))
    np.bitwise_xor(h, np.uint64(89869747), out=h)
    np.bitwise_xor(h, tmp, out=h)
    np.multiply(h, np.uint64(3644798167), out=h)
    return int(np.bitwise_xor.reduce(h)) if h.size else 0


def _frozenset_hash(shuffled_xor: int, size: int) -> int:
    """CPython's frozenset hash from the XOR of its entries' shuffled
    hashes and its size (the size term and the final dispersion)."""
    h = shuffled_xor ^ (((size + 1) * 1927868237) & _U64)
    h ^= (h >> 11) ^ (h >> 25)
    h = (h * 69069 + 907133923) & _U64
    if h == _U64:
        h = 590923713
    return h - (1 << 64) if h >> 63 else h


def _union(a: np.ndarray, b: np.ndarray, assume_unique: bool) -> np.ndarray:
    return np.union1d(a, b)


def _count(name: str, n: int) -> None:
    """Add ``n`` to the counter ``name``, under a tracer."""
    tracer = OBS.current_tracer()
    if tracer is not None:
        tracer.metrics.counter(name).inc(n)


class PairSet(abc.Set):
    """Immutable set of (lo, hi) eid pairs over a sorted, deduplicated
    packed uint64 array (``pack_pairs``): the public pair set of every
    result.  It equals, and hashes like, the frozenset of the same tuples,
    so either serves as the other's dict key; ``isinstance(s, frozenset)``
    is False.

    ``len``, ``in``, ``hash``, ``==`` with another ``PairSet`` and the
    operators ``& | - ^`` between two ``PairSet``s work on the array and
    return ``PairSet``s; nothing builds a Python tuple.  Iteration yields
    (lo, hi) tuples of Python ints in sorted order.  Algebra with any other
    set or iterable materializes this set as a frozenset and returns a
    frozenset (a ``set`` on the left of a reflected operator keeps its
    type).  Under a tracer, each iteration or such fallback adds ``len``
    to the ``pairs.materialized`` counter.

    The array is held as a read-only view, without a copy: whoever hands
    it over must not write to it afterwards."""

    __slots__ = ("_packed", "_hash")

    def __init__(self, packed: np.ndarray):
        a = np.asarray(packed, PACKED_DTYPE).view()
        a.flags.writeable = False
        self._packed = a
        self._hash = None

    @property
    def packed(self) -> np.ndarray:
        """The sorted deduplicated packed uint64 array (read-only)."""
        return self._packed

    def __len__(self) -> int:
        return self._packed.size

    def __contains__(self, pair) -> bool:
        hash(pair)          # an unhashable probe raises, as for a frozenset
        if not isinstance(pair, tuple) or len(pair) != 2:
            return False
        lo, hi = pair
        # a frozenset finds the tuple iff each element hashes like and
        # equals the stored eid, whose hash is the eid itself
        a, b = hash(lo), hash(hi)
        if not (0 <= a <= 0xFFFFFFFF and 0 <= b <= 0xFFFFFFFF) \
                or lo != a or hi != b:
            return False
        key = np.uint64((a << 32) | b)
        i = int(np.searchsorted(self._packed, key))
        return i < self._packed.size and bool(self._packed[i] == key)

    def _chunk(self, i: int):
        p = self._packed[i:i + _CHUNK]
        return zip((p >> np.uint64(32)).tolist(),
                   (p & np.uint64(0xFFFFFFFF)).tolist())

    def __iter__(self):
        _count(_MATERIALIZED, len(self))
        return itertools.chain.from_iterable(
            map(self._chunk, range(0, self._packed.size, _CHUNK)))

    def __hash__(self) -> int:
        if self._hash is None:
            x = 0
            for i in range(0, self._packed.size, _CHUNK):
                p = self._packed[i:i + _CHUNK]
                x ^= _shuffled_xor(_tuple_hashes(
                    p >> np.uint64(32), p & np.uint64(0xFFFFFFFF)))
            self._hash = _frozenset_hash(x, self._packed.size)
        return self._hash

    def __eq__(self, other):
        if isinstance(other, PairSet):
            return np.array_equal(self._packed, other._packed)
        return super().__eq__(other)

    def __reduce__(self):
        return PairSet, (self._packed,)

    def __repr__(self) -> str:
        return f"PairSet(<{len(self)} pairs>)"

    @classmethod
    def _from_iterable(cls, it):
        return frozenset(it)

    def _algebra(self, other, vectorised, name: str):
        if isinstance(other, PairSet):
            return PairSet(vectorised(self._packed, other._packed,
                                      assume_unique=True))
        if not isinstance(other, abc.Iterable):
            return NotImplemented
        if not isinstance(other, (set, frozenset)):
            other = frozenset(other)
        return getattr(frozenset(self), name)(other)

    def _reflected(self, other, name: str):
        if not isinstance(other, abc.Iterable):
            return NotImplemented
        if not isinstance(other, (set, frozenset)):
            other = frozenset(other)
        return getattr(other, name)(frozenset(self))

    def __and__(self, other):
        return self._algebra(other, np.intersect1d, "__and__")

    def __or__(self, other):
        return self._algebra(other, _union, "__or__")

    def __sub__(self, other):
        return self._algebra(other, np.setdiff1d, "__sub__")

    def __xor__(self, other):
        return self._algebra(other, np.setxor1d, "__xor__")

    def __rand__(self, other):
        return self._reflected(other, "__and__")

    def __ror__(self, other):
        return self._reflected(other, "__or__")

    def __rsub__(self, other):
        return self._reflected(other, "__sub__")

    def __rxor__(self, other):
        return self._reflected(other, "__xor__")


def packed_to_pair_set(packed: np.ndarray) -> PairSet:
    """Sorted deduplicated packed array -> public ``PairSet``, with no copy
    and no Python pair object made."""
    return PairSet(packed)


# the historical name: the public sets were frozensets of tuples
packed_to_frozenset = packed_to_pair_set


class CollectedPairs(NamedTuple):
    """Deduplicated packed uint64 pair arrays (see ``pack_pairs``)."""
    blocked: np.ndarray
    matched: np.ndarray


@dataclass(frozen=True)
class BalanceMetrics:
    """Planned vs realized per-shard load under a ``repro.balance``
    ShardPlan (the skew telemetry of ISSUE 3: wall-clock is the MAX of
    per-shard matcher work, so the imbalance ratio max/mean is the direct
    parallel-efficiency loss).

    planned_*            what the partition planner promised (profile-based)
    realized_*           what the run delivered (post-shuffle valid counts;
                         comparisons re-derived through the window cost
                         model from the realized contiguous rank layout)
    imbalance_*          max/mean of per-shard comparison counts (1.0 =
                         perfectly level)
    straggler_shard      shard id with the largest realized comparison load
    halo_entities        total entities replicated across boundaries
    cap_link             planned per-(mapper, dest) shuffle capacity
                         (None: capacity derived from cfg.cap_factor)
    """
    partitioner: str
    planned_load: Tuple[int, ...]
    realized_load: Tuple[int, ...]
    planned_comparisons: Tuple[int, ...]
    realized_comparisons: Tuple[int, ...]
    imbalance_planned: float
    imbalance_realized: float
    straggler_shard: int
    halo_entities: int
    cap_link: Optional[int] = None


@dataclass(frozen=True)
class PerfStats:
    """Execution-cache telemetry for one ``resolve``/``link`` call (the
    steady-state contract of ISSUE 4: after warmup, every call should be
    ``cache_hits > 0, cache_misses == traces == 0``).

    cache_hits      executables reused from the repro.perf cache
    cache_misses    executables built (== programs lowered) by this call
    traces          jit traces actually performed (a healthy cache has
                    traces == cache_misses; more means a keying bug let one
                    executable see two shapes)
    cache_entries   total executables resident after the call
    """
    cache_hits: int
    cache_misses: int
    traces: int
    cache_entries: int

    @property
    def steady_state(self) -> bool:
        """True when the call ran entirely from cached executables — at
        least one hit and no build/trace.  A bypassed cache (jit_cache=
        False, legacy shims) reports all-zero counters and is NOT steady
        state: it re-traced every call."""
        return self.cache_hits > 0 and self.traces == 0 and \
            self.cache_misses == 0


@dataclass(frozen=True)
class ERMetrics:
    """Blocking quality vs the sequential-SN oracle (the standard blocking
    metrics; the paper reports |B| and completeness of the variants).

    reduction_ratio     1 - |blocked| / |all comparable pairs|
    pairs_completeness  |blocked ∩ oracle| / |oracle|
    balance             planned-vs-realized shard load (profile-backed runs)
    resilience          overflow-recovery telemetry (retries / escalations /
                        final caps — DESIGN.md §11)
    """
    reduction_ratio: float
    pairs_completeness: float
    oracle_pairs: int
    total_comparisons: int
    balance: Optional[BalanceMetrics] = None
    resilience: Optional[ResilienceStats] = None
    quality: Optional[object] = None  # ground-truth QualityMetrics
    #                                   (repro.quality.evaluate attaches
    #                                   PC/PQ/RR/F vs a labeled corpus's
    #                                   gold pair set — None unless a truth
    #                                   set was supplied)


@dataclass(frozen=True)
class BlockingResult:
    """Outcome of the blocking stage (candidate generation)."""
    pairs: AbstractSet[Pair]        # blocked (candidate) pairs, (lo, hi)
    #                                 eids: a PairSet; a frozenset for the
    #                                 multi-pass union and linkage
    load: Tuple[int, ...]           # per-shard valid counts (skew telemetry)
    overflow: int                   # entities dropped by capacity limits
    variant: str
    runner: str
    window: int
    num_shards: int
    cand_count: Tuple[int, ...] = ()  # per-shard cascade-gate survivors
    cand_overflow: int = 0          # cascade survivors dropped by cand_cap
    matcher_evals: int = 0          # last-matcher evaluations actually run
    pair_overflow: int = 0          # emitted pair-index slots dropped by
    #                                 pair_cap (emit="pairs"; can lose
    #                                 blocked pairs AND matches — counted,
    #                                 never silent)
    pruned: int = 0                 # band slots dropped by meta-blocking
    #                                 comparison pruning (prune_policy=
    #                                 "evidence"): deliberate low-evidence
    #                                 filtering, accounted like overflow but
    #                                 never retried

    @property
    def max_load(self) -> int:
        """Largest per-shard valid count — the straggler's load (wall-clock
        scales with this, not the mean)."""
        return max(self.load) if self.load else 0

    @property
    def total_load(self) -> int:
        """Sum of per-shard valid counts (== entities that survived the
        shuffle; compare with the input n to spot capacity overflow)."""
        return sum(self.load)


@dataclass(frozen=True)
class ERResult:
    """Full entity-resolution outcome: blocking + matching (+ metrics).

    ``balance`` is populated whenever the run executed under a profile-
    backed ShardPlan (any ``cfg.partitioner`` default-bounds run); runs on
    explicit raw bounds have no plan to compare against and carry None."""
    blocking: BlockingResult
    matches: AbstractSet[Pair]      # matcher-accepted pairs: a PairSet; a
    #                                 frozenset after linkage untagging
    metrics: Optional[ERMetrics] = None
    balance: Optional[BalanceMetrics] = None
    perf: Optional[PerfStats] = None  # executable-cache telemetry for this
    #                                   call (hits / misses / traces)
    resilience: Optional[ResilienceStats] = None  # overflow-recovery
    #                                   telemetry (retries / escalations /
    #                                   final caps — DESIGN.md §11)
    trace: Optional[object] = None  # repro.obs.TraceReport when the run
    #                                 executed under ERConfig.trace=True
    #                                 (spans + metrics + the legacy stats
    #                                 unified — DESIGN.md §12)

    @property
    def pairs(self) -> AbstractSet[Pair]:
        """The blocked (candidate) pair set — sugar for blocking.pairs."""
        return self.blocking.pairs


@dataclass(frozen=True)
class MultiPassResult:
    """Outcome of a multi-pass run (``ERConfig.passes`` non-empty).

    One full ER pipeline execution per ``SortKeySpec``; the top-level
    ``blocking``/``matches`` hold the UNION across passes (the recall
    lever: a pair blocked by any pass is blocked), while ``passes`` keeps
    each pass's complete single-pass ``ERResult`` — per-pass loads,
    overflow, balance, and perf stay individually auditable.  The union
    ``blocking`` aggregates accounting additively (overflow / cand_overflow
    / pair_overflow / matcher_evals are summed; ``load`` is left empty —
    per-pass shard loads live on ``passes[i].blocking.load``).  ``metrics``
    (when requested) compares the union pair set against the union of the
    per-pass sequential oracles."""
    passes: Tuple[ERResult, ...]
    pass_names: Tuple[str, ...]
    blocking: BlockingResult
    matches: FrozenSet[Pair]
    metrics: Optional[ERMetrics] = None
    resilience: Optional[ResilienceStats] = None  # summed across passes
    trace: Optional[object] = None  # repro.obs.TraceReport spanning every
    #                                 pass (ERConfig.trace=True)

    @property
    def pairs(self) -> FrozenSet[Pair]:
        """The union blocked pair set — sugar for blocking.pairs."""
        return self.blocking.pairs

    def pass_result(self, name: str) -> ERResult:
        """The single-pass ERResult for the pass named ``name``."""
        try:
            return self.passes[self.pass_names.index(name)]
        except ValueError:
            raise KeyError(f"no pass named {name!r}; passes: "
                           f"{self.pass_names}") from None


# -- pair extraction (band mask -> host pairs) --------------------------------------

# the leaves of one stacked part that host collection reads, besides the
# eid vector: the bands or the emitted index buffers, and the accounting
COLLECTED_FIELDS = ("mask", "match", "mask_idx", "mask_n", "mask_overflow",
                    "match_idx", "match_n", "match_overflow", "cand_count",
                    "cand_overflow", "matcher_evals", "pruned")


def collected_leaves(out: dict, parts) -> dict:
    """The subtree of a stacked shard-program output that collection
    (``variants.collect`` and the runners' accounting) reads: ``load``,
    ``overflow`` and, per part in ``parts``, the ``eid`` vector and the
    ``COLLECTED_FIELDS`` it holds.  The payload, keys, validity and scores
    are left out, so a device-to-host fetch of this subtree moves only
    what is read."""
    keep = {"load": out["load"], "overflow": out["overflow"]}
    for p in parts:
        if p in out:
            part = out[p]
            keep[p] = {k: part[k] for k in COLLECTED_FIELDS if k in part}
            keep[p]["eid"] = _eid(part)
    return keep


def _eid(part: dict):
    """A part's (r, M) eid vector: top level (device-emitted pairs and
    ``collected_leaves``) or inside the part's entities."""
    return part["eid"] if "eid" in part else part["ents"]["eid"]


def packed_pairs_from_idx(part: dict, field: str = "match") -> np.ndarray:
    """Device-emitted packed indices -> deduplicated packed pair array.

    ``part``: stacked per-shard output with ``eid`` (r, M) plus the emitted
    buffers ``<field>_idx`` (r, cap) int32 flat band indices ``(d-1)*M+i``
    and ``<field>_n`` (r,) valid counts (window.emit_band_indices).  Eid
    translation is vectorized: one mask + two fancy gathers + ``np.unique``
    over ~cap slots instead of an O(r*w*M) band scan."""
    eid = np.asarray(_eid(part))                          # (r, M)
    idx = np.asarray(part[field + "_idx"])                # (r, cap)
    cnt = np.asarray(part[field + "_n"]).reshape(-1)      # (r,)
    m = eid.shape[1]
    keep = np.arange(idx.shape[1])[None, :] < cnt[:, None]
    ss, pp = np.nonzero(keep)
    if ss.size == 0:
        return np.empty((0,), PACKED_DTYPE)
    flat = idx[ss, pp].astype(np.int64)
    d = flat // m + 1
    i = flat % m
    a = eid[ss, i]
    b = eid[ss, i + d]              # in-bounds: band masks force i + d < M
    return np.unique(pack_pairs(a, b))


def packed_pairs_from_part(part: dict, field: str = "match") -> np.ndarray:
    """Collect a part through whichever representation it carries:
    device-emitted index buffers (emit="pairs") or boolean bands."""
    return packed_pair_sets_from_part(part, (field,))[0]


def packed_pair_sets_from_part(part: dict, fields=("mask", "match")
                               ) -> Tuple[np.ndarray, ...]:
    """One sorted deduplicated packed array per field of ``fields``, from
    the part's index buffers (emit="pairs") or, through one slot map, from
    its bands (``packed_pair_sets_from_band``)."""
    if all(f + "_idx" in part for f in fields):
        return tuple(packed_pairs_from_idx(part, f) for f in fields)
    return packed_pair_sets_from_band(part, fields)


def packed_pairs_from_band(part: dict, field: str = "match") -> np.ndarray:
    """Vectorized band -> deduplicated packed pair array (the hot host path).

    ``part``: stacked per-shard output dict with ``eid`` (r, M), at its top
    level or under ``ents``, and a boolean band ``field`` of shape
    (r, w-1, M); band[s, d-1, i] pairs slot i with slot i+d of shard s.
    One slot map, one boolean compress, one in-place sort — no Python pair
    objects anywhere on the path (``packed_pair_sets_from_band``)."""
    return packed_pair_sets_from_band(part, (field,))[0]


def packed_pair_sets_from_band(part: dict, fields=("mask", "match")
                               ) -> Tuple[np.ndarray, ...]:
    """The packed pair set of each band in ``fields``, from one slot map.

    The slot map holds, per band slot (s, d-1, i), the packed pair of
    ``eid[s, i]`` and ``eid[s, i+d]``; each set is the map compressed by
    its band, sorted in place and deduplicated (``_sort_unique``).  The map
    covers the columns up to the last one that any of the bands selects,
    so trailing padding costs nothing.  Under a tracer ``collect.slots``
    adds the map's entries."""
    eid = np.asarray(_eid(part))                          # (r, M)
    bands = [np.asarray(part[f]) for f in fields]         # (r, w-1, M) each
    r, k, m = bands[0].shape
    used = np.zeros(m, bool)
    for b in bands:
        used |= b.reshape(-1, m).any(axis=0)
    sel = np.flatnonzero(used)
    c = int(sel[-1]) + 1 if sel.size else 0
    if any(b[:, d - 1, max(0, m - d):c].any() for b in bands
           for d in range(max(1, m - c + 1), k + 1)):
        raise IndexError("band selects a slot past its shard's last row")
    slots = _slot_map(eid, k, c)
    _count(_SLOTS, slots.size)
    return tuple(_sort_unique(slots[b[:, :, :c]]) for b in bands)


def _slot_map(eid: np.ndarray, k: int, c: int) -> np.ndarray:
    """(r, k, c) packed pair of ``eid[s, i]`` and ``eid[s, i+d]`` per band
    slot (s, d-1, i).  ``min(a << 32 | b, b << 32 | a)`` is
    ``pack_pairs(a, b)`` for eids below 2^32.  Slots past the shard's end
    (i + d >= M) are left unwritten, and padding eids (-1) give garbage:
    no band selects either."""
    r, m = eid.shape
    e = eid[:, :min(m, c + k)].astype(PACKED_DTYPE)
    eh = e << PACKED_DTYPE(32)
    slots = np.empty((r, k, c), PACKED_DTYPE)
    tmp = np.empty((r, c), PACKED_DTYPE)
    for d in range(1, k + 1):
        n = max(0, min(c, m - d))
        row, t = slots[:, d - 1, :n], tmp[:, :n]
        np.bitwise_or(eh[:, :n], e[:, d:d + n], out=row)
        np.bitwise_or(eh[:, d:d + n], e[:, :n], out=t)
        np.minimum(row, t, out=row)
    return slots


def _sort_unique(a: np.ndarray) -> np.ndarray:
    """Sort the fresh packed array ``a`` in place and drop repeats (under a
    tracer ``collect.duplicates`` adds how many)."""
    a.sort()
    rep = a[1:] == a[:-1]
    n = int(np.count_nonzero(rep))
    _count(_DUPLICATES, n)
    if n:
        a = a[np.concatenate(([True], ~rep))]
    return a


def union_packed(arrays) -> np.ndarray:
    """Union of sorted deduplicated packed arrays: the one array itself
    when there is one, else concatenated, sorted and deduplicated."""
    if len(arrays) == 1:
        return arrays[0]
    if not arrays:
        return np.empty((0,), PACKED_DTYPE)
    return _sort_unique(np.concatenate(arrays))


def pairs_from_band(part: dict, field: str = "match") -> Set[Pair]:
    """Band -> Python pair set.  Kept as the public/reference surface (and
    the benchmark baseline); the collection hot path is
    ``packed_pairs_from_band``."""
    eid = np.asarray(part["ents"]["eid"])                 # (r, M)
    band = np.asarray(part[field])                        # (r, w-1, M)
    ss, ds, iis = np.nonzero(band)
    if ss.size == 0:
        return set()
    a = eid[ss, iis]
    b = eid[ss, iis + ds + 1]       # in-bounds: masks force i + d < M
    lo = np.minimum(a, b)
    hi = np.maximum(a, b)
    return set(zip(lo.tolist(), hi.tolist()))


def compute_metrics(blocked: AbstractSet[Pair], oracle: Set[Pair],
                    total_comparisons: int) -> ERMetrics:
    """Standard blocking-quality metrics of ``blocked`` against the
    sequential-SN ``oracle`` pair set: reduction ratio = 1 − |blocked| /
    ``total_comparisons`` (the full comparison space) and pairs
    completeness = |blocked ∩ oracle| / |oracle| (1.0 when no oracle pair
    was lost; degenerate inputs score 1.0 by convention)."""
    n_oracle = len(oracle)
    pc = 1.0 if n_oracle == 0 else len(blocked & oracle) / n_oracle
    rr = 1.0 if total_comparisons <= 0 else \
        1.0 - len(blocked) / total_comparisons
    return ERMetrics(reduction_ratio=rr, pairs_completeness=pc,
                     oracle_pairs=n_oracle,
                     total_comparisons=total_comparisons)
