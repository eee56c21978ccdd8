"""ERConfig — the single frozen configuration for an entity-resolution run.

Absorbs the old ``pipeline.SNConfig`` (window / variant / hops / capacity /
matcher) and adds the execution choices that used to live in free-function
signatures: which runner executes the shard program, how many shards, how
boundaries are derived, and whether the run is dual-source linkage.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

from repro.core.match import CascadeMatcher, default_matcher

VARIANTS = ("srp", "repsn", "jobsn")
RUNNERS = ("sequential", "vmap", "shard_map")
# legacy boundary derivations + the repro.balance planner registry
# (uniform | blocksplit | pairrange — profile-backed ShardPlans with
# planned comparison counts, rank-granular splits, and exact capacities)
PARTITIONERS = ("balanced", "range", "sample",
                "uniform", "blocksplit", "pairrange")
BAND_ENGINES = ("scan", "pallas")
EMIT_MODES = ("band", "pairs")
SORT_KEY_KINDS = ("identity", "prefix", "word")
OVERFLOW_POLICIES = ("count", "retry", "raise")
WINDOW_POLICIES = ("fixed", "adaptive")
PRUNE_POLICIES = ("off", "evidence")


@dataclass(frozen=True)
class SortKeySpec:
    """One blocking pass of multi-pass SN: how the sort key is derived.

    Multi-pass Sorted Neighborhood (Papadakis et al., arXiv:1905.06167 —
    the standard recall lever over single-key SN) runs the whole blocking
    workflow once per sort key and unions the pair sets.  A spec names one
    derivation, resolved by ``core.keys.derive_sort_key``:

      kind="identity"  use the entity's own ``key`` field (source="key") or
                       a 1-D integer payload field named by ``source``
      kind="prefix"    pack ``width`` characters of the padded-byte payload
                       field ``source``, starting at ``offset``
                       (``core.keys.prefix_key`` — the paper's "first
                       letters of the title" key family; shifting ``offset``
                       per pass is the classic multi-pass choice)
      kind="word"      column ``index`` of a 2-D integer payload field
                       ``source`` (e.g. one word of the bit-packed trigram
                       signature), masked into the int32 key space

    Derived keys are always non-negative int32 < 2^30 (the entities.py key
    schema).  Specs are frozen/hashable; ``name`` labels the pass in
    ``MultiPassResult``.
    """
    name: str = "key"
    source: str = "key"
    kind: str = "identity"
    offset: int = 0
    width: int = 2
    index: int = 0

    def __post_init__(self):
        if self.kind not in SORT_KEY_KINDS:
            raise ValueError(f"unknown sort-key kind {self.kind!r}; choose "
                             f"from {SORT_KEY_KINDS}")
        if self.kind == "prefix" and not 1 <= self.width <= 5:
            raise ValueError(f"prefix width must be in 1..5 (int32 key "
                             f"space), got {self.width}")
        if self.offset < 0 or self.index < 0:
            raise ValueError("offset/index must be >= 0")
        # parameters that would be silently ignored are rejected — a pass
        # with a mis-applied offset/index quietly derives the WRONG key
        if self.kind != "prefix" and self.offset:
            raise ValueError(f"offset only applies to kind='prefix' "
                             f"(got kind={self.kind!r})")
        if self.kind != "word" and self.index:
            raise ValueError(f"index only applies to kind='word' "
                             f"(got kind={self.kind!r})")


@dataclass(frozen=True)
class ERConfig:
    """Frozen configuration for ``repro.api.resolve``.

    Blocking / matching (paper §4):
      window       SN window size w (pairs at sorted distance 1..w-1)
      variant      registered variant name: "srp" | "repsn" | "jobsn"
      hops         RepSN halo hops (1 = paper; r-1 = complete for any skew)
      cap_factor   shuffle link capacity = cap0 * cap_factor / r; 0 -> cap0
                   (never overflows)
      matcher      cascade match strategy (paper §5.1 skip optimization)
      return_scores  keep band scores in raw runner output

    Band engine (core/window.py — how each shard's window band is evaluated):
      band_engine   "scan" (w-1 shifted passes of the cheap matchers,
                    then the last matcher on the skip rule's survivors
                    only, packed into a band-sized buffer and scored in
                    chunks: no capacity) | "pallas" (fused cheap-band
                    kernel -> cumsum candidate compaction into cand_cap
                    -> the full cascade on survivors)
      band_block    Pallas row-block size Bi (band width w-1 must fit:
                    w-1 <= band_block; VMEM grows as band_block^2)
      cand_cap      per-shard survivor capacity of the cascade compaction;
                    0 -> full band (w-1)*M: never overflows, but the
                    expensive stage then scores (and gathers payload for)
                    the whole band — a finite cap is both the FLOP and the
                    memory lever (DESIGN.md §6 sizing rule).  Overflowing
                    candidates are dropped AND counted (cand_overflow in
                    results) — the SRP capacity model applied to matching.
                    None (default) -> auto-sized by ``balance.suggest_caps``
                    from the key profile on the pallas engine (falls back
                    to 0 where no profile-backed plan exists — raw bounds,
                    direct runner calls)
      band_interpret  force the Pallas interpreter on/off; None -> auto
                    (native kernel on TPU; off-TPU the cheap stage runs as
                    a band-shaped jnp evaluation — same math, without the
                    tile kernel's 2*band_block scores per row.  True forces
                    the Pallas interpreter: the kernel-validation path)

    Pair emission (how blocked/matched pairs leave the device):
      emit          "band" (transfer the (w-1, M) boolean bands, extract
                    pairs on host) | "pairs" (compact each band into packed
                    (d-1)*M+i index buffers ON DEVICE via the cumsum
                    machinery; the host consumes small int buffers + per-
                    shard counts — the steady-state transfer path)
      pair_cap      per-shard, per-part capacity of the emitted index
                    buffers; 0 -> (w-1)*M (never overflows).  Overflowing
                    slots are dropped AND counted (pair_overflow in
                    results — blocked pairs CAN be lost here, unlike
                    cand_cap, so size it >= (w-1)*max_load for parity).
                    None (default) -> auto-sized by ``balance.suggest_caps``
                    under emit="pairs" (the profile band bound, which never
                    truncates; falls back to 0 without a profile)

    Overflow recovery (repro.resilience — DESIGN.md §11):
      on_overflow   what a resolve does when a finite capacity actually
                    overflowed (``overflow``/``cand_overflow``/
                    ``pair_overflow`` > 0):
                      "count"  (legacy) keep the truncated result, counters
                               report the drops
                      "retry"  re-execute the affected resolve (or the one
                               overflowing stream chunk) with every
                               overflowed cap doubled, up to ``retry_limit``
                               escalations — doubled caps stay on a
                               power-of-two ladder from the base cap, so
                               retried shapes still bucket into the
                               repro.perf executable cache; a ladder that
                               still overflows raises CapacityOverflowError
                               (never a silent drop)
                      "raise"  raise CapacityOverflowError immediately
      retry_limit   maximum cap-doubling rounds per resolve under
                    on_overflow="retry"

    Execution cache:
      jit_cache     route device runners through the repro.perf executable
                    cache: each (config statics, shapes) combination lowers
                    to one jitted executable, reused across calls (cache
                    hits/misses/traces reported on ERResult.perf).  False
                    keeps the legacy trace-per-call behavior

    Execution:
      runner       "sequential" (host oracle) | "vmap" (single device,
                   named-axis shards) | "shard_map" (real device mesh)
      num_shards   r for sequential/vmap runners (shard_map takes r from
                   its mesh axis)
      partitioner  how shard boundaries are planned from the data:
                   legacy "balanced" | "range" | "sample" (key bounds
                   only), or the repro.balance planners "uniform"
                   (even key-space baseline) | "blocksplit" (greedy
                   comparison-count balance over key blocks, splitting
                   oversized blocks) | "pairrange" (equal SN pair-space
                   ranges) — planner names produce a full ShardPlan with
                   planned per-shard loads, rank-granular routing, and
                   exact padded capacities (explicit ``bounds``/ShardPlans
                   passed to resolve() always win)

    Scenario:
      linkage          dual-source R x S mode: only cross-source pairs are
                       blocked/matched (entities carry a "src" payload tag)
      compute_metrics  run the host oracle and attach reduction-ratio /
                       pairs-completeness metrics to the result
      passes           multi-pass SN (empty = single pass on the entity
                       ``key``): one SortKeySpec per blocking pass.  The
                       whole variant x runner x engine pipeline runs once
                       per derived sort key and ``resolve``/``link`` return
                       a ``MultiPassResult`` whose union pair set is the
                       recall lever of the blocking survey (arXiv:
                       1905.06167); per-pass results keep their own
                       overflow/metrics accounting.  Orchestrated host-side
                       — passes do NOT enter ``static_fingerprint`` (each
                       pass reuses the single-pass executable; only the key
                       VALUES differ)

    Observability (repro.obs — DESIGN.md §12):
      trace            record a span/metrics ``TraceReport`` for the run
                       and attach it as ``result.trace`` (resolve / link /
                       resolve_stream; the serve service keeps a tracer for
                       its lifetime and exposes ``trace_report()``).
                       Host-side only — excluded from
                       ``static_fingerprint``, so traced and untraced runs
                       share executables and pair sets bit-identically
                       (invariant 12); the disabled path costs one
                       thread-local lookup per span site

    Quality levers (repro.quality — DESIGN.md §14):
      window_policy    "fixed" (every entity uses ``window``) | "adaptive"
                       (each entity's effective window grows with the size
                       of its key block: weff = clip(block_count, window,
                       window_max), a pure function of the global
                       ``KeyProfile``.  The band program compiles ONCE at
                       window_max; per-entity weff rides the payload as a
                       traced ``_weff`` field, so the executable cache and
                       stream/resume invariants hold unchanged)
      window_max       adaptive ceiling (>= window; dense key blocks reach
                       it, sparse regions stay at ``window``)
      prune_policy     "off" | "evidence": meta-blocking comparison pruning
                       — drop candidate pairs whose CHEAP cascade evidence
                       falls below ``prune_threshold`` before the expensive
                       matcher ever sees them.  Pruned pairs leave the
                       blocked set (reduction ratio improves) and are
                       counted in ``pruned`` — accounted like overflow, but
                       deliberate: never retried
      prune_threshold  normalized cheap-evidence keep bar in [0, 1); a pair
                       survives iff cheap_score >= threshold * cheap_weight
                       (invariant 14: a gold pair at/above the bar is NEVER
                       pruned, in either band engine)

    Serving admission control (repro.serve — DESIGN.md §13) is NOT
    configured here: ``AdmissionConfig`` is a service-level policy passed
    to ``api.serve(..., admission=...)``.  It changes when requests are
    refused or deferred, never what a correct resolve produces, so none
    of its knobs participate in ``static_fingerprint``.
    """
    window: int = 10
    variant: str = "repsn"
    hops: int = 1
    cap_factor: float = 0.0
    matcher: CascadeMatcher = field(default_factory=default_matcher)
    return_scores: bool = False

    band_engine: str = "scan"
    band_block: int = 256
    cand_cap: Optional[int] = None
    band_interpret: Optional[bool] = None

    emit: str = "band"
    pair_cap: Optional[int] = None
    jit_cache: bool = True

    on_overflow: str = "count"
    retry_limit: int = 3

    runner: str = "vmap"
    num_shards: int = 8
    partitioner: str = "balanced"

    linkage: bool = False
    compute_metrics: bool = False
    passes: Tuple[SortKeySpec, ...] = ()

    trace: bool = False

    window_policy: str = "fixed"
    window_max: int = 0
    prune_policy: str = "off"
    prune_threshold: float = 0.0

    def __post_init__(self):
        if not isinstance(self.passes, tuple) or any(
                not isinstance(p, SortKeySpec) for p in self.passes):
            raise ValueError("passes must be a tuple of SortKeySpec")
        if len({p.name for p in self.passes}) != len(self.passes):
            raise ValueError("pass names must be unique")
        if self.window < 2:
            raise ValueError(f"window must be >= 2, got {self.window}")
        if self.runner not in RUNNERS:
            raise ValueError(f"unknown runner {self.runner!r}; "
                             f"choose from {RUNNERS}")
        if self.partitioner not in PARTITIONERS:
            # planners registered via repro.balance.register_partitioner are
            # first-class citizens of the config surface
            from repro.balance.planners import available_partitioners
            if self.partitioner not in available_partitioners():
                raise ValueError(
                    f"unknown partitioner {self.partitioner!r}; choose from "
                    f"{PARTITIONERS} or a registered planner "
                    f"({available_partitioners()})")
        if self.num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {self.num_shards}")
        if self.band_engine not in BAND_ENGINES:
            raise ValueError(f"unknown band engine {self.band_engine!r}; "
                             f"choose from {BAND_ENGINES}")
        if self.band_block < 1:
            raise ValueError(f"band_block must be >= 1, got {self.band_block}")
        if self.cand_cap is not None and self.cand_cap < 0:
            raise ValueError(f"cand_cap must be >= 0 (0 = unbounded, "
                             f"None = auto), got {self.cand_cap}")
        if self.emit not in EMIT_MODES:
            raise ValueError(f"unknown emit mode {self.emit!r}; choose from "
                             f"{EMIT_MODES}")
        if self.pair_cap is not None and self.pair_cap < 0:
            raise ValueError(f"pair_cap must be >= 0 (0 = full band, never "
                             f"overflows; None = auto), got {self.pair_cap}")
        if self.on_overflow not in OVERFLOW_POLICIES:
            raise ValueError(f"unknown on_overflow policy "
                             f"{self.on_overflow!r}; choose from "
                             f"{OVERFLOW_POLICIES}")
        if self.retry_limit < 0:
            raise ValueError(f"retry_limit must be >= 0, "
                             f"got {self.retry_limit}")
        if self.emit == "pairs" and self.return_scores:
            raise ValueError(
                "emit='pairs' transfers packed pair indices instead of "
                "bands, so per-slot scores are not materialized on host; "
                "use emit='band' with return_scores=True")
        if self.window_policy not in WINDOW_POLICIES:
            raise ValueError(f"unknown window_policy {self.window_policy!r}; "
                             f"choose from {WINDOW_POLICIES}")
        if self.window_policy == "adaptive":
            if self.linkage:
                raise ValueError(
                    "window_policy='adaptive' does not support linkage "
                    "mode (the dual-source oracle has no per-entity "
                    "window form yet); use a fixed window")
            if self.window_max < self.window:
                raise ValueError(
                    f"window_policy='adaptive' needs window_max >= window "
                    f"(the per-entity effective window grows FROM window UP "
                    f"TO window_max), got window_max={self.window_max} < "
                    f"window={self.window}")
            if self.band_engine == "pallas" \
                    and self.window_max - 1 > self.band_block:
                raise ValueError(
                    f"band_engine='pallas' under window_policy='adaptive' "
                    f"compiles the band at window_max={self.window_max}, "
                    f"whose band width ({self.window_max - 1}) must fit one "
                    f"row block, but band_block={self.band_block}")
        elif self.window_max:
            raise ValueError(
                f"window_max only applies to window_policy='adaptive' "
                f"(got window_policy={self.window_policy!r} with "
                f"window_max={self.window_max})")
        if self.prune_policy not in PRUNE_POLICIES:
            raise ValueError(f"unknown prune_policy {self.prune_policy!r}; "
                             f"choose from {PRUNE_POLICIES}")
        if self.prune_policy == "evidence":
            if not 0.0 <= self.prune_threshold < 1.0:
                raise ValueError(
                    f"prune_threshold must be in [0, 1) (a normalized "
                    f"cheap-evidence fraction), got {self.prune_threshold}")
        elif self.prune_threshold:
            raise ValueError(
                f"prune_threshold only applies to prune_policy='evidence' "
                f"(got prune_policy={self.prune_policy!r} with "
                f"prune_threshold={self.prune_threshold})")
        if self.band_engine == "pallas" and self.window - 1 > self.band_block:
            # the band kernels need the whole w-1 band inside one row block
            # (plus its successor); catching this here beats a kernel assert
            raise ValueError(
                f"band_engine='pallas' needs the band width (window-1="
                f"{self.window - 1}) to fit one row block, but band_block="
                f"{self.band_block}; raise band_block (VMEM grows as "
                f"band_block^2), lower window, or use band_engine='scan'")
        # variant names are validated lazily by the registry (so configs can
        # be built before a plugin variant registers itself)

    def with_(self, **kw) -> "ERConfig":
        """Functional update (dataclasses.replace sugar)."""
        return replace(self, **kw)

    def static_fingerprint(self) -> tuple:
        """Stable hashable key of every field that shapes the traced shard
        program — the config half of a ``repro.perf`` executable-cache key.

        Two configs with equal fingerprints lower to the same program for
        same-shaped inputs; fields that only steer host-side planning or
        result assembly (runner, num_shards, partitioner, compute_metrics,
        jit_cache, passes — each blocking pass reruns the same program on
        re-derived key values) are deliberately excluded so e.g. switching
        partitioners reuses the compiled executable (boundaries are traced
        arguments).  ``on_overflow``/``retry_limit`` are host-side recovery
        policy and excluded too: a retry re-executes under a cfg whose
        DOUBLED caps fingerprint to their own (bucketed) entries.
        ``trace`` is likewise excluded — spans only read host clocks
        (invariant 12), so a traced run must HIT the very executables an
        untraced one built.  Auto
        (None) caps are resolved to concrete ints by the facade/stream
        before any runner call, so a fingerprint with a None cap only
        arises from direct raw-runner use (where None means 0)."""
        return ("ERConfig", self.window, self.variant, self.hops,
                self.cap_factor, self.matcher, self.return_scores,
                self.band_engine, self.band_block, self.cand_cap,
                self.band_interpret, self.emit, self.pair_cap, self.linkage,
                self.window_policy, self.window_max,
                self.prune_policy, self.prune_threshold)

    @classmethod
    def from_sn_config(cls, sn_cfg, **kw) -> "ERConfig":
        """Lift an old ``pipeline.SNConfig`` into an ERConfig."""
        return cls(window=sn_cfg.window, variant=sn_cfg.variant,
                   hops=sn_cfg.hops, cap_factor=sn_cfg.cap_factor,
                   matcher=sn_cfg.matcher,
                   return_scores=sn_cfg.return_scores, **kw)
