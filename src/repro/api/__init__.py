"""``repro.api`` — the public entity-resolution facade.

One entry point for the paper's parallel Sorted Neighborhood workflows:

    from repro import api

    res = api.resolve(ents, api.ERConfig(variant="repsn", runner="vmap",
                                         num_shards=8, window=10))
    res.blocking.pairs      # PairSet of blocked (candidate) pairs
    res.matches             # PairSet of matcher-accepted pairs
    # a PairSet equals and hashes like the frozenset of the same (lo, hi)
    # tuples, but is not a frozenset instance
    res.blocking.load       # per-shard valid counts (skew telemetry)
    res.metrics             # reduction ratio / pairs completeness vs oracle

Pieces (each importable on its own):

  * config.ERConfig        frozen run configuration (variant, runner, window,
                           partitioner, capacity, matcher, linkage mode)
  * variants               registry of SN variants (srp | repsn | jobsn);
                           ``@register_variant`` adds new ones without
                           touching any dispatch code
  * runners                Runner protocol + SequentialRunner / VmapRunner /
                           ShardMapRunner
  * results                typed BlockingResult / ERResult / ERMetrics
  * linkage                dual-source (R x S) record linkage: source tags,
                           cross-source band masks, host oracle
  * repro.balance          skew-aware load balancing: KeyProfile (the
                           analysis job), partition planners (uniform |
                           blocksplit | pairrange) producing ShardPlans
                           with planned loads + exact capacities, reported
                           back as ERResult.balance
  * facade.resolve/link    glue the above together — and, under
                           ``ERConfig.passes`` (multi-pass SN over several
                           derived sort keys), return a ``MultiPassResult``
                           with the union + per-pass outcomes
  * repro.stream           out-of-core streaming twin: ``resolve_stream``
                           consumes an iterator of chunks, externally
                           sorts, and resolves chunk-by-chunk with a w-1
                           seam halo — bit-identical pair sets with device
                           residency bounded by the chunk size
  * repro.serve            online incremental twin: ``api.serve(cfg)``
                           starts a ``ResolutionService`` (persistent
                           sorted index + neighborhood-delta matching
                           behind a micro-batched queue) whose served pair
                           sets stay bit-identical to a from-scratch
                           ``resolve`` of the live corpus under mutation
  * repro.resilience       fault tolerance: checkpointed/resumable streaming
                           (``resolve_stream(checkpoint_dir=...)`` +
                           ``api.resume``), the ``ERConfig.on_overflow``
                           cap-escalation retry ladder, and the
                           deterministic fault-injection harness
  * repro.obs              unified tracing + metrics (DESIGN.md §12):
                           ``ERConfig(trace=True)`` attaches a
                           ``TraceReport`` (spans, counters, histograms,
                           every legacy stats type behind one schema) to
                           any resolve/stream result, exportable as a
                           Chrome/Perfetto ``trace.json``
"""
# repro.obs is a leaf (stdlib/numpy only at import), so the eager import is
# cycle-safe — unlike serve/resilience, which resolve lazily below
from repro.obs import (SCHEMA_VERSION, TraceReport, Tracer, pack_stats,
                       unpack_stats)
from repro.api.config import ERConfig, SortKeySpec
from repro.api.facade import (default_bounds, link, make_runner, resolve,
                              resume, serve)
from repro.api.linkage import sequential_link_pairs, tag_sources
from repro.api.results import (BalanceMetrics, BlockingResult, ERMetrics,
                               ERResult, MultiPassResult, PairSet,
                               PerfStats, ResilienceStats, pack_pairs,
                               packed_pairs_from_band, packed_pairs_from_idx,
                               packed_pairs_from_part, packed_to_frozenset,
                               packed_to_pair_set, pairs_from_band,
                               unpack_pairs)
from repro.api.runners import (PackedOutcome, Runner, RunnerOutcome,
                               SequentialRunner, ShardMapRunner, VmapRunner,
                               shard_input)
from repro.api.variants import (available_variants, get_variant,
                                register_variant)
from repro.balance import (KeyProfile, ShardPlan, available_partitioners,
                           get_partitioner, plan_shards, profile_keys,
                           register_partitioner)
from repro.core.window import (available_band_engines, get_band_engine,
                               register_band_engine)

_SERVE_TYPES = ("ResolutionService", "IncrementalResult", "ServeStats")
_RESILIENCE_TYPES = ("StreamCheckpoint", "FaultPlan", "InjectedFault",
                     "CapacityOverflowError")


def __getattr__(name):
    # the serve/resilience types resolve lazily (PEP 562): both packages
    # import repro.api submodules, so an eager import here would be a cycle
    if name in _SERVE_TYPES:
        import repro.serve as _serve
        return getattr(_serve, name)
    if name in _RESILIENCE_TYPES:
        import repro.resilience as _resilience
        return getattr(_resilience, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "ERConfig", "SortKeySpec",
    "resolve", "link", "serve", "resume", "make_runner", "default_bounds",
    "ResolutionService", "IncrementalResult", "ServeStats",
    "ResilienceStats", "StreamCheckpoint", "FaultPlan", "InjectedFault",
    "CapacityOverflowError",
    "BlockingResult", "ERResult", "ERMetrics", "BalanceMetrics", "PerfStats",
    "MultiPassResult",
    "pairs_from_band",
    "packed_pairs_from_band", "packed_pairs_from_idx",
    "packed_pairs_from_part", "pack_pairs", "unpack_pairs",
    "packed_to_frozenset", "packed_to_pair_set", "PairSet",
    "Runner", "RunnerOutcome", "PackedOutcome",
    "SequentialRunner", "VmapRunner", "ShardMapRunner", "shard_input",
    "register_variant", "get_variant", "available_variants",
    "register_band_engine", "get_band_engine", "available_band_engines",
    "KeyProfile", "ShardPlan", "profile_keys", "plan_shards",
    "register_partitioner", "get_partitioner", "available_partitioners",
    "tag_sources", "sequential_link_pairs",
    "Tracer", "TraceReport", "pack_stats", "unpack_stats", "SCHEMA_VERSION",
]
