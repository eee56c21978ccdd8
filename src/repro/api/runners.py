"""Pluggable runners — who executes the variant's shard program.

  * SequentialRunner  host numpy, wraps the ``sn.py`` oracle with the chosen
                      variant's SEMANTICS (srp: per-partition windows;
                      repsn/jobsn: the complete SN pair set) — the reference
                      every parallel run is checked against
  * VmapRunner        single device, r shards on a vmapped named axis
                      (property tests, skew studies)
  * ShardMapRunner    real devices: shards live on a mesh axis (multi-CPU
                      subprocess / TPU mesh)

All three satisfy the ``Runner`` protocol: ``resolve(ents, bounds, cfg)``
returns a ``RunnerOutcome`` with identical semantics, so callers (and the
facade) never branch on the execution substrate.  The device runners also
expose ``run_raw`` returning the stacked per-shard output dict (band masks,
halos, scores) for benchmarks and invariant tests.

``bounds`` may be a raw (r-1,) boundary array OR a ``repro.balance``
ShardPlan — plans additionally carry rank-granular per-entity routing
(attached as a ``_dest`` payload tag consumed by ``srp.srp_shard``) and the
planned shuffle capacity (used when ``cfg.cap_factor`` doesn't override it),
so every variant x runner x band-engine combination executes planner output
with zero call-site changes.

Steady-state execution (ISSUE 4): with ``cfg.jit_cache`` (the default) the
device runners route through the ``repro.perf`` executable cache — each
(config statics, planner capacity, input shapes) combination is lowered to
ONE jitted executable (boundary VALUES are traced arguments, so replanning
never retraces), with the stacked shard input donated where the backend
supports it.  ``SequentialRunner._match`` jit-caches its chunk scorer the
same way, padding the tail chunk so every chunk reuses one executable.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, NamedTuple, Protocol, Tuple, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as OBS
from repro.api import linkage as LK
from repro.api import results as RES
from repro.api.variants import get_variant, link_capacity
from repro.balance.planners import as_plan
from repro.core import entities as E
from repro.perf import cache as PC


def _apply_plan(ents: dict, bounds, r: int, cfg):
    """Normalize (bounds | ShardPlan) for a device runner: returns
    (ents_with_routing, bounds_array, cap_link).  A partition count other
    than the runner's shard count is rejected — entities routed past the
    last shard would be dropped by ``bucketize`` WITHOUT being counted as
    overflow (its accounting only covers dest < r)."""
    plan = as_plan(bounds)
    if plan.num_shards != r:
        raise ValueError(
            f"plan defines {plan.num_shards} partitions but the runner has "
            f"{r} shards")
    if plan.dest is not None:
        ents = dict(ents)
        ents["payload"] = dict(ents["payload"],
                               _dest=jnp.asarray(plan.dest, jnp.int32))
    # explicit cap_factor keeps its historical override (overflow stays an
    # accounted outcome); otherwise the planner's exact capacity applies
    cap_link = plan.cap_link if cfg.cap_factor <= 0 else None
    return ents, jnp.asarray(plan.bounds, jnp.int32), cap_link


def _cache_fingerprint(cfg):
    """Config half of an executable-cache key, or None to bypass the cache
    (cfg.jit_cache=False, or a legacy ``pipeline.SNConfig`` shim object
    without the ERConfig surface)."""
    if not getattr(cfg, "jit_cache", True):
        return None
    fp = getattr(cfg, "static_fingerprint", None)
    return fp() if fp is not None else None


class RunnerOutcome(NamedTuple):
    """What every runner returns: host pair sets + accounting.

    ``cand_count`` is the PER-SHARD cascade-gate survivors kept (zeros
    for a one-matcher cascade, which has no gate) — per-shard like
    ``load`` so the DESIGN.md §6 cand_cap sizing rule (cap ~1.25x the
    busiest shard) is executable from the public result.
    ``cand_overflow`` counts survivors dropped by ``cfg.cand_cap`` (pallas;
    may lose MATCHES, never blocked pairs); ``matcher_evals`` counts
    evaluations of the cascade's last matcher ACTUALLY run — the scan
    engine's survivors in whole chunks, one per band slot for a
    one-matcher cascade, one per cand_cap buffer slot for pallas (static
    shapes, reported honestly so benchmarks can verify the §5.1 FLOP
    cut)."""
    blocked: RES.PairSet
    matched: RES.PairSet
    load: Tuple[int, ...]
    overflow: int
    num_shards: int
    cand_count: Tuple[int, ...] = ()
    cand_overflow: int = 0
    matcher_evals: int = 0
    pair_overflow: int = 0      # emitted index-buffer slots dropped by
    #                             cfg.pair_cap (emit="pairs" only; counted,
    #                             never silent — can lose blocked pairs)
    pruned: int = 0             # band slots dropped by meta-blocking
    #                             comparison pruning (prune_policy=
    #                             "evidence"; deliberate, never retried)


class PackedOutcome(NamedTuple):
    """``RunnerOutcome``'s packed-array twin: identical accounting, but the
    pair sets stay as deduplicated PACKED uint64 arrays (``(lo << 32) |
    hi``, see ``results.pack_pairs``).

    This is the collection hot path for callers that aggregate MANY runner
    invocations — ``repro.stream`` unions one of these per chunk with a
    single sort at the end (``results.union_packed``), instead of
    materializing a Python pair set per chunk.  ``to_outcome()`` wraps the
    arrays in the public ``PairSet`` form."""
    blocked: "np.ndarray"
    matched: "np.ndarray"
    load: Tuple[int, ...]
    overflow: int
    num_shards: int
    cand_count: Tuple[int, ...] = ()
    cand_overflow: int = 0
    matcher_evals: int = 0
    pair_overflow: int = 0
    pruned: int = 0

    def to_outcome(self) -> RunnerOutcome:
        """The public RunnerOutcome (``results.PairSet``s over the packed
        arrays, no copy), inside a ``to_outcome`` span.  Under a tracer the
        ``pairs.materialized`` counter is registered here, so a job whose
        pair sets are never iterated reads 0."""
        with OBS.span("to_outcome") as sp:
            if sp.enabled:
                OBS.current_tracer().metrics.counter("pairs.materialized")
            return RunnerOutcome(
                blocked=RES.packed_to_pair_set(self.blocked),
                matched=RES.packed_to_pair_set(self.matched),
                load=self.load, overflow=self.overflow,
                num_shards=self.num_shards, cand_count=self.cand_count,
                cand_overflow=self.cand_overflow,
                matcher_evals=self.matcher_evals,
                pair_overflow=self.pair_overflow,
                pruned=self.pruned)


@runtime_checkable
class Runner(Protocol):
    """The execution contract every runner satisfies (see module doc)."""

    name: str

    @property
    def shards(self) -> int:
        """Number of shards this runner executes (r)."""
        ...

    def resolve(self, ents: dict, bounds, cfg) -> RunnerOutcome:
        """Run blocking + matching; pair sets as ``results.PairSet``s."""
        ...

    def resolve_packed(self, ents: dict, bounds, cfg) -> PackedOutcome:
        """Like ``resolve`` but pair sets stay packed uint64 arrays (the
        aggregation hot path — see ``PackedOutcome``)."""
        ...


def shard_input(ents: dict, r: int) -> dict:
    """Round-robin split into r mapper shards (paper: mappers scan disjoint
    input partitions), padded to equal capacity."""
    n = ents["key"].shape[0]
    cap0 = int(np.ceil(n / r))
    pad = r * cap0 - n
    padded = E.concat(ents, E.empty_like(ents, pad)) if pad else ents
    return jax.tree.map(
        lambda x: x.reshape((r, cap0) + x.shape[1:]), padded)


def _exchange_counts(ents: dict, bounds, stacked: dict, variant, cfg,
                    cap_link) -> dict:
    """What one run of the shard program sends between shards, from the
    plan and the shapes on the host (no device work; the valid flags and
    keys were fetched by the planner):

      ``shuffle.bytes``       the all_to_all's slots bound for another
                              shard, ``r * (r-1) * cap_link``, times the
                              bytes of a row after ``_dest`` is stripped
                              (buffer padding included)
      ``shuffle.rows_moved``  valid rows whose reducer (the plan's dest)
                              is not the mapper split holding them
      ``halo.rows``           rows of the halo or boundary exchange
                              (``variant.halo_rows``)

    ``ents`` are the records before ``_apply_plan``, ``stacked`` the
    mapper splits of ``shard_input``."""
    r, rows = stacked["key"].shape[:2]
    cap = link_capacity(rows, r, cfg, cap_link)
    payload = {k: v for k, v in stacked["payload"].items() if k != "_dest"}
    row_bytes = sum(x.dtype.itemsize * int(np.prod(x.shape[2:]))
                    for x in jax.tree.leaves(dict(stacked, payload=payload)))
    dest = as_plan(bounds).assignment(np.asarray(ents["key"]))
    moved = np.asarray(ents["valid"]) & \
        (dest != np.arange(dest.shape[0]) // rows)
    return {"shuffle.bytes": r * (r - 1) * cap * row_bytes,
            "shuffle.rows_moved": int(moved.sum()),
            "halo.rows": variant.halo_rows(r, cfg)}


def _device_outcome_packed(out: dict, cfg, r: int) -> PackedOutcome:
    """Stacked device output -> PackedOutcome (collection + accounting; the
    shared back half of every device runner's resolve/resolve_packed).

    Collection first fetches, in one ``jax.device_get``, the leaves it
    reads (``results.collected_leaves``: no payload), then works on the
    host.  Under an active tracer the whole collection runs inside a
    ``collect`` span carrying the realized per-shard loads, the fetch
    inside its child ``transfer`` span, and the ``transfer_bytes`` counter
    adds the bytes fetched — the Afrati/Ullman communication-cost
    attribution of DESIGN.md §12."""
    variant = get_variant(cfg.variant)
    sp = OBS.span("collect")
    with sp:
        tsp = OBS.span("transfer")
        with tsp:
            out = jax.device_get(RES.collected_leaves(out, variant.parts))
            if tsp.enabled:
                OBS.current_tracer().metrics.counter("transfer_bytes").inc(
                    sum(x.nbytes for x in jax.tree.leaves(out)))
        col = variant.collect(out)
        load = tuple(int(x) for x in np.asarray(out["load"])[0])
        overflow = int(np.asarray(out["overflow"])[0])
        cand_count = np.zeros(r, np.int64)
        cand_overflow = matcher_evals = pair_overflow = pruned = 0
        for p in variant.parts:
            if p in out:
                cand_count += np.asarray(out[p]["cand_count"], np.int64)
                cand_overflow += \
                    int(np.asarray(out[p]["cand_overflow"]).sum())
                matcher_evals += \
                    int(np.asarray(out[p]["matcher_evals"]).sum())
                if "pruned" in out[p]:  # meta-blocking comparison pruning
                    pruned += int(np.asarray(out[p]["pruned"]).sum())
                if "mask_overflow" in out[p]:  # device-side pair emission
                    pair_overflow += \
                        int(np.asarray(out[p]["mask_overflow"]).sum()) + \
                        int(np.asarray(out[p]["match_overflow"]).sum())
        if sp.enabled:
            sp.set(load=load)
            # the skip rule's engagement: survivors over blocked slots
            metrics = OBS.current_tracer().metrics
            metrics.counter("band.survivors").inc(int(cand_count.sum()))
            metrics.counter("band.expensive_evals").inc(matcher_evals)
    return PackedOutcome(blocked=col.blocked, matched=col.matched,
                         load=load, overflow=overflow, num_shards=r,
                         cand_count=tuple(int(c) for c in cand_count),
                         cand_overflow=cand_overflow,
                         matcher_evals=matcher_evals,
                         pair_overflow=pair_overflow,
                         pruned=pruned)


@dataclass(frozen=True)
class VmapRunner:
    """r shards on one device via ``jax.vmap(axis_name=...)``."""
    num_shards: int = 8
    name = "vmap"

    @property
    def shards(self) -> int:
        """Number of vmapped shards (== cfg.num_shards)."""
        return self.num_shards

    def run_raw(self, ents: dict, bounds, cfg) -> dict:
        """Execute the variant's shard program and return the STACKED
        per-shard output dict (band masks / emitted index buffers, halos,
        accounting — leading dim r) without host collection; benchmarks and
        invariant tests read this, ``resolve`` consumes it.  Routed through
        the executable cache unless ``cfg.jit_cache`` is off."""
        r = self.num_shards
        variant = get_variant(cfg.variant)
        ents, b, cap_link = _apply_plan(ents, bounds, r, cfg)
        fn = partial(variant.shard_program, r=r, axis="sn", cfg=cfg,
                     cap_link=cap_link)
        stacked = shard_input(ents, r)

        def program(st, bd):
            return jax.vmap(lambda e: fn(e, bounds=bd),
                            axis_name="sn")(st)

        fp = _cache_fingerprint(cfg)
        rows = int(stacked["key"].shape[1])
        sp = OBS.span("shard_program", device=True, runner="vmap",
                      shards=r, rows_per_shard=rows)
        with sp:
            if fp is None:
                out = program(stacked, b)    # legacy trace-per-call path
            else:
                call = PC.executable_cache().get_or_build(
                    ("vmap", r, "sn", fp, cap_link,
                     PC.tree_fingerprint((stacked, b))),
                    lambda: program, donate_argnums=(0,))
                out = call(stacked, b)
            if sp.enabled:
                # async dispatch would end the span before the device ran;
                # blocking only when traced keeps the untraced path
                # identical (invariant 12: no retraces, same pair sets)
                out = jax.block_until_ready(out)
        return out

    def resolve(self, ents: dict, bounds, cfg) -> RunnerOutcome:
        """Run blocking + matching on r vmapped shards; see ``Runner``."""
        return self.resolve_packed(ents, bounds, cfg).to_outcome()

    def resolve_packed(self, ents: dict, bounds, cfg) -> PackedOutcome:
        """``resolve`` with pair sets left as packed uint64 arrays."""
        return _device_outcome_packed(self.run_raw(ents, bounds, cfg), cfg,
                                      self.num_shards)


@dataclass(frozen=True)
class ShardMapRunner:
    """Real devices: shards live on mesh axis ``axis``.  Output arrays carry
    a leading per-shard dim, exactly like VmapRunner."""
    mesh: Any = None                 # jax Mesh; None -> all devices, 1-D
    axis: str = "data"
    name = "shard_map"

    def __post_init__(self):
        if self.mesh is None:
            object.__setattr__(self, "mesh", jax.make_mesh(
                (len(jax.devices()),), (self.axis,)))

    @property
    def shards(self) -> int:
        """Number of shards == devices on the mesh axis."""
        return int(self.mesh.shape[self.axis])

    def run_raw(self, ents: dict, bounds, cfg) -> dict:
        """Execute the variant's shard program under ``shard_map`` and
        return the stacked per-shard output dict (leading dim r, exactly
        like ``VmapRunner.run_raw``); cached/jitted per (mesh, config
        statics, shapes) unless ``cfg.jit_cache`` is off.

        The mapper splits are placed on the mesh, one per device, inside
        a ``distribute`` span before the program's ``shard_program`` span;
        when traced, each blocks on its work, ``distribute_bytes`` adds
        the bytes placed, and ``_exchange_counts`` are added in an
        ``exchange_counts`` span after ``shard_program``."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh, axis = self.mesh, self.axis
        r = int(mesh.shape[axis])
        variant = get_variant(cfg.variant)
        records = ents
        ents, b, cap_link = _apply_plan(ents, bounds, r, cfg)
        stacked = shard_input(ents, r)
        dsp = OBS.span("distribute", device=True, chips=r)
        with dsp:
            stacked = jax.device_put(stacked, NamedSharding(mesh, P(axis)))
            if dsp.enabled:
                stacked = jax.block_until_ready(stacked)
                OBS.current_tracer().metrics.counter(
                    "distribute_bytes").inc(
                        sum(x.nbytes for x in jax.tree.leaves(stacked)))
        fn = partial(variant.shard_program, r=r, axis=axis, cfg=cfg,
                     cap_link=cap_link)

        def make_program():
            # bounds ride as a replicated traced argument so replanning
            # never rebuilds; the eval_shape pass (out_specs need the output
            # tree; vmap binds the axis name so the collectives trace) runs
            # once per cache entry instead of once per call
            def body(stacked_local, bounds_rep):
                # stacked_local: (1, cap0, ...) — this shard's partition
                local = jax.tree.map(lambda x: x[0], stacked_local)
                out = fn(local, bounds=bounds_rep)
                return jax.tree.map(lambda x: jnp.expand_dims(x, 0), out)

            # shapes alone: the placed input's mesh sharding means nothing
            # to the vmap
            out_sds = jax.eval_shape(
                lambda st, bd: jax.vmap(lambda l: fn(l, bounds=bd),
                                        axis_name=axis)(st),
                *jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype),
                              (stacked, b)))
            out_specs = jax.tree.map(lambda _: P(axis), out_sds)
            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(jax.tree.map(lambda _: P(axis), stacked), P()),
                out_specs=out_specs, check_vma=False)

        fp = _cache_fingerprint(cfg)
        rows = int(stacked["key"].shape[1])
        sp = OBS.span("shard_program", device=True, runner="shard_map",
                      shards=r, rows_per_shard=rows)
        with sp:
            if fp is None:
                out = make_program()(stacked, b)   # legacy per-call path
            else:
                call = PC.executable_cache().get_or_build(
                    ("shard_map", axis, self.mesh, fp,
                     cap_link, PC.tree_fingerprint((stacked, b))),
                    make_program, donate_argnums=(0,))
                out = call(stacked, b)
            if sp.enabled:
                out = jax.block_until_ready(out)  # see VmapRunner.run_raw
        if sp.enabled:
            # host bookkeeping in a span of its own, so neither the
            # program's span nor the attempt's self time holds it
            with OBS.span("exchange_counts"):
                metrics = OBS.current_tracer().metrics
                counts = _exchange_counts(records, bounds, stacked, variant,
                                          cfg, cap_link)
                for name, v in counts.items():
                    metrics.counter(name).inc(v)
        return out

    def resolve(self, ents: dict, bounds, cfg) -> RunnerOutcome:
        """Run blocking + matching on the mesh shards; see ``Runner``."""
        return self.resolve_packed(ents, bounds, cfg).to_outcome()

    def resolve_packed(self, ents: dict, bounds, cfg) -> PackedOutcome:
        """``resolve`` with pair sets left as packed uint64 arrays."""
        return _device_outcome_packed(self.run_raw(ents, bounds, cfg), cfg,
                                      self.shards)


@dataclass(frozen=True)
class SequentialRunner:
    """Host oracle: variant-faithful sequential blocking + batched matching.
    ``load`` reports per-PARTITION sizes (what each reducer would hold)."""
    num_shards: int = 1
    name = "sequential"
    match_chunk: int = 1 << 16

    @property
    def shards(self) -> int:
        """Default partition count (a ShardPlan passed to resolve wins)."""
        return self.num_shards

    def resolve(self, ents: dict, bounds, cfg) -> RunnerOutcome:
        """Variant-faithful host resolve; see ``Runner``."""
        return self.resolve_packed(ents, bounds, cfg).to_outcome()

    def resolve_packed(self, ents: dict, bounds, cfg) -> PackedOutcome:
        """``resolve`` with pair sets left as packed uint64 arrays (the
        internal representation this runner already uses)."""
        plan = as_plan(bounds)
        bounds = np.asarray(plan.bounds)
        r = plan.num_shards
        valid = np.asarray(ents["valid"])
        keys = np.asarray(ents["key"])[valid]
        eids = np.asarray(ents["eid"])[valid]
        # partition ids under the plan (rank-granular when it carries dest)
        part = plan.assignment(np.asarray(ents["key"]), valid)

        weff_all = ents["payload"].get("_weff")
        weff = None if weff_all is None else np.asarray(weff_all)[valid]

        with OBS.span("block", runner="sequential", shards=r):
            blocked = RES.pack_pair_set(
                get_variant(cfg.variant).sequential_pairs(
                    keys, eids, bounds, cfg.window, part=part, weff=weff))
            if getattr(cfg, "linkage", False) and "src" in ents["payload"]:
                src = np.asarray(ents["payload"]["src"])[valid]
                blocked = LK.filter_cross_source_packed(blocked, eids, src)
        pruned = 0
        if getattr(cfg, "prune_policy", "off") == "evidence":
            blocked, pruned = self._prune(ents, blocked, cfg)
        with OBS.span("match", pairs=int(blocked.size)):
            matched = self._match(ents, blocked, cfg)

        load = tuple(np.bincount(part, minlength=r).astype(int).tolist())
        return PackedOutcome(blocked=blocked, matched=matched,
                             load=load, overflow=0, num_shards=r,
                             matcher_evals=int(blocked.size),
                             pruned=pruned)

    def _prune(self, ents: dict, blocked: np.ndarray, cfg
               ) -> Tuple[np.ndarray, int]:
        """Meta-blocking comparison pruning, sequential-oracle form: score
        each blocked pair's CHEAP cascade evidence with the same jnp ops
        the band engines' ``prune_low_evidence`` uses, keep pairs at/above
        ``prune_threshold`` of the cheap prefix weight.  Identical keep
        decisions to the device engines (same math, same GATE_EPS slack)."""
        from repro.core import window as W
        from repro.core.match import cosine_sim, jaccard_sig

        split = W.split_cascade(cfg.matcher, ents["payload"])
        if split is None:
            raise ValueError(
                "prune_policy='evidence' needs a matcher whose cascade "
                "starts with a kernel-supported cheap stage (cosine/jaccard "
                "on a present payload field); split_cascade found none")
        if blocked.size == 0:
            return blocked, 0
        valid = np.asarray(ents["valid"])
        rows = np.nonzero(valid)[0]
        eids = np.asarray(ents["eid"])[rows]
        order = np.argsort(eids)
        sorted_eids, sorted_rows = eids[order], rows[order]
        plo, phi = RES.unpack_pairs(np.sort(blocked))
        ra = sorted_rows[np.searchsorted(sorted_eids, plo)]
        rb = sorted_rows[np.searchsorted(sorted_eids, phi)]
        cheap = jnp.zeros((ra.shape[0],), jnp.float32)
        if split.feat_field is not None:
            feat = jnp.asarray(ents["payload"][split.feat_field])
            cheap = cheap + split.w_cos * cosine_sim(feat[ra], feat[rb])
        if split.sig_field is not None:
            sig = jnp.asarray(ents["payload"][split.sig_field])
            cheap = cheap + split.w_jac * jaccard_sig(sig[ra], sig[rb])
        bar = cfg.prune_threshold * (split.w_cos + split.w_jac) - W.GATE_EPS
        keep = np.asarray(cheap) >= bar
        kept = np.sort(blocked)[keep]
        return kept, int(blocked.size - kept.size)

    def _match(self, ents: dict, blocked: np.ndarray, cfg) -> np.ndarray:
        """Batch-score blocked pairs (packed uint64 array) with the cascade
        matcher (skip=False: identical accept/reject decisions, exact
        scores).  Returns the matched subset, still packed.

        The chunk scorer is jit-compiled ONCE per (payload schema, chunk
        shape, matcher) through the repro.perf executable cache — payload
        moves to device once per call and chunks gather inside the compiled
        program; the tail chunk is padded to ``match_chunk`` so it reuses
        the same executable instead of compiling a second shape."""
        if blocked.size == 0:
            return blocked
        valid = np.asarray(ents["valid"])
        rows = np.nonzero(valid)[0]
        eids = np.asarray(ents["eid"])[rows]
        order = np.argsort(eids)
        sorted_eids, sorted_rows = eids[order], rows[order]
        blocked = np.sort(blocked)          # == lexicographic (lo, hi) order
        plo, phi = RES.unpack_pairs(blocked)
        ra = sorted_rows[np.searchsorted(sorted_eids, plo)]
        rb = sorted_rows[np.searchsorted(sorted_eids, phi)]
        payload = {k: jnp.asarray(v) for k, v in ents["payload"].items()}

        chunk = self.match_chunk
        matcher = cfg.matcher

        def program(pl, ia, ib):
            pa = {k: v[ia] for k, v in pl.items()}
            pb = {k: v[ib] for k, v in pl.items()}
            score, _ = matcher.combined(pa, pb, skip=False)
            return score >= matcher.threshold

        if getattr(cfg, "jit_cache", True):
            scorer = PC.executable_cache().get_or_build(
                ("seq_match", matcher, chunk,
                 PC.tree_fingerprint(payload)),
                lambda: program)
        else:
            scorer = program

        keep = np.zeros(blocked.shape[0], bool)
        for s in range(0, blocked.shape[0], chunk):
            ia, ib = ra[s:s + chunk], rb[s:s + chunk]
            ln = ia.shape[0]
            if ln < chunk:                  # pad the tail: one executable
                ia = np.concatenate([ia, np.zeros(chunk - ln, ia.dtype)])
                ib = np.concatenate([ib, np.zeros(chunk - ln, ib.dtype)])
            got = np.asarray(scorer(payload, jnp.asarray(ia),
                                    jnp.asarray(ib)))
            keep[s:s + ln] = got[:ln]
        return blocked[keep]
