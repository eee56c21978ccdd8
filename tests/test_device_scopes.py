"""Named device stages of the shard program, and the host spans around it.

  * the compiled shard program's HLO metadata names every stage of
    ``repro.obs.scopes`` for srp / repsn / jobsn with both band engines,
    and every shuffle sub-stage the variant runs (SRP has no halo), under
    vmap here and under shard_map on 4 virtual devices; the benchmark's
    stage reader (``bench.scopes.stage_of``) charges each sub-stage to
    ``shuffle``
  * the stages are metadata only: with them turned off the lowered program
    is the same text, the optimised program the same up to instruction
    names, and the executable-cache keys, trace counts and pair sets are
    unchanged; traced and untraced runs share keys and pair sets
  * a traced resolve records ``to_outcome`` under ``attempt`` and
    ``transfer`` under ``collect``; ``transfer_bytes`` counts exactly the
    leaves fetched, which hold no payload; its public pair sets are
    ``PairSet``s that count ``pairs.materialized`` only when iterated
"""
import contextlib
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.api import results as RES
from repro.core import entities as E
from repro.core.match import CascadeMatcher, Matcher
from repro.obs.scopes import SHUFFLE_HALO, SHUFFLE_STAGES, STAGES
from repro.perf import cache as PC

REPO = Path(__file__).resolve().parents[1]
N, R, W = 300, 4, 6

# the benchmark's cascade: trigram Jaccard gates edit distance
MATCHER = CascadeMatcher(matchers=(
    Matcher(field="sig", kind="jaccard", weight=0.5, cost=1.0),
    Matcher(field="text", kind="edit", weight=0.5, cost=10.0)),
    threshold=0.75)


@pytest.fixture(scope="module")
def ents():
    return E.synth_entities(np.random.default_rng(3), N, n_keys=60,
                            dup_frac=0.25, text_len=12)


def _cfg(**kw):
    kw.setdefault("window", W)
    kw.setdefault("num_shards", R)
    kw.setdefault("hops", R - 1)
    kw.setdefault("matcher", MATCHER)
    if kw.get("band_engine") == "pallas":
        kw.setdefault("band_interpret", True)   # the kernel, interpreted
    return api.ERConfig(**kw)


@pytest.fixture
def programs(monkeypatch):
    """Clears the executable cache and records, for every shard program
    dispatched, (cache key, jitted program, arguments)."""
    cache = PC.executable_cache()
    cache.clear()
    seen = []
    build = cache.get_or_build

    def get_or_build(key, make, **kw):
        fn = build(key, make, **kw)

        def call(*args):
            seen.append((key, fn, args))
            return fn(*args)
        return call

    monkeypatch.setattr(cache, "get_or_build", get_or_build)
    yield seen
    cache.clear()


def op_names(hlo_text: str) -> set:
    """Every ``op_name`` path of an HLO text."""
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def named_in(path: str, stage: str) -> bool:
    """Whether an ``op_name`` path names ``stage`` as a whole element."""
    return re.search(r"(^|[/(])" + re.escape(stage) + r"($|[/)])",
                     path) is not None


def stages_named(hlo_text: str, stages=STAGES) -> set:
    """The stages that some ``op_name`` of an HLO text names."""
    paths = op_names(hlo_text)
    return {s for s in stages if any(named_in(p, s) for p in paths)}


def shuffle_stages_of(variant: str) -> set:
    """The shuffle sub-stages a variant runs: SRP has no halo."""
    return set(SHUFFLE_STAGES) - ({SHUFFLE_HALO} if variant == "srp"
                                  else set())


def sub_stage_readings(hlo_text: str) -> set:
    """What ``bench.scopes.stage_of`` makes of every path in a shuffle
    sub-stage."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from bench.scopes import stage_of
    return {stage_of(p) for p in op_names(hlo_text)
            if any(named_in(p, s) for s in SHUFFLE_STAGES)}


def canonical(hlo_text: str) -> str:
    """HLO text without metadata, with instruction and computation names
    numbered in order of appearance (XLA names some instructions after
    their ``op_name``)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    ids = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: ids.setdefault(m.group(0), f"%v{len(ids)}"),
                  text)


@contextlib.contextmanager
def no_stages():
    """Trace with every ``jax.named_scope`` a no-op."""
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


@pytest.mark.parametrize("engine", ["scan", "pallas"])
@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
def test_vmap_program_names_every_stage(ents, programs, variant, engine):
    api.resolve(ents, _cfg(variant=variant, band_engine=engine))
    (_, fn, args), = programs
    text = fn.lower(*args).compile().as_text()
    assert stages_named(text) == set(STAGES)
    assert stages_named(text, SHUFFLE_STAGES) == shuffle_stages_of(variant)
    assert sub_stage_readings(text) == {"shuffle"}


def test_shard_map_program_names_every_stage():
    code = textwrap.dedent("""
        import json, os, re
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=4")
        import numpy as np
        from repro import api
        from repro.core import entities as E
        from repro.perf import cache as PC
        from repro.obs.scopes import SHUFFLE_STAGES
        from tests.test_device_scopes import (MATCHER, stages_named,
                                              sub_stage_readings)
        ents = E.synth_entities(np.random.default_rng(3), 300, n_keys=60,
                                dup_frac=0.25, text_len=12)
        cache = PC.executable_cache()
        build, seen = cache.get_or_build, []
        def get_or_build(key, make, **kw):
            fn = build(key, make, **kw)
            def call(*args):
                seen.append((fn, args))
                return fn(*args)
            return call
        cache.get_or_build = get_or_build
        out = {}
        for variant in ("srp", "repsn", "jobsn"):
            for engine in ("scan", "pallas"):
                seen.clear()
                cfg = api.ERConfig(window=6, variant=variant, hops=3,
                                   runner="shard_map", matcher=MATCHER,
                                   band_engine=engine,
                                   band_interpret=engine == "pallas" or None)
                api.resolve(ents, cfg)
                fn, args = seen[0]
                text = fn.lower(*args).compile().as_text()
                out[variant + "/" + engine] = [
                    sorted(stages_named(text)),
                    sorted(stages_named(text, SHUFFLE_STAGES)),
                    sorted(sub_stage_readings(text))]
        print("@@" + json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{REPO / 'src'}:{REPO}")
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("@@")]
    assert lines, done.stderr[-3000:]
    got = json.loads(lines[-1][2:])
    assert got == {f"{v}/{e}": [sorted(STAGES),
                                sorted(shuffle_stages_of(v)), ["shuffle"]]
                   for v in ("srp", "repsn", "jobsn")
                   for e in ("scan", "pallas")}


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_stages_change_only_metadata(ents, programs, engine):
    cfg = _cfg(variant="repsn", band_engine=engine)
    runs = []
    for scoped in (True, False):
        PC.executable_cache().clear()
        programs.clear()
        with contextlib.nullcontext() if scoped else no_stages():
            res = api.resolve(ents, cfg)
            (key, fn, args), = programs
            lowered = fn.lower(*args)
            runs.append((key, res, lowered.as_text(),
                         lowered.compile().as_text()))
    (k1, r1, low1, hlo1), (k2, r2, low2, hlo2) = runs
    assert k1 == k2
    assert r1.perf.traces == r2.perf.traces == 1
    assert r1.pairs == r2.pairs and r1.matches == r2.matches
    assert low1 == low2
    assert stages_named(hlo1) == set(STAGES) and not stages_named(hlo2)
    assert stages_named(hlo1, SHUFFLE_STAGES) == set(SHUFFLE_STAGES)
    assert not stages_named(hlo2, SHUFFLE_STAGES)
    assert canonical(hlo1) == canonical(hlo2)


def test_traced_and_untraced_share_programs_and_pairs(ents, programs):
    cfg = _cfg(variant="jobsn")
    plain = api.resolve(ents, cfg)
    traced = api.resolve(ents, cfg.with_(trace=True))
    (k1, _, _), (k2, _, _) = programs
    assert k1 == k2
    assert plain.perf.traces == 1 and traced.perf.traces == 0
    assert traced.pairs == plain.pairs and traced.matches == plain.matches


def test_traced_resolve_records_to_outcome_and_transfer(ents):
    res = api.resolve(ents, _cfg(variant="repsn", trace=True))
    spans = res.trace.spans
    name = {s.index: s.name for s in spans}
    parents = {s.name: name.get(s.parent) for s in spans}
    assert parents["to_outcome"] == "attempt"
    assert parents["transfer"] == "collect"
    assert parents["collect"] == "attempt"
    collect = next(s for s in spans if s.name == "collect")
    assert "transfer_bytes" not in collect.attrs and "load" in collect.attrs


def test_public_pair_sets_materialize_only_when_iterated(ents):
    from repro import obs
    tracer = obs.Tracer()
    count = lambda: tracer.metrics.to_dict()["pairs.materialized"]["value"]
    with obs.activate(tracer):
        res = api.resolve(ents, _cfg(variant="repsn"))
        for s in (res.blocking.pairs, res.matches):
            assert isinstance(s, RES.PairSet) and hash(s) == hash(frozenset(
                zip(*(x.tolist() for x in RES.unpack_pairs(s.packed)))))
            len(s)
        assert count() == 0
        n = len(res.blocking.pairs)
        assert n > 0 and len(list(res.blocking.pairs)) == n
        assert count() == n
    name = {s.index: s.name for s in tracer.spans()}
    assert [name.get(s.parent) for s in tracer.spans()
            if s.name == "to_outcome"] == ["attempt"]


@pytest.mark.parametrize("emit", ["band", "pairs"])
def test_transfer_bytes_are_the_fetched_leaves(ents, emit):
    cfg = _cfg(variant="jobsn", emit=emit)
    runner = api.VmapRunner(R)
    bounds = api.default_bounds(ents, cfg, R)
    fetched = RES.collected_leaves(runner.run_raw(ents, bounds, cfg),
                                   api.get_variant("jobsn").parts)
    want = sum(x.nbytes for x in jax.tree.leaves(fetched))
    res = api.resolve(ents, cfg.with_(trace=True), bounds=bounds)
    got = res.trace.metrics()["metrics"]["transfer_bytes"]["value"]
    assert got == want


def test_collected_leaves_hold_no_payload_and_collect_the_same(ents):
    cfg = _cfg(variant="jobsn")
    variant = api.get_variant("jobsn")
    out = api.VmapRunner(R).run_raw(ents, api.default_bounds(ents, cfg, R),
                                    cfg)
    leaves = RES.collected_leaves(out, variant.parts)
    assert set(leaves) == {"load", "overflow", "main", "boundary"}
    for p in variant.parts:
        assert "ents" not in leaves[p] and "halo_len" not in leaves[p]
        assert leaves[p]["eid"] is out[p]["ents"]["eid"]
        assert {"mask", "match", "cand_count"} <= set(leaves[p])
    fetched = sum(x.nbytes for x in jax.tree.leaves(leaves))
    assert fetched < sum(x.nbytes for x in jax.tree.leaves(out))
    a, b = variant.collect(out), variant.collect(jax.device_get(leaves))
    np.testing.assert_array_equal(a.blocked, b.blocked)
    np.testing.assert_array_equal(a.matched, b.matched)
