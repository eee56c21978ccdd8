"""The scan band engine runs the cascade's last matcher on the skip rule's
survivors only.

``ScanBandEngine`` scores every band slot with every matcher but the last,
packs the blocked slots the skip rule keeps and scores those in chunks of
``window.SURVIVOR_CHUNK`` (``window.score_survivors``).  Every case runs a
resolve with it and with ``FullBand``, the whole band through
``window.band_scores`` (the oracle), and requires:

  * the same blocked and matched sets, and the same ``mask``, ``match``
    and ``return_scores`` bands, bit for bit;
  * ``cand_count`` per shard equal to a numpy count of the gate
    (``bench/reference.py``'s cascade, float64) over the blocked slots;
  * ``matcher_evals`` at least the survivors and at most the band slots,
    and the band slots for a one-matcher cascade, which has no gate.

Cases: srp / repsn / jobsn under vmap and under shard_map on 4 virtual
devices, with the default matcher and with the benchmark's trigram +
edit-distance matcher on text; linkage; adaptive windows;
``prune_policy="evidence"``; a one-matcher cascade; survivor counts of
0, 1, C, C+1 and every blocked slot with a small chunk C, so every chunk
edge is hit; and the benchmark's corpus at n = 4,000 against its plain
reference.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import config as CF
from repro.api import runners as RN
from repro.core import entities as E
from repro.core import window as W
from repro.core.match import CascadeMatcher, Matcher, default_matcher
from repro.perf import cache as PC

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))
from bench import check, corpus, reference  # noqa: E402
from bench.run import entities as bench_entities, er_config, load_cell  # noqa: E402,E501

TEXT = CascadeMatcher(matchers=(
    Matcher(field="sig", kind="jaccard", weight=0.5, cost=1.0),
    Matcher(field="text", kind="edit", weight=0.5, cost=10.0)),
    threshold=0.75)
ONE = CascadeMatcher(matchers=(
    Matcher(field="text", kind="edit", weight=1.0),), threshold=0.8)
MATCHERS = {"default": default_matcher(), "text": TEXT, "one": ONE}

# chunk of the survivor-count cases, and the survivors each plants
CHUNK = 64
EDGES = {"0": 0, "1": 1, "C": CHUNK, "C+1": CHUNK + 1, "all": None}


class FullBand(W.BandEngine):
    """The oracle: every band slot through ``CascadeMatcher.combined``
    (``band_scores``), then the engines' masking and threshold."""

    def band(self, ents, cfg, *, halo_len, mode):
        w, m = cfg.window, ents["valid"].shape[0]
        scores, mask = W.band_scores(ents, w, cfg.matcher,
                                     halo_len=halo_len, mode=mode)
        src = self._src(ents, cfg)
        if src is not None:
            mask = mask & W.cross_source_rows(src, w)
        pruned = jnp.int32(0)
        if cfg.prune_policy == "evidence":
            mask, pruned = W.prune_low_evidence(
                ents["payload"], cfg.matcher, w, mask, cfg.prune_threshold)
        scores = jnp.where(mask, scores, 0.0)
        return {"mask": mask, "match": (scores >= cfg.matcher.threshold)
                & mask, "matcher_evals": jnp.int32((w - 1) * m),
                "cand_count": jnp.int32(0), "cand_overflow": jnp.int32(0),
                "pruned": pruned, "scores": scores}


def _matcher_dict(matcher):
    return {"threshold": matcher.threshold,
            "matchers": [{"field": m.field, "kind": m.kind,
                          "weight": m.weight, "cost": m.cost}
                         for m in matcher.matchers]}


def gate_counts(out, parts, matcher) -> np.ndarray:
    """Per shard, the blocked slots whose pair survives the skip rule, by
    the plain numpy cascade."""
    md = _matcher_dict(matcher)
    counts = None
    for p in parts:
        if p not in out:
            continue
        mask = np.asarray(out[p]["mask"])
        payload = {f: np.asarray(v)
                   for f, v in out[p]["ents"]["payload"].items()}
        r, rows, m = mask.shape
        counts = np.zeros(r, np.int64) if counts is None else counts
        for s in range(r):
            for d in range(1, rows + 1):
                pa = {x["field"]: payload[x["field"]][s, :m - d]
                      for x in md["matchers"]}
                pb = {x["field"]: payload[x["field"]][s, d:]
                      for x in md["matchers"]}
                _, alive = reference.cascade(pa, pb, md)
                counts[s] += int((alive & mask[s, d - 1, :m - d]).sum())
    return counts


def _twins(k):
    """Sorted records in which exactly ``k`` blocked slots survive the
    trigram gate: record pairs (2t, 2t+1), t < k, share a trigram set and
    every other record has one of its own (``k=None``: one set for all)."""
    n = 140
    bit = np.arange(n) if k is not None else np.zeros(n, np.int64)
    if k:
        bit[1:2 * k:2] = bit[0:2 * k:2]
    sig = np.zeros((n, 32), np.uint32)
    sig[np.arange(n), bit // 32] = np.uint32(1) << (bit % 32).astype(
        np.uint32)
    text = np.full((n, 8), ord("a"), np.uint8)
    return E.make_entities(np.arange(n, dtype=np.int32),
                           np.arange(n, dtype=np.int32),
                           payload={"sig": sig, "text": text})


def _linkage_sides():
    rng = np.random.default_rng(5)
    lhs = E.synth_entities(rng, 200, n_keys=48, dup_frac=0.0, text_len=12)
    take = rng.permutation(200)[:80]
    rhs = E.make_entities(
        np.asarray(lhs["key"])[take], np.arange(80, dtype=np.int32),
        payload={k: np.asarray(v)[take] for k, v in lhs["payload"].items()})
    return lhs, rhs


def _case(name):
    """(resolve(cfg) -> ERResult, cfg, expected survivors or None)."""
    kind, _, rest = name.partition("-")
    if kind == "reference":
        cell = load_cell("pub1.4m-w10.zipf")
        cfg = dict(cell.cfg, n=4000)
        rec = corpus.make_corpus(cfg, cell.traffic, 3913000004)
        ents = bench_entities(rec, cfg["matcher"])
        return (lambda c: api.resolve(ents, c)), \
            er_config(cfg).with_(return_scores=True), None
    if kind == "survivors":
        ents = _twins(EDGES[rest])
        cfg = api.ERConfig(window=4, variant="srp", num_shards=1, hops=1,
                           matcher=TEXT, return_scores=True)
        return (lambda c: api.resolve(ents, c)), cfg, EDGES[rest]
    ents = E.synth_entities(np.random.default_rng(3), 300, n_keys=60,
                            dup_frac=0.25, text_len=12,
                            skew=0.3 if kind == "adaptive" else 0.0)
    if kind in ("vmap", "shard_map"):
        variant, matcher = rest.split("-")
        cfg = api.ERConfig(window=6, variant=variant, runner=kind,
                           num_shards=4, hops=3,
                           matcher=MATCHERS[matcher], return_scores=True)
        return (lambda c: api.resolve(ents, c)), cfg, None
    base = dict(window=5, variant="repsn", num_shards=4, hops=3,
                return_scores=True)
    if kind == "linkage":
        lhs, rhs = _linkage_sides()
        cfg = api.ERConfig(matcher=MATCHERS[rest], **base)
        return (lambda c: api.link(lhs, rhs, c)), cfg, None
    if kind == "adaptive":
        cfg = api.ERConfig(matcher=TEXT, window_policy="adaptive",
                           window_max=9, **base)
    elif kind == "prune":
        cfg = api.ERConfig(matcher=TEXT, prune_policy="evidence",
                           prune_threshold=0.3, **base)
    else:
        cfg = api.ERConfig(matcher=MATCHERS[kind], **base)
    return (lambda c: api.resolve(ents, c)), cfg, None


def compare(name, monkeypatch) -> dict:
    """Run case ``name`` with the scan engine and with the oracle; the
    facts the test asserts on, as plain values."""
    monkeypatch.setitem(W._BAND_ENGINES, "fullband", FullBand)
    monkeypatch.setattr(CF, "BAND_ENGINES", CF.BAND_ENGINES + ("fullband",))
    if name.startswith("survivors"):
        monkeypatch.setattr(W, "SURVIVOR_CHUNK", CHUNK)
        PC.executable_cache().clear()
    run, cfg, planted = _case(name)
    seen = []
    collect = RN._device_outcome_packed

    def record(out, cfg_, r):
        seen.append(out)
        return collect(out, cfg_, r)

    monkeypatch.setattr(RN, "_device_outcome_packed", record)
    try:
        scan = run(cfg)
        full = run(cfg.with_(band_engine="fullband"))
    finally:
        if name.startswith("survivors"):
            PC.executable_cache().clear()
    (got,), (want,) = seen[:len(seen) // 2], seen[len(seen) // 2:]
    parts = [p for p in ("main", "boundary") if p in got]
    bands = {f: all(np.array_equal(np.asarray(got[p][f]),
                                   np.asarray(want[p][f])) for p in parts)
             for f in ("mask", "match", "scores") if f in got[parts[0]]}
    slots = sum(int(np.asarray(got[p]["mask"]).size) for p in parts)
    facts = {
        "pairs": scan.blocking.pairs == full.blocking.pairs,
        "matches": scan.matches == full.matches,
        "bands": bands,
        "n_blocked": len(scan.blocking.pairs),
        "n_matched": len(scan.matches),
        "cand_count": list(scan.blocking.cand_count),
        "gate": gate_counts(want, parts, cfg.matcher).tolist(),
        "evals": scan.blocking.matcher_evals,
        "slots": slots, "planted": planted,
        "one_matcher": len(cfg.matcher.matchers) < 2}
    if name.startswith("reference"):
        cell = load_cell("pub1.4m-w10.zipf")
        c = dict(cell.cfg, n=4000)
        rec = corpus.make_corpus(c, cell.traffic, 3913000004)
        ref = reference.resolve(rec, c["er"]["window"], c["matcher"])
        facts["reference"] = check.compare(
            rec, c["matcher"], ref, check.packed(scan.blocking.pairs),
            check.packed(scan.matches))
    return facts


VMAP = [f"vmap-{v}-{m}" for v in ("srp", "repsn", "jobsn")
        for m in ("default", "text")]
SHARD_MAP = [f"shard_map-{v}-{m}" for v in ("srp", "repsn", "jobsn")
             for m in ("default", "text")]
CASES = VMAP + SHARD_MAP + [
    "linkage-default", "linkage-text", "adaptive", "prune", "one",
    *(f"survivors-{k}" for k in EDGES), "reference-n4000"]


@pytest.fixture(scope="module")
def on_four_devices():
    """The shard_map cases, run in a fresh process with 4 CPU devices."""
    code = textwrap.dedent(f"""
        import json, os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=4")
        import pytest
        from tests.test_scan_survivors import compare
        out = {{}}
        with pytest.MonkeyPatch.context() as mp:
            for name in {SHARD_MAP!r}:
                out[name] = compare(name, mp)
        print("@@" + json.dumps(out))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{REPO / 'src'}:{REPO}")
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=900)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("@@")]
    assert lines, done.stderr[-3000:]
    return json.loads(lines[-1][2:])


@pytest.mark.parametrize("name", CASES)
def test_scan_engine_matches_full_band(name, request, monkeypatch):
    if name.startswith("shard_map"):
        facts = request.getfixturevalue("on_four_devices")[name]
    else:
        facts = compare(name, monkeypatch)
    assert facts["pairs"] and facts["matches"]
    assert facts["bands"] == {"mask": True, "match": True, "scores": True}
    assert facts["n_blocked"] > 0
    survivors = sum(facts["cand_count"])
    if facts["one_matcher"]:
        assert survivors == 0 and facts["evals"] == facts["slots"]
    else:
        assert facts["cand_count"] == facts["gate"]
        assert survivors <= facts["evals"] <= facts["slots"]
    if facts["planted"] is not None:
        assert survivors == facts["planted"]
    elif name.startswith("survivors"):
        assert survivors == facts["n_blocked"]     # every blocked slot
    else:
        assert facts["n_matched"] > 0
    if name.startswith("reference"):
        assert facts["reference"] == {"blocked_diff": 0, "match_gap": 0.0}


def test_survivor_counters_in_the_trace():
    """A traced resolve counts the survivors and the last matcher's
    evaluations, as the public result reports them."""
    from repro import obs
    ents = E.synth_entities(np.random.default_rng(3), 300, n_keys=60,
                            dup_frac=0.25, text_len=12)
    tracer = obs.Tracer()
    with obs.activate(tracer):
        res = api.resolve(ents, api.ERConfig(window=6, num_shards=4,
                                             hops=3, matcher=TEXT))
    counters = tracer.metrics.to_dict()
    survivors = sum(res.blocking.cand_count)
    assert 0 < survivors < len(res.blocking.pairs)
    assert counters["band.survivors"]["value"] == survivors
    assert counters["band.expensive_evals"]["value"] == \
        res.blocking.matcher_evals
