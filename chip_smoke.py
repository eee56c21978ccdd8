#!/usr/bin/env python3
"""Smoke run of the paper's batch resolve on TPU.

Drives ``repro.api.resolve`` (sorted-neighborhood blocking plus the §5.1
matcher cascade) at the paper's data scale: 1.4M publication-like records
(arXiv:1010.3053 §5.1, ``docs/paper-map.md``), window w = 10, synthetic data
from ``--seed``.  Every blocked and matched pair set must equal the
sequential host oracle (``runner="sequential"``) exactly, and the blocked
count must equal the closed-form SN pair count.

    python chip_smoke.py             # one chip: vmap runner, 8 shards, for
                                     # repsn/scan, repsn/pallas (native
                                     # kernel), jobsn/scan
    python chip_smoke.py --chips 4   # four chips: shard_map runner over a
                                     # 4-chip mesh, repsn and jobsn

Earlier lines of standard output are one JSON object per phase (cold and
steady seconds, pair counts, executable-cache counters, device peak bytes,
the persistent compile-cache directory).  The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}

and is printed only when every phase passed.  Without a TPU, or with fewer
chips than asked for, the script exits non-zero before any phase runs.  It
runs in one process and starts none.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

N_PAPER = 1_400_000     # §5.1: 1.4M publication records
WINDOW = 10             # §5.1: w = 10 (and 100)
VMAP_SHARDS = 8


# The blocksplit planner sizes each shard's receive buffer to its planned
# load.  The legacy default partitioner gives every shard room for all n
# rows, which at n = 1.4M and 8 vmapped shards needs more than one chip's
# 16 GB of HBM.
PARTITIONER = "blocksplit"


def one_chip_phases() -> list:
    """(label, ERConfig overrides) of the one-chip phases.  The pallas phase
    pins ``band_interpret=False``: the native kernel, never the
    interpreter."""
    vmap = dict(runner="vmap", num_shards=VMAP_SHARDS,
                partitioner=PARTITIONER)
    return [
        ("repsn/scan", dict(vmap, variant="repsn", band_engine="scan")),
        ("repsn/pallas", dict(vmap, variant="repsn", band_engine="pallas",
                              band_interpret=False)),
        ("jobsn/scan", dict(vmap, variant="jobsn", band_engine="scan")),
    ]


def multi_chip_phases() -> list:
    """(label, ERConfig overrides) of the multi-chip phases."""
    mesh = dict(runner="shard_map", partitioner=PARTITIONER)
    return [("repsn/shard_map", dict(mesh, variant="repsn")),
            ("jobsn/shard_map", dict(mesh, variant="jobsn"))]


def build_corpus(n: int, seed: int) -> dict:
    """The §5.1-scale corpus: about n/10 blocking keys, 16-byte titles for
    the edit-distance stage (the widths ``benchmarks/bench_sn`` uses)."""
    import numpy as np

    from repro.core import entities as E
    return E.synth_entities(np.random.default_rng(seed), n,
                            n_keys=max(n // 10, 1), text_len=16)


def _peak_bytes(devices) -> list:
    """Per-device ``peak_bytes_in_use`` (None where the backend has no
    memory statistics)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def run_phases(ents: dict, n: int, phases: list, *, devices,
               mesh=None) -> list:
    """Resolve ``ents`` once cold and once steady per phase and check the
    results against the sequential oracle.  Returns one record per phase;
    raises AssertionError on the first mismatch."""
    from benchmarks.bench_sn import paper_cascade
    from repro import api
    from repro.api.variants import get_variant
    from repro.core import sn

    matcher = paper_cascade()
    expected = sn.expected_pair_count(n, WINDOW)
    # every phase's variant is boundary-complete, so the sequential runner
    # gives each the same full SN pair set: one oracle run serves them all
    variants = {ov["variant"] for _, ov in phases}
    assert all(get_variant(v).boundary_complete for v in variants), variants
    t0 = time.perf_counter()
    oracle = api.resolve(ents, api.ERConfig(
        window=WINDOW, variant=phases[0][1]["variant"], runner="sequential",
        num_shards=VMAP_SHARDS, matcher=matcher))
    print(json.dumps({"oracle": "sequential",
                      "seconds": time.perf_counter() - t0,
                      "blocked": len(oracle.blocking.pairs),
                      "matched": len(oracle.matches)}), flush=True)
    assert len(oracle.blocking.pairs) == expected

    records = []
    for label, overrides in phases:
        cfg = api.ERConfig(window=WINDOW, matcher=matcher, **overrides)
        t0 = time.perf_counter()
        cold = api.resolve(ents, cfg, mesh=mesh)
        cold_s = time.perf_counter() - t0
        rec = {"phase": label, "n": n, "window": WINDOW,
               "runner": cfg.runner, "shards": cold.blocking.num_shards,
               "cold_s": cold_s,
               "cold_cache": [cold.perf.cache_hits, cold.perf.cache_misses],
               "cold_blocked": len(cold.blocking.pairs),
               "cold_matched_equal": cold.matches == oracle.matches}
        del cold
        # resolve returns host pair sets built from the device output, so
        # the device work is complete when it returns
        t0 = time.perf_counter()
        steady = api.resolve(ents, cfg, mesh=mesh)
        rec.update({
            "steady_s": time.perf_counter() - t0,
            "steady_cache": [steady.perf.cache_hits,
                             steady.perf.cache_misses],
            "blocked": len(steady.blocking.pairs),
            "matched": len(steady.matches),
            "expected_blocked": expected,
            "overflow": steady.blocking.overflow,
            "cand_overflow": steady.blocking.cand_overflow,
            "blocked_equal": steady.blocking.pairs == oracle.blocking.pairs,
            "matched_equal": steady.matches == oracle.matches,
            "peak_bytes_in_use": _peak_bytes(devices)})
        del steady
        if not records:
            rec["split_s"] = _traced_split(ents, cfg, mesh)
        print(json.dumps(rec), flush=True)
        records.append(rec)
        assert rec["blocked"] == rec["cold_blocked"] == expected, rec
        assert rec["overflow"] == 0 and rec["cand_overflow"] == 0, rec
        assert rec["blocked_equal"] and rec["matched_equal"] \
            and rec["cold_matched_equal"], rec
    return records


def _traced_split(ents: dict, cfg, mesh) -> dict:
    """Self seconds per span of one traced warm resolve: ``shard_program``
    (the device program), ``collect`` (host collection) and ``attempt``,
    whose self time is the frozenset materialisation of the public
    result."""
    from repro import api
    res = api.resolve(ents, cfg.with_(trace=True), mesh=mesh)
    return dict(res.trace.self_times())


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.perf.cache import enable_compilation_cache
    cache_dir = enable_compilation_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    used = devices[:args.chips]
    print(json.dumps({"devices": [str(d) for d in used],
                      "kind": devices[0].device_kind,
                      "compile_cache_dir": cache_dir}), flush=True)

    t0 = time.perf_counter()
    ents = build_corpus(N_PAPER, args.seed)
    print(json.dumps({"corpus": N_PAPER, "seed": args.seed,
                      "seconds": time.perf_counter() - t0}), flush=True)
    if args.chips == 1:
        run_phases(ents, N_PAPER, one_chip_phases(), devices=used)
    else:
        mesh = jax.make_mesh((args.chips,), ("data",), devices=used)
        run_phases(ents, N_PAPER, multi_chip_phases(), devices=used,
                   mesh=mesh)
    print(json.dumps({"compile_cache_dir": cache_dir,
                      "compile_cache_entries": _cache_entries(cache_dir)}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
