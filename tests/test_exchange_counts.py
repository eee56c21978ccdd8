"""What a shard_map resolve places on the mesh and sends between shards.

On 4 virtual CPU devices, a traced resolve through ``ShardMapRunner``
records a ``distribute`` span (the mapper splits placed on the mesh) under
``attempt``, before ``shard_program``, and, in an ``exchange_counts`` span
after it, adds per job:

  * ``distribute_bytes``    every byte of the stacked mapper splits
  * ``shuffle.bytes``       ``r * (r-1) * cap_link`` all_to_all slots bound
                            for another shard, times a row's bytes without
                            the planner's ``_dest`` tag
  * ``shuffle.rows_moved``  valid rows whose reducer is not the mapper
                            split that holds them
  * ``halo.rows``           ``(r-1) * (w-1) * hops`` for RepSN,
                            ``(r-1) * (w-1)`` for JobSN, 0 for SRP

each computed here from the plan and the shapes, not from the runner's
helper.  A vmap resolve records none of them.  Pair sets are the same
traced and untraced, and equal to the sequential oracle's.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
N, R, W, HOPS = 302, 4, 6, 2
COUNTERS = ("distribute_bytes", "shuffle.bytes", "shuffle.rows_moved",
            "halo.rows")


def expected(ents, plan, variant: str) -> dict:
    """The four counters of one job, from their definitions."""
    valid = np.asarray(ents["valid"])
    n = valid.shape[0]
    rows = -(-n // R)
    cap_link = plan.cap_link if plan.cap_link is not None else rows
    row = sum(np.asarray(x)[0].nbytes for x in
              (ents["key"], ents["eid"], ents["valid"],
               *ents["payload"].values()))
    tagged = row + (4 if plan.dest is not None else 0)   # int32 _dest
    dest = plan.assignment(np.asarray(ents["key"]))
    moved = valid & (dest != np.arange(n) // rows)
    halo = {"repsn": (R - 1) * (W - 1) * HOPS, "jobsn": (R - 1) * (W - 1),
            "srp": 0}[variant]
    return {"distribute_bytes": R * rows * tagged,
            "shuffle.bytes": R * (R - 1) * cap_link * row,
            "shuffle.rows_moved": int(moved.sum()), "halo.rows": halo}


def runs() -> dict:
    """Per (variant, partitioner, runner): the traced run's spans and
    counters, the expected counters, and whether the pair sets agree
    (run in a process with 4 virtual devices)."""
    from repro import api
    from repro.core import entities as E
    from repro.perf import cache as PC
    from tests.test_device_scopes import MATCHER
    ents = E.synth_entities(np.random.default_rng(5), N, n_keys=4,
                            dup_frac=0.25, text_len=12)
    out = {}
    for variant in ("repsn", "jobsn", "srp"):
        for part in ("balanced", "blocksplit"):
            cfg = api.ERConfig(window=W, variant=variant, hops=HOPS,
                               partitioner=part, matcher=MATCHER,
                               num_shards=R)
            oracle = api.resolve(ents, cfg.with_(runner="sequential"))
            for runner in ("shard_map", "vmap"):
                c = cfg.with_(runner=runner)
                PC.executable_cache().clear()
                plain = api.resolve(ents, c)
                traced = api.resolve(ents, c.with_(trace=True))
                spans = traced.trace.spans
                name = {s.index: s.name for s in spans}
                under = [s.name for s in spans
                         if name.get(s.parent) == "attempt"]
                dist = [s.attrs for s in spans if s.name == "distribute"]
                got = traced.trace.metrics()["metrics"]
                out[f"{variant}/{part}/{runner}"] = {
                    "under_attempt": under, "distribute": dist,
                    "counters": {k: got[k]["value"] for k in COUNTERS
                                 if k in got},
                    "want": expected(ents, api.plan_shards(ents, c, R),
                                     variant),
                    "same_pairs": (plain.pairs == traced.pairs
                                   == oracle.pairs),
                    "same_matches": (plain.matches == traced.matches
                                     == oracle.matches),
                    "traces": [plain.perf.traces, traced.perf.traces]}
    return out


def test_distribute_span_and_exchange_counters_on_four_devices():
    script = ("import json\nfrom tests.test_exchange_counts import runs\n"
              "print('@@' + json.dumps(runs()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{REPO / 'src'}:{REPO}")
    done = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("@@")]
    assert lines, done.stderr[-3000:]
    got = json.loads(lines[-1][2:])
    assert len(got) == 12
    for key, run in got.items():
        assert run["same_pairs"] and run["same_matches"], key
        assert run["traces"] == [1, 0], key
        if key.endswith("/shard_map"):
            assert run["under_attempt"][:4] == \
                ["distribute", "shard_program", "exchange_counts",
                 "collect"], key
            assert run["distribute"] == [{"device": True, "chips": R}], key
            assert run["counters"] == run["want"], key
        else:
            assert "distribute" not in run["under_attempt"], key
            assert run["distribute"] == [] and run["counters"] == {}, key
    # balanced routes by key bounds, blocksplit splits a key block and
    # routes by the plan's dest (the _dest tag is placed, not shuffled)
    row = got["repsn/balanced/shard_map"]["want"]["distribute_bytes"]
    assert got["repsn/blocksplit/shard_map"]["want"]["distribute_bytes"] \
        == row + 4 * R * -(-N // R)
    assert min(run["want"]["shuffle.rows_moved"]
               for run in got.values()) > 0
