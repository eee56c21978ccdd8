"""Integration tests on REAL multiple devices (8 CPU host devices via
subprocess — jax locks the device count at first init, so these re-exec)."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_with_devices(n, body: str, timeout=900) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count={n}")
        import json
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("@@R@@" + json.dumps(out))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}/src:{REPO}:" + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=timeout)
    for line in p.stdout.splitlines():
        if line.startswith("@@R@@"):
            return json.loads(line[5:])
    raise AssertionError(f"subprocess failed:\n{p.stdout[-3000:]}\n"
                         f"{p.stderr[-3000:]}")


def test_sn_pipeline_shard_map_matches_oracle():
    """The REAL-collective path (repro.api resolve with the shard_map runner
    over 8 devices) produces exactly the sequential SN pair set — same
    oracle as the vmap property tests."""
    out = run_with_devices(8, """
        import numpy as np, jax
        from repro import api
        from repro.core import entities as E, partition as P, sn
        rng = np.random.default_rng(5)
        n, w, nk = 400, 6, 128
        ents = E.synth_entities(rng, n, n_keys=nk, dup_frac=0.3)
        keys, eids = np.asarray(ents["key"]), np.asarray(ents["eid"])
        oracle = sn.sequential_sn_pairs(keys, eids, w)
        mesh = jax.make_mesh((8,), ("data",))
        res = {}
        for variant in ["repsn", "jobsn"]:
            o = api.resolve(ents,
                            api.ERConfig(window=w, variant=variant, hops=7,
                                         runner="shard_map"),
                            bounds=P.balanced_partition(keys, 8), mesh=mesh)
            got = set(o.blocking.pairs)
            res[variant] = [len(oracle - got), len(got - oracle),
                            o.blocking.overflow]
        out = res
    """)
    assert out["repsn"] == [0, 0, 0]
    assert out["jobsn"] == [0, 0, 0]


def test_dual_source_linkage_shard_map():
    """Dual-source R x S linkage on real devices: only cross-source pairs,
    equal to the host linkage oracle."""
    out = run_with_devices(8, """
        import numpy as np, jax
        from repro import api
        from repro.core import entities as E
        rng = np.random.default_rng(9)
        w = 5
        lhs = E.synth_entities(rng, 300, n_keys=96, dup_frac=0.0)
        take = rng.permutation(300)[:120]
        rhs = E.make_entities(np.asarray(lhs["key"])[take],
                              np.arange(120, dtype=np.int32),
                              payload={k: np.asarray(v)[take]
                                       for k, v in lhs["payload"].items()})
        mesh = jax.make_mesh((8,), ("data",))
        merged, offset = api.tag_sources(lhs, rhs)
        oracle = api.linkage.untag_pairs(api.sequential_link_pairs(
            np.asarray(merged["key"]), np.asarray(merged["eid"]),
            np.asarray(merged["payload"]["src"]), w), offset)
        res = api.link(lhs, rhs,
                       api.ERConfig(window=w, variant="repsn", hops=7,
                                    runner="shard_map"), mesh=mesh)
        got = set(res.blocking.pairs)
        out = {"diff": [len(oracle - got), len(got - oracle)],
               "n_matches": len(res.matches),
               "cross_only": all(0 <= a < 300 and 0 <= b < 120
                                 for a, b in got)}
    """)
    assert out["diff"] == [0, 0]
    assert out["cross_only"]
    assert out["n_matches"] > 0

