"""Runs of the harness with the timed path broken underneath must come out
not correct; a sound run must come out correct.

Each test skips the harness's look for a chip and drives the rest of a run
at a size the CPU holds: set-up, the window, the check.  Faults are planted
in the program where the answer is produced, and the executable cache is
emptied so the shard program is traced again with the fault in it."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.cells import SPEC
from bench.run import run_cell

N = 4000
CELL = "pub1.4m-w10.zipf"


@pytest.fixture
def fresh_programs():
    from repro.perf import cache as PC
    PC.executable_cache().clear()
    yield
    PC.executable_cache().clear()


def run(cell=CELL, **kw):
    return run_cell(cell, 77, 0.05, False, require_tpu=False, n=N,
                    cache=False, log=lambda s: None, spec=SPEC, **kw)


def test_sound_run_is_correct(fresh_programs):
    out = run()
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert list(out)[-1] == "checks"


def _every_blocked_pair_matches(monkeypatch):
    from repro.core import window as W
    get = W.get_band_engine

    class Broken:
        def __init__(self, engine):
            self.engine = engine

        def __getattr__(self, name):
            return getattr(self.engine, name)

        def band(self, e, cfg, **kw):
            out = self.engine.band(e, cfg, **kw)
            return dict(out, match=out["mask"])

    monkeypatch.setattr(W, "get_band_engine", lambda name: Broken(get(name)))


def _one_blocked_pair_altered(monkeypatch):
    from repro.api import runners
    to_outcome = runners.PackedOutcome.to_outcome

    def altered(self):
        out = to_outcome(self)
        lo, hi = min(out.blocked)
        return out._replace(blocked=(out.blocked - {(lo, hi)})
                            | {(lo, hi + 1 if hi + 1 != lo else hi + 2)})

    monkeypatch.setattr(runners.PackedOutcome, "to_outcome", altered)


def _half_the_shards_left_out(monkeypatch):
    from repro.api import runners
    shard_input = runners.shard_input

    def half(ents, r):
        st = shard_input(ents, r)
        st["valid"] = st["valid"].at[r // 2:].set(False)
        return st

    monkeypatch.setattr(runners, "shard_input", half)


def _halo_exchange_left_out(monkeypatch):
    from repro.core import entities as E
    from repro.core import repsn

    def no_halo(sorted_ents, w, r, axis, hops=1):
        return E.empty_like(sorted_ents, w - 1)

    monkeypatch.setattr(repsn, "halo_exchange", no_halo)


def _shuffle_left_out(monkeypatch):
    from repro.core import srp
    monkeypatch.setattr(srp, "exchange", lambda bucketed, r, axis: bucketed)


FAULTS = {
    "every_blocked_pair_matches": _every_blocked_pair_matches,
    "one_blocked_pair_altered": _one_blocked_pair_altered,
    "half_the_shards_left_out": _half_the_shards_left_out,
    "halo_exchange_left_out": _halo_exchange_left_out,
    "shuffle_left_out": _shuffle_left_out,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch, fresh_programs):
    FAULTS[fault](monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


def test_four_chip_cell_on_virtual_devices():
    """The shard_map cell on 4 virtual CPU devices: sound, then with the
    halo collective-permute and the all_to_all shuffle left out."""
    root = Path(__file__).resolve().parents[2]
    script = f"""
import json, sys
sys.path[:0] = [{str(root / 'src')!r}, {str(root)!r}]
from bench.tests.test_faults import four_chip_runs
print(json.dumps(four_chip_runs()))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got == {"sound": True, "halo_exchange_left_out": False,
                   "shuffle_left_out": False}


def four_chip_runs() -> dict:
    """Correct flags of the 4-chip cell, sound and with each exchange
    left out (run in a process with 4 virtual devices)."""
    from repro.perf import cache as PC
    cell = "pub1.4m-w10-x4.zipf"
    got = {"sound": run(cell)["correct"]}
    for fault in ("halo_exchange_left_out", "shuffle_left_out"):
        mp = pytest.MonkeyPatch()
        try:
            PC.executable_cache().clear()
            FAULTS[fault](mp)
            got[fault] = run(cell)["correct"]
        finally:
            mp.undo()
    return got


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  json.loads((Path(__file__).resolve()
                                              .parents[2] / "BENCHMARK.json")
                                             .read_text())["workloads"]])
def test_without_a_tpu_the_run_fails_and_prints_no_result(cell):
    root = Path(__file__).resolve().parents[2]
    done = subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=root)
    assert done.returncode != 0 and done.stdout == ""
    assert "TPU" in done.stderr


def test_traced_run_reports_span_metrics_and_skips_device_ones(
        fresh_programs):
    """On the CPU the profiler trace has no TPU plane: the span and counter
    readers report, the device readers return nothing and are left out."""
    out = run_cell(CELL, 78, 0.05, True, require_tpu=False, n=N,
                   cache=False, log=lambda s: None, spec=SPEC)
    assert out["correct"]
    got = set(out["metrics"])
    assert {"plan_s", "load_imbalance", "shard_program_s", "collect_s",
            "attempt_self_s"} <= got
    assert not got & {"device_idle_pct", "band_roofline", "collective_s",
                      "peak_hbm_bytes"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert "breakdown" not in out and list(out)[-1] == "checks"
