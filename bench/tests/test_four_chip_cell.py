"""A traced run of the four-chip cell on 4 virtual CPU devices: correct,
with the span and counter readers reporting, ``distribute_s`` among them,
and the device readers left out, since the trace has no TPU plane."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "pub1.4m-w10-x4.zipf"


def traced_run() -> dict:
    """The result line of a traced run at n = 4,000 (run in a process with
    4 virtual devices)."""
    from bench.run import run_cell
    return run_cell(CELL, 79, 0.05, True, require_tpu=False, n=4000,
                    cache=False, log=lambda s: None)


def test_traced_four_chip_run_reports_distribute_s():
    script = ("import json\nfrom bench.tests.test_four_chip_cell import "
              "traced_run\nprint('@@' + json.dumps(traced_run()))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}:{ROOT}")
    done = subprocess.run([sys.executable, "-c", script], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    lines = [ln for ln in done.stdout.splitlines() if ln.startswith("@@")]
    assert lines, done.stderr[-3000:]
    out = json.loads(lines[-1][2:])
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["device"]["count"] == 4
    got = set(out["metrics"])
    assert got == {"plan_s", "load_imbalance", "shard_program_s",
                   "collect_s", "attempt_self_s", "result_build_s",
                   "transfer_s", "distribute_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["metrics"]["distribute_s"]["unit"] == "s"
    assert "breakdown" not in out and list(out)[-1] == "checks"
