"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phases (the same resolve calls and oracle checks it makes on the chip) pass
at a tiny size on the CPU, with the Pallas interpreter standing in for the
native kernel."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
N = 2_000


def _env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=f"{ROOT / 'src'}:{ROOT}:" +
                os.environ.get("PYTHONPATH", ""), **extra)


@pytest.fixture(scope="module")
def smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
        yield chip_smoke
    finally:
        sys.path.remove(str(ROOT))


def test_exits_nonzero_without_tpu():
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(), cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "TPU" in p.stderr


def test_one_chip_phases_match_oracle(smoke):
    import jax
    ents = smoke.build_corpus(N, seed=3)
    # the interpreter stands in for the native kernel the chip compiles
    phases = [(label, dict(ov, band_interpret=True) if
               ov["band_engine"] == "pallas" else ov)
              for label, ov in smoke.one_chip_phases()]
    records = smoke.run_phases(ents, N, phases, devices=jax.devices())
    assert [r["phase"] for r in records] == \
        ["repsn/scan", "repsn/pallas", "jobsn/scan"]
    for r in records:
        assert r["blocked_equal"] and r["matched_equal"], r
        assert r["blocked"] == r["expected_blocked"]
        assert r["steady_cache"] == [1, 0]       # the cold call compiled
    assert records[0]["split_s"]["shard_program"] > 0


def test_multi_chip_phases_match_oracle():
    """The --chips 4 phases on four virtual CPU devices."""
    code = textwrap.dedent(f"""
        import jax
        import chip_smoke as C
        devs = jax.devices()[:4]
        mesh = jax.make_mesh((4,), ("data",), devices=devs)
        recs = C.run_phases(C.build_corpus({N}, seed=3), {N},
                            C.multi_chip_phases(), devices=devs, mesh=mesh)
        print("@@R@@" + json.dumps([[r["phase"], r["shards"],
              r["blocked_equal"], r["matched_equal"]] for r in recs]))
    """)
    p = subprocess.run(
        [sys.executable, "-c", "import json\n" + code],
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith("@@R@@")]
    assert lines, p.stderr[-3000:]
    assert json.loads(lines[0][5:]) == [["repsn/shard_map", 4, True, True],
                                        ["jobsn/shard_map", 4, True, True]]
