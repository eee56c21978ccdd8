"""The plain reference and the comparison that decides ``correct``."""
import numpy as np
import pytest

from bench import check, corpus, reference
from bench.control import control
from bench.tests.cells import SPEC
from bench.run import load_cell


def _levenshtein(a: bytes, b: bytes) -> int:
    dp = list(range(len(b) + 1))
    for i in range(1, len(a) + 1):
        prev, dp[0] = dp[0], i
        for j in range(1, len(b) + 1):
            prev, dp[j] = dp[j], min(dp[j] + 1, dp[j - 1] + 1,
                                     prev + (a[i - 1] != b[j - 1]))
    return dp[-1]


def test_levenshtein_matches_the_textbook_loop():
    rng = np.random.default_rng(1)
    a = rng.integers(97, 100, (400, 8)).astype(np.uint8)
    b = rng.integers(97, 100, (400, 8)).astype(np.uint8)
    for i, (la, lb) in enumerate(rng.integers(0, 9, (400, 2))):
        a[i, la:] = 0
        b[i, lb:] = 0
    got = reference.levenshtein(a, b)
    want = [_levenshtein(bytes(x[x > 0]), bytes(y[y > 0]))
            for x, y in zip(a, b)]
    np.testing.assert_array_equal(got, want)


def test_reference_blocking_is_the_sn_pair_set():
    from repro.core import sn
    c = load_cell("pub1.4m-w10.zipf", SPEC)
    cfg = dict(c.cfg, n=300)
    rec = corpus.make_corpus(cfg, c.traffic, 2)
    rec["eid"] = np.random.default_rng(2).permutation(300).astype(np.int32)
    blocked = reference.resolve(rec, 5, cfg["matcher"])[0]
    assert set(zip(*reference.unpack(blocked))) == \
        sn.sequential_sn_pairs(rec["key"], rec["eid"], 5)


@pytest.mark.parametrize("cell", ["pub1.4m-w10.zipf"])
def test_reference_against_itself_is_correct(cell):
    c = load_cell(cell, SPEC)
    cfg = dict(c.cfg, n=20000)
    rec = corpus.make_corpus(cfg, c.traffic, 3)
    ref = reference.resolve(rec, 10, cfg["matcher"])
    numbers = check.compare(rec, cfg["matcher"], ref, ref[0], ref[1])
    assert numbers == {"blocked_diff": 0, "match_gap": 0.0}


@pytest.mark.parametrize("cell", ["pub1.4m-w10.zipf"])
def test_bfloat16_control_is_not_correct(cell):
    """The reference in bfloat16, put in the program's place, fails
    ``match_gap``: the graded duplicates put pairs near the threshold."""
    out = control(cell, 4, n=20000, spec=SPEC)
    assert not out["correct"]
    assert out["checks"]["blocked_diff"]["value"] == 0
    gap = out["checks"]["match_gap"]
    assert gap["value"] > 3 * gap["limit"]
