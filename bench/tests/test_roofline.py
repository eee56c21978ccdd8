"""The peaks table and the band work behind ``band_roofline``."""
import json

import pytest

from bench import roofline
from bench.run import ROOT

CFG = json.loads((ROOT / "bench/configs/pub1.4m-w10.json").read_text())
WORK = dict(matcher=CFG["matcher"], widths={"sig": (32,), "text": (64,)},
            row_bytes=4 + 4 + 4 * 32 + 64)


@pytest.mark.parametrize("n,w", [(1_400_000, 10), (140_000, 100), (5, 10),
                                 (10, 2)])
def test_blocked_slots_are_the_sn_pair_count(n, w):
    from repro.core import sn
    work = roofline.band_work(n=n, w=w, survivors=0, **WORK)
    assert work["slots"] == sn.expected_pair_count(n, w)


def test_expensive_matcher_counts_survivors_only():
    a = roofline.band_work(n=1000, w=10, survivors=0, **WORK)
    b = roofline.band_work(n=1000, w=10, survivors=10, **WORK)
    assert b["int_ops"] - a["int_ops"] == 10 * (6 * 64 * 64 + 2 * 64)
    assert b["flops"] - a["flops"] == 10 * 4


def test_share_cannot_pass_100_percent():
    from bench.metrics import band_roofline
    from types import SimpleNamespace
    work = roofline.band_work(n=1_400_000, w=10, survivors=250_000, **WORK)
    least, bound = roofline.least_time(work, "TPU v5 lite")
    assert bound in ("ops", "bytes") and least > 0
    for jobs in (1, 4):
        for busy in (least, 2 * least, 1.0):
            run = SimpleNamespace(
                jobs=jobs, work=work, device_kind="TPU v5 lite",
                trace={"busy_in_s": {"shard_program": busy * jobs}})
            assert 0 < band_roofline.read(run) <= 100.0 + 1e-9


def test_unknown_device_kind_is_an_error():
    work = roofline.band_work(n=100, w=10, survivors=0, **WORK)
    with pytest.raises(KeyError):
        roofline.least_time(work, "TPU v99")
