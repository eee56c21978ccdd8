"""The reduction from a profiler trace to device numbers."""
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import devtrace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(*device_events, host=()):
    host_plane = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 100, 900), *host])])
    devs = [NS(name=f"/device:TPU:{i}", lines=[
        NS(name="XLA Modules", events=[ev("jit_program", 0, 5000)]),
        NS(name=devtrace.OPS_LINE, events=list(evs))])
        for i, evs in enumerate(device_events)]
    return NS(planes=[host_plane, *devs])


def test_union_and_overlap():
    u = devtrace.union(np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]],
                                float))
    np.testing.assert_array_equal(u, [[0, 3], [5, 9], [10, 11]])
    assert devtrace.overlap(u, np.array([[2, 6], [8, 20]], float)) == 4


def op(name, start, dur):
    return ev(f"%{name} = f32[8]{{0}} {name.split('.')[0]}(f32[8]{{0}} %p)",
              start, dur)


def test_busy_idle_marks_collectives_and_idle_by_span():
    chip0 = [op("fusion.1", 50, 100),          # clipped to [100, 150)
             op("while.3", 190, 120),          # holds fusion.2: not a leaf
             op("fusion.2", 200, 100),
             op("all-to-all.4", 400, 50),
             op("fusion.5", 950, 100)]         # clipped to [950, 1000)
    chip1 = [op("collective-permute-done.1", 300, 300)]
    spans = [(0.0, 5e-7, 0, "job"), (1e-7, 3e-7, 1, "collect")]
    d = devtrace.reduce(
        profile(chip0, chip1, host=[ev("shard_program", 180, 100)]),
        chips=2, host_spans=spans)
    assert d["window_s"] == pytest.approx(900e-9)
    # chip 0: 50 + 120 + 50 + 50 = 270 ns busy; chip 1: 300 ns
    assert d["busy_s"] == pytest.approx(285e-9)
    # inside shard_program [180, 280): chip 0 90 ns, chip 1 0 ns
    assert d["busy_in_s"]["shard_program"] == pytest.approx(45e-9)
    assert d["collective_s"] == pytest.approx(175e-9)
    assert d["collective_ops"]
    ops = dict(d["device_ops"])
    assert "while.3" not in ops and ops["fusion.2"] == pytest.approx(50e-9)
    assert d["device_ops"][0] == ["collective-permute-done.1",
                                  pytest.approx(150e-9)]
    # chip 0 idles in [150,190) [310,400) [450,950); job spans [100,600),
    # collect [200,400)
    assert d["idle_gaps"] == [["no span", pytest.approx(350e-9)],
                              ["job", pytest.approx(190e-9)],
                              ["collect", pytest.approx(90e-9)]]


def test_recorded_v5e_trace():
    """A trace recorded on one v5e by ``record_trace.py``: three sorts of
    about 13 microseconds inside a 19.6 ms window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(DATA / "v5e_sort.xplane.pb"))
    d = devtrace.reduce(pd, chips=4)
    assert d["chips"] == 1
    assert d["window_s"] == pytest.approx(0.019554768)
    assert d["busy_s"] == pytest.approx(3.9767e-05)
    assert d["device_ops"][0] == ["sort.6", pytest.approx(3.7655e-05)]
    assert not d["collective_ops"]
    assert d["idle_gaps"] == [["no span",
                               pytest.approx(d["window_s"] - d["busy_s"])]]


def test_one_chip_without_collectives():
    d = devtrace.reduce(profile([ev("fusion.1", 200, 100)]), chips=1)
    assert not d["collective_ops"] and d["collective_s"] == 0
    assert d["busy_in_s"]["shard_program"] == 0


def test_no_device_plane_reads_nothing():
    assert devtrace.reduce(NS(planes=profile().planes[:1]), chips=1) is None
