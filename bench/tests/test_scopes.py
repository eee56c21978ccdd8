"""Device time by the shard program's named stages (``bench/scopes.py``)."""
from pathlib import Path

import pytest

from bench import devtrace, scopes
from bench.tests.test_devtrace import ev, op, profile

DATA = Path(__file__).resolve().parent / "data"


@pytest.mark.parametrize("path, stage", [
    ("jit(p)/vmap(shuffle)/jit(sort)/sort", "shuffle"),
    ("jit(p)/vmap(band/select)/while/body/closed_call/band/align/"
     "jit(_roll_dynamic)/gather", "band/align"),
    ("jit(p)/vmap(band/select)/while", "band/select"),
    ("jit(p)/band/cheap/and;band/expensive/mul", "band/cheap"),
    ("jit(p)/band/expensive/pallas_call:", "band/expensive"),
    ("jit(p)/shuffled/band/cheapest/add", None),
    ("jit(p)/reduce_sum", None),
    ("", None),
])
def test_stage_of_takes_the_innermost_named_stage(path, stage):
    assert scopes.stage_of(path) == stage


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(no, value):
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(no, v) for no, v in fields)


def test_op_paths_reads_tf_op_stats_of_device_planes():
    stat_md = lambda sid, name: _field(5, _msg((1, sid), (2, _msg(
        (1, sid), (2, name)))))
    event_md = lambda eid, name, *stats: _field(4, _msg((1, eid), (2, _msg(
        (1, eid), (2, name), *((5, s) for s in stats)))))
    plane = (_field(1, 7) + _field(2, "/device:TPU:0")
             + _field(3, _msg((2, "XLA Ops")))           # a line: skipped
             + stat_md(1, "hlo_category") + stat_md(2, "tf_op")
             + stat_md(3, "jit(p)/band/cheap/add")
             + event_md(10, "%fusion.1 = f32[8]",
                        _msg((1, 1), (5, "fusion")),
                        _msg((1, 2), (5, "jit(p)/vmap(shuffle)/sort")))
             + event_md(11, "%add.2 = f32[8]", _msg((1, 2), (7, 3)))
             + event_md(12, "%copy.3 = f32[8]", _msg((1, 1), (5, "copy"))))
    host = _field(2, "/host:CPU") + event_md(1, "shard_program",
                                             _msg((1, 2), (5, "x")))
    raw = _field(1, plane) + _field(1, host) + _field(4, "hostname")
    assert scopes.op_paths(raw) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(p)/vmap(shuffle)/sort",
        "%add.2 = f32[8]": "jit(p)/band/cheap/add"}}


SYNTHETIC = {
    "fusion.1": "jit(p)/vmap(shuffle)/jit(sort)/sort",
    "while.2": "jit(p)/vmap(band/select)/while",
    "fusion.3": "jit(p)/vmap(band/select)/while/body/closed_call/"
                "band/align/jit(_roll_dynamic)/gather",
    "fusion.4": "jit(p)/vmap(band/select)/while/body/band/cheap/and;"
                "band/expensive/mul",
    "copy.5": "jit(p)/copy",
    "fusion.6": "jit(p)/vmap(band/select)/band/expensive/while",
    "fusion.7": "jit(p)/vmap(shuffle)/all-to-all",
}


def synthetic():
    chip0 = [op("fusion.7", 50, 100),     # inside the window, outside the mark
             op("fusion.1", 200, 100),
             op("while.2", 300, 300),     # 100 ns of its own
             op("fusion.3", 320, 100),
             op("fusion.4", 450, 100),
             op("copy.5", 700, 100),
             op("fusion.6", 850, 100)]    # half inside the mark
    chip1 = [op("fusion.1", 160, 200)]
    pd = profile(chip0, chip1, host=[ev("shard_program", 150, 750)])
    paths = {f"/device:TPU:{k}": {e.name: SYNTHETIC[devtrace.op_name(e.name)]
                                  for e in chip}
             for k, chip in enumerate((chip0, chip1))}
    return pd, paths


def test_reduce_charges_each_instant_to_the_innermost_op():
    pd, paths = synthetic()
    got = scopes.reduce(pd, paths, chips=1)
    assert got["busy_by_scope"] == {
        "shuffle": pytest.approx(100e-9), "band/align": pytest.approx(100e-9),
        "band/cheap": pytest.approx(100e-9),
        "band/expensive": pytest.approx(50e-9),
        "band/select": pytest.approx(100e-9)}
    assert got["unscoped_s"] == pytest.approx(100e-9)
    assert got["busy_in_s"] == pytest.approx(550e-9)
    assert got["busy_in_s"] == pytest.approx(devtrace.reduce(
        pd, chips=1)["busy_in_s"]["shard_program"])


def test_reduce_means_over_chips_and_adds_up_to_busy():
    pd, paths = synthetic()
    got = scopes.reduce(pd, paths, chips=2)
    assert got["busy_by_scope"]["shuffle"] == pytest.approx(150e-9)
    assert got["busy_by_scope"]["band/cheap"] == pytest.approx(50e-9)
    assert got["busy_in_s"] == pytest.approx(devtrace.reduce(
        pd, chips=2)["busy_in_s"]["shard_program"])
    whole = scopes.reduce(pd, paths, chips=2, mark=devtrace.WINDOW)
    assert whole["busy_in_s"] == pytest.approx(devtrace.reduce(
        pd, chips=2)["busy_s"])


def test_ops_without_a_path_are_unscoped():
    pd, _ = synthetic()
    got = scopes.reduce(pd, {}, chips=1)
    assert set(got["busy_by_scope"].values()) == {0.0}
    assert got["unscoped_s"] == pytest.approx(550e-9)


def test_metrics_per_job_and_unscoped_share():
    pd, paths = synthetic()
    m = scopes.metrics(scopes.reduce(pd, paths, chips=1), jobs=2)
    assert m["band_align_s"] == pytest.approx(50e-9)
    assert m["band_expensive_s"] == pytest.approx(25e-9)
    assert m["device_unscoped_pct"] == pytest.approx(100 * 100 / 550)
    assert set(m) == {"shuffle_s", "band_align_s", "band_cheap_s",
                      "band_expensive_s", "band_select_s",
                      "device_unscoped_pct"}


def test_no_device_plane_reads_nothing():
    _, paths = synthetic()
    assert scopes.reduce(profile(), paths, chips=1) is None
    assert scopes.metrics(None, jobs=1) == {}


def test_recorded_v5e_scoped_trace():
    """A trace recorded on one v5e by ``record_scoped_trace.py``: three
    runs of a program with a sort in ``shuffle``, a fusion in
    ``band/cheap``, the ``pallas_call`` named ``recorded_kernel`` in
    ``band/expensive`` and an unscoped reduction; XLA's layout copy and
    cloned iota carry no ``op_name`` and are unscoped too."""
    from jax.profiler import ProfileData
    path = DATA / "v5e_scopes.xplane.pb"
    paths = scopes.op_paths(path.read_bytes())
    kernel, = (p for name, p in paths["/device:TPU:0"].items()
               if name.startswith("%recorded_kernel"))
    assert scopes.stage_of(kernel) == "band/expensive"
    pd = ProfileData.from_file(str(path))
    got = scopes.reduce(pd, paths, chips=1)
    assert got["busy_by_scope"] == {
        "shuffle": pytest.approx(1.041155e-3), "band/align": 0.0,
        "band/cheap": pytest.approx(2.634e-6),
        "band/expensive": pytest.approx(2.542e-6), "band/select": 0.0}
    assert got["unscoped_s"] == pytest.approx(2.6111e-5)
    d = devtrace.reduce(pd, chips=1)
    assert got["busy_in_s"] == pytest.approx(d["busy_in_s"]["shard_program"])
    assert got["busy_in_s"] == pytest.approx(d["busy_s"])
    m = scopes.metrics(got, jobs=3)
    assert m["device_unscoped_pct"] == pytest.approx(100 * 2.6111e-5 /
                                                     1.072442e-3)


def test_scoped_run_on_the_cpu_reads_spans_and_no_stages():
    """``run_scoped`` drives a traced run of the cell; on the CPU the trace
    has no TPU plane, so no stage is read, while the span readers of the
    public result and the transfer report."""
    from bench.tests.cells import SPEC
    from repro.perf import cache as PC
    PC.executable_cache().clear()
    out = scopes.run_scoped("pub1.4m-w10.zipf", 3913000007, 0.05,
                            require_tpu=False, n=4000, cache=False,
                            log=lambda s: None, spec=SPEC)
    PC.executable_cache().clear()
    assert out["correct"]
    assert out["scopes"] == {"reduced": None, "metrics": {}}
    assert out["metrics"]["result_build_s"]["value"] > 0
    assert out["metrics"]["transfer_s"]["value"] > 0
