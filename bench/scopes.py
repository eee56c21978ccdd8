#!/usr/bin/env python3
"""Device time of the traced window by the shard program's named stages.

The program names its stages with ``jax.named_scope`` (``shuffle``,
``band/align``, ``band/cheap``, ``band/expensive``, ``band/select``).  A
scope is HLO metadata: the ``op_name`` of each instruction.  A TPU profile
carries it on every operation, as the ``tf_op`` stat of the operation's
event metadata in the ``.xplane.pb`` the profiler writes;
``jax.profiler.ProfileData`` does not expose metadata stats, so
``op_paths`` reads them from the file's bytes with a small protobuf
reader.  ``reduce`` then charges the device-busy time inside the
``shard_program`` annotation to stages:

  * each instant of busy time belongs to the innermost "XLA Ops" event
    running then (a loop's own time, outside its body's operations, to
    the loop), so the stages and the unscoped time add up to the busy
    time inside the annotation;
  * an event belongs to the innermost stage in its ``op_name`` path (a
    stage may show as ``vmap(band/cheap)``; of a ``;``-joined name, the
    first path counts).  A fusion carries the ``op_name`` XLA gave it;
  * time of an event with no stage is unscoped, never spread over the
    stages.

Times are seconds, means over the chips, as in ``bench/devtrace.py``,
whose window and annotation handling this module shares.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does and prints its result
line with a ``scopes`` entry: the busy time by stage and the per-job
metrics ``metrics()`` gives.  ``bench/run.py`` removes the trace before
its readers run and passes them no stage times, so these metrics are not
yet in its result line.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent

STAGES = ("shuffle", "band/align", "band/cheap", "band/expensive",
          "band/select")
_STAGE = re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, STAGES)) +
                    r")(?=[/):;]|$)")
METRICS = {"shuffle": "shuffle_s", "band/align": "band_align_s",
           "band/cheap": "band_cheap_s", "band/expensive": "band_expensive_s",
           "band/select": "band_select_s"}


def stage_of(path: str):
    """The innermost stage named in an ``op_name`` path, or None."""
    found = _STAGE.findall(path.split(";", 1)[0])
    return found[-1] if found else None


# -- the .xplane.pb protobuf, as far as op_name paths need it ---------------

def _fields(buf):
    """(field number, value) of each field of one protobuf message:
    varints as int, length-delimited fields as memoryview; fixed-width
    fields are skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if b < 0x80:
                return out
            shift += 7

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, varint()
        elif wire == 2:
            size = varint()
            yield field, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_paths(raw: bytes) -> dict:
    """{device plane name: {event name: op_name path}} from the bytes of
    an ``.xplane.pb`` (``XSpace``: planes 1; ``XPlane``: name 2,
    event_metadata 4, stat_metadata 5; ``XEventMetadata``: name 2, stats
    5; ``XStat``: metadata_id 1, str_value 5, ref_value 7).  The path is
    the ``tf_op`` stat; events without one are left out."""
    out = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map_entry(v)[1])
            elif g == 5:
                sid, md = _map_entry(v)
                stat_names[sid] = next(
                    (bytes(x).decode() for h, x in _fields(md) if h == 2),
                    "")
        if not name.startswith("/device:TPU:"):
            continue
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"),
                     None)
        paths = {}
        for md in events:
            ev_name, path = None, None
            for g, v in _fields(md):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op:
                        path = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7), "")
            if ev_name is not None and path is not None:
                paths.setdefault(ev_name, path)
        out[name] = paths
    return out


# -- the reduction -----------------------------------------------------------

def _inside(a, b, marks, j):
    """Length of [a, b) inside the disjoint sorted ``marks`` from index
    ``j`` on (segments arrive in order); returns (length, next j)."""
    while j < len(marks) and marks[j, 1] <= a:
        j += 1
    tot, k = 0.0, j
    while k < len(marks) and marks[k, 0] < b:
        tot += min(b, marks[k, 1]) - max(a, marks[k, 0])
        k += 1
    return tot, j


def busy_by_stage(events, marks) -> dict:
    """Busy time inside ``marks`` charged to the innermost event running:
    {stage or None: ns}.  ``events``: (start, end, stage) in ns."""
    evs = sorted((e for e in events if e[1] > e[0]),
                 key=lambda e: (e[0], -e[1]))
    cuts = sorted({t for s, e, _ in evs for t in (s, e)})
    out = defaultdict(float)
    stack, i, j = [], 0, 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        while i < len(evs) and evs[i][0] <= a:
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            t, j = _inside(a, b, marks, j)
            if t > 0:
                out[stack[-1][2]] += t
    return out


def reduce(pd, paths: dict, *, chips: int, mark: str = "shard_program"):
    """Busy time inside the ``mark`` annotation of the traced window by
    stage, or None where the profile ``pd`` holds no TPU plane.
    ``paths``: ``op_paths`` of the same trace.  Returns {"busy_in_s",
    "busy_by_scope" (every stage, 0.0 where none ran), "unscoped_s"}."""
    from bench import devtrace
    host, dev = devtrace._planes(pd)
    if not dev:
        return None
    ev = devtrace._host_events(host, (devtrace.WINDOW, mark))
    if len(ev[devtrace.WINDOW]) != 1:
        raise ValueError(f"expected one {devtrace.WINDOW!r} annotation, "
                         f"found {len(ev[devtrace.WINDOW])}")
    w0, w1 = ev[devtrace.WINDOW][0]
    marks = devtrace.union(devtrace.clip(ev[mark], w0, w1))
    per_chip = []
    for plane in dev[:chips]:
        names = paths.get(plane.name, {})
        per_chip.append(busy_by_stage(
            ((e.start_ns, e.start_ns + e.duration_ns,
              stage_of(names.get(e.name, "")))
             for line in plane.lines if line.name == devtrace.OPS_LINE
             for e in line.events
             if e.start_ns < w1 and e.start_ns + e.duration_ns > w0),
            marks))
    mean = lambda key: float(np.mean([c.get(key, 0.0) for c in per_chip])) \
        * 1e-9
    return {"busy_in_s": sum(mean(k) for k in (*STAGES, None)),
            "busy_by_scope": {s: mean(s) for s in STAGES},
            "unscoped_s": mean(None)}


def metrics(scoped, jobs: int) -> dict:
    """The per-job stage metrics of a ``reduce`` result: seconds per job
    by stage, and ``device_unscoped_pct``, the unscoped share of the busy
    time inside the annotation.  Empty where nothing was reduced."""
    if scoped is None or scoped["busy_in_s"] <= 0:
        return {}
    out = {METRICS[s]: v / jobs for s, v in scoped["busy_by_scope"].items()}
    out["device_unscoped_pct"] = \
        100.0 * scoped["unscoped_s"] / scoped["busy_in_s"]
    return out


def run_scoped(workload: str, seed: int, seconds: float, **kw) -> dict:
    """One traced run of the cell (``bench.run.run_cell``) with the stage
    reduction of its trace added under ``scopes``."""
    from bench import devtrace, run
    kept = {}
    load = devtrace.load

    def keep(trace_dir):
        kept["raw"] = Path(devtrace.find_xplane(trace_dir)).read_bytes()
        kept["pd"] = load(trace_dir)
        return kept["pd"]

    devtrace.load = keep
    try:
        out = run.run_cell(workload, seed, seconds, True, **kw)
    finally:
        devtrace.load = load
    scoped = None
    if "pd" in kept:
        chips = run.load_cell(workload, kw.get("spec")).chips
        scoped = reduce(kept["pd"], op_paths(kept["raw"]), chips=chips)
    out["scopes"] = {"reduced": scoped,
                     "metrics": metrics(scoped, out["attempted"])}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import run
    try:
        out = run_scoped(args.workload, args.seed, args.seconds)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    for _p in (str(ROOT / "src"), str(ROOT)):
        if _p not in sys.path:
            sys.path.insert(0, _p)
    sys.exit(main())
