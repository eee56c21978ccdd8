"""Reduce a ``jax.profiler`` trace of the measured window to device numbers.

The harness brackets the window in a ``TraceAnnotation`` named
``bench.window``, and the program's device spans (``shard_program``) appear
as annotations of their own when its tracer runs with ``jax_profiler=True``.
From the trace this module takes, for the first ``chips`` TPU planes:

  * busy: the union of the intervals in which an operation of the "XLA Ops"
    line ran, clipped to the window (nested events count once);
  * busy inside each named host annotation, such as ``shard_program``;
  * collective time: summed durations of the collective operations;
  * the operations that took most time (leaf operations, so a loop does not
    count its body twice), by HLO instruction name;
  * the idle time of the first chip, split by the innermost host span open
    at the time, so each gap is named by what the host was doing.

An operation event is named by its HLO text, ``%name = shape op(...)``; the
instruction name is the part before `` = ``.  Device timestamps come to the
host's clock through the profiler's own alignment, which was within about
a millisecond on a TPU v5e.  Times are seconds.  Device
numbers are means over the chips.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-to-all|collective-permute|all-reduce|all-gather|reduce-scatter")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (k, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.append(np.nonzero(new)[0][1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


def clip(iv: np.ndarray, lo, hi) -> np.ndarray:
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint interval sets."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def op_name(event_name: str) -> str:
    """HLO instruction name of an "XLA Ops" event."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def idle_by_span(gaps: np.ndarray, spans, lo, hi) -> dict:
    """Idle time inside each stretch of [lo, hi) where one host span is the
    innermost open, summed by span name ("no span" where none is open).
    ``gaps``: disjoint sorted idle intervals; ``spans``: (start, end,
    depth, name) on the same clock."""
    cuts = np.unique(np.clip([lo, hi, *(t for s, e, _, _ in spans
                                         for t in (s, e))], lo, hi))
    lens = gaps[:, 1] - gaps[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(lens)])

    def covered(t):          # idle time in [lo, t)
        j = np.searchsorted(gaps[:, 0], t, side="right") - 1
        if j < 0:
            return 0.0
        return cum[j] + min(t, gaps[j, 1]) - gaps[j, 0]

    out = {}
    for a, b in zip(cuts[:-1], cuts[1:]):
        idle = covered(b) - covered(a)
        if idle > 0:
            name = _label(spans, (a + b) / 2)
            out[name] = out.get(name, 0.0) + idle
    return out


def _planes(pd):
    host, dev = [], []
    for p in pd.planes:
        if p.name.startswith("/device:TPU:"):
            dev.append(p)
        elif p.name.startswith("/host:"):
            host.append(p)
    key = lambda p: int(re.sub(r"\D", "", p.name.split(":")[-1]) or 0)
    return host, sorted(dev, key=key)


def _host_events(host_planes, names) -> dict:
    out = {n: [] for n in names}
    for p in host_planes:
        for line in p.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns, e.start_ns + e.duration_ns))
    return {n: np.array(v, np.float64).reshape(-1, 2) for n, v in out.items()}


def load(trace_dir: str):
    """The profile a ``jax.profiler`` trace wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(trace_dir))


def reduce(pd, *, chips: int, marks=("shard_program",), host_spans=None,
           top: int = 10):
    """Device numbers of the traced window in profile ``pd`` (planes, lines
    and events as ``jax.profiler.ProfileData`` gives them), or None when it
    holds no TPU plane.  ``host_spans``: (start_s, end_s, depth, name)
    tuples in seconds from the start of the window, used to name idle
    gaps."""
    host, dev = _planes(pd)
    if not dev:
        return None
    ev = _host_events(host, (WINDOW,) + tuple(marks))
    if len(ev[WINDOW]) != 1:
        raise ValueError(f"expected one {WINDOW!r} annotation, found "
                         f"{len(ev[WINDOW])}")
    w0, w1 = ev[WINDOW][0]
    spans = [(w0 + a * 1e9, w0 + b * 1e9, d, name)
             for a, b, d, name in host_spans or ()]
    mark_iv = {m: union(clip(ev[m], w0, w1)) for m in marks}
    busy, in_mark, coll, ops = [], {m: [] for m in marks}, [], {}
    gaps = None
    for k, plane in enumerate(dev[:chips]):
        evs = sorted((e.start_ns, e.duration_ns, op_name(e.name))
                     for line in plane.lines if line.name == OPS_LINE
                     for e in line.events
                     if e.start_ns < w1 and e.start_ns + e.duration_ns > w0)
        c = 0.0
        for i, (s, d, name) in enumerate(evs):
            if i + 1 < len(evs) and evs[i + 1][0] < s + d:
                continue             # holds the events that follow: not a leaf
            d = min(s + d, w1) - max(s, w0)
            ops[name] = ops.get(name, 0.0) + d
            if COLLECTIVE.search(name):
                c += d
        iv = np.array([(s, s + d) for s, d, _ in evs], np.float64)
        u = union(clip(iv.reshape(-1, 2), w0, w1))
        busy.append((u[:, 1] - u[:, 0]).sum())
        for m in marks:
            in_mark[m].append(overlap(u, mark_iv[m]))
        coll.append(c)
        if k == 0:
            edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
            gaps = edges[edges[:, 1] > edges[:, 0]]
    n = len(busy)
    ns = 1e-9
    idle = idle_by_span(gaps, spans, w0, w1)
    return {
        "chips": n,
        "window_s": (w1 - w0) * ns,
        "busy_s": float(np.mean(busy)) * ns,
        "busy_in_s": {m: float(np.mean(v)) * ns for m, v in in_mark.items()},
        "collective_s": float(np.mean(coll)) * ns,
        "collective_ops": any(COLLECTIVE.search(k) for k in ops),
        "device_ops": [[k, v * ns / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }


def _label(spans, t) -> str:
    """Name of the innermost host span open at trace time ``t``."""
    best, depth = "no span", -1
    for s, e, d, name in spans:
        if s <= t < e and d > depth:
            best, depth = name, d
    return best
