#!/usr/bin/env python3
"""Chip benchmark of the batch resolve (``repro.api.resolve``).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/``) and a traffic mix
(``bench/traffic/``) in ``BENCHMARK.json``.  A run

  1. keeps JAX's persistent compilation cache in ``<checkout>/.jax_cache``;
  2. builds the cell's records from ``--seed`` (``bench/corpus.py``) and
     puts them on the first chip;
  3. warms up with one whole resolve (set-up ends here: ``setup_s``);
  4. runs whole resolves back to back for ``--seconds``, one at a time: the
     window ends with the job that crosses the limit, and ``resolve_s`` is
     window seconds over jobs.  A job is the resolve, a fingerprint of its
     pair sets and the release of its result, as a batch user's loop frees
     each result; the last job's sets are packed for the check outside the
     clock, and its release is timed and added;
  5. checks, outside the window, the last job's pair sets against the plain
     reference (``bench/reference.py``, ``bench/check.py``) and every
     job's fingerprint against the last job's;
  6. prints the numbers compared beside their limits on standard error, and
     one JSON result line on standard output.

With ``--trace 1`` the window runs under the program's span tracer and the
JAX profiler, and the result holds the per-layer metrics, read by
``bench/metrics/<name>.py``, instead of the end-to-end ones.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _paths() -> None:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_cell(name: str, spec: dict | None = None) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``spec``, a dict of
    the same form) with its configuration file, traffic mix and the metrics
    it reports."""
    from bench import corpus
    if spec is None:
        with open(ROOT / "BENCHMARK.json") as f:
            spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        cfg = json.load(f)
    mine = lambda ms: [m for m in ms if name in m.get("workloads", [name])]
    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), cfg=cfg,
        traffic=corpus.load_traffic(cell["traffic"]),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def enable_compile_cache() -> None:
    """Persistent compilation cache at a fixed path in the checkout, for
    every program, so only a checkout's first run compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_for(n: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < n):
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} devices; JAX found {len(devs)}")
    return devs[:n]


def er_config(cfg: dict):
    from repro import api
    from repro.core.match import CascadeMatcher, Matcher
    m = cfg["matcher"]
    matcher = CascadeMatcher(
        matchers=tuple(Matcher(field=x["field"], kind=x["kind"],
                               weight=x["weight"], cost=x["cost"])
                       for x in m["matchers"]),
        threshold=m["threshold"])
    return api.ERConfig(matcher=matcher, **cfg["er"])


def entities(rec: dict, matcher: dict):
    """The records as the program's entity dict, with the payload fields the
    matcher reads, on the default device and uncommitted, so a program over
    several chips may move them."""
    import jax.numpy as jnp
    from repro.core import entities as E
    put = jnp.asarray
    return E.make_entities(
        put(rec["key"]), put(rec["eid"]),
        payload={m["field"]: put(rec[m["field"]])
                 for m in matcher["matchers"]})


class CompileCount:
    """Counts compilations (persistent-cache loads included) while open."""

    def __init__(self):
        self.n = 0

    def _on(self, event, duration_secs=None, **_):
        if event == COMPILE_EVENT:
            self.n += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def window(call, seconds: float, bracket=contextlib.nullcontext):
    """Whole jobs back to back until ``seconds`` have passed, inside
    ``bracket()``.  A job is the resolve, a fingerprint of its pair sets and
    the release of its result, as a batch user's loop frees each result.
    The window ends with the job whose fingerprint crosses the limit.  That
    job's pair sets are then packed for the check, off the clock and outside
    the bracket, and the release of its result is timed and added, so every
    job counts the same work.  Returns (seconds, per-job fingerprints,
    per-job capacity drops, (blocked, matched, load) of the last job)."""
    from bench import check
    from repro import obs
    fps, drops = [], []
    with bracket():
        t0 = time.perf_counter()
        while True:
            with obs.span("job"):
                res = call()
            with obs.span("fingerprint"):
                b = res.blocking
                fps.append((len(b.pairs), hash(b.pairs), len(res.matches),
                            hash(res.matches)))
                drops.append(b.overflow + b.cand_overflow + b.pair_overflow)
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            with obs.span("release"):
                del res, b
    kept = (check.packed(b.pairs), check.packed(res.matches), tuple(b.load))
    t = time.perf_counter()
    del res, b
    return elapsed + time.perf_counter() - t, fps, drops, kept


def span_totals(spans):
    """(inclusive, self) seconds summed per span name."""
    child = defaultdict(float)
    for r in spans:
        if r.parent >= 0:
            child[r.parent] += r.dur
    total, self_ = defaultdict(float), defaultdict(float)
    for r in spans:
        total[r.name] += r.dur
        self_[r.name] += r.dur - child[r.index]
    return total, self_


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, n: int | None = None,
             cache: bool = True, log=None,
             spec: dict | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``n`` replaces the configuration's record count and ``spec`` the
    benchmark file, for tests at a size the CPU holds.  ``log(line)``
    receives the progress lines."""
    _paths()
    from bench import check, corpus, devtrace, reference, roofline
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = load_cell(workload, spec)
    cfg = dict(cell.cfg, n=n or cell.cfg["n"])
    if cache:
        enable_compile_cache()
    import jax
    devs = chips_for(cell.chips, require_tpu)
    from repro import api, obs

    with CompileCount() as setup_compiles:
        t = time.perf_counter()
        rec = corpus.make_corpus(cfg, cell.traffic, seed)
        ents = entities(rec, cfg["matcher"])
        er = er_config(cfg)
        mesh = jax.make_mesh((cell.chips,), ("data",), devices=devs) \
            if er.runner == "shard_map" else None
        call = lambda: api.resolve(ents, er, mesh=mesh)
        log(f"corpus n={cfg['n']} seed={seed}: "
            f"{time.perf_counter() - t:.3f} s")
        t = time.perf_counter()
        warm = call()
        log(f"warm-up resolve: {time.perf_counter() - t:.3f} s, blocked "
            f"{len(warm.blocking.pairs)}, matched {len(warm.matches)}")
        del warm
    setup_s = time.perf_counter() - T_START
    log(f"setup: {setup_s:.3f} s, {setup_compiles.n} compilations")

    tracer = tdir = None
    with CompileCount() as window_compiles:
        if trace:
            tracer = obs.Tracer(jax_profiler=True)
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans come from obs
            jax.profiler.start_trace(tdir, profiler_options=opts)
            sync = []

            @contextlib.contextmanager
            def bracket():
                with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                    sync.append(tracer.wall())
                    yield

            try:
                with obs.activate(tracer):
                    elapsed, fps, drops, kept = window(call, seconds,
                                                       bracket)
            finally:
                jax.profiler.stop_trace()
            t_sync = sync[0]
        else:
            elapsed, fps, drops, kept = window(call, seconds)
    jobs = len(fps)
    log(f"window: {elapsed:.3f} s, {jobs} jobs, "
        f"{window_compiles.n} compilations inside")
    blocked, matched, load = kept
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs) or None
    del ents, call
    t = time.perf_counter()
    ref = reference.resolve(rec, cfg["er"]["window"], cfg["matcher"])
    last = check.compare(rec, cfg["matcher"], ref, blocked, matched)
    _, last_ok = check.verdict(last, cfg["limits"])
    numbers = dict(last, overflow=int(sum(drops)),
                   jobs_differ=sum(fp != fps[-1] for fp in fps))
    checks, ok = check.verdict(numbers, cfg["limits"])
    log(f"reference and check: {time.perf_counter() - t:.3f} s")
    failed = sum(d > 0 or fp != fps[-1] or not last_ok
                 for d, fp in zip(drops, fps))

    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(ok), "attempted": jobs, "failed": int(failed)}
    if not trace:
        metrics = {"resolve_s": elapsed / jobs, "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": metrics[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        spans = tracer.spans()
        total, self_ = span_totals(spans)
        host = [(r.t0 - t_sync, r.t0 + r.dur - t_sync, r.depth, r.name)
                for r in spans]
        dev = devtrace.reduce(devtrace.load(tdir), chips=cell.chips,
                              host_spans=host)
        shutil.rmtree(tdir, ignore_errors=True)
        per_job = lambda d: (lambda k: d[k] / jobs if k in d else None)
        run = SimpleNamespace(
            jobs=jobs, self_s=per_job(self_), total_s=per_job(total),
            result={"load": load}, trace=dev, peak_bytes=peak,
            device_kind=d0.device_kind,
            work=roofline.band_work(
                n=cfg["n"], w=cfg["er"]["window"], matcher=cfg["matcher"],
                widths={f: v.shape[1:] for f, v in rec.items()},
                row_bytes=sum(v[0].nbytes for v in rec.values()),
                survivors=ref[2]))
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        if dev is not None:
            device.update(busy_s=dev["busy_s"], window_s=dev["window_s"])
            out["breakdown"] = {"device_ops": dev["device_ops"],
                                "idle_gaps": dev["idle_gaps"]}
            if d0.device_kind in roofline.PEAKS:
                log("band least time bound: "
                    f"{roofline.least_time(run.work, d0.device_kind)[1]}")
    out["device"] = device
    out["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
