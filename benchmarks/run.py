"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable detail to
stderr-ish comment lines).  Usage:

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Tables:
  fig8_scalability      paper Fig. 8: speedup vs #cores, w in {10, 100}
  tbl1_fig9_skew        paper Table 1 + Fig. 9: Gini vs runtime
  sec52_jobsn_vs_repsn  paper §5.2: JobSN vs RepSN (+ SRP baseline)
  band_engine           §5.1 cascade: scan vs pallas band engine + packed
                        pair collection; writes BENCH_band_engine.json
  balance               skew-aware planners (uniform/blocksplit/pairrange)
                        on the Zipfian corpus; writes BENCH_balance.json
  stream                out-of-core resolve_stream vs monolithic resolve
                        (pairs/s, peak device bytes, parity for all
                        variants x engines); writes BENCH_stream.json
  serve                 online incremental serving: sustained micro-batch
                        inserts/deletes into a ResolutionService
                        (inserts/s, p50/p95 latency, zero-retrace steady
                        state, parity); writes BENCH_serve.json
  overload              overload-hardened serving: open-loop load at
                        1x/2x/5x warm capacity under chaos, shed/expired/
                        degraded accounting, goodput + p95/p99, repair
                        parity; writes BENCH_overload.json
  resilience            fault tolerance: checkpointed stream overhead,
                        kill/resume wall time + parity, overflow-retry
                        zero-dropped-pairs; writes BENCH_resilience.json
  obs                   observability: traced vs untraced steady resolve,
                        disabled-path cost, zero extra retraces, streamed
                        trace coverage per variant; writes BENCH_obs.json
                        + the Chrome trace BENCH_obs_trace.json
  recall                ground-truth match quality (repro.quality): the
                        PC/RR/F Pareto across fixed-w / multi-pass /
                        adaptive / meta-blocked blocking configs on the
                        labeled skewed corpus, plus the clean-corpus
                        full-window PC=1.0 gate and streamed/traced
                        parity; writes BENCH_recall.json
  kernels               Pallas band kernels vs jnp oracle (CPU timings)
  dedup_e2e             end-to-end corpus dedup throughput + SN-vs-n^2 factor

Every BENCH_*.json goes through ``write_bench``, which stamps the shared
``schema_version`` (``repro.obs.SCHEMA_VERSION``) and a ``machine_proxy_s``
host-speed micro-bench so cross-machine comparisons (perf_smoke) can
validate and normalize uniformly.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


def _machine_proxy(reps: int = 3) -> float:
    """Best-of-``reps`` seconds for a fixed synthetic numpy workload (the
    same dedup/concat shape the pair-collection path performs) — a
    machine-speed proxy stamped into every BENCH blob so perf_smoke can
    normalize absolute numbers across machine classes."""
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2 ** 31, 200_000).astype(np.uint64)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.unique(np.concatenate([a, a[::2]]))
        best = min(best, time.perf_counter() - t0)
    return best


def write_bench(path: str, res: dict) -> None:
    """THE one BENCH_*.json writer: stamps the shared ``schema_version``
    (from ``repro.obs``) and the ``machine_proxy_s`` host-speed proxy,
    then writes the blob.  perf_smoke refuses blobs whose schema_version
    does not match its own — a drifted writer/reader pair fails loudly
    instead of silently comparing mismatched fields."""
    from repro.obs import SCHEMA_VERSION
    res = dict(res)
    res["schema_version"] = SCHEMA_VERSION
    res["machine_proxy_s"] = _machine_proxy()
    with open(path, "w") as f:
        json.dump(res, f, indent=2)


def fig8_scalability(quick: bool):
    from benchmarks._subproc import run_with_devices
    n = 20_000 if quick else 80_000
    windows = [10] if quick else [10, 100]
    base = {}
    for w in windows:
        for r in ([1, 4] if quick else [1, 2, 4, 8]):
            res = run_with_devices(
                r, "benchmarks.bench_sn", "scalability_body",
                {"n": n, "w": w, "reps": 2 if quick else 3})
            key = f"fig8_w{w}_r{r}"
            if r == 1:
                base[w] = res["seconds"]
            speedup = base[w] / res["seconds"]
            _row(key, res["seconds"] * 1e6,
                 f"wall_speedup={speedup:.2f};"
                 f"critical_path_speedup={res['work_speedup']:.2f};"
                 f"pairs={res['pairs']}")


def tbl1_fig9_skew(quick: bool):
    from benchmarks._subproc import run_with_devices
    n = 20_000 if quick else 60_000
    strategies = ["manual", "even", "even_40", "even_85"] if quick else \
        ["manual", "even", "even_40", "even_55", "even_70", "even_85"]
    for s in strategies:
        res = run_with_devices(
            8, "benchmarks.bench_sn", "skew_body",
            {"n": n, "w": 20, "strategy": s, "reps": 2 if quick else 3})
        _row(f"fig9_{s}", res["seconds"] * 1e6,
             f"gini={res['gini']};max_load={res['max_load']};"
             f"pairs={res['pairs']}")


def sec52_jobsn_vs_repsn(quick: bool):
    from benchmarks._subproc import run_with_devices
    n = 20_000 if quick else 60_000
    res = run_with_devices(
        8, "benchmarks.bench_sn", "jobsn_vs_repsn_body",
        {"n": n, "w": 20 if quick else 50, "reps": 2 if quick else 3},
        timeout=2400)
    for variant, v in res.items():
        _row(f"sec52_{variant}", v["seconds"] * 1e6,
             f"pairs={v['pairs']};coll_bytes={v['collective_bytes']:.2e};"
             f"permutes={v['permute_count']}")


def band_engine(quick: bool):
    """Scan vs pallas band engine + host pair collection; persists the full
    result dict to BENCH_band_engine.json so later PRs have a perf
    trajectory baseline (the perf-smoke CI gate compares steady-state
    ``pairs_per_s`` against the committed copy — benchmarks/perf_smoke.py)."""
    from benchmarks.bench_sn import band_engine_body
    res = band_engine_body(
        n=6_000 if quick else 20_000, w=8 if quick else 10,
        r=4, reps=5, collect_pairs=100_000)
    for engine, v in res["engines"].items():
        _row(f"band_engine_{engine}", v["steady_seconds"] * 1e6,
             f"cold_us={v['cold_seconds'] * 1e6:.0f};"
             f"matcher_evals={v['matcher_evals']};"
             f"band_slots={v['band_slots']};"
             f"cand_cap={v['cand_cap']};"
             f"pair_cap={v['pair_cap']};"
             f"pairs_per_s={v['pairs_per_s']:.2e}")
    c = res["collection"]
    _row("band_engine_collection", c["packed_seconds"] * 1e6,
         f"pairs={c['pairs']};set_us={c['set_seconds'] * 1e6:.0f};"
         f"packed_speedup={c['speedup']:.1f}x")
    write_bench("BENCH_band_engine.json", res)


def balance(quick: bool):
    """Skew-aware load balancing (ISSUE 3): uniform vs blocksplit vs
    pairrange on the Zipfian corpus; persists BENCH_balance.json (the
    acceptance record: >= 3x imbalance reduction at n >= 6000, 8 shards,
    exponent >= 1.0, with exact pair-set parity)."""
    from benchmarks.bench_sn import balance_body
    res = balance_body(n=6_000 if quick else 20_000, w=10, r=8,
                       exponent=1.0, reps=5)
    for planner, v in res["planners"].items():
        _row(f"balance_{planner}", v["steady_seconds"] * 1e6,
             f"cold_us={v['cold_seconds'] * 1e6:.0f};"
             f"imbalance={v['imbalance_planned']:.2f};"
             f"cap_link={v['cap_link']};"
             f"band_slots={v['band_slots_per_shard']};"
             f"split={v['split_routing']};"
             f"oracle_equal={v['oracle_equal']}")
    _row("balance_reduction", 0.0,
         f"blocksplit={res['imbalance_reduction']['blocksplit']:.1f}x;"
         f"pairrange={res['imbalance_reduction']['pairrange']:.1f}x;"
         f"parity={res['parity']['all_equal_oracle']}")
    write_bench("BENCH_balance.json", res)


def stream(quick: bool):
    """Out-of-core streaming (ISSUE 5): chunked resolve_stream vs
    monolithic resolve on a corpus 4x the chunk size; persists
    BENCH_stream.json (the acceptance record: bit-identical pair sets for
    all variants x engines with per-chunk device residency a fraction of
    the monolithic staging bytes)."""
    from benchmarks.bench_sn import stream_body
    res = stream_body(n=4_800 if quick else 24_000,
                      chunk=1_200 if quick else 6_000,
                      w=8 if quick else 10, r=4, reps=3)
    for engine, v in res["engines"].items():
        _row(f"stream_{engine}", v["stream_steady_seconds"] * 1e6,
             f"mono_us={v['mono_steady_seconds'] * 1e6:.0f};"
             f"stream_pairs_per_s={v['stream_pairs_per_s']:.2e};"
             f"mono_pairs_per_s={v['mono_pairs_per_s']:.2e};"
             f"residency={v['residency_ratio']:.3f};"
             f"steady_chunks={v['steady_chunks']}/{v['chunks']}")
    _row("stream_parity", 0.0,
         f"all_equal={res['parity_all']};"
         f"combos={len(res['parity'])}")
    write_bench("BENCH_stream.json", res)


def serve(quick: bool):
    """Online incremental serving (ISSUE 6 acceptance): sustained insert
    throughput + steady p50/p95 latency over an n-entity base corpus, the
    zero-retrace steady-state claim, and final parity vs a from-scratch
    resolve.  Writes BENCH_serve.json (gated by perf_smoke --serve)."""
    from benchmarks.bench_sn import serve_body
    res = serve_body(n=5_000 if quick else 50_000,
                     batch=100 if quick else 200,
                     ops=12 if quick else 24)
    _row("serve_insert", res["seconds"] * 1e6,
         f"inserts_per_s={res['sustained_inserts_per_s']:.2e};"
         f"p50_ms={res['p50_ms']:.1f};p95_ms={res['p95_ms']:.1f};"
         f"steady={res['steady_batches']}/{res['batches']};"
         f"zero_retrace={res['steady_after_warm']};"
         f"shapes={len(res['shapes'])}")
    _row("serve_parity", 0.0,
         f"blocked={res['parity']['blocked_equal']};"
         f"matched={res['parity']['matched_equal']};"
         f"pairs={res['pairs']};live={res['live_entities']}")
    write_bench("BENCH_serve.json", res)


def overload(quick: bool):
    """Overload-hardened serving (ISSUE 9 acceptance): an open-loop load
    generator at 1x/2x/5x measured warm capacity under chaos (latency
    spikes + injected matcher errors), queue_policy=shed_oldest +
    per-request deadlines.  Gates (perf_smoke --overload): zero hung and
    zero silently-dropped futures at every rate, the admission policy
    engaged at the top rate, and post-pressure ``repair()`` bit-parity.
    Writes BENCH_overload.json."""
    from benchmarks.bench_sn import overload_body
    res = overload_body(n=1_500 if quick else 6_000,
                        batch=60 if quick else 120,
                        ops=10 if quick else 24,
                        warm=4 if quick else 5)
    for ph in res["rates"]:
        _row(f"overload_{ph['rate']:g}x", ph["p95_ms"] * 1e3,
             f"goodput_rps={ph['goodput_rps']:.2f};ok={ph['ok']};"
             f"shed={ph['shed']};expired={ph['expired']};"
             f"chaos={ph['chaos_errors']};hung={ph['hung']};"
             f"degraded={ph['degraded_batches']};"
             f"p99_ms={ph['p99_ms']:.1f}")
    _row("overload_repair", 0.0,
         f"blocked={res['parity']['blocked_equal']};"
         f"matched={res['parity']['matched_equal']};"
         f"repairs={res['repairs']};dirty={res['dirty_after_repair']};"
         f"health={res['health_final']}")
    write_bench("BENCH_overload.json", res)


def resilience(quick: bool):
    """Fault tolerance (ISSUE 7 acceptance): checkpoint write overhead vs
    plain streaming, kill-at-chunk-k resume wall time + pair parity, and
    the overflow-retry ladder recovering every pair a tiny pair_cap would
    have dropped.  Writes BENCH_resilience.json (gated by perf_smoke
    --resilience: overhead <= 15%, zero dropped pairs, parity)."""
    from benchmarks.bench_sn import resilience_body
    res = resilience_body(n=4_800 if quick else 24_000,
                          chunk=1_200 if quick else 6_000,
                          w=8 if quick else 10, r=4, reps=3)
    _row("resilience_ckpt", res["ckpt_steady_seconds"] * 1e6,
         f"plain_us={res['plain_steady_seconds'] * 1e6:.0f};"
         f"overhead={res['checkpoint_overhead']:.3f};"
         f"parity={res['checkpointed_parity']}")
    rs = res["resume"]
    _row("resilience_resume", rs["resume_seconds"] * 1e6,
         f"killed_us={rs['killed_seconds'] * 1e6:.0f};"
         f"kill_at={rs['kill_at']}/{rs['chunks']};"
         f"blocked={rs['blocked_equal']};matched={rs['matched_equal']}")
    rt = res["retry"]
    _row("resilience_retry", 0.0,
         f"retries={rt['retries']};escalations={rt['escalations']};"
         f"pair_cap={rt['start_pair_cap']}->{rt['final_pair_cap']};"
         f"dropped={rt['dropped_pairs']};overflow={rt['pair_overflow']};"
         f"blocked={rt['blocked_equal']}")
    write_bench("BENCH_resilience.json", res)


def obs(quick: bool):
    """Observability layer (ISSUE 8 acceptance): traced vs untraced steady
    resolve, the deterministic disabled-path cost, zero extra retraces
    under tracing, and per-variant streamed trace coverage.  Writes
    BENCH_obs.json + the Chrome trace BENCH_obs_trace.json (gated by
    perf_smoke --obs: traced overhead <= 5%, disabled <= 1%, zero extra
    retraces, coverage >= 0.9)."""
    from benchmarks.bench_sn import obs_body
    res = obs_body(n=4_000 if quick else 12_000,
                   chunk=1_000 if quick else 3_000,
                   w=8, r=4, reps=5)
    _row("obs_traced", res["steady_traced_seconds"] * 1e6,
         f"untraced_us={res['steady_untraced_seconds'] * 1e6:.0f};"
         f"overhead={res['traced_overhead']:.4f};"
         f"spans={res['spans_per_resolve']};"
         f"zero_retrace={res['zero_extra_retraces']}")
    _row("obs_disabled", res["noop_span_seconds"] * 1e6,
         f"overhead={res['disabled_overhead']:.5f}")
    for variant, v in res["stream"].items():
        _row(f"obs_stream_{variant}", v["wall_s"] * 1e6,
             f"coverage={v['coverage']:.3f};spans={v['spans']};"
             f"chunks={v['chunks']}")
    write_bench("BENCH_obs.json", res)


def recall(quick: bool):
    """Ground-truth match quality (ISSUE 10 acceptance): PC / PQ / RR / F
    for >= 4 blocking configurations (fixed-w frontier, multi-pass,
    adaptive windows, evidence-pruned meta-blocking) on the labeled skewed
    corpus, with streamed + traced bit-parity per config; persists
    BENCH_recall.json (gated by perf_smoke --recall: Pareto points
    present, adaptive dominates the mid fixed window, PC=1.0 clean-corpus
    full-window gate, pruning engaged without dropping gold pairs)."""
    from benchmarks.bench_sn import recall_body
    res = recall_body(n=1_200 if quick else 4_000,
                      reps=2 if quick else 3)
    for name, v in res["configs"].items():
        _row(f"recall_{name}", v["steady_seconds"] * 1e6,
             f"pc={v['pc']:.4f};rr={v['rr']:.4f};f={v['f']:.4f};"
             f"blocked={v['blocked']};pruned={v['pruned']};"
             f"streamed={v['streamed_equal']};traced={v['traced_equal']}")
    g = res["gates"]
    _row("recall_gates", 0.0,
         f"full_window_pc={g['full_window_pc']:.4f};"
         f"adaptive_dominates={g['adaptive_dominates_fixed']};"
         f"pruning_engaged={g['pruning_engaged']};"
         f"gold_dropped={g['pruned_gold_dropped']};"
         f"multipass_recovers={g['multipass_recovers_typos']};"
         f"parity={g['parity_all']}")
    write_bench("BENCH_recall.json", res)


def kernels(quick: bool):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    m, f, w = (2048, 128, 64) if quick else (8192, 128, 128)
    feat = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))

    def timeit(fn, *args, reps=5, **kw):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args, **kw))
        return (time.perf_counter() - t0) / reps * 1e6

    us_ref = timeit(ref.banded_sim_ref, feat, window=w)
    flops = 2.0 * m * w * f
    _row("kernel_banded_sim_ref_jnp", us_ref,
         f"gflops={flops/us_ref/1e3:.2f}")
    us_k = timeit(ops.banded_dot_band, feat, window=w, interpret=True)
    _row("kernel_banded_sim_pallas_interp", us_k,
         "interpret-mode(correctness-path; native on TPU)")

    sig = jnp.asarray(rng.integers(0, 2**32, size=(m, 8),
                                   dtype=np.uint64).astype(np.uint32))
    us_j = timeit(ref.jaccard_band_ref, sig, window=w)
    _row("kernel_jaccard_ref_jnp", us_j, f"pairs_per_s={m*w/us_j*1e6:.2e}")


def dedup_e2e(quick: bool):
    from repro.data.corpus import dedup_corpus, synth_corpus
    n = 4096 if quick else 16384
    docs = synth_corpus(0, n_docs=n, doc_len=64, vocab=1000, dup_frac=0.25)
    t0 = time.perf_counter()
    res = dedup_corpus(docs, r=8, window=10)
    dt = time.perf_counter() - t0
    naive_cmp = n * (n - 1) / 2
    sn_cmp = n * 9
    _row("dedup_e2e", dt * 1e6,
         f"docs_per_s={n/dt:.0f};dropped={res.n_dropped};"
         f"cmp_reduction={naive_cmp/sn_cmp:.0f}x;gini={res.gini:.2f}")


TABLES = {
    "fig8_scalability": fig8_scalability,
    "tbl1_fig9_skew": tbl1_fig9_skew,
    "sec52_jobsn_vs_repsn": sec52_jobsn_vs_repsn,
    "band_engine": band_engine,
    "balance": balance,
    "stream": stream,
    "serve": serve,
    "overload": overload,
    "resilience": resilience,
    "obs": obs,
    "recall": recall,
    "kernels": kernels,
    "dedup_e2e": dedup_e2e,
}


def main() -> None:
    """Run the selected tables; the first table that raises ends the run
    with its traceback and a non-zero exit."""
    from repro.perf.cache import enable_compilation_cache
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compilation_cache()
    print("name,us_per_call,derived")
    for name, fn in TABLES.items():
        if args.only and name != args.only:
            continue
        fn(args.quick)


if __name__ == "__main__":
    main()
