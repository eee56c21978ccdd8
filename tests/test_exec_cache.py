"""Executable cache + device-side pair emission (ISSUE 4).

Steady-state contract: after one warm call, repeated same-shaped
``resolve()`` calls perform ZERO new jit traces (asserted through the
trace-counting wrapper the cache installs around every shard program) and
report pure cache hits on ``ERResult.perf``; any change to input shape,
window, or another static config field is a miss that retraces.  Device-
emitted packed pairs (emit="pairs") must be bit-identical to the host
band-extraction path across all 3 variants x {vmap, shard_map} x
{scan, pallas}, and pair_cap overflow is counted, never silent.  The
persistent compilation cache lives where JAX_COMPILATION_CACHE_DIR says, or
at the repo's fixed default.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro import api
from repro.core import entities as E
from repro.core import partition as P
from repro.perf.cache import executable_cache

N, R, WIN, NK = 240, 4, 6, 64


@pytest.fixture(scope="module")
def ents():
    return E.synth_entities(np.random.default_rng(7), N, n_keys=NK,
                            dup_frac=0.25, text_len=12)


@pytest.fixture(scope="module")
def bounds(ents):
    return P.balanced_partition(np.asarray(ents["key"]), R)


def _cfg(**kw):
    kw.setdefault("window", WIN)
    kw.setdefault("num_shards", R)
    kw.setdefault("hops", R - 1)
    kw.setdefault("band_interpret", True)
    return api.ERConfig(**kw)


# -- executable cache ---------------------------------------------------------------


def test_second_call_zero_new_traces(ents, bounds):
    """The tentpole contract: a same-shape second call dispatches a cached
    executable — no build, no trace."""
    cache = executable_cache()
    cache.clear()
    cfg = _cfg(variant="repsn", runner="vmap")
    first = api.resolve(ents, cfg, bounds=bounds)
    assert first.perf is not None
    assert first.perf.cache_misses >= 1
    assert first.perf.traces == first.perf.cache_misses  # one trace per build
    second = api.resolve(ents, cfg, bounds=bounds)
    assert second.perf.traces == 0
    assert second.perf.cache_misses == 0
    assert second.perf.cache_hits >= 1
    assert second.perf.steady_state
    assert second.blocking.pairs == first.blocking.pairs
    assert second.matches == first.matches


def test_bounds_values_are_traced_not_keyed(ents):
    """Replanning boundaries must NOT retrace: bounds ride as a traced
    argument, so two different same-shaped boundary arrays share one
    executable (the replanning-per-request serving pattern)."""
    cfg = _cfg(variant="srp", runner="vmap")
    b1 = P.balanced_partition(np.asarray(ents["key"]), R)
    b2 = np.asarray(b1) + 1
    api.resolve(ents, cfg, bounds=b1)
    moved = api.resolve(ents, cfg, bounds=np.asarray(b2, np.int32))
    assert moved.perf.traces == 0 and moved.perf.steady_state


@pytest.mark.parametrize("change", [
    {"window": WIN + 1},                      # static cfg field
    {"band_engine": "pallas"},                # engine swap
    {"cand_cap": 64, "band_engine": "pallas"},  # cascade capacity
    {"emit": "pairs"},                        # emission mode
])
def test_static_cfg_change_misses(ents, bounds, change):
    cfg = _cfg(variant="repsn", runner="vmap")
    api.resolve(ents, cfg, bounds=bounds)           # warm base entry
    base = api.resolve(ents, cfg, bounds=bounds)
    assert base.perf.steady_state
    changed = api.resolve(ents, cfg.with_(**change), bounds=bounds)
    assert changed.perf.cache_misses >= 1
    assert changed.perf.traces == changed.perf.cache_misses


def test_shape_change_misses(ents, bounds):
    cfg = _cfg(variant="repsn", runner="vmap")
    api.resolve(ents, cfg, bounds=bounds)
    smaller = E.synth_entities(np.random.default_rng(8), N - 40, n_keys=NK,
                               dup_frac=0.25, text_len=12)
    res = api.resolve(smaller, cfg, bounds=bounds)
    assert res.perf.cache_misses >= 1


def test_jit_cache_off_bypasses(ents, bounds):
    cfg = _cfg(variant="repsn", runner="vmap", jit_cache=False)
    on = api.resolve(ents, cfg.with_(jit_cache=True), bounds=bounds)
    off = api.resolve(ents, cfg, bounds=bounds)
    assert off.perf.cache_hits == 0 and off.perf.cache_misses == 0
    assert off.blocking.pairs == on.blocking.pairs
    assert off.matches == on.matches


def test_shard_map_second_call_steady(ents):
    runner = api.ShardMapRunner()
    r = runner.shards
    cfg = _cfg(variant="jobsn", runner="shard_map", num_shards=r,
               hops=max(r - 1, 1))
    b = api.default_bounds(ents, cfg, r)
    api.resolve(ents, cfg, bounds=b)
    res = api.resolve(ents, cfg, bounds=b)
    assert res.perf.steady_state and res.perf.cache_hits >= 1


def test_lru_eviction_bounds_cache():
    """The cache never holds more than max_entries executables; evicted
    keys rebuild on next use (counted, never an error)."""
    from repro.perf.cache import ExecutableCache
    cache = ExecutableCache(max_entries=2)
    calls = []
    for k in ["a", "b", "c"]:
        cache.get_or_build(k, lambda k=k: lambda: calls.append(k))
    assert len(cache) == 2
    assert cache.stats.evictions == 1
    cache.get_or_build("c", lambda: (lambda: None))      # hit, no rebuild
    assert cache.stats.hits == 1
    cache.get_or_build("a", lambda: (lambda: None))      # evicted: rebuilds
    assert cache.stats.misses == 4


# -- device-side pair emission ------------------------------------------------------


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
@pytest.mark.parametrize("runner_name", ["vmap", "shard_map"])
@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_emitted_pairs_bit_identical(ents, bounds, variant, runner_name,
                                     engine):
    """Device-emitted packed pairs == host packed_pairs_from_band output,
    bit for bit, across variants x runners x engines."""
    if runner_name == "vmap":
        runner, b = api.VmapRunner(R), bounds
        cfg = _cfg(variant=variant, runner="vmap", band_engine=engine,
                   cand_cap=256 if engine == "pallas" else 0)
    else:
        runner = api.ShardMapRunner()
        cfg = _cfg(variant=variant, runner="shard_map",
                   num_shards=runner.shards, hops=max(runner.shards - 1, 1),
                   band_engine=engine,
                   cand_cap=256 if engine == "pallas" else 0)
        b = api.default_bounds(ents, cfg, runner.shards)
    variant_obj = api.get_variant(variant)
    col_band = variant_obj.collect(runner.run_raw(ents, b, cfg))
    col_idx = variant_obj.collect(
        runner.run_raw(ents, b, cfg.with_(emit="pairs")))
    np.testing.assert_array_equal(col_band.blocked, col_idx.blocked)
    np.testing.assert_array_equal(col_band.matched, col_idx.matched)
    assert col_band.blocked.size > 0


def test_emitted_part_transfers_no_bands(ents, bounds):
    """emit='pairs' parts carry index buffers + eids only — the O(w*M)
    bands and the full payload tree stay on device."""
    cfg = _cfg(variant="repsn", runner="vmap", emit="pairs")
    out = api.VmapRunner(R).run_raw(ents, bounds, cfg)
    part = out["main"]
    for absent in ("mask", "match", "ents"):
        assert absent not in part
    for present in ("mask_idx", "mask_n", "mask_overflow", "match_idx",
                    "match_n", "match_overflow", "eid"):
        assert present in part


def test_pair_cap_overflow_counted(ents, bounds):
    """pair_cap exceeded: dropped slots counted in pair_overflow (blocked
    pairs CAN be lost here — the capacity contract is count, never
    silence); a roomy cap loses nothing."""
    cfg = _cfg(variant="srp", runner="vmap", emit="pairs")
    full = api.resolve(ents, cfg, bounds=bounds)
    assert full.blocking.pair_overflow == 0
    tight = api.resolve(ents, cfg.with_(pair_cap=8), bounds=bounds)
    assert tight.blocking.pair_overflow > 0
    assert tight.blocking.pairs <= full.blocking.pairs
    assert len(full.blocking.pairs) - len(tight.blocking.pairs) \
        <= tight.blocking.pair_overflow
    assert tight.matches <= full.matches


def test_pair_emission_config_validation():
    with pytest.raises(ValueError, match="emit"):
        api.ERConfig(emit="bands")
    with pytest.raises(ValueError, match="pair_cap"):
        api.ERConfig(pair_cap=-1)
    with pytest.raises(ValueError, match="emit='pairs'"):
        api.ERConfig(emit="pairs", return_scores=True)


def test_linkage_emission_parity(ents):
    """Cross-source masking happens before compaction, so linkage runs
    agree between emission modes too."""
    rng = np.random.default_rng(3)
    lhs = E.synth_entities(rng, 160, n_keys=48, dup_frac=0.0, text_len=12)
    take = rng.permutation(160)[:60]
    rhs = E.make_entities(
        np.asarray(lhs["key"])[take], np.arange(60, dtype=np.int32),
        payload={k: np.asarray(v)[take] for k, v in lhs["payload"].items()})
    cfg = _cfg(window=5, variant="repsn", runner="vmap")
    band = api.link(lhs, rhs, cfg)
    idx = api.link(lhs, rhs, cfg.with_(emit="pairs"))
    assert band.blocking.pairs == idx.blocking.pairs
    assert band.matches == idx.matches
    assert band.matches                     # planted duplicates found


# -- sequential chunk scorer --------------------------------------------------------


def test_seq_match_tail_padding_parity(ents, bounds):
    """A chunk size that doesn't divide the pair count pads the tail chunk
    instead of compiling a second shape: identical matches, one scorer
    executable."""
    cache = executable_cache()
    cfg = _cfg(variant="repsn", runner="sequential")
    big = api.SequentialRunner(num_shards=R).resolve(ents, bounds, cfg)
    cache.clear()
    h0, m0, t0 = cache.stats.snapshot()
    small = api.SequentialRunner(num_shards=R, match_chunk=128).resolve(
        ents, bounds, cfg)
    h1, m1, t1 = cache.stats.snapshot()
    assert small.matched == big.matched
    assert small.blocked == big.blocked
    assert m1 - m0 == 1 and t1 - t0 == 1    # ONE executable, tail included
    # warm second run: pure hits
    api.SequentialRunner(num_shards=R, match_chunk=128).resolve(
        ents, bounds, cfg)
    h2, m2, t2 = cache.stats.snapshot()
    assert m2 - m1 == 0 and t2 - t1 == 0 and h2 > h1


# -- persistent compilation cache ---------------------------------------------------


@pytest.fixture
def restore_jax_cache_config():
    """Put JAX's compilation-cache settings back after a test moves them."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_enable_compilation_cache", before[1])
    cc.reset_cache()


def test_compilation_cache_defaults_to_repo_dir(monkeypatch,
                                                restore_jax_cache_config):
    import jax
    from repro.perf.cache import REPO_CACHE_DIR, enable_compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == Path(__file__).resolve().parents[1] / \
        ".jax_cache"
    assert enable_compilation_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert jax.config.jax_enable_compilation_cache


def test_compilation_cache_env_dir_is_the_only_location(
        monkeypatch, tmp_path, restore_jax_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets no directory of
    its own: JAX reads the variable itself."""
    import jax
    from repro.perf.cache import enable_compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_entries_land_in_env_dir(tmp_path):
    """A fresh process with JAX_COMPILATION_CACHE_DIR writes its entries
    there and adds none to the repo's default directory."""
    from repro.perf.cache import REPO_CACHE_DIR
    count = lambda p: len(os.listdir(p)) if os.path.isdir(p) else 0
    repo_before = count(REPO_CACHE_DIR)
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.perf.cache import enable_compilation_cache
        print(enable_compilation_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * 3)(
            jnp.arange(8.0)))
    """)
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"),
               PYTHONPATH=f"{root / 'src'}:" + os.environ.get("PYTHONPATH",
                                                              ""))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == str(tmp_path / "cc")
    assert count(tmp_path / "cc") > 0
    assert count(REPO_CACHE_DIR) == repo_before
