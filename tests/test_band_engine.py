"""BandEngine seam: pallas-vs-scan parity + cascade accounting.

The pallas engine (fused cheap-band kernel -> cumsum compaction -> exact
matcher on survivors, core/window.py) must reproduce the scan oracle's
blocked AND matched pair sets exactly — across all three variants, both
device runners, awkward M/block geometry, and the linkage cross-source mask.
Kernels run under the Pallas interpreter on CPU (same code path compiles
natively on TPU).

Also covered: the cand_cap capacity model (overflow counted, matches-only
losses), the cumsum compaction primitive, and the §5.1 FLOP claim
(survivors <= matcher_evals <= each engine's buffer: pallas's cand_cap
buffer, the scan engine's band).
"""
import numpy as np
import pytest

from repro import api
from repro.core import entities as E
from repro.core import partition as P
from repro.core import window as W

N, R, WIN, NK = 260, 4, 6, 64
BB = 32          # small band_block so shards (M=260) span many blocks


@pytest.fixture(scope="module")
def ents():
    return E.synth_entities(np.random.default_rng(11), N, n_keys=NK,
                            dup_frac=0.25)


@pytest.fixture(scope="module")
def bounds(ents):
    return P.balanced_partition(np.asarray(ents["key"]), R)


def _cfg(**kw):
    kw.setdefault("window", WIN)
    kw.setdefault("num_shards", R)
    kw.setdefault("hops", R - 1)
    kw.setdefault("band_block", BB)
    kw.setdefault("band_interpret", True)
    return api.ERConfig(**kw)


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
def test_vmap_parity_all_variants(ents, bounds, variant):
    """Acceptance: identical blocked/matched sets, and each engine's
    expensive-matcher evaluations lie between its own gate survivors and
    its own buffer: pallas scores its cand_cap buffer (sized above the
    survivor count, well under the band), scan its survivors in whole
    chunks, at most one per (w-1, M) band slot."""
    cfg = _cfg(variant=variant, runner="vmap")
    scan = api.resolve(ents, cfg, bounds=bounds)
    pal = api.resolve(ents, cfg.with_(band_engine="pallas", cand_cap=256),
                      bounds=bounds)
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches
    assert pal.blocking.cand_overflow == 0
    raw = api.VmapRunner(R).run_raw(ents, bounds, cfg)
    masks = [np.asarray(raw[p]["mask"]) for p in ("main", "boundary")
             if p in raw]
    slots = sum(x.size for x in masks)
    # the FLOP lever: the cap-sized buffer, vs every (w-1, M) band slot
    assert 0 < pal.blocking.matcher_evals < slots
    # every match is a gate survivor, every kept survivor was scored;
    # cand_count is per-shard (the public probe for the cand_cap sizing rule)
    for res, buffer in ((pal, 256 * R * len(masks)), (scan, slots)):
        assert len(res.blocking.cand_count) == R
        assert len(res.matches) <= sum(res.blocking.cand_count) \
            <= res.blocking.matcher_evals <= buffer


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
def test_shard_map_parity(ents, variant):
    """Same contract under the real-device runner (in-process mesh)."""
    r = api.ShardMapRunner().shards
    cfg = _cfg(variant=variant, runner="shard_map",
               hops=max(r - 1, 1))
    b = api.default_bounds(ents, cfg, r)
    scan = api.resolve(ents, cfg, bounds=b)
    pal = api.resolve(ents, cfg.with_(band_engine="pallas"), bounds=b)
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches


@pytest.mark.parametrize("n,band_block,window", [
    (300, 128, 6),     # M not a multiple of block_i (padding path)
    (130, 8, 9),       # window-1 == band_block (band fills the block)
    (40, 64, 5),       # M < block_i (block clamped, then padded)
])
def test_parity_block_geometry(n, band_block, window):
    ents = E.synth_entities(np.random.default_rng(3), n, n_keys=32,
                            dup_frac=0.3)
    bounds = P.balanced_partition(np.asarray(ents["key"]), 2)
    cfg = _cfg(window=window, variant="repsn", runner="vmap", num_shards=2,
               hops=1, band_block=band_block)
    scan = api.resolve(ents, cfg, bounds=bounds)
    pal = api.resolve(ents, cfg.with_(band_engine="pallas"), bounds=bounds)
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
def test_linkage_parity(variant):
    """Cross-source band mask feeds the cascade gate BEFORE compaction, so
    linkage runs must agree engine-to-engine too."""
    rng = np.random.default_rng(5)
    lhs = E.synth_entities(rng, 200, n_keys=48, dup_frac=0.0)
    take = rng.permutation(200)[:80]
    rhs = E.make_entities(
        np.asarray(lhs["key"])[take], np.arange(80, dtype=np.int32),
        payload={k: np.asarray(v)[take] for k, v in lhs["payload"].items()})
    cfg = _cfg(window=5, variant=variant, runner="vmap")
    scan = api.link(lhs, rhs, cfg)
    pal = api.link(lhs, rhs, cfg.with_(band_engine="pallas"))
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches
    assert scan.matches        # planted duplicates must be found


@pytest.mark.parametrize("variant", ["srp", "repsn", "jobsn"])
def test_auto_mode_jnp_cheap_band_parity(ents, bounds, variant):
    """band_interpret=None off-TPU routes the cheap stage through
    window.cheap_band_jnp (band-shaped jnp, no tile kernel) — the path
    every real CPU user of band_engine='pallas' takes; it must reproduce
    the scan oracle exactly, like the forced-interpreter kernel path the
    other parity tests pin."""
    cfg = _cfg(variant=variant, runner="vmap", band_interpret=None)
    scan = api.resolve(ents, cfg, bounds=bounds)
    pal = api.resolve(ents, cfg.with_(band_engine="pallas", cand_cap=256),
                      bounds=bounds)
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches
    assert pal.blocking.cand_overflow == 0


def test_cheap_band_jnp_matches_kernel_math(ents):
    """The jnp cheap band computes the same weighted partial scores as the
    matchers it mirrors, row d-1 holding distance-d pairs."""
    import jax.numpy as jnp
    from repro.core.match import cosine_sim, jaccard_sig, default_matcher
    payload = {k: np.asarray(v)[:64] for k, v in ents["payload"].items()}
    payload = {k: jnp.asarray(v) for k, v in payload.items()}
    matcher = default_matcher()
    split = W.split_cascade(matcher, payload)
    w = 5
    rows = np.asarray(W.cheap_band_jnp(payload, split, w))
    for d in range(1, w):
        want = split.w_cos * cosine_sim(
            payload["feat"], jnp.roll(payload["feat"], -d, axis=0)) + \
            split.w_jac * jaccard_sig(
                payload["sig"], jnp.roll(payload["sig"], -d, axis=0))
        np.testing.assert_allclose(rows[d - 1], np.asarray(want), rtol=1e-6)


def test_cand_cap_overflow_counted(ents, bounds):
    """cand_cap exceeded: counted in cand_overflow, never silent — blocked
    pairs are untouched (pre-compaction mask) and at most cand_overflow
    matches can be lost (the SRP capacity model applied to matching)."""
    cfg = _cfg(variant="srp", runner="vmap")
    full = api.resolve(ents, cfg.with_(band_engine="pallas"), bounds=bounds)
    tight = api.resolve(ents, cfg.with_(band_engine="pallas", cand_cap=4),
                        bounds=bounds)
    assert tight.blocking.cand_overflow > 0
    assert tight.blocking.pairs == full.blocking.pairs
    assert tight.matches <= full.matches
    assert len(full.matches - tight.matches) <= tight.blocking.cand_overflow
    # roomy cap -> identical outcome, zero overflow
    roomy = api.resolve(ents, cfg.with_(band_engine="pallas", cand_cap=4096),
                        bounds=bounds)
    assert roomy.blocking.cand_overflow == 0
    assert roomy.matches == full.matches


def test_compact_candidates_cumsum():
    """The cumsum compaction packs gate survivors in band order and accounts
    for capacity exactly."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    gate = jnp.asarray(rng.random((5, 37)) < 0.2)
    want = np.flatnonzero(np.asarray(gate).reshape(-1))
    for cap in [3, len(want), 4 * len(want) + 1]:
        ci, cd, cv, n_cand, ovf = W.compact_candidates(gate, cap)
        ci, cd, cv = np.asarray(ci), np.asarray(cd), np.asarray(cv)
        assert int(n_cand) == len(want)
        assert int(ovf) == max(len(want) - cap, 0)
        kept = min(cap, len(want))
        assert cv.sum() == kept
        got_flat = (cd[:kept] - 1) * 37 + ci[:kept]
        np.testing.assert_array_equal(got_flat, want[:kept])


def test_unsupported_cascade_falls_back_to_scan(ents, bounds):
    """A cascade whose first matcher has no kernel (edit distance) cannot be
    gated by the fused kernel — the pallas engine must fall back to the scan
    oracle rather than mis-gate."""
    from repro.core.match import CascadeMatcher, Matcher
    payload = dict(ents["payload"])
    payload["text"] = np.zeros((N, 8), np.uint8)
    tents = E.make_entities(ents["key"], ents["eid"], payload=payload)
    matcher = CascadeMatcher(
        matchers=(Matcher(field="text", kind="edit", weight=1.0),),
        threshold=0.9)
    cfg = _cfg(variant="srp", runner="vmap", matcher=matcher)
    scan = api.resolve(tents, cfg, bounds=bounds)
    pal = api.resolve(tents, cfg.with_(band_engine="pallas"), bounds=bounds)
    assert pal.blocking.pairs == scan.blocking.pairs
    assert pal.matches == scan.matches


def test_band_engine_config_validation():
    with pytest.raises(ValueError, match="unknown band engine"):
        api.ERConfig(band_engine="pallass")
    with pytest.raises(ValueError, match="band_block"):
        api.ERConfig(band_engine="pallas", window=300, band_block=256)
    with pytest.raises(ValueError, match="cand_cap"):
        api.ERConfig(cand_cap=-1)
    # scan engine has no block constraint
    api.ERConfig(band_engine="scan", window=300, band_block=256)


# -- packed pair plumbing -----------------------------------------------------------


def test_packed_pair_roundtrip():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 2**31 - 1, size=1000)
    b = rng.integers(0, 2**31 - 1, size=1000)
    packed = api.pack_pairs(a, b)
    lo, hi = api.unpack_pairs(packed)
    np.testing.assert_array_equal(lo, np.minimum(a, b))
    np.testing.assert_array_equal(hi, np.maximum(a, b))
    assert api.packed_to_frozenset(packed) == \
        {(int(min(x, y)), int(max(x, y))) for x, y in zip(a, b)}


def test_packed_collection_matches_set_baseline(ents, bounds):
    """packed_pairs_from_band (hot path) == pairs_from_band (reference)."""
    cfg = _cfg(variant="jobsn", runner="vmap")
    out = api.VmapRunner(R).run_raw(ents, bounds, cfg)
    for part in ["main", "boundary"]:
        for field in ["mask", "match"]:
            packed = api.packed_pairs_from_band(out[part], field)
            assert api.packed_to_frozenset(packed) == \
                api.pairs_from_band(out[part], field)
