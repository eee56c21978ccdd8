"""Per-kernel allclose tests vs the ref.py oracles: shape & dtype sweeps
(deliverable c).  Kernels run in interpret mode on CPU — same code path
compiles natively on TPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("m,f,w,bi", [
    (256, 128, 16, 256),
    (512, 64, 64, 256),
    (300, 32, 10, 128),      # non-multiple M (padding path)
    (128, 256, 128, 128),    # window == block
    (1024, 128, 200, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_banded_sim(m, f, w, bi, dtype):
    feat = jnp.asarray(RNG.normal(size=(m, f)).astype(np.float32), dtype)
    got = ops.banded_dot_band(feat, window=w, block_i=bi, interpret=True)
    want = ref.banded_sim_ref(feat, window=w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("m,words,w,bi", [
    (256, 8, 16, 256),
    (512, 4, 64, 256),
    (192, 16, 32, 64),
    (130, 2, 8, 128),        # padding path
])
def test_jaccard_band(m, words, w, bi):
    sig = jnp.asarray(
        RNG.integers(0, 2**32, size=(m, words), dtype=np.uint64)
        .astype(np.uint32))
    got = ops.jaccard_band(sig, window=w, block_i=bi, interpret=True)
    want = ref.jaccard_band_ref(sig, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("m,f,words,w,bi", [
    (256, 64, 8, 16, 256),
    (300, 32, 4, 10, 128),   # non-multiple M (padding path)
    (64, 32, 8, 48, 64),     # window fills most of the block
])
@pytest.mark.parametrize("w_cos,w_jac", [(0.5, 0.5), (1.0, 0.0), (0.0, 2.0)])
def test_fused_cheap_band(m, f, words, w, bi, w_cos, w_jac):
    """Fused kernel == w_cos*cosine + w_jac*jaccard of the jnp oracles,
    including the jaccard empty-vs-empty == 1.0 convention."""
    feat = jnp.asarray(RNG.normal(size=(m, f)).astype(np.float32))
    sig = jnp.asarray(RNG.integers(0, 2**32, size=(m, words),
                                   dtype=np.uint64).astype(np.uint32))
    got = ops.fused_cheap_band(feat, sig, window=w, w_cos=w_cos, w_jac=w_jac,
                               block_i=bi, interpret=True)
    cos = np.clip(0.5 * (np.asarray(ref.banded_sim_ref(feat, window=w))
                         + 1.0), 0.0, 1.0)
    jac = np.asarray(ref.jaccard_band_ref(sig, window=w))
    ok = (np.arange(m)[:, None] + 1 + np.arange(w)[None, :]) < m
    want = np.where(ok, w_cos * cos + w_jac * jac, 0.0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_fused_band_empty_sig_convention():
    """All-zero signatures: jaccard_sig says empty-vs-empty similarity is
    1.0 — the kernel must agree or the cascade gate would under-select."""
    m, w = 64, 4
    feat = jnp.zeros((m, 8), jnp.float32)
    sig = jnp.zeros((m, 4), jnp.uint32)
    got = np.asarray(ops.fused_cheap_band(feat, sig, window=w, w_cos=0.0,
                                          w_jac=1.0, block_i=64,
                                          interpret=True))
    ok = (np.arange(m)[:, None] + 1 + np.arange(w)[None, :]) < m
    np.testing.assert_allclose(got, np.where(ok, 1.0, 0.0))


def test_small_m_auto_grows_block():
    """M smaller than the window used to trip the kernels'
    ``window <= block_i`` assert via ``bi = min(block_i, m)``; the resolved
    block now grows to the window and M is padded."""
    m, f, w = 8, 16, 16
    feat = jnp.asarray(RNG.normal(size=(m, f)).astype(np.float32))
    got = ops.banded_dot_band(feat, window=w, block_i=256, interpret=True)
    want = ref.banded_sim_ref(feat, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    sig = jnp.asarray(RNG.integers(0, 2**32, size=(m, 4), dtype=np.uint64)
                      .astype(np.uint32))
    got_j = ops.jaccard_band(sig, window=w, block_i=256, interpret=True)
    np.testing.assert_allclose(np.asarray(got_j),
                               np.asarray(ref.jaccard_band_ref(sig, window=w)),
                               rtol=1e-6, atol=1e-6)


def test_window_exceeding_block_is_actionable():
    feat = jnp.zeros((512, 8), jnp.float32)
    with pytest.raises(ValueError, match="window=300 exceeds block_i=256"):
        ops.banded_dot_band(feat, window=300, block_i=256, interpret=True)


def test_band_kernel_matches_window_module():
    """The Pallas band path and the core window module agree on scores."""
    from repro.core import entities as E
    from repro.core import window as W
    from repro.core.match import CascadeMatcher, Matcher
    rng = np.random.default_rng(3)
    n, w = 256, 9
    ents = E.synth_entities(rng, n, n_keys=32)
    ents = E.sort_entities(ents)
    matcher = CascadeMatcher(
        matchers=(Matcher(field="feat", kind="cosine", weight=1.0),),
        threshold=0.75)
    scores, mask = W.band_scores(ents, w, matcher)      # (w-1, M)
    dot = ops.banded_dot_band(ents["payload"]["feat"], window=w - 1,
                              interpret=True)           # (M, w-1)
    cos = np.clip(0.5 * (np.asarray(dot) + 1.0), 0.0, 1.0)
    want = np.where(np.asarray(mask), cos.T, 0.0)
    np.testing.assert_allclose(
        np.where(np.asarray(mask), np.asarray(scores), 0.0), want,
        rtol=1e-5, atol=1e-5)
