"""Data pipeline: dedup stage behaviour."""
import numpy as np

from repro.data.corpus import dedup_corpus, doc_entities, synth_corpus


def test_dedup_finds_planted_duplicates():
    docs = synth_corpus(0, 1024, doc_len=32, vocab=500, dup_frac=0.3)
    res = dedup_corpus(docs, r=4, window=10, threshold=0.95)
    # exact duplicates share identical signatures+features -> must be found
    assert res.n_dropped > 0
    assert res.overflow == 0
    # survivors should contain at most one copy of each exact-dup group
    kept = docs[res.keep]
    uniq = np.unique(kept, axis=0)
    dup_left = len(kept) - len(uniq)
    total_dups = len(docs) - len(np.unique(docs, axis=0))
    assert dup_left < total_dups * 0.35, (dup_left, total_dups)


def test_dedup_never_drops_all():
    docs = synth_corpus(1, 256, doc_len=16, vocab=100, dup_frac=0.9)
    res = dedup_corpus(docs, r=2, window=6)
    assert res.keep.sum() >= len(np.unique(docs, axis=0)) * 0.5


def test_doc_entities_shapes():
    docs = synth_corpus(0, 64, doc_len=32, vocab=500)
    ents = doc_entities(docs)
    assert ents["key"].shape == (64,)
    assert (np.asarray(ents["key"]) >= 0).all()
    assert ents["payload"]["sig"].dtype.name == "uint32"
    f = np.asarray(ents["payload"]["feat"])
    np.testing.assert_allclose(np.linalg.norm(f, axis=1), 1.0, atol=1e-3)
