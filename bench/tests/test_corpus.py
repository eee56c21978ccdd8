"""The traffic generator: deterministic in its seed, keys fixed by the
mix's layout, and every mix resolves to the full sorted-neighborhood pair
count."""
import numpy as np
import pytest

from bench import corpus
from bench.tests.cells import SPEC
from bench.run import entities, er_config, load_cell

CELLS = ["pub1.4m-w10.zipf"]


def small(cell: str, n: int = 4000):
    c = load_cell(cell, SPEC)
    return dict(c.cfg, n=n), c.traffic


@pytest.mark.parametrize("cell", CELLS)
def test_deterministic_in_seed(cell):
    cfg, traffic = small(cell)
    seed = 2**31 + 12345           # more than 32 signed bits hold
    a = corpus.make_corpus(cfg, traffic, seed)
    b = corpus.make_corpus(cfg, traffic, seed + 2**64)
    c = corpus.make_corpus(cfg, traffic, seed + 1)
    for f in a:
        np.testing.assert_array_equal(a[f], b[f])
    assert not np.array_equal(a["text"], c["text"])
    assert not np.array_equal(a["eid"], c["eid"])
    # one key per row for every seed: the plan, and so the compiled shapes,
    # are the same
    np.testing.assert_array_equal(a["key"], c["key"])


def test_records_are_titles_with_prefix_keys_and_title_rank_ids():
    cfg, traffic = small("pub1.4m-w10.zipf", 3000)
    rec = corpus.make_corpus(cfg, traffic, 5)
    text, L, kc = rec["text"], cfg["title_bytes"], cfg["key_chars"]
    length = (text > 0).sum(axis=1)
    # letters and single spaces, no padding inside, no trailing space
    assert np.array_equal((text > 0).cumprod(axis=1).sum(axis=1), length)
    assert set(np.unique(text)) <= {0, 32, *range(97, 123)}
    assert not (text[np.arange(len(text)), length - 1] == 32).any()
    titles = [bytes(t[t > 0]) for t in text]
    assert not any(b"  " in t for t in titles)
    # eids are the titles' ranks; keys order the titles' first characters
    assert [titles[i] for i in np.argsort(rec["eid"])] == sorted(titles)
    order = np.argsort(rec["eid"])
    assert (np.diff(rec["key"][order]) >= 0).all()
    same = rec["key"][:, None] == rec["key"][None, :100]
    prefix = np.array([[a[:kc] == b[:kc] for b in titles[:100]]
                       for a in titles])
    assert np.array_equal(same, prefix)
    assert length.max() == L and length.min() >= kc


def test_signature_is_the_hashed_trigram_set():
    cfg, traffic = small("pub1.4m-w10.zipf", 200)
    rec = corpus.make_corpus(cfg, traffic, 8)
    bits = 32 * cfg["sig_words"]
    for t, sig in zip(rec["text"], rec["sig"]):
        s = bytes(t[t > 0])
        want = {((((s[p] << 16) | (s[p + 1] << 8) | s[p + 2]) * 2654435761)
                 % 2**32) >> (32 - (bits.bit_length() - 1))
                for p in range(len(s) - 2)}
        got = {j for j in range(bits) if (int(sig[j >> 5]) >> (j & 31)) & 1}
        assert got == want


def test_keys_are_skewed_and_duplicates_sort_near_their_original():
    cfg, traffic = small("pub1.4m-w10.zipf", 20000)
    rec = corpus.make_corpus(cfg, traffic, 5)
    counts = np.unique(rec["key"], return_counts=True)[1]
    assert counts.max() > 20 * counts.mean()
    # most duplicates sort next to their original, a few typos apart
    order = np.argsort(rec["eid"])
    t = rec["text"][order]
    close = ((t[1:] != t[:-1]).sum(axis=1) <= traffic["title_typos"][1])
    assert close.mean() > 0.5 * traffic["dup_frac"]


@pytest.mark.parametrize("cell", CELLS)
def test_resolve_gives_the_full_pair_count(cell):
    from repro import api
    from repro.core import sn

    cfg, traffic = small(cell)
    rec = corpus.make_corpus(cfg, traffic, 9)
    res = api.resolve(entities(rec, cfg["matcher"]), er_config(cfg))
    assert len(res.blocking.pairs) == sn.expected_pair_count(cfg["n"], 10)
    assert len(res.matches) > 0
