"""The benchmark's one corpus generator: publication records from a seed.

A traffic mix is a JSON file of parameters under ``bench/traffic/``; a
configuration fixes the record widths and the scale.  This module turns the
two into the records a run resolves, in the form the paper's job reads them
(arXiv:1010.3053 section 5.1: sort key a prefix of the title, records
matched on the title by edit distance and trigram similarity):

    text  (n, L)  uint8    lowercase title, words joined by single spaces,
                           cut to L bytes, zero-padded
    key   (n,)    int32    the title's first ``key_chars`` characters in
                           base 27 (space 0, a..z 1..26): its sort order is
                           the titles' order
    eid   (n,)    int32    record id: the title's rank in title order, so
                           records with one key sort by the rest of the
                           title, as a sort on the whole title would
    sig   (n, W)  uint32   the title's set of character trigrams, each
                           hashed to one of 32 * W bits (Jaccard matcher)

Titles are words drawn from a vocabulary by Zipf's law; the hot words are
the short ones, and the sort keys take their skew from the titles' first
words.  A share ``dup_frac`` of the records are planted duplicates: a copy
of another record's title with a graded number of typos, none in the key's
characters.  The grades spread duplicate scores across the match
threshold, so a run computed in a lower precision than stated flips pairs
near it, and a comparison of matched sets can tell the two apart.

The mix's ``layout_seed`` draws the vocabulary, the words that make each
record's key, and which record duplicates which; ``--seed`` draws the rest
of every title and every typo.  So every seed gives the same keys in the
same rows, the blocking plan's exact capacities (compiled shapes) are the
same, and only the first run in a checkout compiles; the titles, their
order within a key, and so the blocked and matched pairs differ.

The generator imports nothing of the system under test: it is part of the
yardstick, and later changes to the program cannot move it.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
LOWER, UPPER, SPACE = ord("a"), ord("z") + 1, ord(" ")
BLOCK = 1 << 17          # rows per step where a step holds (rows, bits)


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``seed`` (any whole number, negative or above 2**63
    included) and a sub-stream index, so the corpus and any later draw of
    the run never share a stream."""
    return np.random.default_rng([seed % (1 << 64), stream])


def load_traffic(name: str) -> dict:
    """The traffic mix ``bench/traffic/<name>.json``."""
    with open(BENCH / "traffic" / f"{name}.json") as f:
        return json.load(f)


def vocabulary(lay, traffic: dict):
    """(letters (V, hi) uint8 zero-padded, lengths (V,), alias table): word
    rank r has mass r**-s and about ``at_rank_1 + per_decade * log10(r)``
    letters, give or take ``spread`` (the frequent words are the short
    ones), within ``[lo, hi]``."""
    V = int(traffic["vocabulary"])
    wl = traffic["word_letters"]
    lo, hi = wl["range"]
    rank = np.arange(1, V + 1, dtype=np.float64)
    mean = wl["at_rank_1"] + wl["per_decade"] * np.log10(rank)
    jitter = lay.uniform(-wl["spread"], wl["spread"], size=V)
    lens = np.clip(np.rint(mean + jitter), lo, hi).astype(np.int64)
    letters = lay.integers(LOWER, UPPER, size=(V, hi), dtype=np.uint8)
    letters[np.arange(hi)[None, :] >= lens[:, None]] = 0
    p = rank ** -float(traffic["word_exponent"])
    return letters, lens, alias_table(p / p.sum())


def alias_table(p: np.ndarray):
    """Walker's alias table of the distribution ``p``: (prob, alias)."""
    V = p.size
    q = p * V
    prob, alias = np.ones(V), np.arange(V)
    small = list(np.nonzero(q < 1)[0])
    large = list(np.nonzero(q >= 1)[0])
    while small and large:
        s, g = small.pop(), large.pop()
        prob[s], alias[s] = q[s], g
        q[g] -= 1 - q[s]
        (small if q[g] < 1 else large).append(g)
    return prob, alias


def draw_words(rng, table, shape) -> np.ndarray:
    prob, alias = table
    i = (rng.random(shape) * prob.size).astype(np.int64)
    return np.where(rng.random(shape) < prob[i], i, alias[i])


def head_words(lens: np.ndarray, words: np.ndarray, key_chars: int):
    """Words each title needs before its first ``key_chars`` characters are
    fixed (words joined by single spaces)."""
    reach = np.cumsum(lens[words] + 1, axis=1) - 1
    if (reach[:, -1] < key_chars).any():
        raise ValueError("head words too short to cover the key")
    return (reach < key_chars).sum(axis=1) + 1


def assemble(letters, lens, words, count, L: int) -> np.ndarray:
    """(n, L) titles: the first ``count[i]`` of ``words[i]`` joined by
    single spaces, cut to L bytes."""
    n, c_max = words.shape
    V, hi = letters.shape
    spelled = np.zeros((V, hi + 1), np.uint8)      # each word and a space
    spelled[:, :hi] = letters
    spelled[np.arange(V), lens] = SPACE
    out = np.zeros((n, L + hi + 1), np.uint8)
    pos = np.zeros(n, np.int64)
    cols = np.arange(hi + 1)
    for k in range(c_max):
        on = np.nonzero((count > k) & (pos < L))[0]
        w = words[on, k]
        # a word's zero padding is overwritten by the next word
        out[on[:, None], pos[on, None] + cols] = spelled[w]
        pos[on] += lens[w] + 1
    out = out[:, :L]
    last = np.minimum(pos, L) - 1                  # no trailing space
    end = out[np.arange(n), last] == SPACE
    out[np.nonzero(end)[0], last[end]] = 0
    return np.ascontiguousarray(out)


def typos(rng, text: np.ndarray, grade, key_chars: int) -> np.ndarray:
    """Copies of ``text`` rows with ``grade[i]`` letter substitutions each,
    at positions past the key's characters."""
    t = text.copy()
    length = (t > 0).sum(axis=1)
    rows = np.arange(t.shape[0])
    for k in range(int(grade.max(initial=0))):
        hit = grade > k
        pos = key_chars + (rng.random(t.shape[0])
                           * (length - key_chars)).astype(np.int64)
        ch = rng.integers(LOWER, UPPER, size=t.shape[0], dtype=np.uint8)
        t[rows[hit], pos[hit]] = ch[hit]
    return t


def sort_key(text: np.ndarray, key_chars: int) -> np.ndarray:
    code = np.where(text[:, :key_chars] == SPACE, 0,
                    text[:, :key_chars].astype(np.int64) - LOWER + 1)
    return (code @ (27 ** np.arange(key_chars - 1, -1, -1))).astype(np.int32)


def trigram_signature(text: np.ndarray, words: int) -> np.ndarray:
    """(n, words) uint32: bit h(t) set for every trigram t of each title,
    h a multiplicative hash onto 32 * words bits (a power of two)."""
    n, L = text.shape
    bits = 32 * words
    shift = 32 - (bits.bit_length() - 1)
    if 1 << (32 - shift) != bits:
        raise ValueError("32 * sig_words must be a power of two")
    sig = np.empty((n, words), np.uint32)
    for s in range(0, n, BLOCK):
        t = text[s:s + BLOCK].astype(np.uint32)
        code = (t[:, :-2] << 16) | (t[:, 1:-1] << 8) | t[:, 2:]
        h = (code * np.uint32(2654435761)) >> np.uint32(shift)
        on = t[:, 2:] > 0
        flat = (np.arange(t.shape[0])[:, None] * bits + h)[on]
        hot = np.zeros(t.shape[0] * bits, bool)
        hot[flat] = True
        sig[s:s + BLOCK] = np.packbits(
            hot.reshape(t.shape[0], bits), axis=1,
            bitorder="little").view(np.uint32)
    return sig


def make_corpus(cfg: dict, traffic: dict, seed: int) -> dict:
    """Host numpy records for configuration ``cfg`` under ``traffic``.

    The same (cfg, traffic, seed) gives the same records, bit for bit; the
    keys, row by row, are the same for every seed."""
    lay, rng = rng_for(int(traffic["layout_seed"])), rng_for(seed, 1)
    n, L = int(cfg["n"]), int(cfg["title_bytes"])
    kc, W = int(cfg["key_chars"]), int(cfg["sig_words"])
    letters, lens, table = vocabulary(lay, traffic)
    lo, hi = traffic["title_words"]

    # every word has at least lo letters, so this many cover the key
    n_head = -(-(kc + 1) // (int(traffic["word_letters"]["range"][0]) + 1))
    head = draw_words(lay, table, (n, n_head))
    h = head_words(lens, head, kc)
    words = draw_words(rng, table, (n, hi))
    mine = np.arange(n_head)[None, :] < h[:, None]
    words[:, :n_head][mine] = head[mine]
    count = np.maximum(rng.integers(lo, hi + 1, size=n), h)
    text = assemble(letters, lens, words, count, L)

    n_dup = int(n * float(traffic["dup_frac"]))
    if n_dup:
        # duplicates copy originals only: no chain of copies of copies
        perm = lay.permutation(n)
        dst = np.sort(perm[:n_dup])
        originals = perm[n_dup:]
        src = originals[lay.integers(0, originals.size, size=n_dup)]
        lo_t, hi_t = traffic["title_typos"]
        text[dst] = typos(rng, text[src],
                          rng.integers(lo_t, hi_t + 1, size=n_dup), kc)

    key = sort_key(text, kc)
    order = np.argsort(text.view(f"S{L}").ravel(), kind="stable")
    eid = np.empty(n, np.int32)
    eid[order] = np.arange(n, dtype=np.int32)
    return {"key": key, "eid": eid, "sig": trigram_signature(text, W),
            "text": text}
