"""Data pipeline: deterministic synthetic corpus + SN-dedup integration.

The paper's technique as a *data-pipeline stage*: corpus deduplication.
``dedup_corpus`` runs documents through the distributed SN blocking +
matching workflow and yields a keep-mask.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import entities as E
from repro.core import keys as K
from repro.core import partition as P


# -- synthetic document corpus -----------------------------------------------------

def synth_corpus(seed: int, n_docs: int, *, doc_len: int = 64,
                 vocab: int = 1000, dup_frac: float = 0.25,
                 near_dup_noise: int = 2) -> np.ndarray:
    """Token documents (n_docs, doc_len) with planted near-duplicates."""
    rng = np.random.default_rng(seed)
    docs = rng.integers(1, vocab, size=(n_docs, doc_len), dtype=np.int32)
    n_dup = int(n_docs * dup_frac)
    src = rng.integers(0, n_docs, size=n_dup)
    dst = rng.integers(0, n_docs, size=n_dup)
    docs[dst] = docs[src]
    # near-duplicates: perturb a few tokens
    for d in dst[: n_dup // 2]:
        pos = rng.integers(0, doc_len, size=near_dup_noise)
        docs[d, pos] = rng.integers(1, vocab, size=near_dup_noise)
    return docs


def zipf_entities(seed: int, n: int, *, n_clusters: int = 256,
                  exponent: float = 1.1, dup_frac: float = 0.2,
                  cluster_width: int = 1, key_space: int = 1 << 20,
                  feat_dim: int = 32, sig_words: int = 8,
                  shuffle_clusters: bool = False) -> dict:
    """Skewed entity corpus: Zipfian sort-key clusters (the hot-key workload
    the repro.balance planners exist for).

    Cluster c (1-based rank) receives mass ∝ c^-exponent over ``n_clusters``
    clusters; exponent is unrestricted (>= 0), unlike numpy's ``zipf`` which
    needs a > 1.  Each cluster occupies ``cluster_width`` adjacent sort keys
    (1 = a single hot key, exercising mid-block splits), and clusters sit in
    rank order along the key space — hot keys contiguous at the low end, the
    shape that breaks uniform range partitioning hardest (real sort keys
    cluster the same way: surname prefixes, timestamps, geo codes).  Set
    ``shuffle_clusters`` for scattered hot keys instead.

    ``dup_frac`` of the entities are planted near-duplicates (same key,
    near-identical payload) so matchers find real matches, mirroring
    ``entities.synth_entities``.
    """
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, n_clusters + 1, dtype=np.float64)
    p = ranks ** -float(exponent)
    p /= p.sum()
    cluster = rng.choice(n_clusters, size=n, p=p)
    order = rng.permutation(n_clusters) if shuffle_clusters \
        else np.arange(n_clusters)
    stride = max(key_space // n_clusters, cluster_width)
    keys = (order[cluster] * stride
            + rng.integers(0, cluster_width, size=n)).astype(np.int32)
    feat = rng.normal(size=(n, feat_dim)).astype(np.float32)
    sig = rng.integers(0, 2 ** 32, size=(n, sig_words),
                       dtype=np.uint64).astype(np.uint32)
    n_dup = int(n * dup_frac)
    if n_dup:
        src = rng.integers(0, n, size=n_dup)
        dst = rng.integers(0, n, size=n_dup)
        keys[dst] = keys[src]
        feat[dst] = feat[src] + 0.01 * rng.normal(
            size=(n_dup, feat_dim)).astype(np.float32)
        sig[dst] = sig[src]
    feat /= np.linalg.norm(feat, axis=1, keepdims=True) + 1e-9
    return E.make_entities(
        keys, np.arange(n, dtype=np.int32),
        payload={"feat": jnp.asarray(feat), "sig": jnp.asarray(sig)})


def synth_entity_chunks(seed: int, n: int, chunk: int, *,
                        n_keys: int = 1000, sig_words: int = 8,
                        feat_dim: int = 32, dup_frac: float = 0.2,
                        skew: float = 0.0,
                        text_len: int = 0) -> Iterator[dict]:
    """Chunked ``entities.synth_entities``: the out-of-core corpus source
    for ``repro.stream`` (yields ceil(n / chunk) entity chunks, generated
    one at a time — nothing larger than ``chunk`` is ever materialized).

    Eids are globally unique (chunk c owns ``[c*chunk, c*chunk+len)``);
    duplicates are planted WITHIN each chunk (near-identical payloads),
    while cross-chunk near-neighbors arise from the shared key space —
    exactly the layout an external sort has to repair."""
    from repro.core import entities as E
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    rng = np.random.default_rng(seed)
    for start in range(0, n, chunk):
        size = min(chunk, n - start)
        ents = E.synth_entities(rng, size, n_keys=n_keys,
                                sig_words=sig_words, feat_dim=feat_dim,
                                dup_frac=dup_frac, skew=skew,
                                text_len=text_len)
        ents["eid"] = jnp.asarray(
            np.arange(start, start + size, dtype=np.int32))
        yield ents


def zipf_entity_chunks(seed: int, n: int, chunk: int, *,
                       n_clusters: int = 256, exponent: float = 1.1,
                       dup_frac: float = 0.2, cluster_width: int = 1,
                       key_space: int = 1 << 20, feat_dim: int = 32,
                       sig_words: int = 8) -> Iterator[dict]:
    """Chunked ``zipf_entities``: the skewed out-of-core corpus (hot-key
    clusters in every chunk) that exercises the streaming per-chunk
    planning hook.  Eids are globally unique, one chunk at a time."""
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    for i, start in enumerate(range(0, n, chunk)):
        size = min(chunk, n - start)
        ents = zipf_entities(seed + i, size, n_clusters=n_clusters,
                             exponent=exponent, dup_frac=dup_frac,
                             cluster_width=cluster_width,
                             key_space=key_space, feat_dim=feat_dim,
                             sig_words=sig_words)
        ents["eid"] = jnp.asarray(
            np.arange(start, start + size, dtype=np.int32))
        yield ents


def doc_entities(docs: np.ndarray, *, sig_words: int = 8,
                 feat_dim: int = 64) -> dict:
    """Documents -> entity records: blocking key from the leading tokens,
    minhash-style bit signature + mean-pooled hashed features as payload."""
    n, L = docs.shape
    # blocking key: first two tokens folded into <2^30 (the 'title prefix')
    key = (docs[:, 0].astype(np.int64) * 1009 + docs[:, 1]) % (1 << 24)
    rng = np.random.default_rng(0)
    proj = rng.normal(size=(1024, feat_dim)).astype(np.float32) / 8.0
    feat = proj[docs.astype(np.int64) % 1024].mean(axis=1)
    feat /= np.linalg.norm(feat, axis=1, keepdims=True) + 1e-9
    # token-set bit signature
    bits = (docs.astype(np.int64) * 2654435761 % (sig_words * 32)).astype(
        np.int64)
    sig = np.zeros((n, sig_words), np.uint32)
    rows = np.repeat(np.arange(n), L)
    w = bits.reshape(-1) // 32
    b = bits.reshape(-1) % 32
    np.bitwise_or.at(sig, (rows, w), (1 << b.astype(np.uint32)))
    return E.make_entities(
        key.astype(np.int32), np.arange(n, dtype=np.int32),
        payload={"feat": jnp.asarray(feat), "sig": jnp.asarray(sig)})


@dataclass
class DedupResult:
    keep: np.ndarray                 # (n_docs,) bool
    n_pairs: int
    n_dropped: int
    gini: float
    overflow: int


def dedup_corpus(docs: np.ndarray, *, r: int = 4, window: int = 10,
                 variant: str = "repsn", threshold: float = 0.9,
                 balance: bool = True) -> DedupResult:
    """The paper's workflow as a corpus-dedup stage.  Keeps the lowest-eid
    member of every matched pair (union-find-free greedy: drop the higher)."""
    from repro import api
    ents = doc_entities(docs)
    keys_np = np.asarray(ents["key"])
    bounds = P.balanced_partition(keys_np, r) if balance else \
        P.range_partition(1 << 24, r)
    from dataclasses import replace
    from repro.core.match import default_matcher
    matcher = replace(default_matcher(), threshold=threshold)
    cfg = api.ERConfig(window=window, variant=variant, matcher=matcher,
                       runner="vmap", num_shards=r)
    res = api.resolve(ents, cfg, bounds=bounds)
    keep = np.ones(docs.shape[0], bool)
    for a, b in sorted(res.matches):
        if keep[a]:
            keep[b] = False
    sizes = np.asarray(P.partition_sizes(bounds, ents["key"], r=r))
    return DedupResult(keep=keep, n_pairs=len(res.matches),
                       n_dropped=int((~keep).sum()),
                       gini=P.gini(sizes), overflow=res.blocking.overflow)
