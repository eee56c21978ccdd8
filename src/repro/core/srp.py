"""SRP — Sorted Reduce Partitions (paper §4.1) as a TPU collective program.

The MapReduce shuffle with composite key ``p(k).k`` becomes:

  1. map-side: compute dest = p(key) per entity (partition.shard_of)
  2. bucketize into a fixed-capacity (r, cap_link) buffer, ranked within each
     destination by a LOCAL stable sort (XLA collectives are static-shape, so
     the variable-size Hadoop shuffle becomes capacity + overflow accounting,
     like MoE capacity-factor routing — see DESIGN.md §2)
  3. one ``all_to_all`` over the shard axis
  4. reduce-side local sort by (key, eid)  ->  globally range-sorted shards

Every function here is written per-shard against a named axis, so the same
code runs under ``shard_map`` (real devices) and ``jax.vmap(axis_name=...)``
(single-device property tests).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import entities as E
from repro.core import partition as P
from repro.obs.scopes import SHUFFLE_EXCHANGE, SHUFFLE_ROUTE, SHUFFLE_SORT


def bucketize(ents: dict, dest: jax.Array, r: int,
              cap_link: int) -> Tuple[dict, jax.Array]:
    """Scatter local entities into (r * cap_link) slots grouped by dest.

    Returns (bucketed_entities, overflow_count).  Entities beyond a bucket's
    capacity are dropped and counted (never silently lost)."""
    n = dest.shape[0]
    d = jnp.where(ents["valid"], dest, r)                 # invalid -> dump
    order = jnp.argsort(d, stable=True)
    sd = d[order]
    counts = jnp.zeros((r + 1,), jnp.int32).at[sd].add(1)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(n, dtype=jnp.int32) - offs[sd]
    keep = (pos < cap_link) & (sd < r)
    n_slots = r * cap_link
    slot = jnp.where(keep, sd * cap_link + pos, n_slots)

    src = E.permute(ents, order)
    out = E.empty_like(ents, n_slots + 1)

    def scat(buf, val):
        return buf.at[slot].set(val, mode="drop")

    out["key"] = scat(out["key"], jnp.where(keep, src["key"], E.INVALID_KEY))
    out["eid"] = scat(out["eid"], src["eid"])
    out["valid"] = scat(out["valid"], src["valid"] & keep)
    out["payload"] = {k: scat(out["payload"][k], v)
                      for k, v in src["payload"].items()}
    out = jax.tree.map(lambda a: a[:n_slots], out)
    overflow = jnp.sum((~keep) & (sd < r)).astype(jnp.int32)
    return out, overflow


def exchange(bucketed: dict, r: int, axis: str) -> dict:
    """The shuffle: one all_to_all per field over the shard axis."""
    def a2a(x):
        xr = x.reshape((r, x.shape[0] // r) + x.shape[1:])
        y = jax.lax.all_to_all(xr, axis, split_axis=0, concat_axis=0,
                               tiled=False)
        return y.reshape((-1,) + x.shape[1:])
    return jax.tree.map(a2a, bucketed)


def srp_shard(ents: dict, bounds: jax.Array, r: int, axis: str,
              cap_link: int) -> Tuple[dict, jax.Array]:
    """Full SRP for one mapper shard: returns (sorted reduce partition,
    global overflow count).  The result's shard index == partition index
    (monotone p => shard-local sort == global range sort).

    A ``_dest`` payload field (attached by the runners from a rank-granular
    ``repro.balance`` ShardPlan) overrides the key->shard partition function:
    it lets a planner split an oversized key block across shards while
    staying monotone in the global (key, eid) sort order, so the sorted-
    reduce-partition invariant — and every downstream window/halo step —
    holds unchanged.  The tag is consumed map-side and stripped before the
    shuffle (nothing reads it after routing; keeping it would waste
    all_to_all bandwidth and halo-permute bytes).

    Device stages (``repro.obs.scopes``): routing and bucketize in
    ``shuffle/route``, the all_to_all and the overflow ``psum`` in
    ``shuffle/exchange``, the reduce-side sort in ``shuffle/sort``."""
    with jax.named_scope(SHUFFLE_ROUTE):
        dest = ents["payload"].get("_dest")
        if dest is None:
            dest = P.shard_of(bounds, ents["key"])
        else:
            ents = dict(ents)
            ents["payload"] = {k: v for k, v in ents["payload"].items()
                               if k != "_dest"}
        buf, overflow = bucketize(ents, dest, r, cap_link)
    with jax.named_scope(SHUFFLE_EXCHANGE):
        recv = exchange(buf, r, axis)
    with jax.named_scope(SHUFFLE_SORT):
        sorted_ents = E.sort_entities(recv)
    with jax.named_scope(SHUFFLE_EXCHANGE):
        overflow = jax.lax.psum(overflow, axis)
    return sorted_ents, overflow


def local_load(ents: dict, axis: str) -> jax.Array:
    """Per-shard valid counts, all-gathered (skew telemetry, paper §5.3)."""
    return jax.lax.all_gather(E.n_valid(ents), axis)
