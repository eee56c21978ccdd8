"""Device stage names — the ``jax.named_scope``s of the shard program.

Host spans cannot see inside one compiled program, so the shard program
names its stages on the device instead.  Each stage is a
``jax.named_scope``: trace-time metadata only (the HLO ``op_name`` of
every instruction traced inside it, which a device profile carries on
each operation), so the optimised program, its fusions and its
executable-cache key are the same with or without them.

  ``shuffle``          SRP bucketize, the all_to_all and the local sort;
                       the RepSN halo and the JobSN boundary replication.
                       Four sub-stages open inside it:
    ``shuffle/route``     each row's reducer, the argsort by it and the
                          scatter into the per-destination buckets
    ``shuffle/exchange``  the all_to_all, the overflow ``psum`` and the
                          load ``all_gather``
    ``shuffle/sort``      the reduce-side ``(key, eid)`` sort
    ``shuffle/halo``      the RepSN tail window and its ring
                          ``ppermute``; the JobSN boundary group
  ``band/align``       putting each row beside its partner at distance d:
                       the scan engine's rolls, the pallas engine's
                       candidate gathers
  ``band/cheap``       every matcher of the cascade but the most expensive
                       (by ``cost``); the pallas engine's fused kernel
                       (``pallas_call`` name ``fused_cheap_band``)
  ``band/expensive``   the most expensive matcher of a cascade of two or
                       more: on every band slot (scan) or on the
                       ``cand_cap`` buffer (pallas)
  ``band/select``      everything else of the band: masks, the cascade
                       gate and skip rule, compaction, the threshold, the
                       match scatter and ``emit_band_indices``

Scopes nest: ``band/select`` wraps the whole band and the three other band
stages open inside it, and ``shuffle`` wraps its four sub-stages, so an
operation belongs to the INNERMOST stage in its ``op_name`` path.  Under
``vmap`` the path shows a stage as ``vmap(band/cheap)``; a shuffle
sub-stage's path holds ``shuffle`` and then the sub-stage, as in
``vmap(shuffle)/shuffle/route``, so a reader that knows only ``STAGES``
charges it to ``shuffle``.  A fused operation carries the ``op_name`` XLA
gave the fusion, that of its root.
"""
from __future__ import annotations

SHUFFLE = "shuffle"
BAND_ALIGN = "band/align"
BAND_CHEAP = "band/cheap"
BAND_EXPENSIVE = "band/expensive"
BAND_SELECT = "band/select"

SHUFFLE_ROUTE = "shuffle/route"
SHUFFLE_EXCHANGE = "shuffle/exchange"
SHUFFLE_SORT = "shuffle/sort"
SHUFFLE_HALO = "shuffle/halo"

STAGES = (SHUFFLE, BAND_ALIGN, BAND_CHEAP, BAND_EXPENSIVE, BAND_SELECT)
SHUFFLE_STAGES = (SHUFFLE_ROUTE, SHUFFLE_EXCHANGE, SHUFFLE_SORT, SHUFFLE_HALO)
