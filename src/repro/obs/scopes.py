"""Device stage names — the ``jax.named_scope``s of the shard program.

Host spans cannot see inside one compiled program, so the shard program
names its stages on the device instead.  Each stage is a
``jax.named_scope``: trace-time metadata only (the HLO ``op_name`` of
every instruction traced inside it, which a device profile carries on
each operation), so the optimised program, its fusions and its
executable-cache key are the same with or without them.

  ``shuffle``          SRP bucketize, the all_to_all and the local sort;
                       the RepSN halo and the JobSN boundary replication
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
stages open inside it, so an operation belongs to the INNERMOST stage in
its ``op_name`` path.  Under ``vmap`` the path shows a stage as
``vmap(band/cheap)``.  A fused operation carries the ``op_name`` XLA gave
the fusion, that of its root.
"""
from __future__ import annotations

SHUFFLE = "shuffle"
BAND_ALIGN = "band/align"
BAND_CHEAP = "band/cheap"
BAND_EXPENSIVE = "band/expensive"
BAND_SELECT = "band/select"

STAGES = (SHUFFLE, BAND_ALIGN, BAND_CHEAP, BAND_EXPENSIVE, BAND_SELECT)
