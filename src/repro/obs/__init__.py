"""``repro.obs`` — unified tracing + metrics (DESIGN.md §12).

One observability substrate for the whole pipeline:

  * ``span(name, **attrs)``   the instrumentation primitive: a context
                              manager that is a shared no-op singleton
                              when no tracer is active (near-zero
                              disabled cost) and records monotonic
                              timing + nesting when one is
  * ``Tracer`` / ``activate`` per-run span collector, installed
                              per-thread; ``export_chrome`` writes a
                              Chrome/Perfetto ``trace.json``
  * ``Counter`` / ``Gauge`` / ``Histogram`` / ``MetricsRegistry``
                              typed metrics behind one ``to_dict`` schema
                              (``Histogram`` is a bounded ring buffer —
                              the serve latency window rides on it)
  * ``TraceReport``           the per-run artifact ``ERConfig.trace=True``
                              attaches to results: spans + metrics + the
                              five legacy stats types unified behind
                              ``metrics()`` (``pack_stats``/
                              ``unpack_stats`` round-trip them losslessly)
  * ``scopes``                the device stage names of the shard program
                              (``shuffle``, ``band/align``, ``band/cheap``,
                              ``band/expensive``, ``band/select``, and
                              the shuffle's ``shuffle/route``,
                              ``/exchange``, ``/sort``, ``/halo``): the
                              ``jax.named_scope``s a device profile carries
                              on every operation

A device resolve records, under its ``attempt`` span: on a mesh first
``distribute`` (the mapper splits placed one per device; counter
``distribute_bytes``), then ``shard_program`` (the device program,
blocked on when traced), on a mesh ``exchange_counts`` (traced only:
the ``shuffle.bytes``, ``shuffle.rows_moved`` and ``halo.rows`` counters,
from the plan and the shapes on the host), ``collect`` with its
child ``transfer`` (the one device-to-host fetch of the leaves collection
reads; the ``transfer_bytes`` counter counts exactly those bytes), and
``to_outcome`` (the public ``PairSet``s wrapped around the packed pairs;
counter ``pairs.materialized``, registered there, adds the pairs later
turned into tuples).

Every module here is a leaf (stdlib + numpy only at import time), so the
instrumented subsystems — ``repro.api``, ``repro.stream``, ``repro.serve``,
``repro.resilience`` — import ``repro.obs`` without cycles; the schema's
class lookups resolve lazily at unpack time.

Invariant 12: tracing never changes pair sets or retrace counts.
"""
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.report import TraceReport
from repro.obs.schema import (SCHEMA_VERSION, STATS_KINDS, pack_stats,
                              unpack_stats)
from repro.obs.trace import (NOOP_SPAN, SpanRecord, Tracer, activate,
                             current_tracer, span, write_chrome)

__all__ = [
    "span", "Tracer", "activate", "current_tracer", "SpanRecord",
    "NOOP_SPAN", "write_chrome",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "TraceReport", "pack_stats", "unpack_stats", "SCHEMA_VERSION",
    "STATS_KINDS",
]
