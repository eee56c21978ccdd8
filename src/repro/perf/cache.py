"""Executable cache — compile each shard program once, dispatch forever.

The steady-state throughput problem (ISSUE 4): every ``resolve()`` used to
re-trace its program from scratch — ``VmapRunner`` ran the batching
interpreter op-by-op, ``ShardMapRunner`` additionally paid an ``eval_shape``
pass per call — so repeated runs over same-shaped inputs (the serving
workload the ROADMAP north-star describes) paid compile-class overheads on
every call.  This module gives the runners a process-wide cache mapping

    (runner kind, cfg static fingerprint, cap_link, input shapes/dtypes)
        -> one jitted executable

so the second same-shaped call is a single XLA dispatch.  Three rules keep
it honest:

  * **Keys are exact.**  Anything that changes the traced program — config
    statics (``ERConfig.static_fingerprint()``), planner capacity, input
    tree structure, shapes, dtypes — is in the key.  Boundary *values* are
    traced arguments, so replanning boundaries never retraces.
  * **Traces are counted, not assumed.**  The cached callable wraps the
    program in a trace counter before ``jax.jit``; ``CacheStats.traces``
    increments only when XLA actually (re)traces, which is what the
    zero-retrace tests assert (a key bug would show up as a trace, never
    as silent recompilation).
  * **Donation only for buffers we own.**  Callers donate argument 0 (the
    stacked shard input, rebuilt per call) on backends that support buffer
    donation; user-held entity arrays are never donated.

``facade.resolve`` snapshots ``CacheStats`` around each run and reports the
delta as ``ERResult.perf`` (hits / misses / traces / entries).

Across processes, ``enable_compilation_cache`` turns on JAX's persistent
compilation cache.  Entry points (``chip_smoke.py``, ``benchmarks/run.py``)
call it; importing the library never does.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Tuple

import jax

# Default persistent-cache location: a fixed path inside the checkout, so a
# later run finds what an earlier one cached.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the only location: JAX reads
    it itself and nothing is set here.  Otherwise the cache lives at
    ``REPO_CACHE_DIR``.  Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


# Executables retained before least-recently-used eviction: enough for many
# concurrent (variant x engine x shape) working sets, small enough that a
# serving process resolving arbitrarily-shaped batches doesn't accrete
# compiled programs without bound (each entry holds a lowered executable).
DEFAULT_MAX_ENTRIES = 256


@dataclass
class CacheStats:
    """Counters for the executable cache (process-wide, monotone).

    ``misses`` counts cache builds; ``traces`` counts actual jit traces of
    cached programs — equal in a healthy cache (every executable traced
    exactly once), diverging only if a keying bug lets one cached entry see
    two shapes.  ``evictions`` counts LRU drops (an evicted key rebuilds on
    next use; a high rate means the working set exceeds ``max_entries``)."""
    hits: int = 0
    misses: int = 0
    traces: int = 0
    evictions: int = 0

    def snapshot(self) -> Tuple[int, int, int]:
        """Current (hits, misses, traces) — pair with ``delta`` to meter
        one region of work (the counters are process-wide and monotone)."""
        return (self.hits, self.misses, self.traces)

    def delta(self, since: Tuple[int, int, int]) -> Tuple[int, int, int]:
        """(hits, misses, traces) accrued since a ``snapshot()`` — how
        ``facade.resolve`` builds per-call ``PerfStats`` and how
        ``repro.stream`` attributes cache behavior to individual chunks
        (a steady-state chunk shows hits > 0, misses == traces == 0)."""
        h, m, t = since
        return (self.hits - h, self.misses - m, self.traces - t)


def tree_fingerprint(tree) -> Tuple:
    """Hashable (structure, shapes, dtypes) key of an argument pytree —
    the part of a cache key that makes same-key imply same-trace.  Works on
    concrete arrays and abstract tracers alike (only ``.shape``/``.dtype``
    are read), so cached calls stay usable under an outer ``jax.jit``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


def supports_donation() -> bool:
    """Buffer donation is a no-op (with a warning) on CPU; only donate
    where XLA can actually reuse the buffer."""
    return jax.default_backend() not in ("cpu",)


class ExecutableCache:
    """Maps hashable program keys to jitted executables (see module doc),
    bounded by LRU eviction so long-lived serving processes don't retain
    one compiled program per distinct shape forever."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES):
        self._fns: "OrderedDict[Any, Callable]" = OrderedDict()
        self.max_entries = max_entries
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        """Drop all executables (stats keep counting — they are monotone
        telemetry, not per-entry state)."""
        self._fns.clear()

    def get_or_build(self, key, build: Callable[[], Callable], *,
                     donate_argnums: Tuple[int, ...] = ()) -> Callable:
        """Return the jitted executable for ``key``, building (and jitting,
        with a trace counter) via ``build()`` on a miss."""
        fn = self._fns.get(key)
        if fn is not None:
            self.stats.hits += 1
            self._fns.move_to_end(key)       # LRU freshness
            return fn
        self.stats.misses += 1
        program = build()

        def traced(*args):
            self.stats.traces += 1
            return program(*args)

        donate = donate_argnums if supports_donation() else ()
        fn = jax.jit(traced, donate_argnums=donate)
        self._fns[key] = fn
        while len(self._fns) > self.max_entries:
            self._fns.popitem(last=False)    # least recently used
            self.stats.evictions += 1
        return fn


_GLOBAL_CACHE = ExecutableCache()


def executable_cache() -> ExecutableCache:
    """The process-wide cache every runner routes through."""
    return _GLOBAL_CACHE
