#!/usr/bin/env python3
"""Record the small TPU trace with named stages that ``test_scopes.py``
reads.

    python3 bench/tests/record_scoped_trace.py <out_dir>

Run on one TPU chip: a jitted program whose sort runs in the ``shuffle``
scope, one elementwise fusion in ``band/cheap``, a ``pallas_call`` named
``recorded_kernel`` in ``band/expensive``, and an unscoped reduction.  It
runs three times, each inside a ``shard_program`` annotation, all inside
the ``bench.window`` annotation the harness puts around its window.  The
sleeps keep every device operation inside its annotations, clear of the
profiler's alignment of device and host clocks (about a millisecond).
"""
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS, COLS, BLOCK = 512, 2048, 64


def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def recorded_kernel(x):
    spec = pl.BlockSpec((BLOCK, COLS), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel, grid=(ROWS // BLOCK,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="recorded_kernel")(x)


@jax.jit
def program(x):
    with jax.named_scope("shuffle"):
        s = jnp.sort(x, axis=-1)
    with jax.named_scope("band/cheap"):
        c = jnp.tanh(s) * 3.0 + s
    with jax.named_scope("band/expensive"):
        k = recorded_kernel(c)
    return k.sum(axis=0)


def main(out: str) -> None:
    x = jax.random.normal(jax.random.key(0), (ROWS, COLS))
    program(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            time.sleep(0.005)
            with jax.profiler.TraceAnnotation("shard_program"):
                time.sleep(0.005)
                program(x).block_until_ready()
                time.sleep(0.005)
        time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
