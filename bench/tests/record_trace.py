#!/usr/bin/env python3
"""Record the small TPU trace ``test_devtrace.py`` reads.

    python3 bench/tests/record_trace.py <out_dir>

Run on one TPU chip: three sorts of an (8, 4096) array, each inside a
``shard_program`` annotation, with sleeps between them, all inside the
``bench.window`` annotation the harness puts around its window.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.sort(x, axis=-1) * 2)
    x = jax.random.normal(jax.random.key(0), (8, 4096))
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("shard_program"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
