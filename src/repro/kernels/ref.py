"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def banded_sim_ref(feat: jax.Array, *, window: int) -> jax.Array:
    """(M, F) -> band (M, window): band[i, d] = <feat[i], feat[i+1+d]>,
    zero past the end."""
    m = feat.shape[0]
    f32 = feat.astype(jnp.float32)
    cols = []
    for d in range(1, window + 1):
        s = jnp.sum(f32 * jnp.roll(f32, -d, axis=0), axis=-1)
        ok = jnp.arange(m) + d < m
        cols.append(jnp.where(ok, s, 0.0))
    return jnp.stack(cols, axis=1)


def jaccard_band_ref(sig: jax.Array, *, window: int) -> jax.Array:
    m = sig.shape[0]
    cols = []
    for d in range(1, window + 1):
        o = jnp.roll(sig, -d, axis=0)
        inter = jax.lax.population_count(sig & o).sum(-1).astype(jnp.float32)
        union = jax.lax.population_count(sig | o).sum(-1).astype(jnp.float32)
        jac = inter / jnp.maximum(union, 1.0)
        ok = jnp.arange(m) + d < m
        cols.append(jnp.where(ok, jac, 0.0))
    return jnp.stack(cols, axis=1)

