"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernels VALIDATE on CPU via the
Pallas interpreter and compile natively on TPU — same code path).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.banded_sim import banded_sim_tiles
from repro.kernels.fused_band import NATIVE_BLOCK_ALIGN, fused_band_scores
from repro.kernels.jaccard_band import jaccard_band_tiles
from repro.kernels.local_attn import local_attention


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def resolve_block_i(m: int, window: int, block_i: int, align: int = 1
                    ) -> int:
    """Pick the row-block size for a band kernel.

    The band kernels require ``window <= block_i`` (each row's whole band
    lives in its own tile + the successor tile).  Naively clamping
    ``bi = min(block_i, m)`` violates that for small M, so when the clamped
    block is too small for the window we grow it back up to ``window`` (the
    caller pads M up to a multiple of the block — safe, padded rows are
    masked).  A window that cannot fit in ``block_i`` at all is a config
    error, reported actionably instead of tripping the kernel's assert.
    The result is rounded up to a multiple of ``align`` (the TPU compiler's
    block granularity for native kernels)."""
    if window > block_i:
        raise ValueError(
            f"band window={window} exceeds block_i={block_i}; the band "
            f"kernels need window <= block_i (one tile + successor covers "
            f"the whole band).  Raise block_i (VMEM grows as block_i^2) or "
            f"use the scan band engine")
    bi = max(min(block_i, m), window)
    return -(-bi // align) * align


def band_from_tiles(tiles: jax.Array, *, window: int,
                    block_i: int) -> jax.Array:
    """(M, 2*Bi) tiles -> (M, window) band.

    band[g, d] = tiles[g, (g % Bi) + 1 + d]; entries with global j >= M are
    zeroed."""
    m = tiles.shape[0]
    r = jnp.arange(m, dtype=jnp.int32)
    local = r % block_i
    cols = local[:, None] + 1 + jnp.arange(window, dtype=jnp.int32)[None, :]
    band = jnp.take_along_axis(tiles, cols, axis=1)
    ok = (r[:, None] + 1 + jnp.arange(window)[None, :]) < m
    return jnp.where(ok, band, 0.0)


@partial(jax.jit, static_argnames=("window", "block_i", "interpret"))
def banded_dot_band(feat: jax.Array, *, window: int, block_i: int = 256,
                    interpret: bool = None) -> jax.Array:
    """Banded <feat_i, feat_j> similarity: (M, F) -> (M, window)."""
    interpret = default_interpret() if interpret is None else interpret
    m, f = feat.shape
    bi = resolve_block_i(m, window, block_i)
    pad = (-m) % bi
    if pad:
        feat = jnp.pad(feat, ((0, pad), (0, 0)))
    tiles = banded_sim_tiles(feat, window=window, block_i=bi,
                             interpret=interpret)
    return band_from_tiles(tiles, window=window, block_i=bi)[:m]


@partial(jax.jit, static_argnames=("window", "block_i", "interpret"))
def jaccard_band(sig: jax.Array, *, window: int, block_i: int = 256,
                 interpret: bool = None) -> jax.Array:
    """Banded Jaccard over bit signatures: (M, W32) -> (M, window)."""
    interpret = default_interpret() if interpret is None else interpret
    m, words = sig.shape
    bi = resolve_block_i(m, window, block_i)
    pad = (-m) % bi
    if pad:
        sig = jnp.pad(sig, ((0, pad), (0, 0)))
    tiles = jaccard_band_tiles(sig, window=window, block_i=bi,
                               interpret=interpret)
    return band_from_tiles(tiles, window=window, block_i=bi)[:m]


@partial(jax.jit, static_argnames=("window", "w_cos", "w_jac", "block_i",
                                   "interpret"))
def fused_cheap_band(feat: jax.Array, sig: jax.Array, *, window: int,
                     w_cos: float, w_jac: float, block_i: int = 256,
                     interpret: bool = None) -> jax.Array:
    """Fused cheap-cascade band: (M, F) x (M, W32) -> (M, window) weighted
    partial score ``w_cos*cosine + w_jac*jaccard`` (unnormalized — the
    cascade gate in core/window.py compares against a pre-scaled tau).

    Either half is disabled by a zero weight (pass a (M, 1) dummy array for
    the unused input).  The band is emitted directly by the kernel — no
    (M, 2*block_i) tile intermediate, no host-side gather."""
    interpret = default_interpret() if interpret is None else interpret
    m = feat.shape[0]
    bi = resolve_block_i(m, window, block_i,
                         align=1 if interpret else NATIVE_BLOCK_ALIGN)
    pad = (-m) % bi
    if pad:
        feat = jnp.pad(feat, ((0, pad), (0, 0)))
        sig = jnp.pad(sig, ((0, pad), (0, 0)))
    return fused_band_scores(feat, sig, window=window, w_cos=w_cos,
                             w_jac=w_jac, block_i=bi, m_valid=m,
                             interpret=interpret)[:m]


@partial(jax.jit,
         static_argnames=("window", "block_q", "block_k", "softcap",
                          "interpret"))
def local_attn(q: jax.Array, k: jax.Array, v: jax.Array, *, window: int,
               block_q: int = 256, block_k: int = 256, softcap: float = 0.0,
               interpret: bool = None) -> jax.Array:
    """Sliding-window flash attention: (BH, S, D) x3 -> (BH, S, D)."""
    interpret = default_interpret() if interpret is None else interpret
    s = q.shape[1]
    bq = bk = min(block_q, block_k, s)
    return local_attention(q, k, v, window=window, block_q=bq, block_k=bk,
                           softcap=softcap, interpret=interpret)
