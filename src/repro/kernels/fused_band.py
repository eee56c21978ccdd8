"""Pallas TPU kernel: fused cheap-cascade band (cosine + bit-packed Jaccard).

This is stage 1 of the paper's §5.1 skip optimization on device: the CHEAP
matchers of the cascade are evaluated for every sliding-window pair in one
``pallas_call``, and the result gates the expensive matcher (see
``core/window.PallasBandEngine``).

Compared to running ``banded_sim`` and ``jaccard_band`` back to back, the
fused kernel

  * loads each (Bi, F) feature block and (Bi, W) signature block into VMEM
    once and emits one weighted partial score ``w_cos*cos + w_jac*jac``;
  * extracts the (Bi, window) band IN-KERNEL (a strided lane rotate of the
    (Bi, 2*Bi) tile) instead of materializing (M, 2*Bi) tiles in HBM and
    gathering outside (``ops.band_from_tiles``), cutting the kernel's HBM
    write traffic by 2*Bi/window;
  * masks out-of-range pairs (global j >= M) in-kernel.

The rotate needs the tile's rows in reversed order within each block (see
the kernel), so the wrapper passes a block-reversed copy of each input and
reverses the output rows back: two O(M*(F+W)) and one O(M*window) XLA
permutations, exact, against the kernel's O(M*2*Bi*(F+W)) tile work.

VMEM per block: (Bi,F) f32 *3 + (Bi,W) u32 *3 + (Bi,2Bi) f32 tile +
(Bi,window) out; Bi=256, F<=512, W<=16: ~2.1 MB — comfortably resident.
Either half of the cascade can be disabled statically (weight 0.0) and its
input replaced by a (M, 1) dummy; the kernel body then never touches it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mosaic compiles the strided rotate only for lane widths 2*Bi that are
# whole multiples of 128, so a natively compiled block is a multiple of 64.
NATIVE_BLOCK_ALIGN = 64


def _fused_band_kernel(rfeat_ref, feat_ref, nfeat_ref, rsig_ref, sig_ref,
                       nsig_ref, o_ref, *, block_i: int, window: int,
                       w_cos: float, w_jac: float, sig_words: int,
                       m_total: int):
    # The row-side inputs (rfeat, rsig) hold this block in REVERSED row
    # order: tile row q scores entity bi-1-q against the 2*Bi partners of
    # this block and its successor, in natural order.
    bi = block_i
    acc = jnp.zeros((bi, 2 * bi), jnp.float32)
    if w_cos > 0.0:
        xr = rfeat_ref[...].astype(jnp.float32)          # (Bi, F) reversed
        x = feat_ref[...].astype(jnp.float32)
        nxt = nfeat_ref[...].astype(jnp.float32)
        s1 = jax.lax.dot_general(                        # row-block self
            xr, x, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        s2 = jax.lax.dot_general(                        # vs successor block
            xr, nxt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dots = jnp.concatenate([s1, s2], axis=1)         # (Bi, 2Bi)
        acc = acc + w_cos * jnp.clip(0.5 * (dots + 1.0), 0.0, 1.0)
    if w_jac > 0.0:
        a = rsig_ref[...]                                # (Bi, W) reversed
        both_t = jnp.concatenate([sig_ref[...], nsig_ref[...]], axis=0).T
        inter = jnp.zeros((bi, 2 * bi), jnp.int32)
        union = jnp.zeros((bi, 2 * bi), jnp.int32)
        for wd in range(sig_words):                      # static unroll
            x = a[:, wd:wd + 1]                          # (Bi, 1)
            y = both_t[wd:wd + 1, :]                     # (1, 2Bi)
            inter = inter + jax.lax.population_count(x & y).astype(jnp.int32)
            union = union + jax.lax.population_count(x | y).astype(jnp.int32)
        # match core.match.jaccard_sig exactly: empty-vs-empty -> 1.0
        jac = jnp.where(union > 0,
                        inter.astype(jnp.float32) /
                        jnp.maximum(union.astype(jnp.float32), 1.0), 1.0)
        acc = acc + w_jac * jac
    # in-kernel band extraction.  Tile row q (entity r = bi-1-q) needs
    # columns r+1 .. r+window.  A strided lane rotate shifts row q right by
    # bi + q == -(r+1) mod 2Bi, so column d of the rotated row is partner
    # r+1+d (< 2Bi: nothing wraps into the kept [:, :window]).  Mosaic's
    # strided rotate only shifts by an amount that GROWS with the row, hence
    # the reversed row order.
    band = pltpu.roll(acc, bi, 1, stride=1, stride_axis=0)[:, :window]
    r = bi - 1 - jax.lax.broadcasted_iota(jnp.int32, (bi, window), 0)
    d = jax.lax.broadcasted_iota(jnp.int32, (bi, window), 1)
    grow = pl.program_id(0) * bi + r                     # global row index
    ok = (grow + 1 + d) < m_total
    o_ref[...] = jnp.where(ok, band, 0.0)


def _reverse_blocks(x: jax.Array, block_i: int) -> jax.Array:
    """Reverse the row order inside every ``block_i``-row block."""
    m = x.shape[0]
    return x.reshape((m // block_i, block_i) + x.shape[1:])[:, ::-1] \
        .reshape(x.shape)


def fused_band_scores(feat: jax.Array, sig: jax.Array, *, window: int,
                      w_cos: float, w_jac: float, block_i: int = 256,
                      m_valid: int = None, interpret: bool = False
                      ) -> jax.Array:
    """feat: (M, F) f32-ish, sig: (M, W) uint32; M % block_i == 0 and
    window <= block_i.  Returns the (M, window) weighted cheap-score band
    ``w_cos*cosine + w_jac*jaccard``.  Entries pairing past ``m_valid``
    (default M — callers that padded pass the unpadded row count) are
    zeroed in-kernel.  Compiled for the TPU (``interpret=False``),
    ``block_i`` must be a multiple of ``NATIVE_BLOCK_ALIGN``."""
    m, f = feat.shape
    _, words = sig.shape
    assert m % block_i == 0, (m, block_i)
    assert window <= block_i, (window, block_i)
    n_blocks = m // block_i
    kernel = functools.partial(
        _fused_band_kernel, block_i=block_i, window=window,
        w_cos=float(w_cos), w_jac=float(w_jac), sig_words=words,
        m_total=m if m_valid is None else m_valid)
    cur = lambda i: (i, 0)
    # the last block's successor view wraps to itself; every such entry has
    # global j >= M and is zeroed by the in-kernel ``ok`` mask.
    nxt = lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0)
    band = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_i, f), cur),
            pl.BlockSpec((block_i, f), cur),
            pl.BlockSpec((block_i, f), nxt),
            pl.BlockSpec((block_i, words), cur),
            pl.BlockSpec((block_i, words), cur),
            pl.BlockSpec((block_i, words), nxt),
        ],
        out_specs=pl.BlockSpec((block_i, window), cur),
        out_shape=jax.ShapeDtypeStruct((m, window), jnp.float32),
        interpret=interpret,
        name="fused_cheap_band",   # fixed: device profiles show it so
    )(_reverse_blocks(feat, block_i), feat, feat,
      _reverse_blocks(sig, block_i), sig, sig)
    return _reverse_blocks(band, block_i)
