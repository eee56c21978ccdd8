"""Compile the fused cheap-band kernel for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed with jax, compiles for a
chip that is described and not attached.  This catches what the Pallas
interpreter accepts and Mosaic refuses (unsupported gathers, rotates,
layouts) at no chip time.  Widths are those of ``synth_entities`` (F = 32
feature floats, W = 8 signature words) at the paper's windows w = 10 and
100 (band widths 9 and 99).

The topology is described only inside a fixture: one process at a time may
load the TPU library, so describing it while this module is imported would
make test workers collect different tests.  Keep every such test in this
one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

M, F, W, BLOCK = 4096, 32, 8, 256
SHARDS = 8

CASES = [(window, w_cos, w_jac)
         for window in (9, 99)
         for w_cos, w_jac in ((0.25, 0.25), (0.25, 0.0), (0.0, 0.25))]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _inputs(sharding, w_cos, w_jac, lead=()):
    """Abstract (feat, sig); a disabled half gets the (M, 1) dummy the band
    engine passes."""
    feat = jax.ShapeDtypeStruct(lead + (M, F if w_cos else 1), jnp.float32,
                                sharding=sharding)
    sig = jax.ShapeDtypeStruct(lead + (M, W if w_jac else 1), jnp.uint32,
                               sharding=sharding)
    return feat, sig


@pytest.mark.parametrize("window,w_cos,w_jac", CASES)
def test_fused_band_compiles_for_v5e(one_chip, no_persistent_cache,
                                     window, w_cos, w_jac):
    feat, sig = _inputs(one_chip, w_cos, w_jac)
    compiled = ops.fused_cheap_band.lower(
        feat, sig, window=window, w_cos=w_cos, w_jac=w_jac, block_i=BLOCK,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the device layout pads the band's lane dimension
    assert compiled.memory_analysis().output_size_in_bytes >= M * window * 4


@pytest.mark.parametrize("window", [9, 99])
def test_fused_band_compiles_under_vmap_for_v5e(one_chip, no_persistent_cache,
                                                window):
    """The vmap runner batches the kernel over its shards (one extra grid
    axis); that lowering must compile too."""
    feat, sig = _inputs(one_chip, 0.25, 0.25, lead=(SHARDS,))
    band = jax.vmap(lambda f, s: ops.fused_cheap_band(
        f, s, window=window, w_cos=0.25, w_jac=0.25, block_i=BLOCK,
        interpret=False))
    compiled = jax.jit(band).lower(feat, sig).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= \
        SHARDS * M * window * 4
