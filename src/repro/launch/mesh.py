"""Production mesh construction.

A FUNCTION (not module-level constant) so importing this module never touches
jax device state.  Single pod: 16x16 = 256 chips (data x model).  Multi-pod:
2 x 16 x 16 = 512 chips with a leading "pod" axis (data parallelism across
pods over DCN/ICI-over-optical; the dry-run proves the pod axis shards).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis (per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s per link (~)
ICI_LINKS = 4                     # 2D torus: 4 links/chip (v5e)
