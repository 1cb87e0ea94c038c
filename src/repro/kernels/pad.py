"""Shared d-axis padding for the (m, d) aggregation kernels.

Every weighted-aggregation kernel tiles the coordinate axis into ``block_d``
columns, which requires d to be a multiple of the tile. Previously each
``pallas_call`` wrapper (`wcwmed_pallas`, `sqdist_pallas`, `wcomb_pallas`)
padded its own copy of X — an extra O(m·d) HBM copy *per kernel launch* in the
multi-kernel ω-CTMA / Weiszfeld pipelines. The fused paths pad once here and
hand the padded matrix to every pass.

Zero-padding is semantics-preserving for all three kernels: the weighted
median of an all-zero column is 0, so padded coordinates contribute
(x - y)² = 0 to distance accumulations and 0 to weighted combinations.
"""
from __future__ import annotations

import jax.numpy as jnp


def pad_cols(x: jnp.ndarray, block_d: int) -> tuple[jnp.ndarray, int, int]:
    """Pad the last axis of ``x`` up to a multiple of ``block_d`` with zeros.

    Returns ``(padded, d, bd)`` where ``d`` is the original size and ``bd`` the
    effective tile (``min(block_d, d)``). No copy is made when d already tiles:
    the dtype is kept, and every kernel upcasts its tile to float32 in VMEM.
    """
    d = x.shape[-1]
    bd = min(block_d, d)
    pad = (-d) % bd
    if pad:
        width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, width)
    return x, d, bd
