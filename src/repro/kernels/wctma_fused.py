"""Fused ω-CTMA (paper Algorithm 1) — single-pass anchor + distances.

The unfused pipeline makes ≥3 full HBM passes over the (m, d) update matrix:

    pass 1  wcwmed_pallas   X -> anchor                (reads X)
    pass 2  sqdist_pallas   X, anchor -> distances     (reads X again)
    pass 3  wcomb_pallas    X, kept -> trimmed mean    (reads X again)

Remark 4.1's O(dm) cost model assumes the aggregator is bandwidth-bound, so
the extra passes are pure roofline loss. This kernel fuses passes 1+2: each
grid program computes the weighted-median anchor for its d-tile (reusing
``wcwmed.wmed_tile`` on the (m, bd) VMEM tile) and immediately accumulates
each worker's squared distance to that tile of the anchor into a revisited
(m, 1) output block — the distance pass piggybacks on the tile already in
VMEM instead of re-reading HBM. The m-element sort / prefix-sum / weight
clipping stays in XLA (O(m log m) scalars), and a single trimmed-combine pass
finishes:

    pass 1  wctma_anchor_dist   X -> anchor, distances (reads X ONCE)
    pass 2  wcomb_padded        X, kept -> trimmed mean

Total: X is read from HBM exactly twice per call, and the zero-pad copy (when
d is not a tile multiple) happens once for both passes (see pad.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_mode

from .pad import pad_cols
from .wcwmed import weight_operands, wmed_tile
from .wreduce import wcomb_padded

# Wider tiles than the standalone median kernel: both fused passes are
# bandwidth-bound streams, and the (m, bd) f32 working set at m=64, bd=2048
# is ~0.5 MB — comfortably double-bufferable in 16 MB VMEM.
DEFAULT_BLOCK_D = 2048


def _anchor_dist_kernel(x_ref, s_ref, ss_ref, anchor_ref, dist_ref, *, m: int):
    j = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)          # (m, bd)

    med = wmed_tile(x, s_ref[...], ss_ref, m)   # (bd,) anchor for this tile
    anchor_ref[...] = med

    part = jnp.sum(jnp.square(x - med[None, :]), axis=1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        dist_ref[...] = jnp.zeros_like(dist_ref)

    dist_ref[...] += part


def wctma_anchor_dist(xp: jnp.ndarray, s: jnp.ndarray, bd: int, *,
                      interpret: Optional[bool] = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Single sweep over a pre-padded (m, dp) matrix returning
    (anchor (dp,), squared distances (m,))."""
    m, dp = xp.shape
    anchor, dist = pl.pallas_call(
        functools.partial(_anchor_dist_kernel, m=m),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((m, bd), lambda j: (0, j)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((bd,), lambda j: (j,)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp,), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        name="wctma_anchor_dist",
        interpret=interpret_mode(interpret),
    )(xp, *weight_operands(s))
    return anchor, dist[:, 0]


def trim_weights(dist: jnp.ndarray, s: jnp.ndarray, lam: float
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CTMA weight trimming (XLA, O(m log m) scalars): keep the (1-λ) weight
    mass of rows closest to the anchor, clipping the boundary row. ``dist``
    only needs to order correctly, so squared distances work. Returns
    (kept (m,), thresh ())."""
    sw = s.astype(jnp.float32)
    order = jnp.argsort(dist)
    ws = sw[order]
    cum = jnp.cumsum(ws)
    thresh = (1.0 - lam) * cum[-1]
    prev = jnp.concatenate([jnp.zeros_like(cum[:1]), cum[:-1]])
    kept_sorted = jnp.clip(thresh - prev, 0.0, ws)
    kept = jnp.zeros_like(kept_sorted).at[order].set(kept_sorted)
    return kept, thresh


@functools.partial(jax.jit, static_argnames=("lam", "block_d", "interpret"))
def wctma_fused(x: jnp.ndarray, s: jnp.ndarray, *, lam: float,
                block_d: int = DEFAULT_BLOCK_D, interpret: Optional[bool] = None
                ) -> jnp.ndarray:
    """Fused ω-CTMA: x (m, d), s (m,) -> (d,) float32. ≡ ref.wctma_ref."""
    xp, d, bd = pad_cols(x, block_d)
    _, dist = wctma_anchor_dist(xp, s, bd, interpret=interpret)
    kept, thresh = trim_weights(dist, s, lam)
    out = wcomb_padded(xp, kept, jnp.maximum(thresh, 1e-30), bd,
                       interpret=interpret)
    return out[:d]
