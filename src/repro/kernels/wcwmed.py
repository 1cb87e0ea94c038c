"""Pallas TPU kernel: weighted coordinate-wise median by rank selection.

GPU implementations sort the m worker values per coordinate. On TPU,
data-dependent sorts map poorly onto the VPU; for the small worker counts of
robust aggregation (m ≤ 64) we instead compute each element's *weighted rank*
with dense masked reductions (an O(m²)-compare schedule that is branch-free
and tiles cleanly into VMEM):

    below_j = Σ_i s_i · [ (x_i, i) ≺ (x_j, j) ]        (strict lexicographic)
    rank_j  = Σ_i       [ (x_i, i) ≺ (x_j, j) ]        (sorted position)
    median  = the element of least rank with below_j + s_j > S/2

with the paper's tie rule: when a prefix comes within ``tie_tol`` of S/2
(the same relative tolerance as the ``core.aggregators`` oracle, shared, so
both agree on near-ties), the element of least such rank is averaged with
the element of the next rank. Selecting by integer rank, not by comparing
weight sums, keeps the pick unique whatever order the sums round in.

Layout: grid over d-tiles; each program holds an (m, bd) tile of X plus the
(m,) weights in VMEM and unrolls the m accumulation steps. The tile-local
selection body lives in ``wmed_tile`` so the fused ω-CTMA kernel
(``wctma_fused.py``) can piggyback its distance pass on the same VMEM tile.
"""
from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregators import tie_tol

from .backend import interpret_mode
from .pad import pad_cols

# 1-D f32 outputs carry XLA's T(1024) tiling on TPU: the tile must match
DEFAULT_BLOCK_D = 1024


def wmed_tile(x: jnp.ndarray, s: jnp.ndarray, s_smem, m: int) -> jnp.ndarray:
    """Weighted median of each column of an (m, bd) VMEM tile.

    s: (m, 1) weights in VMEM; ``s_smem``: the same (m,) weights as an SMEM
    ref. Each row's weight enters the vector math as a scalar from SMEM:
    Mosaic broadcasts a scalar to a tile, but not a (1, 1) vector along
    sublanes and lanes at once."""
    half = s_smem[0]
    for i in range(1, m):
        half = half + s_smem[i]
    half = 0.5 * half
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    below = jnp.zeros_like(x)
    rank = jnp.zeros_like(x)
    for i in range(m):                           # unrolled: m is small & static
        xi = x[i:i + 1]                          # (1, bd)
        before = ((xi < x) | ((xi == x) & (row > i))).astype(jnp.float32)
        below = below + s_smem[i] * before
        rank = rank + before

    cum = below + s                              # inclusive cumulative weight
    none = jnp.float32(m)

    def pick(r):                                 # value at sorted position r
        return jnp.sum(jnp.where(rank == r, x, 0.0), axis=0)

    r_med = jnp.min(jnp.where(cum > half, rank, none), axis=0)
    r_med = jnp.where(r_med == none, 0.0, r_med)  # all-zero weights: oracle's 0
    # a prefix (never the full sum) within tie_tol of S/2 averages with the next
    near = (jnp.abs(cum - half) <= tie_tol(cum, half)) & (rank < m - 1)
    r_tie = jnp.min(jnp.where(near, rank, none), axis=0)
    return jnp.where(r_tie < none, 0.5 * (pick(r_tie) + pick(r_tie + 1.0)),
                     pick(r_med))


def _kernel(x_ref, s_ref, ss_ref, o_ref, *, m: int):
    x = x_ref[...].astype(jnp.float32)          # (m, bd)
    s = s_ref[...]                              # (m, 1)
    o_ref[...] = wmed_tile(x, s, ss_ref, m)


def weight_operands(s: jnp.ndarray) -> tuple:
    """The (m,) weights as the kernels take them: an (m, 1) VMEM column and
    the same weights whole in SMEM (see ``wmed_tile``)."""
    sw = s.astype(jnp.float32)
    return sw[:, None], sw


def wcwmed_padded(xp: jnp.ndarray, s: jnp.ndarray, bd: int, *,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Median over a pre-padded (m, dp) matrix -> (dp,) float32. See pad.py."""
    m, dp = xp.shape
    return pl.pallas_call(
        functools.partial(_kernel, m=m),
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((m, bd), lambda j: (0, j)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bd,), lambda j: (j,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        name="wcwmed",
        interpret=interpret_mode(interpret),
    )(xp, *weight_operands(s))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def wcwmed_pallas(x: jnp.ndarray, s: jnp.ndarray, *, block_d: int = DEFAULT_BLOCK_D,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (m, d), s: (m,) -> (d,) float32."""
    xp, d, bd = pad_cols(x, block_d)
    return wcwmed_padded(xp, s, bd, interpret=interpret)[:d]
