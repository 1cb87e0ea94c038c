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

Two layouts:

- ``wcwmed_pallas`` (``wcwmed``): a flat (m, d) matrix, gridded over d-tiles;
  each program holds an (m, bd) tile plus the (m,) weights in VMEM and
  unrolls the m accumulation steps. The tile-local selection body lives in
  ``wmed_tile`` so the fused ω-CTMA kernel (``wctma_fused.py``) can piggyback
  its distance pass on the same VMEM tile.
- ``wcwmed_leaf`` (``wcwmed_leaf``): one (m, *shape) leaf of a stacked tree,
  read in its own layout. The grid runs over the leaf's leading dims and
  over blocks of rows (and, for very wide rows, of lanes) of its last two
  dims, so only bitcasts of the leaf, no copy or pad, precede it. In a
  block each worker is a dense (rows, lanes) slab; the selection runs over
  strips of 8 f32 rows (16 of bf16) of them, each worker's strip whole f32
  vregs, and compares each unordered pair of workers once.

``wmed_tile`` owns the selection and the tie rule; ``_median_strips`` is the
same arithmetic on separate worker strips, and the two agree bit for bit on
every input without a NaN (a NaN has no place in the (x, i) order, and one
compare a pair counts one of the two before the other where ``wmed_tile``
counts neither). A change to the rule is made in both; the leaf kernel's
tests compare them.
"""
from __future__ import annotations

import functools
import math

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

# wcwmed_leaf: at most these bytes in one grid step's blocks, the m worker
# slabs in and the f32 median out (so at most twice that, double-buffered, in
# VMEM); and the coordinates of one strip of the selection over all m workers
# (8 f32 vregs a worker at m = 4: Mosaic keeps what does not fit in vregs in
# VMEM, and a shorter strip runs slower on a v5e)
LEAF_BLOCK_BYTES = 2 ** 22
LEAF_STRIP = 2 ** 15


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


# ---------------------------------------------------------------------------
# One (m, *shape) leaf in its own layout
# ---------------------------------------------------------------------------

def _median_strips(xs: list, s: list, half, tol, m: int) -> jnp.ndarray:
    """``wmed_tile``'s selection with each worker a separate f32 array: the
    same (x_i, i) order, sums in the same order, the same picks (a column
    holding a NaN aside; see the module's docstring)."""
    zero, one = jnp.float32(0.0), jnp.float32(1.0)
    none = jnp.float32(m)
    below, rank = [None] * m, [None] * m

    def add(acc, k, term):                       # the first term starts it
        acc[k] = term if acc[k] is None else acc[k] + term

    # Pairs in lexicographic order add each below_j's terms in ascending i,
    # as wmed_tile does, and each pair's mask dies at once. i before j in
    # the (x, index) order is x_i <= x_j for i < j, and j before i its
    # negation: each unordered pair is compared once.
    for i in range(m):
        for j in range(i + 1, m):
            b = xs[i] <= xs[j]
            add(below, j, jnp.where(b, s[i], zero))
            add(rank, j, jnp.where(b, one, zero))
            add(below, i, jnp.where(b, zero, s[j]))
            add(rank, i, jnp.where(b, zero, one))
    if m == 1:
        below = rank = [jnp.zeros_like(xs[0])]
    cum = [b + sj for b, sj in zip(below, s)]

    r_med = functools.reduce(jnp.minimum, [
        jnp.where(c > half, r, none) for c, r in zip(cum, rank)])
    r_med = jnp.where(r_med == none, zero, r_med)  # all-zero weights: 0
    # the least near-tie rank; the last rank never ties, so a minimum at
    # m - 1 is no tie
    r_tie = functools.reduce(jnp.minimum, [
        jnp.where(jnp.abs(c - half) <= tol, r, none) for c, r in zip(cum, rank)])
    tie = r_tie < none - one
    r_first = jnp.where(tie, r_tie, r_med)

    def pick(r):                                 # select-sum: one term nonzero
        return functools.reduce(jnp.add, [
            jnp.where(rk == r, x, zero) for rk, x in zip(rank, xs)])

    first = pick(r_first)
    return jnp.where(tie, 0.5 * (first + pick(r_tie + one)), first)


def _leaf_kernel(x_ref, s_ref, o_ref, *, m: int, rows_are_workers: bool):
    """One block: x_ref (m, br, bc) worker slabs, or (1, m, bc) with a row
    per worker; s_ref the (m,) weights in SMEM; o_ref the (br, bc) median."""
    half = s_ref[0]
    for i in range(1, m):
        half = half + s_ref[i]
    half = 0.5 * half
    tol = tie_tol(jax.ShapeDtypeStruct((m,), jnp.float32), half)
    s = [s_ref[i] for i in range(m)]

    nr, nc = o_ref.shape
    sr = min(nr, 32 // x_ref.dtype.itemsize)    # 8 f32 or 16 bf16 rows
    cw = nc                                     # lanes: a divisor of nc
    if nc % 128 == 0:
        cw = max(128, min(nc, LEAF_STRIP // (m * sr)) // 128 * 128)
        while nc % cw:
            cw -= 128

    def load(i, r0, rows, c0):
        if rows_are_workers:                    # an (m, C) leaf: one row each
            return x_ref[0, pl.ds(i, 1), pl.ds(c0, cw)]
        return x_ref[i, pl.ds(r0, rows), pl.ds(c0, cw)]

    def strip(r0, rows, c0):
        xs = [load(i, r0, rows, c0).astype(jnp.float32) for i in range(m)]
        o_ref[pl.ds(r0, rows), pl.ds(c0, cw)] = _median_strips(
            xs, s, half, tol, m)

    n_rows, n_cols = nr // sr, nc // cw

    def body(k, carry):                         # static offsets where one
        r0 = pl.multiple_of((k // n_cols) * sr, sr) if n_rows > 1 else 0
        c0 = pl.multiple_of((k % n_cols) * cw, cw) if n_cols > 1 else 0
        strip(r0, sr, c0)
        return carry

    jax.lax.fori_loop(0, n_rows * n_cols, body, 0)
    if nr % sr:                                 # a full-dim block's last rows
        for c in range(n_cols):
            strip(n_rows * sr, nr % sr, c * cw)


def _leaf_block(m: int, rows: int, c: int, itemsize: int) -> tuple[int, int]:
    """(br, bc): each worker slab's rows and lanes in one block. Lanes are the
    leaf's last dim whole, unless 16 rows of it already overrun
    LEAF_BLOCK_BYTES; rows are the most, a power of two from 16 (a bf16 vreg's
    rows) or the leaf's rows whole, that keep within it."""
    row_bytes = m * itemsize + 4                # each worker's input, f32 out
    bc = c
    if 16 * c * row_bytes > LEAF_BLOCK_BYTES:
        bc = max(128, LEAF_BLOCK_BYTES // (16 * row_bytes) // 128 * 128)
    br = 16
    while 2 * br * bc * row_bytes <= LEAF_BLOCK_BYTES:
        br *= 2
    return min(br, rows), bc


@functools.partial(jax.jit, static_argnames=("interpret",))
def wcwmed_leaf(x: jnp.ndarray, s: jnp.ndarray, *,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (m, *shape), s: (m,) -> the (shape) float32 median, equal bit for
    bit to ``wcwmed_pallas`` on the (m, prod(shape)) view.

    The leaf is viewed as (w, L, R, C) by merging or adding leading dims, a
    bitcast that moves no data: (m, L, R, C) for a leaf of rank 3 or more,
    and (1, 1, m, C) for an (m, C) leaf, whose workers are then one row each.
    A last block that runs past R or C reads padding and its writes past the
    end are dropped: the median is coordinate-wise."""
    m, shape = x.shape[0], x.shape[1:]
    rows_are_workers = len(shape) < 2
    if rows_are_workers:                        # (bw, br) = (1, m): one row each
        x4 = x.reshape(1, 1, m, -1)
        L, R, C = 1, 1, x4.shape[-1]
        bo, bc = _leaf_block(m, 1, C, x.dtype.itemsize)
        bw, br = 1, m
    else:                                       # (bw, br) = (m, bo)
        L, R, C = math.prod(shape[:-2]), shape[-2], shape[-1]
        x4 = x.reshape(m, L, R, C)
        bo, bc = _leaf_block(m, R, C, x.dtype.itemsize)
        bw, br = m, bo
    out = pl.pallas_call(
        functools.partial(_leaf_kernel, m=m, rows_are_workers=rows_are_workers),
        grid=(L, pl.cdiv(R, bo), pl.cdiv(C, bc)),
        in_specs=[
            pl.BlockSpec((bw, None, br, bc), lambda l, r, k: (0, l, r, k)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, bo, bc), lambda l, r, k: (l, r, k)),
        out_shape=jax.ShapeDtypeStruct((L, R, C), jnp.float32),
        name="wcwmed_leaf",
        interpret=interpret_mode(interpret),
    )(x4, s.astype(jnp.float32))
    return out.reshape(shape)
