"""Pallas TPU kernels shared by ω-GM (Weiszfeld) and ω-CTMA:

- ``sqdist``: per-worker squared distances to an anchor, Σ_d (x_id - y_d)²,
  accumulated across d-tiles into an (m,) output (TPU grids execute
  sequentially, so revisiting the same output block is the canonical
  reduction pattern).
- ``wcomb``: weighted combination Σ_i c_i x_i / z over d-tiles — the Weiszfeld
  re-weighted average and the CTMA trimmed mean are both this matvec.
- ``gm_step``: ONE fused Weiszfeld iteration (distance pass + 1/dist
  re-weighting + weighted combine) as a single two-phase ``pallas_call`` —
  the body of the ``lax.fori_loop`` in ``ops.wgm``. Phase 0 sweeps the
  d-tiles accumulating squared distances; phase 1 re-sweeps them emitting the
  re-weighted average, reading the finished (m, 1) distance accumulator from
  VMEM. One launch and zero host round-trips per iteration, vs two launches
  plus an (m,) device→trace round-trip for the unfused pipeline.

All wrappers take a pre-padded (m, dp) matrix via the ``*_padded``
entry points (see pad.py — pad once, launch many) with thin padding wrappers
kept for standalone use.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode

from .pad import pad_cols

DEFAULT_BLOCK_D = 1024


# ---------------------------------------------------------------------------
# sqdist
# ---------------------------------------------------------------------------

def _sqdist_kernel(x_ref, y_ref, o_ref):
    j = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)     # (m, bd)
    y = y_ref[...].astype(jnp.float32)     # (1, bd)
    part = jnp.sum(jnp.square(x - y), axis=1, keepdims=True)  # (m, 1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


def sqdist_padded(xp: jnp.ndarray, yp: jnp.ndarray, bd: int, *,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """xp: (m, dp) pre-padded, yp: (dp,) -> (m,) squared distances."""
    m, dp = xp.shape
    out = pl.pallas_call(
        _sqdist_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((m, bd), lambda j: (0, j)),
            pl.BlockSpec((1, bd), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        name="sqdist",
        interpret=interpret_mode(interpret),
    )(xp, yp.astype(jnp.float32)[None, :])
    return out[:, 0]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def sqdist_pallas(x: jnp.ndarray, y: jnp.ndarray, *, block_d: int = DEFAULT_BLOCK_D,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (m, d), y: (d,) -> (m,) squared distances (float32)."""
    xp, d, bd = pad_cols(x, block_d)
    yp, _, _ = pad_cols(y, bd)
    return sqdist_padded(xp, yp, bd, interpret=interpret)


# ---------------------------------------------------------------------------
# wcomb
# ---------------------------------------------------------------------------

def _wcomb_kernel(x_ref, c_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)     # (m, bd)
    c = c_ref[...].astype(jnp.float32)     # (m, 1)
    o_ref[...] = jnp.sum(c * x, axis=0)


def wcomb_padded(xp: jnp.ndarray, coef: jnp.ndarray, denom, bd: int, *,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Σ_i coef_i xp_i / denom over a pre-padded (m, dp) matrix -> (dp,)."""
    m, dp = xp.shape
    out = pl.pallas_call(
        _wcomb_kernel,
        grid=(dp // bd,),
        in_specs=[
            pl.BlockSpec((m, bd), lambda j: (0, j)),
            pl.BlockSpec((m, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bd,), lambda j: (j,)),
        out_shape=jax.ShapeDtypeStruct((dp,), jnp.float32),
        name="wcomb",
        interpret=interpret_mode(interpret),
    )(xp, coef.astype(jnp.float32)[:, None])
    return out / denom


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def wcomb_pallas(x: jnp.ndarray, coef: jnp.ndarray, denom, *,
                 block_d: int = DEFAULT_BLOCK_D, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Σ_i coef_i x_i / denom. x: (m, d), coef: (m,) -> (d,)."""
    xp, d, bd = pad_cols(x, block_d)
    return wcomb_padded(xp, coef, denom, bd, interpret=interpret)[:d]


# ---------------------------------------------------------------------------
# fused Weiszfeld step (dist + reweight + combine in one launch)
# ---------------------------------------------------------------------------

def _gm_step_kernel(x_ref, s_ref, y_ref, o_ref, dist_ref, *, eps: float):
    phase = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)     # (m, bd)

    @pl.when(phase == 0)
    def _accumulate():
        y = y_ref[...].astype(jnp.float32)  # (1, bd)
        part = jnp.sum(jnp.square(x - y), axis=1, keepdims=True)

        @pl.when(j == 0)
        def _init():
            dist_ref[...] = jnp.zeros_like(dist_ref)

        dist_ref[...] += part

    @pl.when(phase == 1)
    def _combine():
        s = s_ref[...].astype(jnp.float32)  # (m, 1)
        dist = jnp.sqrt(jnp.maximum(dist_ref[...], 0.0))
        invd = s / jnp.maximum(dist, eps)   # (m, 1)
        o_ref[...] = jnp.sum(invd * x, axis=0) / jnp.sum(invd)


def gm_step_padded(xp: jnp.ndarray, s: jnp.ndarray, y: jnp.ndarray, bd: int, *,
                   eps: float = 1e-8, interpret: Optional[bool] = None) -> jnp.ndarray:
    """One Weiszfeld iteration y -> Σ_i (s_i/‖x_i-y‖) x_i / Σ_i (s_i/‖x_i-y‖).

    xp: (m, dp) pre-padded, y: (dp,) -> (dp,). Shape-stable, so it is the
    body of ``lax.fori_loop`` in ops.wgm (traced ONCE regardless of iters).
    """
    m, dp = xp.shape
    y_new, _ = pl.pallas_call(
        functools.partial(_gm_step_kernel, eps=eps),
        grid=(2, dp // bd),
        in_specs=[
            pl.BlockSpec((m, bd), lambda p, j: (0, j)),
            pl.BlockSpec((m, 1), lambda p, j: (0, 0)),
            pl.BlockSpec((1, bd), lambda p, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bd,), lambda p, j: (j,)),
            pl.BlockSpec((m, 1), lambda p, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dp,), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        name="gm_step",
        interpret=interpret_mode(interpret),
    )(xp, s.astype(jnp.float32)[:, None], y.astype(jnp.float32)[None, :])
    return y_new
