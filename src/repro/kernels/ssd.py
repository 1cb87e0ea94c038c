"""Pallas TPU kernel: Mamba-2 SSD intra-chunk block (state-space duality).

One program per (batch, chunk). The chunk-local recurrence is evaluated in its
dual quadratic "masked attention" form — three MXU matmuls over (c × c) and
(c × n) tiles that live entirely in VMEM — and the kernel additionally emits
the chunk's outgoing state contribution. The O(nc) inter-chunk linear
recurrence (tiny) stays in XLA (`ops.ssd_scan`), mirroring
`repro.models.ssm.ssd_chunked` exactly.

Block sizing: c=chunk, h heads, p head_dim, n state. VMEM working set is
c·h·p (x, y) + h·c² (decay mask) + h·p·n (state) floats — e.g. c=64, h=8
per-program slabs keep everything under ~4 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import interpret_mode


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_ref):
    x = x_ref[0].astype(jnp.float32)        # (c, h, p)
    dt = dt_ref[0].astype(jnp.float32)      # (c, h)
    A = a_ref[...].astype(jnp.float32)      # (1, h)
    Bm = b_ref[0].astype(jnp.float32)       # (c, n)
    Cm = c_ref[0].astype(jnp.float32)       # (c, n)
    c, h, p = x.shape

    a = dt * A                              # (c, h) log-decay per step (<0)
    xb = x * dt[..., None]                  # discretized input
    a_hc = a.T                              # (h, c)
    a_cum = jnp.cumsum(a_hc, axis=-1)       # (h, c)

    # decay mask L[h, i, j] = exp(sum_{j<k<=i} a_k), lower-triangular
    seg = a_cum[:, :, None] - a_cum[:, None, :] + a_hc[:, None, :] * 0.0
    seg = a_cum[:, :, None] - a_cum[:, None, :]
    tri = jnp.tril(jnp.ones((c, c), jnp.float32))
    L = jnp.exp(jnp.where(tri[None] > 0, seg, -jnp.inf))

    scores = Cm @ Bm.T                      # (c, c)
    y = jnp.einsum("ij,hij,jhp->ihp", scores, L, xb)
    y_ref[0] = y

    decay_states = jnp.exp(a_cum[:, -1:] - a_cum)          # (h, c)
    s_ref[0] = jnp.einsum("cn,hc,chp->hpn", Bm, decay_states, xb)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_pallas(x, dt, A, Bm, Cm, *, chunk: int, interpret: Optional[bool] = None):
    """Intra-chunk SSD. x: (b, s, h, p); dt: (b, s, h); A: (h,);
    Bm/Cm: (b, s, n). s must divide by `chunk`.
    Returns (y_diag (b, s, h, p), states (b, nc, h, p, n), chunk_decay (b, nc, h))."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    c = chunk
    assert s % c == 0
    nc = s // c
    xc = x.reshape(b * nc, c, h, p)
    dtc = dt.reshape(b * nc, c, h)
    Bc = Bm.reshape(b * nc, c, n)
    Cc = Cm.reshape(b * nc, c, n)

    y, states = pl.pallas_call(
        _kernel,
        grid=(b * nc,),
        in_specs=[
            pl.BlockSpec((1, c, h, p), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, c, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, c, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, c, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, c, h, p), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, h, p, n), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * nc, c, h, p), jnp.float32),
            jax.ShapeDtypeStruct((b * nc, h, p, n), jnp.float32),
        ],
        name="ssd_intra",
        interpret=interpret_mode(interpret),
    )(xc, dtc, A[None, :], Bc, Cc)

    a = (dt * A[None, None, :]).reshape(b, nc, c, h)
    chunk_decay = jnp.exp(jnp.sum(a, axis=2))               # (b, nc, h)
    return (y.reshape(b, s, h, p), states.reshape(b, nc, h, p, n), chunk_decay)
