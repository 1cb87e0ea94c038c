"""Public jit'd wrappers around the Pallas kernels.

``interpret=None`` (every entry point's default) lets the backend decide
(kernels/backend.py): Mosaic kernels when the default backend is a TPU, the
Pallas interpreter anywhere else. An explicit ``True``/``False`` wins.
``use_pallas=False`` falls back to the pure-jnp oracle — the path the
multi-pod dry-run lowers.

HBM-pass accounting for the (m, d) update matrix X (see wctma_fused.py):

    wcwmed          1 pass
    wcwmed_leaf     1 pass over one (m, *shape) leaf of a stacked tree, in
                    its own layout (no flattened copy)
    wgm             1 (anchor) + 2·iters (fused dist+combine step), ONE traced
                    loop body via lax.fori_loop — previously the python loop
                    unrolled 2·iters separate pallas_call launches (and a pad
                    copy each) into every trace
    wctma fused     2 passes (anchor+dist fused, then trimmed combine)
    wctma unfused   ≥3 passes (kept for benchmarking the fusion win)
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from . import ref
from .pad import pad_cols
from .wcwmed import wcwmed_leaf, wcwmed_pallas, wcwmed_padded
from .wreduce import gm_step_padded, sqdist_pallas, wcomb_padded, wcomb_pallas
from .wctma_fused import (DEFAULT_BLOCK_D as FUSED_BLOCK_D, trim_weights,
                          wctma_fused)
from .swa import (paged_decode_pallas, ragged_paged_decode_pallas,
                  swa_decode_pallas)


@partial(jax.jit, static_argnames=("interpret",))
def wmean(x: jnp.ndarray, s: Optional[jnp.ndarray] = None, *,
          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Weighted mean of (m, d) rows via the single-pass combine kernel."""
    if s is None:
        s = jnp.ones((x.shape[0],), jnp.float32)
    xp, d, bd = pad_cols(x, FUSED_BLOCK_D)
    return wcomb_padded(xp, s, jnp.sum(s.astype(jnp.float32)), bd,
                        interpret=interpret)[:d]


def wcwmed(x: jnp.ndarray, s: Optional[jnp.ndarray] = None, *,
           use_pallas: bool = True, interpret: Optional[bool] = None) -> jnp.ndarray:
    """Weighted coordinate-wise median of (m, d) rows."""
    if s is None:
        s = jnp.ones((x.shape[0],), jnp.float32)
    if not use_pallas:
        return ref.wcwmed_ref(x, s)
    return wcwmed_pallas(x, s, interpret=interpret)


@partial(jax.jit, static_argnames=("iters", "eps", "interpret"))
def _wgm_pallas(x: jnp.ndarray, s: jnp.ndarray, *, iters: int, eps: float,
                interpret: Optional[bool]) -> jnp.ndarray:
    """ω-GM: wcwmed anchor + ``iters`` fused Weiszfeld steps.

    X is padded ONCE (pad.py) and the fused dist+reweight+combine kernel is
    the body of a ``lax.fori_loop`` — trace size and launch count in the
    jaxpr are independent of ``iters``.
    """
    xp, d, bd = pad_cols(x, FUSED_BLOCK_D)
    y0 = wcwmed_padded(xp, s, bd, interpret=interpret)     # (dp,), pad cols -> 0

    def body(_, y):
        return gm_step_padded(xp, s, y, bd, eps=eps, interpret=interpret)

    y = jax.lax.fori_loop(0, iters, body, y0)
    return y[:d]


def wgm(x: jnp.ndarray, s: Optional[jnp.ndarray] = None, *, iters: int = 8,
        eps: float = 1e-8, use_pallas: bool = True, interpret: Optional[bool] = None) -> jnp.ndarray:
    """ω-GM via Weiszfeld: fused kernelized distance+reweight+combine loop."""
    if s is None:
        s = jnp.ones((x.shape[0],), jnp.float32)
    if not use_pallas:
        return ref.wgm_ref(x, s, iters=iters)
    return _wgm_pallas(x, s, iters=iters, eps=eps, interpret=interpret)


def wctma(x: jnp.ndarray, s: Optional[jnp.ndarray] = None, *, lam: float,
          use_pallas: bool = True, interpret: Optional[bool] = None,
          fused: bool = True) -> jnp.ndarray:
    """ω-CTMA (Alg. 1). ``fused=True`` (default) computes anchor + distances
    in one grid sweep (2 total HBM passes over X); ``fused=False`` keeps the
    original anchor→sqdist→combine 3-pass pipeline for benchmarking."""
    if s is None:
        s = jnp.ones((x.shape[0],), jnp.float32)
    if not use_pallas:
        return ref.wctma_ref(x, s, lam)
    if fused:
        return wctma_fused(x, s, lam=lam, interpret=interpret)
    x0 = wcwmed(x, s, use_pallas=True, interpret=interpret)
    dist = sqdist_pallas(x, x0, interpret=interpret)
    kept, thresh = trim_weights(dist, s, lam)
    return wcomb_pallas(x, kept, jnp.maximum(thresh, 1e-30), interpret=interpret)


@partial(jax.jit, static_argnames=("lam", "iters", "interpret"))
def _wctma_gm_pallas(x: jnp.ndarray, s: jnp.ndarray, *, lam: float,
                     iters: int = 32, interpret: Optional[bool]) -> jnp.ndarray:
    """ω-CTMA with a GM anchor: shares one padded copy of X across the GM
    loop, the anchor-distance pass and the trimmed combine."""
    xp, d, bd = pad_cols(x, FUSED_BLOCK_D)
    y = wcwmed_padded(xp, s, bd, interpret=interpret)

    def body(_, yy):
        return gm_step_padded(xp, s, yy, bd, interpret=interpret)

    y = jax.lax.fori_loop(0, iters, body, y)
    from .wreduce import sqdist_padded
    dist = sqdist_padded(xp, y, bd, interpret=interpret)
    kept, thresh = trim_weights(dist, s, lam)
    return wcomb_padded(xp, kept, jnp.maximum(thresh, 1e-30), bd,
                        interpret=interpret)[:d]


def wctma_gm(x: jnp.ndarray, s: Optional[jnp.ndarray] = None, *, lam: float,
             iters: int = 32, interpret: Optional[bool] = None) -> jnp.ndarray:
    """ω-CTMA anchored at the weighted geometric median (shared padded X)."""
    if s is None:
        s = jnp.ones((x.shape[0],), jnp.float32)
    return _wctma_gm_pallas(x, s, lam=lam, iters=iters, interpret=interpret)


def make_kernel_aggregator(spec: str, lam: float = 0.0, *,
                           interpret: Optional[bool] = None
                           ) -> Callable[[jnp.ndarray, Optional[jnp.ndarray]], jnp.ndarray]:
    """Deprecated: use ``repro.agg.resolve(spec, backend="pallas")`` — the
    resolved callable also accepts stacked pytrees, and rules without a fused
    pipeline degrade to the jnp oracle exactly as this factory did."""
    warnings.warn("make_kernel_aggregator is deprecated; use "
                  "repro.agg.resolve(spec, lam=..., backend='pallas')",
                  DeprecationWarning, stacklevel=2)
    from repro.agg import resolve
    return resolve(spec, lam=lam, backend="pallas", interpret=interpret)


def swa_decode(q, k_cache, v_cache, pos, *, local: bool,
               use_pallas: bool = True, interpret: Optional[bool] = None):
    """Flash single-token decode over a (ring) KV cache; ``pos`` scalar or
    (B,) per-slot."""
    if not use_pallas:
        return ref.swa_decode_ref(q, k_cache, v_cache, pos, local=local)
    return swa_decode_pallas(q, k_cache, v_cache, pos, local=local, interpret=interpret)


def paged_decode(q, k_pool, v_pool, page_table, pos, *,
                 use_pallas: bool = True, interpret: Optional[bool] = None):
    """Per-slot paged flash decode over a block-table KV page pool (global
    causal layers; see serve/cache.py for the pool/table layout)."""
    if not use_pallas:
        return ref.paged_decode_ref(q, k_pool, v_pool, page_table, pos)
    return paged_decode_pallas(q, k_pool, v_pool, page_table, pos,
                               interpret=interpret)


def ragged_paged_decode(q, k_pool, v_pool, page_table, cu_q_lens, q_lens,
                        kv_lens, *, use_pallas: bool = True,
                        interpret: Optional[bool] = None):
    """Ragged paged attention over a mixed chunked-prefill/decode batch: row
    ``s`` owns packed q tokens ``[cu_q_lens[s], cu_q_lens[s] + q_lens[s])``
    at context depth ``kv_lens[s]`` (see kernels/swa.py for the contract)."""
    if not use_pallas:
        return ref.ragged_paged_decode_ref(q, k_pool, v_pool, page_table,
                                           cu_q_lens, q_lens, kv_lens)
    return ragged_paged_decode_pallas(q, k_pool, v_pool, page_table,
                                      cu_q_lens, q_lens, kv_lens,
                                      interpret=interpret)


def ssd_scan(x, dt, A, Bm, Cm, chunk: int, *, use_pallas: bool = True,
             interpret: Optional[bool] = None):
    """Mamba-2 SSD scan: Pallas intra-chunk kernel + XLA inter-chunk
    recurrence. Semantics identical to models.ssm.ssd_chunked."""
    if not use_pallas:
        return ref.ssd_ref(x, dt, A, Bm, Cm, chunk)
    from .ssd import ssd_intra_pallas

    y_diag, states, chunk_decay = ssd_intra_pallas(x, dt, A, Bm, Cm,
                                                   chunk=chunk, interpret=interpret)
    b, s, h, p = x.shape
    nc = s // chunk
    n = Bm.shape[-1]

    s0 = jnp.zeros((b, h, p, n), jnp.float32)

    def step(carry, inp):
        st, dec = inp
        return carry * dec[..., None, None] + st, carry

    last, prev_states = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)           # (b, nc, h, p, n)

    a = (dt * A[None, None, :]).reshape(b, nc, chunk, h)
    a_cum = jnp.cumsum(jnp.moveaxis(a, -1, -2), axis=-1)    # (b, nc, h, c)
    state_decay = jnp.exp(a_cum)
    Cc = Cm.reshape(b, nc, chunk, n)
    y_off = jnp.einsum("bzcn,bzhpn,bzhc->bzchp", Cc, prev_states, state_decay)
    y = y_diag + y_off.reshape(b, s, h, p)
    return y, last
