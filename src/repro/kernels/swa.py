"""Pallas TPU kernels: flash attention for single-token decode over a
(sliding-window) KV cache — dense per-slot and paged (block-table) variants.

Dense (``swa_decode_pallas``): grid = (batch·kv_head, cache_blocks). The KV
cache streams through VMEM one (bw, hd) block per grid step while the
online-softmax state (running max, denominator, accumulator) lives in VMEM
scratch that persists across the sequential TPU grid — the working set is
O(G·hd + bw·hd) regardless of cache length. ``pos`` may be a scalar (classic
batched decode) or a (B,) vector (slot-mapped serving: every row decodes at
its own absolute depth). This is the long_500k decode hot loop for
gemma-style local layers and recurrentgemma attention blocks.

Paged (``paged_decode_pallas``): grid = (slot·kv_head, pages-of-that-slot).
The KV lives in a fixed page pool ``(n_pages + 1, page_size, KV, hd)`` and a
per-slot block table maps logical pages to physical ones; the table and the
per-slot ``pos`` ride in as scalar-prefetch arguments so the BlockSpec index
map can gather each slot's next physical page for DMA (vLLM-style paged
attention). The online-softmax scratch is carried across the sequential page
axis exactly as in the dense kernel. Unallocated logical pages point at the
pool's last (dump) page; their positions exceed ``pos`` and are masked out.

Both paged kernels read the pool through the free reshape
``(n_pages + 1, P, KV·hd)``: a ``(1, P, hd)`` block at lane-block ``h`` is
exactly KV head ``h`` of one page, so each DMA moves one head's page and the
block's last two dims are ``(P, hd)`` — what Mosaic tiles (hd a multiple of
128, P a multiple of 8, or 16 for bf16 pools). A ``(1, P, 1, hd)`` block of
the 4-D pool would put the size-1 KV block on the sublane axis, which Mosaic
refuses.

Ring-buffer semantics (dense, ``local=True``): slot validity is derived from
the absolute position ``pos`` exactly as in the reference
(`repro.models.layers.attention_decode`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import interpret_mode

DEFAULT_BLOCK_W = 256


def _nt(a, b):
    """a @ b.T without materializing the transpose (the MXU's NT form)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_step(step, q, k, v, valid, o_ref, m_ref, l_ref, acc_ref):
    """One online-softmax block step, shared by the dense and paged kernels.

    ``step`` is the sequential block index (init at 0, emit at the last —
    the TPU grid revisits the same scratch across it); ``valid`` (1, bk)
    masks this block's key columns. q: (G, hd) f32; k/v: (bk, hd) f32."""
    nsteps = pl.num_programs(1)
    scale = q.shape[-1] ** -0.5

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    scores = _nt(q, k) * scale                        # (G, bk)
    scores = jnp.where(valid, scores, -1e30)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(step == nsteps - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _flash_scratch(G: int, hd: int) -> list:
    return [pltpu.VMEM((G, 1), jnp.float32),    # running max
            pltpu.VMEM((G, 1), jnp.float32),    # running denominator
            pltpu.VMEM((G, hd), jnp.float32)]   # output accumulator


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, W: int, bw: int, KV: int, local: bool):
    g = pl.program_id(0)
    c = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (G, hd)
    pos = pos_ref[g // KV]
    k = k_ref[0].astype(jnp.float32)                  # (bw, hd)
    v = v_ref[0].astype(jnp.float32)
    idx = c * bw + jax.lax.broadcasted_iota(jnp.int32, (1, bw), 1)
    if local:
        valid = (idx <= pos % W) | (pos >= W)         # ring buffer occupancy
    else:
        valid = idx <= pos                            # causal prefix
    _flash_step(c, q, k, v, valid, o_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("local", "block_w", "interpret"))
def swa_decode_pallas(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                      pos: jnp.ndarray, *, local: bool, block_w: int = DEFAULT_BLOCK_W,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, H, hd); k/v_cache: (B, W, KV, hd); pos: () or (B,) int32
    -> (B, H, hd).

    A scalar ``pos`` is the classic shared-depth batched decode; a (B,)
    vector is the slot-mapped serving form — each batch row attends at its
    own absolute position (``pos`` is a scalar-prefetch operand; grid row g
    reads ``pos[g // KV]``). Keys/values are assumed already rotary-embedded
    (cache layout identical to the reference decode path)."""
    B, H, hd = q.shape
    _, W, KV, _ = k_cache.shape
    G = H // KV
    bw = min(block_w, W)
    assert W % bw == 0, "cache length must divide the block"
    qg = q.reshape(B * KV, G, hd)
    kg = jnp.moveaxis(k_cache, 2, 1).reshape(B * KV, W, hd)
    vg = jnp.moveaxis(v_cache, 2, 1).reshape(B * KV, W, hd)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * KV, W // bw),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda g, c, p: (g, 0, 0)),
            pl.BlockSpec((1, bw, hd), lambda g, c, p: (g, c, 0)),
            pl.BlockSpec((1, bw, hd), lambda g, c, p: (g, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda g, c, p: (g, 0, 0)),
        scratch_shapes=_flash_scratch(G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, W=W, bw=bw, KV=KV, local=local),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), jnp.float32),
        name="swa_decode",
        interpret=interpret_mode(interpret),
    )(pos_arr, qg, kg, vg)
    return out.reshape(B, H, hd)


def _head_pages(pool: jnp.ndarray) -> jnp.ndarray:
    """(N, P, KV, hd) pool -> its (N, P, KV·hd) view (see module doc)."""
    n, P, KV, hd = pool.shape
    return pool.reshape(n, P, KV * hd)


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, P: int, KV: int):
    g = pl.program_id(0)
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                  # (G, hd)
    pos = pos_ref[g // KV]
    k = k_ref[0].astype(jnp.float32)                  # (P, hd)
    v = v_ref[0].astype(jnp.float32)
    idx = j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    valid = idx <= pos                                # causal prefix
    _flash_step(j, q, k, v, valid, o_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                        v_pool: jnp.ndarray, page_table: jnp.ndarray,
                        pos: jnp.ndarray, *, interpret: Optional[bool] = None
                        ) -> jnp.ndarray:
    """Per-slot paged flash decode for global (causal-prefix) layers.

    q: (S, H, hd); k/v_pool: (n_pages + 1, P, KV, hd) — physical page pools
    whose LAST page is the dump page; page_table: (≥S, pages_per_slot) int32
    mapping each slot's logical pages to physical ones (unallocated entries
    point at the dump page); pos: (S,) int32 per-slot absolute position.
    Returns (S, H, hd) float32.

    The table and pos are scalar-prefetch operands: the k/v BlockSpec index
    maps read ``page_table[slot, j]`` to choose which physical page block to
    stream next, so the kernel touches exactly the pages the block table
    names. Positions past ``pos`` (including every row of an unallocated /
    dump page) are masked in the online softmax."""
    S, H, hd = q.shape
    _, P, KV, _ = k_pool.shape
    G = H // KV
    pps = page_table.shape[1]
    qg = q.reshape(S * KV, G, hd)
    tbl = jnp.asarray(page_table, jnp.int32)
    pos_arr = jnp.asarray(pos, jnp.int32)

    def page_map(g, j, tbl_ref, pos_ref):
        return (tbl_ref[g // KV, j], 0, g % KV)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S * KV, pps),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda g, j, t, p: (g, 0, 0)),
            pl.BlockSpec((1, P, hd), page_map),
            pl.BlockSpec((1, P, hd), page_map),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda g, j, t, p: (g, 0, 0)),
        scratch_shapes=_flash_scratch(G, hd),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, P=P, KV=KV),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S * KV, G, hd), jnp.float32),
        name="paged_decode",
        interpret=interpret_mode(interpret),
    )(tbl, pos_arr, qg, _head_pages(k_pool), _head_pages(v_pool))
    return out.reshape(S, H, hd)


def _ragged_kernel(cu_ref, ql_ref, kvl_ref, tbl_ref, tok_ref, q_ref, k_ref,
                   v_ref, o_ref, m_ref, l_ref, acc_ref, *, P: int):
    """Online softmax over a ragged mixed batch for one KV head, one
    (row, page) per step.

    The q block holds every packed token's G query heads of this KV head as
    ``(G·Tp, hd)`` rows; ``tok_ref`` names each row's token. A step streams
    one page of row ``s``, so only that row's tokens are valid and whole q
    rows are routinely all-masked: ``p`` is masked explicitly (with the
    unmasked ``exp(scores - m_new)`` idiom those rows would contribute
    ``exp(-1e30 - (-1e30)) = 1`` per key). Pages at or past the row's
    ``kv_len`` and rows with no tokens hold no valid key and are skipped."""
    s = pl.program_id(1)
    j = pl.program_id(2)
    hd = q_ref.shape[-1]
    scale = hd ** -0.5

    @pl.when((s == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start, qlen, kvlen = cu_ref[s], ql_ref[s], kvl_ref[s]

    @pl.when((qlen > 0) & (j * P < kvlen))
    def _step():
        q = q_ref[0].astype(jnp.float32)               # (G·Tp, hd)
        k = k_ref[0].astype(jnp.float32)               # (P, hd)
        v = v_ref[0].astype(jnp.float32)
        t = tok_ref[...]                               # (G·Tp, 1)
        in_seq = (t >= start) & (t < start + qlen)
        abs_pos = kvlen - qlen + (t - start)
        key_idx = j * P + jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
        valid = in_seq & (key_idx <= abs_pos)          # (G·Tp, P)

        scores = jnp.where(valid, _nt(q, k) * scale, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((s == pl.num_programs(1) - 1) & (j == pl.num_programs(2) - 1))
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ragged_paged_decode_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, page_table: jnp.ndarray,
                               cu_q_lens: jnp.ndarray, q_lens: jnp.ndarray,
                               kv_lens: jnp.ndarray, *,
                               interpret: Optional[bool] = None) -> jnp.ndarray:
    """Ragged paged flash attention over a mixed prefill-chunk/decode batch.

    q: (T, H, hd) packed query tokens — row ``s`` of the batch owns tokens
    ``[cu_q_lens[s], cu_q_lens[s] + q_lens[s])`` (decode rows are q_len=1
    chunks, prefill chunks longer runs; the gap up to ``cu_q_lens[s+1]`` is
    padding and returns zeros). k/v_pool: (n_pages + 1, P, KV, hd) page
    pools (last page = dump); page_table: (Rn, pps) int32; kv_lens: (Rn,)
    per-row context length AFTER the chunk, so token ``i`` of row ``s``
    attends the causal prefix of ``kv_lens[s] - q_lens[s] + i``.

    grid = (KV heads, rows, pages). q is laid out head-major as
    ``(KV, G·Tp, hd)`` (Tp = T rounded up to 8 sublanes): the block of KV
    head ``h`` stays VMEM-resident across all of its (row, page) steps while
    the row's next physical page of head ``h`` streams in through the
    scalar-prefetched block table; the online-softmax scratch over that
    block is carried across the (row, page) sweep. Every operation is a 2-D
    (rows, lanes) tile op, which is what Mosaic lowers. Semantics match
    :func:`repro.kernels.ref.ragged_paged_decode_ref`."""
    T, H, hd = q.shape
    _, P, KV, _ = k_pool.shape
    G = H // KV
    Rn, pps = page_table.shape
    Tp = -(-T // 8) * 8
    R = G * Tp
    qh = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    qh = qh.reshape(Tp, KV, G, hd).transpose(1, 2, 0, 3).reshape(KV, R, hd)
    tok = jnp.tile(jnp.arange(Tp, dtype=jnp.int32), G)[:, None]    # (R, 1)

    def ragged_page_map(h, s, j, cu, ql, kvl, tbl):
        return (tbl[s, j], 0, h)

    def ragged_head_map(h, s, j, cu, ql, kvl, tbl):
        return (h, 0, 0)

    def ragged_whole_map(h, s, j, cu, ql, kvl, tbl):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(KV, Rn, pps),
        in_specs=[
            pl.BlockSpec((R, 1), ragged_whole_map),
            pl.BlockSpec((1, R, hd), ragged_head_map),
            pl.BlockSpec((1, P, hd), ragged_page_map),
            pl.BlockSpec((1, P, hd), ragged_page_map),
        ],
        out_specs=pl.BlockSpec((1, R, hd), ragged_head_map),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),       # running max
            pltpu.VMEM((R, 1), jnp.float32),       # running denominator
            pltpu.VMEM((R, hd), jnp.float32),      # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, P=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((KV, R, hd), jnp.float32),
        name="ragged_paged_attention",
        interpret=interpret_mode(interpret),
    )(jnp.asarray(cu_q_lens, jnp.int32), jnp.asarray(q_lens, jnp.int32),
      jnp.asarray(kv_lens, jnp.int32), jnp.asarray(page_table, jnp.int32),
      tok, qh, _head_pages(k_pool), _head_pages(v_pool))
    out = out.reshape(KV, G, Tp, hd).transpose(2, 0, 1, 3).reshape(Tp, H, hd)
    return out[:T]
