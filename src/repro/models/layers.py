"""Core transformer layers: RMSNorm, RoPE, GQA attention (full / sliding /
cached decode), gated MLP. Pure functions over param dicts."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import kernels_on

from .config import ModelConfig

Array = jnp.ndarray


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_angles(positions: Array, head_dim: int, theta: float) -> tuple[Array, Array]:
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, S, H, hd); cos/sin: (B, S, hd//2) or (S, hd//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype) -> dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.hd
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, H * hd)) * sc).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, KV * hd)) * sc).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, KV * hd)) * sc).astype(dtype),
        "wo": (jax.random.normal(ks[3], (H * hd, d)) * (H * hd) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((KV * hd,), dtype)
        p["bv"] = jnp.zeros((KV * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _qkv(p: dict, cfg: ModelConfig, x: Array) -> tuple[Array, Array, Array]:
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"])
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"])
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _sdpa(cfg: ModelConfig, q: Array, k: Array, v: Array, mask: Optional[Array]) -> Array:
    """Grouped-query scaled dot-product attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); mask: broadcastable to
    (B, H, Sq, Sk) with True = attend.
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    q = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * (hd ** -0.5)
    if mask is not None:
        # mask (B|1, 1, Sq, Sk) -> (B, KV, G, Sq, Sk)
        scores = jnp.where(mask[:, :, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H * hd)


def make_mask(cfg: ModelConfig, S: int, kind: str, dtype=bool) -> Optional[Array]:
    """(1, 1, S, S) attention mask. kind: attn|global (full or causal), local
    (causal sliding window)."""
    if not cfg.causal and kind in ("attn", "global"):
        return None  # bidirectional encoder
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(S)[None, :]
    m = k_pos <= q_pos
    if kind == "local" and cfg.window > 0:
        m = m & (k_pos > q_pos - cfg.window)
    return m[None, None]


def attention(p: dict, cfg: ModelConfig, x: Array, kind: str,
              positions: Optional[Array] = None) -> Array:
    B, S, _ = x.shape
    q, k, v = _qkv(p, cfg, x)
    pos = positions if positions is not None else jnp.arange(S)
    cos, sin = rope_angles(pos, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    mask = make_mask(cfg, S, kind)
    out = _sdpa(cfg, q, k, v, mask)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def attention_decode(p: dict, cfg: ModelConfig, x: Array, kind: str,
                     k_cache: Array, v_cache: Array, pos: Array
                     ) -> tuple[Array, Array, Array]:
    """Single-token decode. x: (B, 1, d). Caches: (B, W, KV, hd) where W is the
    full seq length (global layers) or the sliding window (local layers, ring
    buffer indexed by pos % W). pos: () int32 — current absolute position — or
    (B,) int32 for slot-mapped serving, where each row decodes at its own
    depth (repro.serve continuous batching).
    Returns (out, k_cache, v_cache)."""
    B = x.shape[0]
    W = k_cache.shape[1]
    q, k, v = _qkv(p, cfg, x)
    pos = jnp.asarray(pos)
    per_slot = pos.ndim > 0
    cos, sin = rope_angles(pos[:, None] if per_slot else pos[None],
                           cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    slot = (pos % W) if kind == "local" else jnp.minimum(pos, W - 1)
    if per_slot:
        rows = jnp.arange(B)
        k_cache = k_cache.at[rows, slot].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, slot].set(v[:, 0].astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    if kernels_on(cfg.use_pallas_decode):
        # flash-decode kernel: streams the cache through VMEM once; handles
        # scalar AND per-slot (B,) pos (scalar-prefetched per row). Blocks
        # of 128 rows, or the whole cache when W does not tile by 128
        from repro.kernels.swa import swa_decode_pallas
        out = swa_decode_pallas(q[:, 0], k_cache, v_cache, pos,
                                local=(kind == "local"),
                                block_w=128 if W % 128 == 0 else W,
                                interpret=cfg.pallas_interpret)
        out = out.reshape(B, 1, -1).astype(x.dtype)
    else:
        # validity: ring slots written so far (local) / prefix (global)
        idx = jnp.arange(W)
        pb = pos[:, None] if per_slot else pos  # (B,1) | ()
        if kind == "local":
            valid = (idx <= pb % W) | (pb >= W)  # all slots valid once wrapped
        else:
            valid = idx <= pb
        # (B,1,1,W) per-slot / (1,1,1,W) shared
        mask = valid[:, None, None, :] if per_slot else valid[None, None, None, :]
        out = _sdpa(cfg, q, k_cache, v_cache, mask)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_cache, v_cache


def attention_decode_paged(p: dict, cfg: ModelConfig, x: Array,
                           k_pool: Array, v_pool: Array, page_table: Array,
                           pos: Array) -> tuple[Array, Array, Array]:
    """Single-token decode against a paged (block-table) KV pool — the serve
    path for global/full-attention layers (local layers keep the dense ring:
    their cache already scales with ``window``, not ``max_len``).

    x: (S, 1, d) — one row per SLOT. k/v_pool: (n_pages + 1, page_size, KV,
    hd) physical page pools whose last page is the dump page. page_table:
    (≥S, pages_per_slot) int32 — each slot's logical→physical page map, with
    unallocated entries (and every entry of a free slot's row) pointing at
    the dump page. pos: (S,) int32 per-slot absolute position. The new KV is
    scattered into page ``pos // page_size`` row ``pos % page_size``; free
    slots land on the dump page. Returns (out, k_pool, v_pool)."""
    S = x.shape[0]
    P = k_pool.shape[1]
    pps = page_table.shape[1]
    q, k, v = _qkv(p, cfg, x)
    pos = jnp.asarray(pos)
    cos, sin = rope_angles(pos[:, None], cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # free slots can run pos past the table span; the clamp is safe because
    # their table rows are all dump — active slots never exceed their pages
    lp = jnp.minimum(pos // P, pps - 1)
    phys = page_table[jnp.arange(S), lp]
    off = pos % P
    k_pool = k_pool.at[phys, off].set(k[:, 0].astype(k_pool.dtype))
    v_pool = v_pool.at[phys, off].set(v[:, 0].astype(v_pool.dtype))
    if kernels_on(cfg.use_pallas_decode):
        from repro.kernels.swa import paged_decode_pallas
        out = paged_decode_pallas(q[:, 0], k_pool, v_pool, page_table, pos,
                                  interpret=cfg.pallas_interpret)
        out = out.reshape(S, 1, -1).astype(x.dtype)
    else:
        # jnp oracle: gather the slot's pages dense, then masked SDPA
        pages = page_table[:S]                            # (S, pps)
        kg = k_pool[pages].reshape(S, pps * P, cfg.n_kv, cfg.hd)
        vg = v_pool[pages].reshape(S, pps * P, cfg.n_kv, cfg.hd)
        valid = jnp.arange(pps * P)[None, :] <= pos[:, None]
        out = _sdpa(cfg, q, kg, vg, valid[:, None, None, :])
    return jnp.einsum("bsh,hd->bsd", out, p["wo"]), k_pool, v_pool


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "wg": (jax.random.normal(ks[0], (d_model, d_ff)) * d_model ** -0.5).astype(dtype),
        "wu": (jax.random.normal(ks[1], (d_model, d_ff)) * d_model ** -0.5).astype(dtype),
        "wd": (jax.random.normal(ks[2], (d_ff, d_model)) * d_ff ** -0.5).astype(dtype),
    }


def mlp(p: dict, x: Array) -> Array:
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    u = jnp.einsum("bsd,df->bsf", x, p["wu"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["wd"])
