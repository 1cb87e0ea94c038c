"""Language-model assembly: embeddings → (scanned) layer groups → logits.

Layers are organized as  [prefix (unrolled)] + [n_full groups (lax.scan)] +
[remainder (unrolled)]  where one group = the architecture's repeating
pattern (e.g. gemma3's 5 local + 1 global, recurrentgemma's rec,rec,attn).
Scanning groups keeps compile time flat in depth; `cfg.remat` wraps each
group in jax.checkpoint (activation recomputation).

Three execution modes per layer kind:
  forward        — full-sequence training/eval
  prefill        — forward + emit decode cache
  decode         — single token with cache

Modality frontends (audio frames / vision patches) are stubs per the
assignment carve-out: batches carry precomputed embeddings of width d_model.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import kernels_on

from .config import ModelConfig
from .griffin import init_lru_cache, init_rglru, rglru_block, rglru_decode
from .layers import attention, attention_decode, attention_decode_paged, init_attention, init_mlp, make_mask, mlp, rms_norm, rope_angles, apply_rope, _qkv, _sdpa
from .moe import init_moe, moe_block
from .ssm import init_ssm, init_ssm_cache, ssm_block, ssm_decode

Array = jnp.ndarray
Pytree = Any


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig) -> tuple[list[str], int, list[str]]:
    """Returns (prefix_kinds, n_full_groups, remainder_kinds)."""
    kinds = list(cfg.layer_kinds())
    g = len(cfg.pattern)
    if not cfg.scan_layers or g >= len(kinds):
        return kinds, 0, []
    n_full = len(kinds) // g
    rem = kinds[n_full * g:]
    return [], n_full, rem


def _mlp_kind(cfg: ModelConfig, kind: str) -> Optional[str]:
    if kind == "ssm":
        return None  # Mamba-2 blocks have no separate MLP
    if cfg.arch_type == "moe":
        return "moe"
    return "dense"


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype)}
    if kind == "ssm":
        p["mix"] = init_ssm(k1, cfg, dtype)
        return p
    if kind == "rec":
        p["mix"] = init_rglru(k1, cfg, dtype)
    else:
        p["mix"] = init_attention(k1, cfg, dtype)
    p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
    if _mlp_kind(cfg, kind) == "moe":
        p["mlp"] = init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_lm(key, cfg: ModelConfig) -> Pytree:
    dtype = jnp.dtype(cfg.dtype)
    prefix, n_full, rem = layer_plan(cfg)
    kE, kP, kG, kR, kU = jax.random.split(key, 5)
    params: dict = {
        "embed": (jax.random.normal(kE, (cfg.vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(kU, (cfg.d_model, cfg.vocab))
                             * cfg.d_model ** -0.5).astype(dtype)
    if prefix:
        params["prefix"] = [
            _init_layer(k, cfg, kind, dtype)
            for k, kind in zip(jax.random.split(kP, len(prefix)), prefix)]
    if n_full:
        def one_group(k):
            return [
                _init_layer(kk, cfg, kind, dtype)
                for kk, kind in zip(jax.random.split(k, len(cfg.pattern)), cfg.pattern)]
        params["groups"] = jax.vmap(one_group)(jax.random.split(kG, n_full))
    if rem:
        params["rem"] = [
            _init_layer(k, cfg, kind, dtype)
            for k, kind in zip(jax.random.split(kR, len(rem)), rem)]
    return params


# ---------------------------------------------------------------------------
# Single-layer forward (three modes)
# ---------------------------------------------------------------------------

def _layer_fwd(lp: dict, cfg: ModelConfig, kind: str, x: Array) -> tuple[Array, Array]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        return x + ssm_block(lp["mix"], cfg, h), jnp.zeros((), jnp.float32)
    if kind == "rec":
        x = x + rglru_block(lp["mix"], cfg, h)
    else:
        x = x + attention(lp["mix"], cfg, h, kind)
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if _mlp_kind(cfg, kind) == "moe":
        y, aux = moe_block(lp["mlp"], cfg, h2)
    else:
        y = mlp(lp["mlp"], h2)
    return x + y, aux


def _attn_prefill(lp: dict, cfg: ModelConfig, kind: str, x: Array, cache_len: int,
                  lens: Optional[Array] = None
                  ) -> tuple[Array, tuple[Array, Array]]:
    """Attention forward that also emits the (ring-layout) KV cache.

    With ``lens`` ((B,) int32 true lengths, right-padded batch) the cache
    write is an exact per-request scatter: only positions < lens[b] (and,
    for local layers, within the trailing window) are written; padded
    positions are dropped, so the emitted cache rows are bit-identical to an
    unpadded prefill (causality keeps the forward itself exact)."""
    B, S, _ = x.shape
    q, k, v = _qkv(lp, cfg, x)
    pos = jnp.arange(S)
    cos, sin = rope_angles(pos, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    mask = make_mask(cfg, S, kind)
    out = _sdpa(cfg, q, k, v, mask)
    out = jnp.einsum("bsh,hd->bsd", out, lp["wo"])
    W = cache_len
    kc = jnp.zeros((B, W, cfg.n_kv, cfg.hd), k.dtype)
    vc = jnp.zeros((B, W, cfg.n_kv, cfg.hd), v.dtype)
    if lens is not None:
        pos_idx = pos[None, :]                               # (1, S)
        if kind == "local":
            tgt = pos_idx % W
            valid = (pos_idx < lens[:, None]) & (pos_idx >= lens[:, None] - W)
        else:
            tgt = jnp.minimum(pos_idx, W - 1)
            valid = pos_idx < lens[:, None]
        tgt = jnp.broadcast_to(jnp.where(valid, tgt, W), (B, S))  # W → dropped
        rows = jnp.arange(B)[:, None]
        kc = kc.at[rows, tgt].set(k, mode="drop")
        vc = vc.at[rows, tgt].set(v, mode="drop")
    elif kind == "local":
        take = min(W, S)
        src_pos = jnp.arange(S - take, S)
        kc = kc.at[:, src_pos % W].set(k[:, -take:])
        vc = vc.at[:, src_pos % W].set(v[:, -take:])
    else:
        take = min(W, S)
        kc = kc.at[:, :take].set(k[:, :take])
        vc = vc.at[:, :take].set(v[:, :take])
    return out, (kc, vc)


def _layer_prefill(lp, cfg, kind, x, cache_len, lens=None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        out, cache = _ssm_prefill(lp["mix"], cfg, h, lens)
        return x + out, cache
    if kind == "rec":
        out, cache = _rec_prefill(lp["mix"], cfg, h, lens)
        x = x + out
    else:
        W = cfg.window if kind == "local" else cache_len
        out, cache = _attn_prefill(lp["mix"], cfg, kind, h, W, lens)
        x = x + out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if _mlp_kind(cfg, kind) == "moe":
        y, _ = moe_block(lp["mlp"], cfg, h2)
    else:
        y = mlp(lp["mlp"], h2)
    return x + y, cache


def _conv_window(conv_in: Array, lens: Array, Kw: int) -> Array:
    """Per-request trailing conv window: rows [lens-Kw+1, lens) of ``conv_in``,
    zero-filled where the window reaches before position 0 (matching the
    zero-initialised decode conv cache)."""
    B, S, _ = conv_in.shape
    offs = lens[:, None] - (Kw - 1) + jnp.arange(Kw - 1)[None, :]   # (B, Kw-1)
    g = conv_in[jnp.arange(B)[:, None], jnp.clip(offs, 0, S - 1)]
    return jnp.where((offs >= 0)[..., None], g, 0).astype(conv_in.dtype)


def _ssm_prefill(p, cfg, x, lens=None):
    """Run ssm_block while capturing the final recurrent + conv state.

    With ``lens`` the padded positions get dt = 0 — decay exp(0·A) = 1 and
    update x·dt = 0 — so the emitted state is exactly the state after the
    request's true last token; the conv cache is gathered per request."""
    from .ssm import SSMCache, _conv1d  # local import to reuse internals
    B_, S, _ = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    P = di // H
    zxbcdt = jnp.einsum("bsd,do->bso", x, p["in_proj"])
    z, xc, Bc, Cc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xc, Bc, Cc], axis=-1)
    Kw = cfg.conv_width
    if lens is None:
        conv_cache = jnp.zeros((B_, Kw - 1, di + 2 * N), x.dtype)
        take = min(Kw - 1, S)
        conv_cache = conv_cache.at[:, Kw - 1 - take:].set(conv_in[:, S - take:])
    else:
        conv_cache = _conv_window(conv_in, lens, Kw)
    conv_out = jax.nn.silu(_conv1d(conv_in, p["conv_w"], p["conv_b"]))
    xc, Bc, Cc = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    if lens is not None:
        pmask = jnp.arange(S)[None, :, None] < lens[:, None, None]
        dt = jnp.where(pmask, dt, 0.0)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xc.reshape(B_, S, H, P)
    y, final_state = ssm_chunked_pad(xh.astype(jnp.float32), dt, A,
                                     Bc.astype(jnp.float32), Cc.astype(jnp.float32),
                                     cfg.ssm_chunk)
    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B_, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), -1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps)
         * (1.0 + p["norm"].astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    return out, SSMCache(conv=conv_cache, state=final_state)


def ssm_chunked_pad(x, dt, A, Bm, Cm, chunk, init_state=None):
    """ssd_chunked that right-pads the sequence to a chunk multiple.

    The pad positions carry dt = 0 (decay exp(0·A) = 1, update x·dt = 0), so
    the returned final state is the state after the last REAL position;
    ``init_state`` ((B, H, P, N) f32) seeds the recurrence for chunked
    prefill continuation (None -> zeros)."""
    from .ssm import ssd_chunked
    s = x.shape[1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, state = ssd_chunked(x, dt, A, Bm, Cm, chunk, init_state=init_state)
    return y[:, :s], state


def _rec_prefill(p, cfg, x, lens=None):
    from .griffin import LRUCache, _conv1d, _rglru_coeffs
    B_, S, _ = x.shape
    w = cfg.lru_width or cfg.d_model
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_in_gate"]))
    u0 = jnp.einsum("bsd,dw->bsw", x, p["w_in_branch"])
    Kw = cfg.conv_width
    if lens is None:
        conv_cache = jnp.zeros((B_, Kw - 1, w), x.dtype)
        take = min(Kw - 1, S)
        conv_cache = conv_cache.at[:, Kw - 1 - take:].set(u0[:, S - take:])
    else:
        conv_cache = _conv_window(u0, lens, Kw)
    u = _conv1d(u0, p["conv_w"], p["conv_b"])
    a, b = _rglru_coeffs(p, u)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    out = jnp.einsum("bsw,wd->bsd", h.astype(x.dtype) * gate, p["w_out"])
    # per-request final state: the scan is causal, so h[b, lens[b]-1] is
    # untouched by the right padding
    h_last = h[:, -1] if lens is None else h[jnp.arange(B_), lens - 1]
    return out, LRUCache(conv=conv_cache, h=h_last)


def _layer_decode(lp, cfg, kind, x, cache, pos, page_table=None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        out, cache = ssm_decode(lp["mix"], cfg, h, cache)
        return x + out, cache
    if kind == "rec":
        out, cache = rglru_decode(lp["mix"], cfg, h, cache)
        x = x + out
    else:
        kc, vc = cache
        if page_table is not None and kind != "local":
            # paged serve path: kc/vc are page pools, not per-slot rows
            out, kc, vc = attention_decode_paged(lp["mix"], cfg, h, kc, vc,
                                                 page_table, pos)
        else:
            out, kc, vc = attention_decode(lp["mix"], cfg, h, kind, kc, vc, pos)
        cache = (kc, vc)
        x = x + out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if _mlp_kind(cfg, kind) == "moe":
        y, _ = moe_block(lp["mlp"], cfg, h2)
    else:
        y = mlp(lp["mlp"], h2)
    return x + y, cache


# ---------------------------------------------------------------------------
# Chunked serve forward (unified ragged step — prefill chunks + decode rows)
# ---------------------------------------------------------------------------

class ChunkCtx(NamedTuple):
    """Per-row geometry of one ragged chunk batch (see :func:`chunk_step`)."""
    slots: Array      # (Rn,) int32 target slot per row; n_slots = dump (dropped)
    sl: Array         # (Rn,) int32 clamped slot index (safe for gathers)
    fresh: Array      # (Rn,) bool — row starts at absolute position 0
    pos0: Array       # (Rn,) int32 absolute position of the row's first token
    positions: Array  # (Rn, C) int32 absolute position per token
    valid: Array      # (Rn, C) bool — token t real iff t < lens[row]
    lens: Array       # (Rn,) int32 true token count per row


def _attn_chunk(lp: dict, cfg: ModelConfig, kind: str, x: Array, kvc,
                ctx: ChunkCtx, page_table: Optional[Array]):
    """Attention over one ragged chunk batch with per-slot cache carry.

    Every row attends its own causal prefix: the chunk's keys plus whatever
    the slot's cache already holds. Cache writes are drop-scatters keyed by
    ``ctx.slots`` (the dump row n_slots vanishes), so padding rows and
    padding tokens never touch live slots; gathers go through the clamped
    ``ctx.sl`` and are garbage-but-finite for dump rows."""
    Rn, C, _ = x.shape
    q, k, v = _qkv(lp, cfg, x)
    cos, sin = rope_angles(ctx.positions, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    rows = jnp.arange(Rn)[:, None]
    if page_table is not None and kind != "local":
        k_pool, v_pool = kvc
        P = k_pool.shape[-3]
        pps = page_table.shape[1]
        dump = k_pool.shape[0] - 1
        trow = page_table[ctx.slots]                       # (Rn, pps)
        logical = jnp.minimum(ctx.positions // P, pps - 1)
        phys = jnp.where(ctx.valid, trow[rows, logical], dump)
        off = ctx.positions % P
        k_pool = k_pool.at[phys, off].set(k.astype(k_pool.dtype))
        v_pool = v_pool.at[phys, off].set(v.astype(v_pool.dtype))
        if kernels_on(cfg.use_pallas_decode):
            from repro.kernels.swa import ragged_paged_decode_pallas
            cu = C * jnp.arange(Rn + 1, dtype=jnp.int32)
            out = ragged_paged_decode_pallas(
                q.reshape(Rn * C, cfg.n_heads, cfg.hd), k_pool, v_pool,
                trow, cu, ctx.lens, ctx.pos0 + ctx.lens,
                interpret=cfg.pallas_interpret)
            out = out.reshape(Rn, C, -1).astype(x.dtype)
        else:
            kg = k_pool[trow].reshape(Rn, pps * P, cfg.n_kv, cfg.hd)
            vg = v_pool[trow].reshape(Rn, pps * P, cfg.n_kv, cfg.hd)
            mask = (jnp.arange(pps * P)[None, None, :]
                    <= ctx.positions[:, :, None])
            out = _sdpa(cfg, q, kg, vg, mask[:, None])
        return jnp.einsum("bsh,hd->bsd", out, lp["wo"]), (k_pool, v_pool)
    kc, vc = kvc
    W = kc.shape[1]
    if kind == "local":
        # gather the previous window from the OLD ring (pre-scatter: the
        # chunk's own keys ride in dense, so nothing here may alias them)
        qprev = ctx.pos0[:, None] - W + jnp.arange(W)[None, :]   # (Rn, W)
        kprev = kc[ctx.sl[:, None], qprev % W]
        vprev = vc[ctx.sl[:, None], qprev % W]
        keys = jnp.concatenate([kprev.astype(k.dtype), k], axis=1)
        vals = jnp.concatenate([vprev.astype(v.dtype), v], axis=1)
        kpos = jnp.concatenate([qprev, ctx.positions], axis=1)   # (Rn, W+C)
        kval = jnp.concatenate([qprev >= 0, ctx.valid], axis=1)
        p_ = ctx.positions[:, :, None]
        mask = (kval[:, None, :] & (kpos[:, None, :] <= p_)
                & (kpos[:, None, :] > p_ - W))
        out = _sdpa(cfg, q, keys, vals, mask[:, None])
        # write back ONLY the last min(W, len) valid tokens: their ring
        # targets are distinct, and every older ring entry they do not
        # overwrite still holds the right absolute position
        keep = ctx.valid & (jnp.arange(C)[None, :] >= ctx.lens[:, None] - W)
        tgt = jnp.where(keep, ctx.positions % W, W)
        kc = kc.at[ctx.slots[:, None], tgt].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[ctx.slots[:, None], tgt].set(v.astype(vc.dtype), mode="drop")
    else:
        tgt = jnp.where(ctx.valid, jnp.minimum(ctx.positions, W - 1), W)
        kc = kc.at[ctx.slots[:, None], tgt].set(k.astype(kc.dtype), mode="drop")
        vc = vc.at[ctx.slots[:, None], tgt].set(v.astype(vc.dtype), mode="drop")
        kg = kc[ctx.sl]                                          # (Rn, W, KV, hd)
        vg = vc[ctx.sl]
        mask = jnp.arange(W)[None, None, :] <= ctx.positions[:, :, None]
        out = _sdpa(cfg, q, kg, vg, mask[:, None])
    return jnp.einsum("bsh,hd->bsd", out, lp["wo"]), (kc, vc)


def _ssm_chunk(p, cfg: ModelConfig, x: Array, cache, ctx: ChunkCtx):
    """ssm_block over one chunk with conv + recurrent state carry.

    The conv history is the previous chunk's trailing ``conv_width - 1``
    inputs (zeros when fresh — matching the decode conv cache init); padded
    tokens get dt = 0, so the emitted state is exactly the state after the
    row's last real token."""
    from .ssm import SSMCache
    Rn, C, _ = x.shape
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    P = di // H
    zxbcdt = jnp.einsum("bsd,do->bso", x, p["in_proj"])
    z, xc, Bc, Cc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + N, 2 * di + 2 * N], axis=-1)
    conv_in = jnp.concatenate([xc, Bc, Cc], axis=-1)
    Kw = cfg.conv_width
    conv_prev = jnp.where(ctx.fresh[:, None, None], 0,
                          cache.conv[ctx.sl]).astype(conv_in.dtype)
    combined = jnp.concatenate([conv_prev, conv_in], axis=1)  # (Rn, Kw-1+C, ·)
    conv_out = sum(combined[:, i:i + C] * p["conv_w"][i] for i in range(Kw))
    conv_out = jax.nn.silu(conv_out + p["conv_b"])
    rows = jnp.arange(Rn)[:, None]
    # trailing window ending at the row's LAST REAL token (combined index
    # lens + j is that token's conv input at history offset j - (Kw-1))
    new_conv = combined[rows, ctx.lens[:, None] + jnp.arange(Kw - 1)[None, :]]
    xc, Bc, Cc = jnp.split(conv_out, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    dt = jnp.where(ctx.valid[..., None], dt, 0.0)
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    xh = xc.reshape(Rn, C, H, P)
    init = jnp.where(ctx.fresh[:, None, None, None], 0.0, cache.state[ctx.sl])
    y, final_state = ssm_chunked_pad(xh.astype(jnp.float32), dt, A,
                                     Bc.astype(jnp.float32),
                                     Cc.astype(jnp.float32),
                                     cfg.ssm_chunk, init_state=init)
    y = y + xh.astype(jnp.float32) * p["d_skip"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(Rn, C, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), -1, keepdims=True)
    y = (y.astype(jnp.float32) * jax.lax.rsqrt(var + cfg.norm_eps)
         * (1.0 + p["norm"].astype(jnp.float32))).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    new_cache = SSMCache(
        conv=cache.conv.at[ctx.slots].set(new_conv.astype(cache.conv.dtype),
                                          mode="drop"),
        state=cache.state.at[ctx.slots].set(final_state, mode="drop"))
    return out, new_cache


def _rec_chunk(p, cfg: ModelConfig, x: Array, cache, ctx: ChunkCtx):
    """rglru_block over one chunk with conv + hidden-state carry.

    The associative scan keeps BOTH outputs — the running decay product
    ``a_cum`` and the zero-init hidden ``h0`` — so the carried state enters
    as ``h = h0 + a_cum · h_init`` (affine-map composition), exactly the
    decode recurrence iterated over the chunk."""
    from .griffin import LRUCache, _rglru_coeffs
    Rn, C, _ = x.shape
    gate = jax.nn.gelu(jnp.einsum("bsd,dw->bsw", x, p["w_in_gate"]))
    u0 = jnp.einsum("bsd,dw->bsw", x, p["w_in_branch"])
    Kw = cfg.conv_width
    conv_prev = jnp.where(ctx.fresh[:, None, None], 0,
                          cache.conv[ctx.sl]).astype(u0.dtype)
    combined = jnp.concatenate([conv_prev, u0], axis=1)
    u = sum(combined[:, i:i + C] * p["conv_w"][i] for i in range(Kw))
    u = u + p["conv_b"]
    rows = jnp.arange(Rn)[:, None]
    new_conv = combined[rows, ctx.lens[:, None] + jnp.arange(Kw - 1)[None, :]]
    a, b = _rglru_coeffs(p, u)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    a_cum, h0 = jax.lax.associative_scan(combine, (a, b), axis=1)
    h_init = jnp.where(ctx.fresh[:, None], 0.0, cache.h[ctx.sl])
    h = h0 + a_cum * h_init[:, None, :]
    out = jnp.einsum("bsw,wd->bsd", h.astype(x.dtype) * gate, p["w_out"])
    h_last = h[jnp.arange(Rn), jnp.maximum(ctx.lens - 1, 0)]
    new_cache = LRUCache(
        conv=cache.conv.at[ctx.slots].set(new_conv.astype(cache.conv.dtype),
                                          mode="drop"),
        h=cache.h.at[ctx.slots].set(h_last, mode="drop"))
    return out, new_cache


def _layer_chunk(lp, cfg, kind, x, cache, ctx, page_table=None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if kind == "ssm":
        out, cache = _ssm_chunk(lp["mix"], cfg, h, cache, ctx)
        return x + out, cache
    if kind == "rec":
        out, cache = _rec_chunk(lp["mix"], cfg, h, cache, ctx)
        x = x + out
    else:
        out, cache = _attn_chunk(lp["mix"], cfg, kind, h, cache, ctx,
                                 page_table)
        x = x + out
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if _mlp_kind(cfg, kind) == "moe":
        y, _ = moe_block(lp["mlp"], cfg, h2)
    else:
        y = mlp(lp["mlp"], h2)
    return x + y, cache


def chunk_step(params: Pytree, cfg: ModelConfig, cache: dict, tokens: Array,
               row_slots: Array, row_lens: Array, row_fresh: Array,
               page_table: Optional[Array] = None) -> tuple[Array, dict]:
    """One unified ragged step over a mixed chunk batch (the serve hot path).

    ``tokens`` (Rn, C) int32 packs prefill CHUNKS and decode rows (C-column
    rows with ``row_lens = 1``) into one call against the slot cache:
    row r appends its ``row_lens[r]`` real tokens to slot ``row_slots[r]``
    (``n_slots`` = dump — the row computes garbage and writes nothing),
    starting at position 0 when ``row_fresh[r]`` else at the slot's current
    ``cache["pos"]``. All mixers carry per-slot chunk state exactly: KV
    scatter (dense rows or block-table pages), local ring window carry, SSM
    conv + recurrent init_state, RG-LRU conv + affine hidden carry. Returns
    (logits (Rn, 1, V) at each row's LAST real token, updated cache) —
    callers jit with ``donate_argnums`` on the cache. Requires a causal
    text-frontend model; padding tokens stay finite but their values are
    never read back."""
    assert cfg.causal and cfg.frontend == "none", \
        "chunked serving requires a causal token-frontend model"
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]                                   # (S,) int32
    S = pos.shape[0]
    Rn, C = tokens.shape
    row_slots = jnp.asarray(row_slots, jnp.int32)
    row_lens = jnp.asarray(row_lens, jnp.int32)
    row_fresh = jnp.asarray(row_fresh, bool)
    sl = jnp.minimum(row_slots, S - 1)
    pos0 = jnp.where(row_fresh, 0, pos[sl])
    positions = pos0[:, None] + jnp.arange(C)[None, :]
    valid = jnp.arange(C)[None, :] < row_lens[:, None]
    ctx = ChunkCtx(slots=row_slots, sl=sl, fresh=row_fresh, pos0=pos0,
                   positions=positions, valid=valid, lens=row_lens)
    x = params["embed"][tokens] * jnp.asarray(cfg.d_model ** 0.5, dtype)
    prefix, n_full, rem = layer_plan(cfg)
    new_cache: dict = {"pos": pos.at[row_slots].set(pos0 + row_lens,
                                                    mode="drop")}

    if prefix:
        cps = []
        for lp, kind, cp in zip(params["prefix"], prefix, cache["prefix"]):
            x, cp = _layer_chunk(lp, cfg, kind, x, cp, ctx, page_table)
            cps.append(cp)
        new_cache["prefix"] = cps

    if n_full:
        def group_body(x, gp_cache):
            gp, gc = gp_cache
            cs = []
            for lp, kind, cp in zip(gp, cfg.pattern, gc):
                x, cp = _layer_chunk(lp, cfg, kind, x, cp, ctx, page_table)
                cs.append(cp)
            return x, tuple(cs)
        x, gcache = jax.lax.scan(group_body, x,
                                 (params["groups"], tuple(cache["groups"])))
        new_cache["groups"] = list(gcache)

    if rem:
        crs = []
        for lp, kind, cp in zip(params["rem"], rem, cache["rem"]):
            x, cp = _layer_chunk(lp, cfg, kind, x, cp, ctx, page_table)
            crs.append(cp)
        new_cache["rem"] = crs

    x = x[jnp.arange(Rn), jnp.maximum(row_lens - 1, 0)][:, None]  # (Rn, 1, d)
    return logits_from_hidden(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params: Pytree, cfg: ModelConfig, batch: dict) -> Array:
    dtype = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio":
        return batch["frames"].astype(dtype)
    tok = params["embed"][batch["tokens"]] * jnp.asarray(cfg.d_model ** 0.5, dtype)
    if cfg.frontend == "vision":
        return jnp.concatenate([batch["patches"].astype(dtype), tok], axis=1)
    return tok


def logits_from_hidden(params: Pytree, cfg: ModelConfig, x: Array) -> Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return jnp.einsum("bsd,dv->bsv", x, params["unembed"])


# ---------------------------------------------------------------------------
# Full forward / loss
# ---------------------------------------------------------------------------

def forward(params: Pytree, cfg: ModelConfig, batch: dict) -> tuple[Array, Array]:
    """Returns (logits, aux_loss)."""
    x = embed_inputs(params, cfg, batch)
    prefix, n_full, rem = layer_plan(cfg)
    aux_total = jnp.zeros((), jnp.float32)

    layer_fwd = (jax.checkpoint(_layer_fwd, static_argnums=(1, 2))
                 if cfg.remat else _layer_fwd)

    for lp, kind in zip(params.get("prefix", []), prefix):
        x, aux = layer_fwd(lp, cfg, kind, x)
        aux_total = aux_total + aux

    if n_full:
        def group_body(x, gp):
            a = jnp.zeros((), jnp.float32)
            for lp, kind in zip(gp, cfg.pattern):
                x, ax = _layer_fwd(lp, cfg, kind, x)
                a = a + ax
            return x, a
        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        x, auxs = jax.lax.scan(group_body, x, params["groups"])
        aux_total = aux_total + jnp.sum(auxs)

    for lp, kind in zip(params.get("rem", []), rem):
        x, aux = layer_fwd(lp, cfg, kind, x)
        aux_total = aux_total + aux

    if cfg.frontend == "vision":
        x = x[:, -batch["tokens"].shape[1]:]  # logits over text positions only
    return logits_from_hidden(params, cfg, x), aux_total


def lm_loss(params: Pytree, cfg: ModelConfig, batch: dict) -> Array:
    """Next-token (or frame-label) cross entropy, mean over valid positions."""
    logits, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    valid = (labels >= 0).astype(jnp.float32)
    lab = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0) + aux


# ---------------------------------------------------------------------------
# Decode cache
# ---------------------------------------------------------------------------

def _kind_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype):
    if kind == "ssm":
        return init_ssm_cache(cfg, batch, dtype)
    if kind == "rec":
        return init_lru_cache(cfg, batch, dtype)
    W = cfg.window if kind == "local" else max_len
    kc = jnp.zeros((batch, W, cfg.n_kv, cfg.hd), dtype)
    return (kc, kc)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    prefix, n_full, rem = layer_plan(cfg)
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    if prefix:
        cache["prefix"] = [_kind_cache(cfg, k, batch, max_len, dtype) for k in prefix]
    if n_full:
        one = [_kind_cache(cfg, k, batch, max_len, dtype) for k in cfg.pattern]
        cache["groups"] = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (n_full,) + l.shape).copy(), one)
    if rem:
        cache["rem"] = [_kind_cache(cfg, k, batch, max_len, dtype) for k in rem]
    return cache


def prefill(params: Pytree, cfg: ModelConfig, batch: dict, max_len: int,
            lens: Optional[Array] = None) -> tuple[Array, dict]:
    """Full forward over the prompt, emitting logits and the decode cache.

    ``lens`` ((B,) int32) enables exact right-padded prefill for the serve
    path: each request's true sequence length (vision: patches + text). The
    emitted per-request cache rows — KV scatter, SSM state (dt-masked),
    RG-LRU state — match an unpadded prefill of that request exactly, and
    ``cache["pos"]`` is the per-slot (B,) position vector that
    ``decode_step`` advances independently. Logits are returned ONLY at each
    request's last real position — shape (B, 1, V), the hidden row is
    gathered BEFORE the unembed so the (B, S, V) matmul never materializes
    on the serving hot path. Requires a causal model."""
    if lens is not None:
        assert cfg.causal, "right-padded exact prefill requires a causal model"
    x = embed_inputs(params, cfg, batch)
    S = x.shape[1]
    prefix, n_full, rem = layer_plan(cfg)
    cache: dict = {}

    if prefix:
        cps = []
        for lp, kind in zip(params["prefix"], prefix):
            x, cp = _layer_prefill(lp, cfg, kind, x, max_len, lens)
            cps.append(cp)
        cache["prefix"] = cps

    if n_full:
        def group_body(x, gp):
            cs = []
            for lp, kind in zip(gp, cfg.pattern):
                x, cp = _layer_prefill(lp, cfg, kind, x, max_len, lens)
                cs.append(cp)
            return x, tuple(cs)
        x, gcache = jax.lax.scan(group_body, x, params["groups"])
        cache["groups"] = list(gcache)

    if rem:
        crs = []
        for lp, kind in zip(params["rem"], rem):
            x, cp = _layer_prefill(lp, cfg, kind, x, max_len, lens)
            crs.append(cp)
        cache["rem"] = crs

    cache["pos"] = (jnp.asarray(S, jnp.int32) if lens is None
                    else lens.astype(jnp.int32))
    if cfg.frontend == "vision":
        x = x[:, -batch["tokens"].shape[1]:]
    if lens is not None:
        idx = lens - 1
        if cfg.frontend == "vision":
            idx = idx - cfg.n_patches        # x is text-relative here
        x = x[jnp.arange(x.shape[0]), idx][:, None]   # (B, 1, d)
    return logits_from_hidden(params, cfg, x), cache


def decode_step(params: Pytree, cfg: ModelConfig, cache: dict, tokens: Array,
                page_table: Optional[Array] = None) -> tuple[Array, dict]:
    """One decode step. tokens: (B, 1) int32. Returns (logits (B,1,V), cache).

    ``cache["pos"]`` may be a scalar (one shared depth — the classic batched
    path) or a (B,) vector (slot-mapped serving: every row decodes at its own
    absolute position; see repro.serve). With ``page_table`` ((≥B,
    pages_per_slot) int32) the cache is the PAGED serve layout: global/full
    attention leaves are block-table page pools (serve/cache.py
    ``init_paged_cache``) and each slot's KV is gathered through its table
    row; local ring, SSM and RG-LRU leaves stay per-slot."""
    dtype = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    x = params["embed"][tokens] * jnp.asarray(cfg.d_model ** 0.5, dtype)
    prefix, n_full, rem = layer_plan(cfg)
    new_cache: dict = {"pos": pos + 1}

    if prefix:
        cps = []
        for lp, kind, cp in zip(params["prefix"], prefix, cache["prefix"]):
            x, cp = _layer_decode(lp, cfg, kind, x, cp, pos, page_table)
            cps.append(cp)
        new_cache["prefix"] = cps

    if n_full:
        def group_body(x, gp_cache):
            gp, gc = gp_cache
            cs = []
            for lp, kind, cp in zip(gp, cfg.pattern, gc):
                x, cp = _layer_decode(lp, cfg, kind, x, cp, pos, page_table)
                cs.append(cp)
            return x, tuple(cs)
        x, gcache = jax.lax.scan(group_body, x, (params["groups"], tuple(cache["groups"])))
        new_cache["groups"] = list(gcache)

    if rem:
        crs = []
        for lp, kind, cp in zip(params["rem"], rem, cache["rem"]):
            x, cp = _layer_decode(lp, cfg, kind, x, cp, pos, page_table)
            crs.append(cp)
        new_cache["rem"] = crs

    return logits_from_hidden(params, cfg, x), new_cache


# ---------------------------------------------------------------------------
# Analytic parameter count (for config validation tests)
# ---------------------------------------------------------------------------

def param_count(cfg: ModelConfig) -> int:
    d, hd = cfg.d_model, cfg.hd
    n = cfg.vocab * d  # embed
    if not cfg.tie_embeddings:
        n += d * cfg.vocab
    n += d  # final norm
    for kind in cfg.layer_kinds():
        n += d  # ln1
        if kind == "ssm":
            di, N, H = cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
            n += d * (2 * di + 2 * N + H)           # in_proj
            n += cfg.conv_width * (di + 2 * N) + (di + 2 * N)
            n += 3 * H + di + di * d                # a_log, dt_bias, d_skip, norm, out
            continue
        if kind == "rec":
            w = cfg.lru_width or d
            n += 2 * d * w + cfg.conv_width * w + w
            n += 2 * (w * w + w) + w + w * d
        else:
            n += d * cfg.n_heads * hd + 2 * d * cfg.n_kv * hd + cfg.n_heads * hd * d
            if cfg.qkv_bias:
                n += cfg.n_heads * hd + 2 * cfg.n_kv * hd
            if cfg.qk_norm:
                n += 2 * hd
        n += d  # ln2
        if _mlp_kind(cfg, kind) == "moe":
            n += d * cfg.n_experts
            n += cfg.n_experts * (2 * d * cfg.d_expert + cfg.d_expert * d)
            if cfg.n_shared:
                n += 3 * d * cfg.n_shared * cfg.d_expert
        else:
            n += 3 * d * cfg.d_ff
    return n
