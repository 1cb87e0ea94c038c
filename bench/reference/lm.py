"""Plain reference of the dense decoder the configuration files describe:
Qwen2-style layers (RMSNorm, RoPE, grouped-query attention with q/k/v
biases, SwiGLU MLP), written from the published description in straight
``jax.numpy`` at float32 with every matrix product at HIGHEST precision.
It imports nothing of the program.

Two conventions follow the configuration file rather than the published
model, and PERF.md lists them: norm scales are stored as offsets from 1, and
``input_embedding_scale`` multiplies the token embeddings.

``mm`` is the matrix product every layer uses. The control computes the same
reference with ``mm_fp8``: operands rounded to float8 (e4m3) with a scale per
tensor, the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def mm_f32(spec: str, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


@jax.custom_jvp
def _fp8(x):
    """x rounded to float8 e4m3 with one scale per tensor. Derivatives pass
    straight through, so the backward pass multiplies full-precision
    cotangents by the rounded forward operands."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@_fp8.defjvp
def _fp8_jvp(primals, tangents):
    return _fp8(primals[0]), tangents[0].astype(F32)


def mm_fp8(spec: str, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def embed_scale(c: dict) -> float:
    rule = c.get("input_embedding_scale", "none")
    if rule == "sqrt_hidden_size":
        return math.sqrt(c["hidden_size"])
    if rule == "none":
        return 1.0
    raise ValueError(f"unknown input_embedding_scale {rule!r}")


def rms_norm(x, g, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + g.astype(F32))


def rope(x, pos, theta):
    """Rotate the two halves of each head (the published Qwen2 layout).
    x: (S, H, hd), pos: (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(c: dict, lw: dict, x, mm=mm_f32):
    """One decoder layer over a whole causal sequence x: (S, d) float32."""
    S = x.shape[0]
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // H
    eps = float(c["rms_norm_eps"])
    pos = jnp.arange(S)
    h = rms_norm(x, lw["ln1"], eps)
    q, k, v = (mm("sd,dh->sh", h, lw[n]) for n in ("wq", "wk", "wv"))
    if c["attention_bias"]:
        q, k, v = q + lw["bq"].astype(F32), k + lw["bk"].astype(F32), v + lw["bv"].astype(F32)
    q = rope(q.reshape(S, H, hd), pos, float(c["rope_theta"]))
    k = rope(k.reshape(S, KV, hd), pos, float(c["rope_theta"]))
    v = v.reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)          # head h reads kv head h // (H/KV)
    v = jnp.repeat(v, H // KV, axis=1)
    s = mm("qhd,khd->hqk", q, k) / math.sqrt(hd)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("hqk,khd->qhd", p, v).reshape(S, H * hd)
    x = x + mm("sh,hd->sd", o, lw["wo"])
    h = rms_norm(x, lw["ln2"], eps)
    a = mm("sd,df->sf", h, lw["wg"])
    b = mm("sd,df->sf", h, lw["wu"])
    return x + mm("sf,fd->sd", jax.nn.silu(a) * b, lw["wd"])


def hidden(c: dict, w: dict, tokens, mm=mm_f32):
    """Final-norm hidden states (S, d) of one sequence of token ids."""
    x = w["embed"][tokens].astype(F32) * embed_scale(c)

    def body(x, lw):
        return layer(c, lw, x, mm), None

    x, _ = jax.lax.scan(body, x, w["layers"])
    return rms_norm(x, w["final_norm"], float(c["rms_norm_eps"]))


def logits(c: dict, w: dict, h, mm=mm_f32):
    """Output logits of hidden states h: (..., d)."""
    if c["tie_word_embeddings"]:
        return mm("...d,vd->...v", h, w["embed"])
    return mm("...d,dv->...v", h, w["unembed"])


def loss_sum(c: dict, w: dict, tokens, labels, mm=mm_f32):
    """Summed next-token cross entropy over rows of tokens and labels
    (R, S); the caller divides by the number of labels."""
    def one(t, y):
        lg = logits(c, w, hidden(c, w, t, mm), mm)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])

    return jnp.sum(jax.vmap(one)(tokens, labels))
