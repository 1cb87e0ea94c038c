"""Mixture-of-Experts block (Qwen-MoE / Kimi-K2 style).

Top-k routing with shared experts. Dispatch uses the sort-based
capacity-buffer formulation: token-expert assignments are sorted by expert id
and scattered into per-expert capacity buffers, so the expert matmuls are
dense batched einsums over (E, C, d) with the *active* FLOP count
(≈ tokens · top_k · capacity_factor of expert compute, not E×) — this is the
TPU-native dispatch; sharding the expert axis over `model` turns the scatter
into the expert-parallel all-to-all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import init_mlp, mlp

Array = jnp.ndarray


def init_moe(key, cfg: ModelConfig, dtype) -> dict:
    d, E, h = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * d ** -0.5).astype(dtype),
        "wg": (jax.random.normal(ks[1], (E, d, h)) * d ** -0.5).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, h)) * d ** -0.5).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, h, d)) * h ** -0.5).astype(dtype),
    }
    if cfg.n_shared > 0:
        p["shared"] = init_mlp(ks[4], d, cfg.n_shared * h, dtype)
    return p


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = int(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return max(8, min(cap, n_tokens))


def _dispatch(xt: Array, expert_idx: Array, gate: Array, E: int, C: int):
    """Sort-based capacity dispatch. xt: (T, d) -> buf (E, C, d) plus the
    (token, gate, slot) indices needed for the combine."""
    T, d = xt.shape
    K = expert_idx.shape[1]
    flat_expert = expert_idx.reshape(-1)                   # (T*K,)
    flat_token = jnp.repeat(jnp.arange(T), K)
    flat_gate = gate.reshape(-1)

    order = jnp.argsort(flat_expert)                       # stable sort by expert
    se, st, sg = flat_expert[order], flat_token[order], flat_gate[order]
    # rank of each assignment within its expert: position minus the first
    # occurrence of that expert in the sorted array (no (N, E) blow-up)
    rank = jnp.arange(T * K) - jnp.searchsorted(se, se, side="left")
    keep = rank < C
    slot = se * C + jnp.where(keep, rank, 0)

    buf = jnp.zeros((E * C, d), xt.dtype)
    vals = jnp.where(keep[:, None], xt[st], 0.0)
    buf = buf.at[slot].add(vals)                           # scatter (unique slots)
    return buf.reshape(E, C, d), st, jnp.where(keep, sg, 0.0), slot


def _combine(eo: Array, st: Array, sg: Array, slot: Array, T: int) -> Array:
    """Inverse of _dispatch: gather expert outputs back to token order."""
    E, C, d = eo.shape
    gathered = eo.reshape(E * C, d)[slot] * sg[:, None]
    return jnp.zeros((T, d), eo.dtype).at[st].add(gathered)


def moe_block(p: dict, cfg: ModelConfig, x: Array) -> tuple[Array, Array]:
    """x: (B, S, d) -> (out, aux_loss).

    Two dispatch modes:
      dense      — single global sort/scatter; correct everywhere, but under
                   SPMD the (E, C, d) capacity buffer is replicated and the
                   scatter-adds are all-reduced across the data axis
                   (~150 GB/layer at kimi scale).
      sharded    — shard_map over the data axes: each data shard sorts its own
                   tokens into a LOCAL capacity slice, so the global buffer is
                   C-sharded and the only cross-shard movement is the
                   expert-parallel all-to-all XLA inserts for the (E@model)
                   einsums. Requires a mesh (repro.dist.context); falls back
                   to dense otherwise. §Perf hillclimb 2, iteration 2.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, K)             # (T, K)
    gate = (gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)).astype(x.dtype)

    # load-balance auxiliary loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], E), axis=0)
    router_mean = jnp.mean(probs, axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(density * router_mean)

    from repro.dist.context import current_mesh
    mesh = current_mesh()
    sharded = (cfg.moe_dispatch == "sharded" and mesh is not None
               and "data" in mesh.axis_names)

    if not sharded:
        C = expert_capacity(cfg, T)
        buf, st, sg, slot = _dispatch(xt, expert_idx, gate, E, C)
        g = jnp.einsum("ecd,edh->ech", buf, p["wg"])
        u = jnp.einsum("ecd,edh->ech", buf, p["wu"])
        eo = jnp.einsum("ech,ehd->ecd", jax.nn.silu(g) * u, p["wd"])
        out = _combine(eo, st, sg, slot, T)
    else:
        from jax.sharding import PartitionSpec as P
        dpax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_dp = 1
        for a in dpax:
            n_dp *= mesh.shape[a]
        assert T % n_dp == 0, (T, n_dp)
        T_l = T // n_dp
        C_l = max(8, int(T_l * K * cfg.capacity_factor / E) + 1)

        def dispatch_local(xt_l, idx_l, gate_l):
            return _dispatch(xt_l, idx_l, gate_l, E, C_l)

        buf, st, sg, slot = jax.shard_map(
            dispatch_local, mesh=mesh,
            in_specs=(P(dpax, None), P(dpax, None), P(dpax, None)),
            out_specs=(P(None, dpax, None), P(dpax), P(dpax), P(dpax)),
        )(xt, expert_idx, gate)
        # Pin the capacity buffer to the 2-D (expert@model, capacity@data)
        # layout: the single reshard below IS the expert-parallel all-to-all
        # (~tokens·top_k·d bytes per device); without the constraint XLA
        # replicates the buffer and all-reduces it (§Perf hillclimb 2, iter 3).
        from jax.sharding import NamedSharding
        ep_ok = (cfg.moe_shard == "ep" and E % mesh.shape["model"] == 0)
        espec = "model" if ep_ok else None
        buf = jax.lax.with_sharding_constraint(
            buf, NamedSharding(mesh, P(espec, dpax, None)))
        g = jnp.einsum("ecd,edh->ech", buf, p["wg"])
        u = jnp.einsum("ecd,edh->ech", buf, p["wu"])
        eo = jnp.einsum("ech,ehd->ecd", jax.nn.silu(g) * u, p["wd"])
        eo = jax.lax.with_sharding_constraint(
            eo, NamedSharding(mesh, P(espec, dpax, None)))

        def combine_local(eo_l, st_l, sg_l, slot_l):
            return _combine(eo_l, st_l, sg_l, slot_l, T_l)

        out = jax.shard_map(
            combine_local, mesh=mesh,
            in_specs=(P(None, dpax, None), P(dpax), P(dpax), P(dpax)),
            out_specs=P(dpax, None),
        )(eo, st, sg, slot)

    if cfg.n_shared > 0:
        out = out + mlp(p["shared"], x).reshape(T, d)
    return out.reshape(B, S, d), aux
