"""Mesh-aware step factories: standard μ²-SGD training, robust data-parallel
training (paper Alg. 2's synchronous group form + Remark 3.1 weighting),
prefill and single-token serve.

Every factory returns a PURE function ``step(...) -> (..., metrics)`` suitable
for ``jax.jit`` — callers add shardings (launch/specs.py) and donation
(``donate_argnums=(0,)`` so the train state / KV cache updates in place). The
robust step keeps per-group corrected momenta as a STACKED pytree — leaves
carry a leading ``(n_groups, ...)`` axis — and aggregates through the unified
``repro.agg`` API, whose stacked branch (dist/robust.py) runs the CTMA/GM
distance pass once globally across leaves with no O(m·d) flatten copy; traced
under a multi-pod ``mesh_context`` that branch auto-upgrades to the
hierarchical cross-pod path (dist/hierarchy.py: pod-sharded momenta, distance
reductions as (m,)-sized psums over the pod axis — see dist/README.md for the
HBM + ICI accounting).

Byzantine group behaviors follow core.attacks (Appendix D), adapted to the
group setting: label_flip poisons a group's labels before its gradients;
sign_flip negates its transmitted momentum; little/empire are omniscient over
the honest groups' stacked buffers and their weights.

The robust step names its phases (``robust_step/grad_x``, ``grad_xprev``,
``momentum``, ``attack``, ``aggregate``, ``update``; ``repro.obs.scopes``)
so a device trace splits by phase; the names are metadata only.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.attacks import _little_zmax, flip_labels
from repro.models.config import ModelConfig
from repro.models.lm import chunk_step, decode_step, init_lm, lm_loss, prefill
from repro.obs.scopes import (AGGREGATE, ATTACK, GRAD_X, GRAD_XPREV, MOMENTUM,
                               UPDATE, phase)
from repro.optim.mu2sgd import (OptConfig, OptState, _project, init_opt,
                                opt_query_points, opt_update, server_step)
from repro.utils import global_norm

Array = jnp.ndarray
Pytree = Any

_tmap = jax.tree_util.tree_map


class RobustDPConfig(NamedTuple):
    """Robust data-parallel group configuration (server side of Alg. 2)."""
    n_groups: int = 4
    agg: str = "ctma:cwmed"          # repro.agg spec: rule[:base][@backend]
    lam: float = 0.25                # λ for the meta-aggregator
    byz_groups: Tuple[int, ...] = ()
    byz_attack: str = "none"         # none | sign_flip | label_flip | little | empire
    weight_mode: str = "counts"      # counts (s_i = update counts) | batch_size
    group_sizes: Optional[Tuple[int, ...]] = None  # relative per-group batch rows
    attack_epsilon: float = 0.1      # empire scale
    attack_z_max: Optional[float] = None  # little deviation; None -> from weights


class TrainState(NamedTuple):
    opt: OptState
    D: Optional[Pytree] = None       # stacked per-group momentum, leaves (G, ...)
    counts: Optional[Array] = None   # (G,) per-group update counts s_t


def init_train_state(cfg: ModelConfig, opt_cfg: OptConfig, key,
                     robust: Optional[RobustDPConfig] = None) -> TrainState:
    params = init_lm(key, cfg)
    opt = init_opt(opt_cfg, params)
    if robust is None:
        return TrainState(opt=opt, D=None, counts=None)
    G = robust.n_groups
    D = _tmap(lambda p: jnp.zeros((G,) + p.shape, p.dtype), params)
    counts = jnp.zeros((G,), jnp.float32)
    return TrainState(opt=opt, D=D, counts=counts)


# ---------------------------------------------------------------------------
# Standard (single-group) train step
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig):
    """step(state, batch) -> (state, {loss, grad_norm}). μ²-SGD evaluates the
    gradient at BOTH query points on the same batch (the variance-reduced
    correction); momentum/sgd evaluate once at w."""

    def loss_fn(params, batch):
        return lm_loss(params, cfg, batch)

    def step(state: TrainState, batch: dict):
        opt = state.opt
        xq, xprev = opt_query_points(opt_cfg, opt)
        loss, g = jax.value_and_grad(loss_fn)(xq, batch)
        g_tilde = jax.grad(loss_fn)(xprev, batch) if opt_cfg.name == "mu2" else None
        new_opt = opt_update(opt_cfg, opt, g, g_tilde)
        metrics = {"loss": loss, "grad_norm": global_norm(g)}
        return state._replace(opt=new_opt), metrics

    return step


# ---------------------------------------------------------------------------
# Robust data-parallel train step
# ---------------------------------------------------------------------------

def _group_sizes(rcfg: RobustDPConfig, B: int) -> list[int]:
    """Static per-group row counts summing to B (Remark 3.1 heterogeneity).

    Relative ``group_sizes`` are apportioned by largest remainder with a
    ≥1-row floor. (The previous ``sizes[-1] += B - sum(sizes)`` rescaling
    could drive the last group to zero or negative rows under skewed ratios —
    an empty slice whose loss is 0/0 = NaN.)"""
    G = rcfg.n_groups
    if rcfg.group_sizes is None:
        base, extra = divmod(B, G)
        assert base >= 1, f"batch {B} too small for {G} groups"
        return [base + (1 if i < extra else 0) for i in range(G)]
    gs = list(rcfg.group_sizes)
    assert len(gs) == G
    assert min(gs) >= 1, f"group_sizes ratios must be >= 1, got {gs}"
    assert B >= G, f"batch {B} too small for {G} groups with >=1 row each"
    total = sum(gs)
    if total == B:
        return gs
    quota = [B * g / total for g in gs]
    sizes = [max(1, int(q)) for q in quota]
    deficit = B - sum(sizes)
    if deficit > 0:       # hand out remaining rows by largest fractional part
        order = sorted(range(G), key=lambda i: quota[i] - int(quota[i]),
                       reverse=True)
        for k in range(deficit):
            sizes[order[k % G]] += 1
    elif deficit < 0:     # the >=1 floor over-allocated: shrink the groups
        order = sorted(range(G), key=lambda i: quota[i] - int(quota[i]))
        k = 0
        while deficit < 0:
            i = order[k % G]
            if sizes[i] > 1:
                sizes[i] -= 1
                deficit += 1
            k += 1
    assert sum(sizes) == B and min(sizes) >= 1, (sizes, B)
    return sizes


def _stack_trees(trees: list) -> Pytree:
    return _tmap(lambda *ls: jnp.stack(ls), *trees)


def _bcast(v: Array, leaf: Array) -> Array:
    """Reshape a (G,) vector for broadcasting against a (G, ...) leaf."""
    return v.reshape(v.shape + (1,) * (leaf.ndim - 1)).astype(jnp.float32)


def _apply_byz_attacks(rcfg: RobustDPConfig, D: Pytree, weights: Array) -> Pytree:
    """Transform the stacked transmitted momenta according to the attack."""
    name = rcfg.byz_attack
    if name in ("none", "label_flip") or not rcfg.byz_groups:
        return D
    G = rcfg.n_groups
    byz = jnp.zeros((G,), bool).at[jnp.asarray(rcfg.byz_groups)].set(True)
    if name == "sign_flip":
        sign = jnp.where(byz, -1.0, 1.0)
        return _tmap(lambda l: (l * _bcast(sign, l)).astype(l.dtype), D)

    # omniscient attacks: weighted mean/std over the HONEST groups
    hw = weights.astype(jnp.float32) * (~byz).astype(jnp.float32) + 1e-30
    hw_sum = jnp.sum(hw)

    def leaf_mean(l):
        return jnp.einsum("g,g...->...", hw, l.astype(jnp.float32)) / hw_sum

    mu = _tmap(leaf_mean, D)
    if name == "empire":
        atk = _tmap(lambda m_: -rcfg.attack_epsilon * m_, mu)
    elif name == "little":
        def leaf_std(l, m_):
            var = jnp.einsum("g,g...->...", hw,
                             jnp.square(l.astype(jnp.float32) - m_)) / hw_sum
            return jnp.sqrt(jnp.maximum(var, 0.0))

        sd = _tmap(leaf_std, D, mu)
        z = (jnp.asarray(rcfg.attack_z_max, jnp.float32)
             if rcfg.attack_z_max is not None
             else _little_zmax(jnp.sum(weights * (~byz)), jnp.sum(weights * byz)))
        atk = _tmap(lambda m_, s_: m_ - z * s_, mu, sd)
    else:
        raise KeyError(f"unknown attack: {name}")

    def splice(l, a):
        return jnp.where(_bcast(byz.astype(jnp.float32), l) > 0,
                         a[None].astype(l.dtype), l)

    return _tmap(splice, D, atk)


def make_robust_train_step(cfg: ModelConfig, opt_cfg: OptConfig,
                           rcfg: RobustDPConfig):
    """Synchronous robust-DP step: the global batch is split across
    ``n_groups`` groups; each computes its corrected momentum on its shard;
    Byzantine groups corrupt theirs; the server robust-aggregates the stacked
    buffers weighted per ``weight_mode`` and applies the AnyTime update."""
    from repro.agg import resolve

    # one resolve path with core.engine: the stacked momenta take the
    # leaf-wise global-distance-pass branch of the layout-polymorphic callable
    agg_fn = resolve(rcfg.agg, lam=rcfg.lam)
    G = rcfg.n_groups
    label_flip_on = (rcfg.byz_attack == "label_flip" and bool(rcfg.byz_groups))
    byz_list = list(rcfg.byz_groups)

    def loss_fn(params, batch):
        return lm_loss(params, cfg, batch)

    def per_group(xq, xprev, gbatch, flip):
        with phase(GRAD_X):
            if label_flip_on:
                lab = gbatch["labels"]
                lab = jnp.where(flip, flip_labels(lab, cfg.vocab), lab)
                gbatch = {**gbatch, "labels": lab}
            loss, g = jax.value_and_grad(loss_fn)(xq, gbatch)
        if opt_cfg.name != "mu2":
            return loss, g, g
        with phase(GRAD_XPREV):
            return loss, g, jax.grad(loss_fn)(xprev, gbatch)

    def step(state: TrainState, batch: dict):
        opt = state.opt
        B = jax.tree_util.tree_leaves(batch)[0].shape[0]
        sizes = _group_sizes(rcfg, B)
        flip_flags = jnp.asarray([i in byz_list for i in range(G)])
        with phase(GRAD_XPREV):         # x_{t-1}, recomputed if implicit
            xq, xprev = opt_query_points(opt_cfg, opt)

        if len(set(sizes)) == 1:
            # uniform groups: ONE traced gradient, vmapped over the group axis
            gb = _tmap(lambda v: v.reshape((G, sizes[0]) + v.shape[1:]), batch)
            losses, g, g_tilde = jax.vmap(
                lambda b, f: per_group(xq, xprev, b, f))(gb, flip_flags)
        else:
            outs = []
            off = 0
            for i, sz in enumerate(sizes):
                with phase(GRAD_X):
                    gbatch = _tmap(lambda v: jax.lax.slice_in_dim(
                        v, off, off + sz), batch)
                outs.append(per_group(xq, xprev, gbatch, flip_flags[i]))
                off += sz
            with phase(GRAD_X):
                losses = jnp.stack([o[0] for o in outs])
                g = _stack_trees([o[1] for o in outs])
            with phase(GRAD_XPREV):
                g_tilde = _stack_trees([o[2] for o in outs])

        # per-group corrected momentum (μ²) / Polyak momentum / raw gradient
        with phase(MOMENTUM):
            counts_new = state.counts + 1.0
            if opt_cfg.name == "mu2":
                beta = (jnp.full((G,), opt_cfg.beta, jnp.float32)
                        if opt_cfg.beta is not None
                        else 1.0 / jnp.maximum(counts_new, 1.0))
                first = counts_new <= 1.0

                def corr(gl, dl, gtl):
                    b = _bcast(beta, gl)
                    upd = gl.astype(jnp.float32) + (1.0 - b) * (
                        dl.astype(jnp.float32) - gtl.astype(jnp.float32))
                    return jnp.where(
                        _bcast(first.astype(jnp.float32), gl) > 0,
                        gl.astype(jnp.float32), upd).astype(dl.dtype)

                D_new = _tmap(corr, g, state.D, g_tilde)
            elif opt_cfg.name == "momentum":
                beta = 0.9 if opt_cfg.beta is None else opt_cfg.beta
                D_new = _tmap(lambda dl, gl: (
                    beta * dl.astype(jnp.float32)
                    + (1.0 - beta) * gl.astype(jnp.float32)).astype(dl.dtype),
                    state.D, g)
            else:  # sgd
                D_new = _tmap(lambda dl, gl: gl.astype(dl.dtype), state.D, g)

        size_w = jnp.asarray(sizes, jnp.float32)
        weights = counts_new if rcfg.weight_mode == "counts" else size_w

        with phase(ATTACK):
            D_new = _apply_byz_attacks(rcfg, D_new, weights)

        with phase(AGGREGATE):
            d_hat = agg_fn(D_new, weights)

        with phase(UPDATE):
            if opt_cfg.name == "mu2":
                new_opt = server_step(opt_cfg, opt, d_hat)
            else:
                # same decoupled weight decay as opt_update/server_step
                w = _tmap(lambda wl, dl: (
                    wl - opt_cfg.lr * dl.astype(wl.dtype)
                    - opt_cfg.lr * opt_cfg.weight_decay * wl), opt.w, d_hat)
                w = _project(opt_cfg, w, opt.anchor)
                new_opt = OptState(w=w, x=w, x_prev=None, d=opt.d,
                                   t=opt.t + 1, anchor=opt.anchor)

        loss = jnp.sum(losses * size_w) / jnp.sum(size_w)
        metrics = {"loss": loss, "grad_norm": global_norm(d_hat)}
        return TrainState(opt=new_opt, D=D_new, counts=counts_new), metrics

    return step


# ---------------------------------------------------------------------------
# Serve path
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ModelConfig, max_len: int):
    """step(params, batch) -> (logits, cache). Full forward over the prompt,
    emitting the ring-layout decode cache sized for ``max_len``."""

    def step(params, batch: dict):
        return prefill(params, cfg, batch, max_len)

    return step


def make_serve_step(cfg: ModelConfig):
    """step(params, cache, tokens) -> (logits (B,1,V), cache). Callers donate
    the cache (``donate_argnums=(1,)``) so the slice update is in-place."""

    def step(params, cache: dict, tokens: Array):
        return decode_step(params, cfg, cache, tokens)

    return step


def make_serve_prefill_step(cfg: ModelConfig, max_len: int):
    """step(params, batch, lens) -> (logits (B,1,V), cache). Exact
    right-padded prefill for the continuous-batching serve path: ``lens``
    ((B,) int32) carries each request's true length, the emitted cache rows
    match an unpadded prefill exactly (KV drop-scatter, dt-masked SSM state,
    gathered RG-LRU state — see models/lm.py), ``cache["pos"]`` is
    per-request, and logits cover ONLY each request's last real position."""

    def step(params, batch: dict, lens: Array):
        return prefill(params, cfg, batch, max_len, lens=lens)

    return step


def sample_tokens(logits: Array, keys: Array, temperature: float,
                  top_k: int = 0) -> Array:
    """Per-row token sampling. logits (B, V) float; keys (B, 2) uint32 raw
    PRNG keys (one per row — the serve engines derive them from the request
    uid and token index, so sampling is identical regardless of slot
    assignment or batch composition). temperature <= 0 → greedy argmax."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    x = logits.astype(jnp.float32) / temperature
    V = x.shape[-1]
    if top_k and top_k < V:
        kth = jax.lax.top_k(x, top_k)[0][..., -1:]
        x = jnp.where(x < kth, -jnp.inf, x)
    g = jax.vmap(lambda k, row: jax.random.gumbel(k, row.shape, jnp.float32))(keys, x)
    return jnp.argmax(x + g, axis=-1).astype(jnp.int32)


def sample_next(row_logits: Array, req_keys: Array, token_idx: Array,
                temperature: float, top_k: int = 0) -> Array:
    """THE sampling path for serving — first token and decode steps alike.
    row_logits (B, V); req_keys (B, 2) uint32 per-request keys; token_idx
    (B,) int32 index of the token being sampled within its request. The
    per-token key is fold_in(req_key, token_idx), which is what makes
    sampled streams independent of slot assignment, batch composition and
    arrival order. temperature <= 0 → greedy (keys/idx ignored)."""
    if temperature <= 0.0:
        return jnp.argmax(row_logits, axis=-1).astype(jnp.int32)
    keys = jax.vmap(jax.random.fold_in)(req_keys, token_idx)
    return sample_tokens(row_logits.astype(jnp.float32), keys, temperature,
                         top_k)


def make_decode_slots_step(cfg: ModelConfig, temperature: float = 0.0,
                           top_k: int = 0, paged: bool = False):
    """step(params, cache, tokens, req_keys, gen_idx[, page_table])
    -> (next_tokens, cache).

    One continuous-batching decode step over all S slots: ``cache["pos"]`` is
    the per-slot (S,) position vector, so slots at different depths decode in
    the same call. ``tokens`` (S, 1) int32 are the slots' current tokens;
    ``req_keys`` (S, 2) uint32 per-slot request PRNG keys and ``gen_idx``
    (S,) int32 per-slot generated-token indices drive sampling (ignored when
    temperature <= 0 — pass zeros). With ``paged=True`` the step takes the
    (S+1, pages_per_slot) int32 block table as a trailing argument and the
    cache is the paged layout (serve/cache.py); free slots' table rows point
    at the dump page, so their writes land in garbage. Callers donate the
    cache (``donate_argnums=(1,)``). Inactive slots decode garbage that the
    engine discards host-side; their rows never influence active slots
    (every op is row-independent; MoE capacity coupling is the documented
    exception — see serve/README.md)."""

    if paged:
        def step(params, cache: dict, tokens: Array, req_keys: Array,
                 gen_idx: Array, page_table: Array):
            logits, cache = decode_step(params, cfg, cache, tokens,
                                        page_table=page_table)
            nxt = sample_next(logits[:, 0], req_keys, gen_idx, temperature,
                              top_k)
            return nxt, cache
        return step

    def step(params, cache: dict, tokens: Array, req_keys: Array,
             gen_idx: Array):
        logits, cache = decode_step(params, cfg, cache, tokens)
        nxt = sample_next(logits[:, 0], req_keys, gen_idx, temperature, top_k)
        return nxt, cache

    return step


def make_unified_step(cfg: ModelConfig, temperature: float = 0.0,
                      top_k: int = 0, paged: bool = False):
    """step(params, cache, tokens, row_slots, row_lens, row_fresh, req_keys,
    tok_idx[, page_table]) -> (next_tokens (Rn,), cache).

    THE single jitted step of the chunked serve engine — it replaces the
    prefill → insert → decode trio: prefill chunks and decode rows share one
    ragged ``chunk_step`` call (models/lm.py), so the compile count is one
    per token-budget SHAPE CLASS — the mixed (S + chunk_rows, C) batch and
    the decode-only (S, 1) batch — independent of the workload's
    prompt-length mix. ``tok_idx`` (Rn,) int32 is each row's sampled-token
    index within its request (decode rows: gen_idx; a chunk row finishing
    its prompt: 0; non-final chunk rows: ignored — their sample is
    discarded host-side). Callers donate the cache
    (``donate_argnums=(1,)``)."""

    if paged:
        def step(params, cache: dict, tokens: Array, row_slots: Array,
                 row_lens: Array, row_fresh: Array, req_keys: Array,
                 tok_idx: Array, page_table: Array):
            logits, cache = chunk_step(params, cfg, cache, tokens, row_slots,
                                       row_lens, row_fresh,
                                       page_table=page_table)
            nxt = sample_next(logits[:, 0], req_keys, tok_idx, temperature,
                              top_k)
            return nxt, cache
        return step

    def step(params, cache: dict, tokens: Array, row_slots: Array,
             row_lens: Array, row_fresh: Array, req_keys: Array,
             tok_idx: Array):
        logits, cache = chunk_step(params, cfg, cache, tokens, row_slots,
                                   row_lens, row_fresh)
        nxt = sample_next(logits[:, 0], req_keys, tok_idx, temperature, top_k)
        return nxt, cache

    return step


# ---------------------------------------------------------------------------
# Replicated (Byzantine-tolerant) serve path
# ---------------------------------------------------------------------------

def make_replicated_prefill_step(cfg: ModelConfig, max_len: int):
    """step(params_stack, batch, lens) -> (logits (R, B, 1, V), cache_stack).

    One jitted call prefills the SAME bucketed prompt batch through all R
    replicas' parameters (stacked pytree, leaves (R, ...)), emitting the
    per-replica slot caches stacked on a leading replica axis."""

    def step(params_stack, batch: dict, lens: Array):
        return jax.vmap(
            lambda p: prefill(p, cfg, batch, max_len, lens=lens))(params_stack)

    return step


def vote_logits_fn(cfg, byz: Tuple[int, ...], n_replicas: int,
                   vote: str = "cwmed", lam: float = 0.25,
                   zeno_rho: float = 1e-3, collect_metrics: bool = False):
    """Build ``(logits (R, S, V), weights (R,), key) -> (voted (S, V),
    scores (R, S))`` — attack injection, robust vote, Zeno++-style pre-vote
    scores, shared by the replicated decode and first-token paths.
    ``collect_metrics`` (STATIC) appends a third output: the shape-static
    ``serve.vote.*`` telemetry dict (disagreement mass + vote margin per
    slot, repro.obs registry names) derived from the TRANSMITTED stack, so
    an attacked replica's dissent is visible even after the robust vote
    suppressed it.

    ``cfg`` is a :class:`repro.core.attacks.LogitAttackConfig`. The score of
    replica r on slot s is ``cos(l_rs, v_s) - rho·‖l_rs - v_s‖²/‖v_s‖²``
    against the robust anchor v (the ω-CWMed of the transmitted stack) — an
    agreeing replica scores ~1, a diverging one falls below 0; the engine
    quarantines on a host-side threshold. The anchor is the same trick as
    Zeno++'s oracle gradient: no trusted replica exists, so the robust vote
    itself is the validation oracle."""
    from repro.agg.logits import resolve_logits
    from repro.core.attacks import corrupt_logits

    vote_fn = resolve_logits(vote, lam=lam)
    anchor_fn = (vote_fn if getattr(vote_fn.spec, "canonical", vote) == "cwmed"
                 else resolve_logits("cwmed"))
    honest = jnp.asarray([i not in byz for i in range(n_replicas)])

    def run(logits: Array, weights: Array, key: Array):
        lg = corrupt_logits(cfg, logits.astype(jnp.float32), honest, weights,
                            key)
        # A zero-mass replica (dead / hanging / quarantined) must not be able
        # to touch the vote AT ALL — but a zero weight alone still lets its
        # row perturb ω-CWMed's tie-averaging (the sorted value between two
        # half-mass honest rows). Substitute unavailable rows with the
        # highest-mass replica's row, so every value in the voted stack comes
        # from a replica that actually holds mass.
        avail = weights > 0
        ref = jnp.take(lg, jnp.argmax(weights), axis=0)          # (S, V)
        lv = jnp.where(avail[:, None, None], lg, ref[None])
        v = anchor_fn(lv, weights)                               # (S, V)
        voted = v if anchor_fn is vote_fn else vote_fn(lv, weights)
        # scores come from the TRUE transmitted rows, so telemetry keeps
        # showing an excluded replica's divergence
        vnorm = jnp.sqrt(jnp.maximum(jnp.sum(jnp.square(v), -1), 1e-12))  # (S,)
        lnorm = jnp.sqrt(jnp.maximum(jnp.sum(jnp.square(lg), -1), 1e-12))
        inner = jnp.einsum("rsv,sv->rs", lg, v)
        dist2 = jnp.sum(jnp.square(lg - v[None]), -1)            # (R, S)
        scores = (inner / (lnorm * vnorm[None])
                  - zeno_rho * dist2 / jnp.square(vnorm)[None])
        if not collect_metrics:
            return voted, scores
        # vote telemetry (shape-static, derived-only): how much vote mass
        # dissented from the voted argmax, and how decisive the vote was
        mass = weights / jnp.maximum(jnp.sum(weights), 1e-30)      # (R,)
        tok = jnp.argmax(voted, axis=-1)                           # (S,)
        dissent = jnp.argmax(lv, axis=-1) != tok[None]             # (R, S)
        top2 = jax.lax.top_k(voted, 2)[0]                          # (S, 2)
        vmetrics = {
            "serve.vote.disagree_mass": jnp.sum(
                jnp.where(dissent, mass[:, None], 0.0), axis=0),   # (S,)
            "serve.vote.margin": top2[:, 0] - top2[:, 1],          # (S,)
        }
        return voted, scores, vmetrics

    return run


def make_replicated_decode_step(cfg: ModelConfig, n_replicas: int,
                                attack, byz: Tuple[int, ...] = (),
                                vote: str = "cwmed", lam: float = 0.25,
                                zeno_rho: float = 1e-3,
                                temperature: float = 0.0, top_k: int = 0,
                                paged: bool = False,
                                collect_metrics: bool = False):
    """step(params_stack, cache_stack, tokens, req_keys, gen_idx, weights,
    key[, page_table]) -> (next_tokens (S,), scores (R, S), cache_stack).

    One continuous-batching decode step for ALL R replicas × S slots: the
    per-replica decode is vmapped over the stacked params/cache (replica r's
    KV cache lives at leaf row r), Byzantine replicas corrupt their reported
    logits per ``attack`` (:class:`LogitAttackConfig`), and each slot's next
    token is sampled from the ``vote``-aggregated logits weighted by the
    runtime (R,) ``weights`` — staleness-derived masses with dead / hanging /
    quarantined replicas zeroed by the engine, so availability changes never
    recompile. ``scores`` are the Zeno++-style pre-vote scores the engine's
    quarantine policy consumes host-side. Every replica decodes the voted
    token regardless of its vote mass, which is what keeps a quarantined
    replica's KV cache coherent for re-admission.

    ``collect_metrics`` (STATIC) appends the ``serve.vote.*`` telemetry dict
    of :func:`vote_logits_fn` as a 4th output — derived values only, so the
    sampled token stream is identical either way and the default lowers to
    the uninstrumented HLO."""
    run_vote = vote_logits_fn(attack, byz, n_replicas, vote=vote, lam=lam,
                              zeno_rho=zeno_rho,
                              collect_metrics=collect_metrics)

    def body(params, cache, tokens, req_keys, gen_idx, weights, key,
             page_table=None):
        def one(p, c):
            return decode_step(p, cfg, c, tokens, page_table=page_table)

        logits, cache = jax.vmap(one)(params, cache)    # (R, S, 1, V)
        voted, scores, *vm = run_vote(logits[:, :, 0, :], weights, key)
        nxt = sample_next(voted, req_keys, gen_idx, temperature, top_k)
        if collect_metrics:
            return nxt, scores, cache, vm[0]
        return nxt, scores, cache

    if paged:
        def step(params, cache, tokens, req_keys, gen_idx, weights, key,
                 page_table):
            return body(params, cache, tokens, req_keys, gen_idx, weights,
                        key, page_table)
        return step

    def step(params, cache, tokens, req_keys, gen_idx, weights, key):
        return body(params, cache, tokens, req_keys, gen_idx, weights, key)

    return step


def make_replicated_unified_step(cfg: ModelConfig, n_replicas: int,
                                 attack, byz: Tuple[int, ...] = (),
                                 vote: str = "cwmed", lam: float = 0.25,
                                 zeno_rho: float = 1e-3,
                                 temperature: float = 0.0, top_k: int = 0,
                                 paged: bool = False,
                                 collect_metrics: bool = False):
    """step(params_stack, cache_stack, tokens, row_slots, row_lens,
    row_fresh, req_keys, tok_idx, weights, key[, page_table])
    -> (next_tokens (Rn,), scores (R, Rn), cache_stack).

    The replicated form of :func:`make_unified_step`: every replica runs the
    SAME ragged chunk batch through its own params/cache (vmapped stacked
    pytrees), Byzantine replicas corrupt their reported per-row logits, and
    each row's token is sampled from the robust vote — so chunked prefill
    AND decode inherit the f < R/2 masking guarantee in one call. Decode
    rows sit at columns 0..S-1 (row index == slot id), which is what keeps
    the engine's host-side quarantine indexing (`scores[r, active_slots]`)
    valid on mixed batches. ``collect_metrics`` (STATIC) appends the
    ``serve.vote.*`` telemetry dict exactly as in
    :func:`make_replicated_decode_step`."""
    run_vote = vote_logits_fn(attack, byz, n_replicas, vote=vote, lam=lam,
                              zeno_rho=zeno_rho,
                              collect_metrics=collect_metrics)

    def body(params, cache, tokens, row_slots, row_lens, row_fresh, req_keys,
             tok_idx, weights, key, page_table=None):
        def one(p, c):
            return chunk_step(p, cfg, c, tokens, row_slots, row_lens,
                              row_fresh, page_table=page_table)

        logits, cache = jax.vmap(one)(params, cache)    # (R, Rn, 1, V)
        voted, scores, *vm = run_vote(logits[:, :, 0, :], weights, key)
        nxt = sample_next(voted, req_keys, tok_idx, temperature, top_k)
        if collect_metrics:
            return nxt, scores, cache, vm[0]
        return nxt, scores, cache

    if paged:
        def step(params, cache, tokens, row_slots, row_lens, row_fresh,
                 req_keys, tok_idx, weights, key, page_table):
            return body(params, cache, tokens, row_slots, row_lens, row_fresh,
                        req_keys, tok_idx, weights, key, page_table)
        return step

    def step(params, cache, tokens, row_slots, row_lens, row_fresh, req_keys,
             tok_idx, weights, key):
        return body(params, cache, tokens, row_slots, row_lens, row_fresh,
                    req_keys, tok_idx, weights, key)

    return step
