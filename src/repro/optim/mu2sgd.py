"""μ²-SGD (Levy 2023) and baseline optimizers with a unified pytree API.

μ²-SGD maintains three sequences:
  - ``w_t``  : projected-SGD iterates,
  - ``x_t``  : AnyTime weighted average of the iterates (the *query* point),
  - ``d_t``  : corrected-momentum gradient estimate at ``x_t``.

Server update (paper Alg. 2 line 7, α_t = t):
    w_{t+1} = Π_K( w_t - η α_t d̂_t ),     x_{t+1} = x_t + α_{t+1}/α_{1:t+1} (w_{t+1} - x_t)

Corrected momentum (worker side, β_t = 1/s_t):
    d_t = g(x_t; z_t) + (1 - β_t) (d_{t-1} - g(x_{t-1}; z_t))

Both the theory schedule (α_t = t, β_t = 1/s_t) and the paper's practical
constant-coefficient variant (γ = α_t/α_{1:t} fixed, β fixed — Appendix D) are
supported. The API is deliberately split so a *train step* owns the gradient
evaluations (μ² needs the gradient at two points with the SAME sample):

    x_t, x_prev = opt_query_points(state)
    g       = grad(loss)(x_t, batch)
    g_tilde = grad(loss)(x_prev, batch)     # only used by mu2
    state   = opt_update(cfg, state, g, g_tilde)
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

Pytree = Any


class OptConfig(NamedTuple):
    name: str = "mu2"          # mu2 | momentum | sgd
    lr: float = 0.01
    beta: Optional[float] = None   # mu2: constant β (None -> 1/t); momentum: Polyak β
    gamma: Optional[float] = None  # mu2: constant AnyTime γ (None -> α_t = t schedule)
    proj_radius: Optional[float] = None  # L2 ball around init (paper's compact K)
    weight_decay: float = 0.0
    # Memory optimization (beyond-paper, see EXPERIMENTS.md §Perf): the AnyTime
    # recursion x_t = (1-γ_t) x_{t-1} + γ_t w_t is exactly invertible, so the
    # previous query point need not be stored — recompute x_{t-1} from (x_t, w_t).
    implicit_x_prev: bool = False


class OptState(NamedTuple):
    w: Pytree                  # iterate
    x: Pytree                  # query point (mu2: AnyTime average; else == w)
    x_prev: Pytree             # previous query point (mu2 correction)
    d: Pytree                  # corrected momentum / momentum buffer
    t: jnp.ndarray             # int32 step counter (0-based before first update)
    anchor: Pytree             # init point for projection


def _tmap(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def init_opt(cfg: OptConfig, params: Pytree) -> OptState:
    zeros = _tmap(jnp.zeros_like, params)
    copy = _tmap(lambda x: x.copy(), params)
    x_prev = None if (cfg.implicit_x_prev or cfg.name != "mu2") else _tmap(lambda x: x.copy(), params)
    anchor = _tmap(lambda x: x.copy(), params) if cfg.proj_radius is not None else None
    return OptState(w=params, x=copy, x_prev=x_prev, d=zeros,
                    t=jnp.zeros((), jnp.int32), anchor=anchor)


def opt_query_points(cfg: OptConfig, state: OptState) -> tuple[Pytree, Pytree]:
    """Points at which the train step must evaluate gradients (x_t, x_{t-1}).

    With ``implicit_x_prev``, inverts the AnyTime recursion instead of reading
    a stored copy: x_{t-1} = (x_t - γ_t w_t) / (1 - γ_t).
    """
    if cfg.name != "mu2":
        return state.w, state.w
    if not cfg.implicit_x_prev:
        return state.x, state.x_prev
    gc = anytime_coeff(state.t + 1, cfg.gamma)
    first = state.t == 0

    def inv(xl, wl):
        rec = (xl.astype(jnp.float32) - gc * wl.astype(jnp.float32)) / (1.0 - gc)
        return jnp.where(first, xl, rec.astype(xl.dtype))

    return state.x, _tmap(inv, state.x, state.w)


def anytime_coeff(t_next: jnp.ndarray, gamma: Optional[float]) -> jnp.ndarray:
    """γ_t = α_t / α_{1:t} for the x-average update at step t_next (1-based)."""
    if gamma is not None:
        return jnp.asarray(gamma, jnp.float32)
    tf = t_next.astype(jnp.float32)
    return 2.0 * tf / (tf * (tf + 1.0))  # α_t = t ⇒ α_{1:t} = t(t+1)/2


def _project(cfg: OptConfig, w: Pytree, anchor: Pytree) -> Pytree:
    if cfg.proj_radius is None:
        return w
    diff = _tmap(jnp.subtract, w, anchor)
    sq = sum(jnp.sum(jnp.square(l.astype(jnp.float32))) for l in jax.tree_util.tree_leaves(diff))
    norm = jnp.sqrt(jnp.maximum(sq, 1e-30))
    scale = jnp.minimum(1.0, cfg.proj_radius / norm)
    return _tmap(lambda a, dl: a + scale * dl, anchor, diff)


def corrected_momentum(cfg: OptConfig, d_prev: Pytree, g: Pytree, g_tilde: Pytree,
                       count: jnp.ndarray) -> Pytree:
    """d = g + (1-β)(d_prev - g_tilde), β = 1/count unless constant."""
    beta = (jnp.asarray(cfg.beta, jnp.float32) if cfg.beta is not None
            else 1.0 / jnp.maximum(count.astype(jnp.float32), 1.0))
    first = count <= 1  # d_1 = g_1
    return _tmap(lambda gl, dl, gtl: jnp.where(first, gl, gl + (1.0 - beta) * (dl - gtl)),
                 g, d_prev, g_tilde)


def server_step(cfg: OptConfig, state: OptState, d_hat: Pytree, lr_scale=1.0) -> OptState:
    """Apply the AnyTime server update with an (aggregated) estimate d̂_t."""
    t_next = state.t + 1
    alpha = (jnp.asarray(1.0, jnp.float32) if cfg.gamma is not None
             else t_next.astype(jnp.float32))
    step_size = cfg.lr * lr_scale * alpha
    # every update is cast back to the state's dtype: the float32 step size
    # would otherwise promote bf16 iterates to f32 after one step, which
    # defeats donation and recompiles the step on the second call
    w_new = _tmap(lambda wl, dl: (wl - step_size * dl.astype(wl.dtype)
                                  - cfg.lr * cfg.weight_decay * wl
                                  ).astype(wl.dtype), state.w, d_hat)
    w_new = _project(cfg, w_new, state.anchor)
    gcoef = anytime_coeff(t_next + 1, cfg.gamma)
    x_new = _tmap(lambda xl, wl: (xl + gcoef.astype(xl.dtype) * (wl - xl)
                                  ).astype(xl.dtype), state.x, w_new)
    x_prev = None if cfg.implicit_x_prev else state.x
    return OptState(w=w_new, x=x_new, x_prev=x_prev, d=state.d, t=t_next,
                    anchor=state.anchor)


def opt_update(cfg: OptConfig, state: OptState, g: Pytree,
               g_tilde: Optional[Pytree] = None, lr_scale=1.0) -> OptState:
    """Single-worker (synchronous, m=1) update for all supported optimizers."""
    t_next = state.t + 1
    # cfg.weight_decay applies to EVERY optimizer, with the same decoupled
    # -lr·wd·w term server_step uses (the sgd/momentum branches used to drop
    # it silently, so sweeps comparing optimizers at wd>0 were inconsistent).
    if cfg.name == "sgd":
        w = _tmap(lambda wl, gl: (wl - cfg.lr * lr_scale * gl.astype(wl.dtype)
                                  - cfg.lr * cfg.weight_decay * wl), state.w, g)
        w = _project(cfg, w, state.anchor)
        return OptState(w=w, x=w, x_prev=None, d=state.d, t=t_next, anchor=state.anchor)
    if cfg.name == "momentum":
        beta = 0.9 if cfg.beta is None else cfg.beta
        d = _tmap(lambda dl, gl: beta * dl + (1.0 - beta) * gl, state.d, g)
        w = _tmap(lambda wl, dl: (wl - cfg.lr * lr_scale * dl.astype(wl.dtype)
                                  - cfg.lr * cfg.weight_decay * wl), state.w, d)
        w = _project(cfg, w, state.anchor)
        return OptState(w=w, x=w, x_prev=None, d=d, t=t_next, anchor=state.anchor)
    if cfg.name == "mu2":
        assert g_tilde is not None, "mu2 requires the gradient at x_prev on the same batch"
        d = corrected_momentum(cfg, state.d, g, g_tilde, t_next)
        new = server_step(cfg, state._replace(d=d), d, lr_scale)
        return new._replace(d=d)
    raise KeyError(f"unknown optimizer {cfg.name}")
