"""Weighted robust aggregation rules (paper Section 3).

All aggregators operate on a stacked matrix ``X`` of shape ``(m, d)`` — one row
per worker — and a weight vector ``s`` of shape ``(m,)`` (``None`` means equal
weights, recovering the classical unweighted rules). Every function returns a
``(d,)`` vector and is jit/vmap friendly (static shapes, no data-dependent
python control flow).

Implemented rules
-----------------
- ``weighted_mean``                      — baseline (non-robust).
- ``weighted_cwmed``   (ω-CWMed)         — Lemma C.3, c_λ = (1 + λ/(1-2λ))².
- ``weighted_gm``      (ω-GM / ω-RFA)    — Lemma C.1, Weiszfeld iterations.
- ``weighted_cwtm``    (ω-CWTM)          — weighted coordinate-wise trimmed mean.
- ``weighted_ctma``    (ω-CTMA, Alg. 1)  — meta-aggregator, c_λ ≤ 60λ(1+c_λ^base).
- ``krum`` / ``bucketing``               — unweighted baselines from prior work.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def tie_tol(cw: Array, half: Array) -> Array:
    """Tolerance for the exact-tie rule: a float32 cumsum of m weights carries
    up to ~m·eps relative rounding, so an exact (atol=0) comparison misses
    genuine ties once prefix sums round (e.g. integer-valued weights past
    2^24). Scale the tolerance with the prefix length and the half-mass.
    ``cw`` is (m, ...) cumulative weights; the ω-CWMed kernel
    (kernels/wcwmed.py) applies the same rule to its rank-order sums."""
    m = cw.shape[0]
    return 4.0 * m * jnp.finfo(cw.dtype).eps * jnp.abs(half)


def _weights(s: Optional[Array], m: int, dtype=jnp.float32) -> Array:
    if s is None:
        return jnp.ones((m,), dtype)
    return s.astype(dtype)


# ---------------------------------------------------------------------------
# Weighted mean / std (also used by the omniscient attacks)
# ---------------------------------------------------------------------------

def weighted_mean(x: Array, s: Optional[Array] = None) -> Array:
    s = _weights(s, x.shape[0], x.dtype)
    return jnp.einsum("m,md->d", s, x) / jnp.sum(s)


def weighted_std(x: Array, s: Optional[Array] = None) -> Array:
    """Coordinate-wise weighted standard deviation."""
    s = _weights(s, x.shape[0], x.dtype)
    mu = weighted_mean(x, s)
    var = jnp.einsum("m,md->d", s, jnp.square(x - mu)) / jnp.sum(s)
    return jnp.sqrt(jnp.maximum(var, 0.0))


# ---------------------------------------------------------------------------
# ω-CWMed — weighted coordinate-wise median
# ---------------------------------------------------------------------------

def weighted_median_1d(v: Array, s: Array) -> Array:
    """Weighted median of a vector ``v`` (shape (m,)) with weights ``s``.

    Definition from the paper: with values sorted ascending and weights carried
    along, pick the first j with cum(s) > S/2; if a prefix hits exactly S/2,
    average elements j and j+1.
    """
    order = jnp.argsort(v)
    vs = v[order]
    ws = s[order]
    cw = jnp.cumsum(ws)
    half = 0.5 * cw[-1]
    jstar = jnp.argmax(cw > half)  # first index strictly past half
    med = vs[jstar]
    # tie handling (mostly relevant for integer weights); the tolerance is
    # relative — see tie_tol — because the f32 cumsum rounds
    tol = tie_tol(cw, half)
    tie = jnp.any(jnp.abs(cw[:-1] - half) <= tol)
    jtie = jnp.argmax(jnp.abs(cw - half) <= tol)
    tied = 0.5 * (vs[jtie] + vs[jnp.minimum(jtie + 1, v.shape[0] - 1)])
    return jnp.where(tie, tied, med)


def weighted_cwmed(x: Array, s: Optional[Array] = None) -> Array:
    """ω-CWMed: weighted median applied independently per coordinate."""
    m, _ = x.shape
    s = _weights(s, m, x.dtype)
    order = jnp.argsort(x, axis=0)                      # (m, d)
    xs = jnp.take_along_axis(x, order, axis=0)          # sorted values
    ws = s[order]                                       # weights in sorted order
    cw = jnp.cumsum(ws, axis=0)
    half = 0.5 * cw[-1]
    past = cw > half
    jstar = jnp.argmax(past, axis=0)                    # (d,)
    med = jnp.take_along_axis(xs, jstar[None], axis=0)[0]
    tol = tie_tol(cw, half)                            # (d,) relative tol
    tie = jnp.any(jnp.abs(cw[:-1] - half) <= tol, axis=0)
    jtie = jnp.argmax(jnp.abs(cw - half) <= tol, axis=0)
    vj = jnp.take_along_axis(xs, jtie[None], axis=0)[0]
    vj1 = jnp.take_along_axis(xs, jnp.minimum(jtie + 1, m - 1)[None], axis=0)[0]
    return jnp.where(tie, 0.5 * (vj + vj1), med)


# ---------------------------------------------------------------------------
# ω-GM — weighted geometric median via smoothed Weiszfeld
# ---------------------------------------------------------------------------

def weighted_gm(
    x: Array,
    s: Optional[Array] = None,
    *,
    iters: int = 32,
    eps: float = 1e-8,
) -> Array:
    """ω-GM: argmin_y Σ_i s_i ||y - x_i||, by eps-smoothed Weiszfeld iteration.

    Initialized at the weighted coordinate-wise median (robust anchor) so a
    single wild Byzantine row cannot dominate the first iterate.
    """
    m, _ = x.shape
    s = _weights(s, m, x.dtype)
    y0 = weighted_cwmed(x, s)

    def body(_, y):
        dist = jnp.sqrt(jnp.maximum(jnp.sum(jnp.square(x - y), axis=1), 0.0))
        invd = s / jnp.maximum(dist, eps)
        return jnp.einsum("m,md->d", invd, x) / jnp.sum(invd)

    return jax.lax.fori_loop(0, iters, body, y0)


# ---------------------------------------------------------------------------
# ω-CWTM — weighted coordinate-wise trimmed mean
# ---------------------------------------------------------------------------

def weighted_cwtm(x: Array, s: Optional[Array] = None, *, lam: float = 0.25) -> Array:
    """Trim λ weight-mass from each tail per coordinate, weighted-average the rest.

    Per coordinate, with sorted values and cumulative weights ``cum``, element i
    keeps the overlap of its weight interval [cum_{i-1}, cum_i] with the
    retained band [λS, (1-λ)S].
    """
    m, _ = x.shape
    s = _weights(s, m, x.dtype)
    order = jnp.argsort(x, axis=0)
    xs = jnp.take_along_axis(x, order, axis=0)
    ws = s[order]
    cum = jnp.cumsum(ws, axis=0)
    total = cum[-1]
    lo, hi = lam * total, (1.0 - lam) * total
    prev = jnp.concatenate([jnp.zeros_like(cum[:1]), cum[:-1]], axis=0)
    kept = jnp.clip(jnp.minimum(cum, hi) - jnp.maximum(prev, lo), 0.0, None)
    return jnp.sum(kept * xs, axis=0) / jnp.maximum(jnp.sum(kept, axis=0), 1e-30)


# ---------------------------------------------------------------------------
# ω-CTMA — Weighted Centered Trimmed Meta Aggregator (Algorithm 1)
# ---------------------------------------------------------------------------

def weighted_ctma(
    x: Array,
    s: Optional[Array] = None,
    *,
    lam: float,
    base: Callable[..., Array] = weighted_cwmed,
    x0: Optional[Array] = None,
) -> Array:
    """Algorithm 1. Anchors at a weighted-robust aggregate ``x0`` (computed with
    ``base`` unless given), keeps the (1-λ) weight-mass of rows closest to the
    anchor (clipping the boundary row's weight so the kept mass is exactly
    (1-λ)·Σs), and returns their weighted average.
    """
    m, _ = x.shape
    s = _weights(s, m, x.dtype)
    if x0 is None:
        x0 = base(x, s)
    dist = jnp.sqrt(jnp.maximum(jnp.sum(jnp.square(x - x0), axis=1), 0.0))
    order = jnp.argsort(dist)
    xs = x[order]
    ws = s[order]
    cum = jnp.cumsum(ws)
    thresh = (1.0 - lam) * cum[-1]
    prev = jnp.concatenate([jnp.zeros_like(cum[:1]), cum[:-1]])
    kept = jnp.clip(thresh - prev, 0.0, ws)  # per-row retained weight mass
    return jnp.einsum("m,md->d", kept, xs) / jnp.maximum(thresh, 1e-30)


# ---------------------------------------------------------------------------
# Unweighted baselines from prior work (for benchmark comparisons)
# ---------------------------------------------------------------------------

def krum(x: Array, s: Optional[Array] = None, *, n_byz: int = 1) -> Array:
    """Krum (Blanchard et al. 2017) — ignores weights (classical rule)."""
    m = x.shape[0]
    d2 = jnp.sum(jnp.square(x[:, None, :] - x[None, :, :]), axis=-1)  # (m, m)
    d2 = jnp.where(jnp.eye(m, dtype=bool), jnp.inf, d2)  # exclude self
    k = max(m - n_byz - 2, 1)
    nearest = jnp.sort(d2, axis=1)[:, :k]
    scores = jnp.sum(nearest, axis=1)
    return x[jnp.argmin(scores)]


def bucketing(
    x: Array,
    s: Optional[Array] = None,
    *,
    bucket: int = 2,
    inner: Callable[..., Array] = weighted_cwmed,
    key: Optional[jax.Array] = None,
) -> Array:
    """Bucketing meta-rule (Karimireddy et al. 2020): random buckets are
    averaged, then the inner rule aggregates bucket means. Used as the BASGDm
    style baseline in benchmarks."""
    m, d = x.shape
    s = _weights(s, m, x.dtype)
    perm = jnp.arange(m) if key is None else jax.random.permutation(key, m)
    pad = (-m) % bucket
    xp = jnp.concatenate([x[perm], jnp.zeros((pad, d), x.dtype)], axis=0)
    sp = jnp.concatenate([s[perm], jnp.zeros((pad,), s.dtype)], axis=0)
    nb = xp.shape[0] // bucket
    xb = xp.reshape(nb, bucket, d)
    sb = sp.reshape(nb, bucket)
    bw = jnp.sum(sb, axis=1)
    bx = jnp.einsum("nb,nbd->nd", sb, xb) / jnp.maximum(bw, 1e-30)[:, None]
    return inner(bx, bw)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def c_lambda(name: str, lam: float) -> float:
    """Theoretical robustness coefficients from Table 1."""
    base = (1.0 + lam / max(1.0 - 2.0 * lam, 1e-9)) ** 2
    if name in ("gm", "cwmed"):
        return base
    if name.startswith("ctma"):
        return 60.0 * lam * (1.0 + base)
    raise KeyError(name)


def make_aggregator(spec: str, lam: float = 0.0, **kw) -> Callable[[Array, Optional[Array]], Array]:
    """Deprecated: use ``repro.agg.resolve(spec, lam=...)`` — the resolved
    callable keeps the pure-jnp semantics on flat ``(m, d)`` inputs (backend
    ``jnp``) and additionally accepts stacked pytrees."""
    warnings.warn("make_aggregator is deprecated; use "
                  "repro.agg.resolve(spec, lam=...)",
                  DeprecationWarning, stacklevel=2)
    from repro.agg import resolve
    return resolve(spec, lam=lam, backend="jnp", **kw)


AGGREGATOR_SPECS = ("mean", "cwmed", "gm", "cwtm", "krum", "ctma:cwmed", "ctma:gm", "bucketing:cwmed")
