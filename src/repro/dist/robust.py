"""Stacked-pytree robust aggregation — the distributed form of core.aggregators.

In the data-parallel train step, per-group updates arrive as a pytree whose
leaves carry a leading group axis ``(m, ...)`` — the natural layout of a
``vmap``-ed gradient or an all-gathered momentum buffer. Flattening that tree
into the (m, d) matrix the flat aggregators expect costs an extra O(m·d) HBM
copy per server step (plus the unflatten on the way out), which Remark 4.1's
bandwidth accounting cannot afford. These aggregators operate leaf-wise
in place instead and agree leaf-for-leaf with ``core.aggregators``:

- coordinate-wise rules (mean, cwmed) are exactly leaf-separable;
- the GM / CTMA distance pass is computed ONCE GLOBALLY — per-leaf partial
  squared norms are reduced into a single (m,) distance vector across all
  leaves (matching the flat ‖x_i - y‖ over the concatenated vector), and the
  resulting per-worker scalar weights are broadcast back into leaf-wise
  combines. No leaf is ever materialized twice.

ω-CTMA names its passes ``anchor``, ``distance`` and ``combine``, ω-GM its
start ``anchor`` and its iterations ``weiszfeld`` (``repro.obs.scopes``), so
a device trace splits an aggregate by pass.

HBM passes over the stacked tree X (d = total parameter count):
    stacked_mean    1     stacked_cwmed   1
    stacked_gm      1 + 2·iters (distance pass + reweighted combine per iter)
    stacked_ctma    base + 2  (global distance pass + trimmed combine)
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.aggregators import weighted_cwmed, weighted_cwtm
from repro.obs.scopes import ANCHOR, COMBINE, DISTANCE, WEISZFELD, phase

Array = jnp.ndarray
Pytree = Any

_tmap = jax.tree_util.tree_map


def _weights(s: Optional[Array], m: int) -> Array:
    if s is None:
        return jnp.ones((m,), jnp.float32)
    return s.astype(jnp.float32)


def _lead(tree: Pytree) -> int:
    """The (shared) leading group-axis size m of a stacked tree."""
    return jax.tree_util.tree_leaves(tree)[0].shape[0]


def _flat2(leaf: Array) -> Array:
    """View an (m, ...) leaf as (m, prod(...)) for coordinate-wise rules."""
    return leaf.reshape(leaf.shape[0], -1)


def stacked_sqdist(tree: Pytree, y: Pytree,
                   scale: Optional[Pytree] = None) -> Array:
    """Global squared distances ‖x_i - y‖² summed across ALL leaves -> (m,).

    This is THE single distance pass shared by stacked_gm and stacked_ctma —
    and, applied to local shards, by the hierarchical path (dist/hierarchy.py),
    whose optional per-leaf ``scale`` pytree makes its cross-pod psum count
    replicated leaves exactly once. Each leaf is read once, partial sums are
    (m,) scalars."""
    def leaf_part(x, yl, f=1.0):
        diff = _flat2(x).astype(jnp.float32) - yl.reshape(1, -1).astype(jnp.float32)
        return f * jnp.sum(jnp.square(diff), axis=1)

    mapped = (_tmap(leaf_part, tree, y) if scale is None
              else _tmap(leaf_part, tree, y, scale))
    return sum(jax.tree_util.tree_leaves(mapped))


def _combine(tree: Pytree, coef: Array, denom) -> Pytree:
    """Leaf-wise Σ_i coef_i x_i / denom with (m,) coefficients, summed over
    the group axis in each leaf's own layout. (As a matrix-vector product
    over the (m, d) view, XLA on a TPU may hold an f32 copy of the leaf as
    the product's operand: 3.5 GB for a 151936 x 1536 embedding at m = 4.)"""
    def leaf(x):
        c = coef.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.sum(c * x.astype(jnp.float32), axis=0) / denom

    return _tmap(leaf, tree)


# ---------------------------------------------------------------------------
# Aggregators
# ---------------------------------------------------------------------------

def stacked_mean(tree: Pytree, s: Optional[Array] = None) -> Pytree:
    s = _weights(s, _lead(tree))
    return _combine(tree, s, jnp.sum(s))


def _jnp_median(x: Array, s: Array) -> Array:
    return weighted_cwmed(_flat2(x).astype(jnp.float32), s).reshape(x.shape[1:])


def stacked_cwmed(tree: Pytree, s: Optional[Array] = None, *,
                  median: Callable[[Array, Array], Array] = _jnp_median
                  ) -> Pytree:
    """ω-CWMed is coordinate-wise, hence exactly leaf-separable: ``median``
    maps each (m, *shape) leaf to its (shape) median — the jnp oracle on the
    leaf's (m, d) view, or the Pallas kernel that the registry hands in on
    TPU, which reads the leaf in its own layout (``kernels/wcwmed.py``
    ``wcwmed_leaf``): a flattened view of a tiled leaf is a relayout copy."""
    s = _weights(s, _lead(tree))
    return _tmap(lambda x: median(x, s), tree)


def stacked_gm(tree: Pytree, s: Optional[Array] = None, *, iters: int = 32,
               eps: float = 1e-8) -> Pytree:
    """ω-GM via Weiszfeld with the distance pass computed once globally."""
    s = _weights(s, _lead(tree))
    with phase(ANCHOR):
        y0 = stacked_cwmed(tree, s)

    def body(_, y):
        dist = jnp.sqrt(jnp.maximum(stacked_sqdist(tree, y), 0.0))
        invd = s / jnp.maximum(dist, eps)
        return _combine(tree, invd, jnp.sum(invd))

    with phase(WEISZFELD):
        return jax.lax.fori_loop(0, iters, body, y0)


def stacked_ctma(tree: Pytree, s: Optional[Array] = None, *, lam: float,
                 base: Callable[..., Pytree] = stacked_cwmed,
                 x0: Optional[Pytree] = None) -> Pytree:
    """ω-CTMA (Alg. 1) on a stacked tree: anchor via ``base``, ONE global
    distance pass across leaves, one m-element sort/prefix in XLA, one
    leaf-wise trimmed combine."""
    from repro.kernels.wctma_fused import trim_weights  # pure jnp, no Pallas

    s = _weights(s, _lead(tree))
    if x0 is None:
        with phase(ANCHOR):
            x0 = base(tree, s)
    with phase(DISTANCE):
        # squared distances order identically to distances — skip the sqrt
        d2 = stacked_sqdist(tree, x0)
    with phase(COMBINE):
        kept, thresh = trim_weights(d2, s, lam)
        return _combine(tree, kept, jnp.maximum(thresh, 1e-30))


def stacked_cwtm(tree: Pytree, s: Optional[Array] = None, *,
                 lam: float = 0.25) -> Pytree:
    """ω-CWTM: coordinate-wise like cwmed, hence exactly leaf-separable."""
    s = _weights(s, _lead(tree))

    def leaf(x):
        return weighted_cwtm(_flat2(x).astype(jnp.float32), s,
                             lam=lam).reshape(x.shape[1:])

    return _tmap(leaf, tree)


def stacked_pairwise_sqdist(tree: Pytree,
                            scale: Optional[Pytree] = None) -> Array:
    """Global (m, m) pairwise squared distances in ONE pass over the tree
    (``scale`` as in :func:`stacked_sqdist` — the hierarchical path's per-leaf
    psum weights).

    Differences are formed directly (like the flat ``core.aggregators.krum``)
    rather than via the Gram identity ‖x_i‖² + ‖x_j‖² − 2⟨x_i,x_j⟩, whose
    float32 cancellation zeroes out small distances between large-norm rows —
    exactly the clustered-honest-momenta regime Krum ranks on."""
    def part(x, f=1.0):
        xf = _flat2(x).astype(jnp.float32)
        return f * jnp.sum(jnp.square(xf[:, None, :] - xf[None, :, :]), axis=-1)

    mapped = _tmap(part, tree) if scale is None else _tmap(part, tree, scale)
    return sum(jax.tree_util.tree_leaves(mapped))


def krum_select(d2: Array, n_byz: int = 1) -> Array:
    """Krum winner index from an (m, m) pairwise squared-distance matrix —
    shared by the stacked path here and the hierarchical path
    (dist/hierarchy.py), so the scoring can never drift between the two."""
    m = d2.shape[0]
    d2 = jnp.where(jnp.eye(m, dtype=bool), jnp.inf, d2)
    k = max(m - n_byz - 2, 1)
    scores = jnp.sum(jnp.sort(d2, axis=1)[:, :k], axis=1)
    return jnp.argmin(scores)


def stacked_krum(tree: Pytree, s: Optional[Array] = None, *,
                 n_byz: int = 1) -> Pytree:
    """Krum on a stacked tree: one global pairwise-distance pass, then the
    winning row sliced out leaf-wise (ignores weights — classical rule)."""
    i = krum_select(stacked_pairwise_sqdist(tree), n_byz)
    return _tmap(lambda x: x[i], tree)


# ---------------------------------------------------------------------------
# Legacy factory — deprecated shim over the unified registry
# ---------------------------------------------------------------------------

def make_stacked_aggregator(spec: str, lam: float = 0.0, **kw
                            ) -> Callable[[Pytree, Optional[Array]], Pytree]:
    """Deprecated: use :func:`repro.agg.resolve` — the resolved callable
    accepts stacked pytrees (this layer) AND flat ``(m, d)`` matrices."""
    warnings.warn("make_stacked_aggregator is deprecated; use "
                  "repro.agg.resolve(spec, lam=...) — the resolved callable "
                  "is layout-polymorphic", DeprecationWarning, stacklevel=2)
    from repro.agg import resolve
    return resolve(spec, lam=lam, **kw)
