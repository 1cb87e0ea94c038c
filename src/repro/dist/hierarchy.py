"""Hierarchical cross-pod robust aggregation (Remark 4.1 at multi-pod scale).

``dist.robust`` already reduces the CTMA/GM/Krum distance passes to a single
global ``(m,)`` vector, but the stacked momenta must be co-resident on one
pod's devices — on the 2×16×16 production mesh that means gathering every
group's full momentum buffer over the ``pod`` axis before aggregating. This
module removes that gather: the stacked ``(G, ...)`` momenta live PARAMETER-
SHARDED over the ``pod`` (and, when divisible, ``model``) mesh axes, each
device computes the distance contribution of its local parameter slice, and a
``lax.psum`` over the reduce axes turns the per-device partial squared-norm
sums into the same global ``(m,)`` (or ``(m, m)`` for Krum) vector the
single-host path produces. The momentum leaves themselves never cross a pod
boundary — only m-sized scalars do, which is what the paper's O(dm)
bandwidth model assumes of the aggregation step.

Why this decomposition is exact:

- ‖x_i − y‖² = Σ_shards ‖x_i − y‖²_shard — squared distances are additive
  over any partition of the coordinates, so a psum of per-shard partials IS
  the global distance (same identity ``stacked_sqdist`` uses across leaves).
- the anchors (ω-CWMed / ω-CWTM / weighted mean) and the final reweighted
  combines are coordinate-wise, hence computed shard-locally with the global
  ``(m,)`` coefficients — no communication at all.
- the trim/reweight coefficients (``trim_weights``, Weiszfeld 1/dist) are
  pure functions of the global distance vector and the replicated weights, so
  every device derives identical coefficients deterministically.

Layout: ``momentum_pspec`` places ``pod`` on the trailing-most leaf dim it
divides, then ``model`` on another divisible dim; the leading group axis is
never sharded (the coordinate-wise anchors need all m rows of each local
coordinate slice). Leaves with no divisible dim stay replicated — their
partial sums are scaled by ``covered/total`` so the psum counts them once.

Entry points mirror ``dist.robust`` (``hier_ctma``, ``hier_gm``, ...) and
self-dispatch on :func:`repro.dist.context.current_mesh`: outside a mesh
context, or on a mesh without a >1 ``pod`` axis, they fall back to the
single-host stacked path bit-for-bit. The ``repro.agg`` registry routes
stacked-pytree inputs through these wrappers for ``@hier`` and ``@auto``
backends, so ``make_robust_train_step`` lowered under a multi-pod
``mesh_context`` picks the hierarchical path with no call-site changes.

NOTE: mesh detection happens at trace time — a step jitted under one mesh
context caches that mesh's shard_map; build a fresh jit per mesh (the dry-run
and launchers already do).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.aggregators import weighted_cwmed, weighted_cwtm
from repro.dist.context import current_axis_size, current_mesh
from repro.dist import robust as _stk

Array = jnp.ndarray
Pytree = Any

_tmap = jax.tree_util.tree_map

POD_AXIS = "pod"
# Axes the distance psum reduces over. ``pod`` is the cross-pod requirement;
# ``model`` rides along when it divides a second dim so the stacked buffers
# are not replicated across the in-pod tensor-parallel ranks.
REDUCE_AXES = (POD_AXIS, "model")


def _axis_size(mesh, name: str) -> int:
    try:
        return int(mesh.shape.get(name, 1))
    except AttributeError:  # pragma: no cover - mesh-like without .shape dict
        return 1


def pod_count(mesh) -> int:
    """Size of the ``pod`` axis (1 when absent / no mesh)."""
    return _axis_size(mesh, POD_AXIS) if mesh is not None else 1


def reduce_axes(mesh) -> tuple:
    """The mesh axes the hierarchical distance psum runs over."""
    return tuple(a for a in REDUCE_AXES
                 if a in mesh.axis_names and _axis_size(mesh, a) > 1)


def momentum_pspec(shape: tuple, mesh) -> P:
    """Pod-sharded layout of one stacked ``(G, ...)`` momentum leaf.

    ``pod`` goes on the trailing-most dim it divides, ``model`` on another
    divisible dim; the leading group axis stays unsharded so the coordinate-
    wise anchors see all m rows of every local coordinate."""
    spec: list = [None] * len(shape)
    for axis in reduce_axes(mesh):
        n = _axis_size(mesh, axis)
        for i in range(len(shape) - 1, 0, -1):
            if spec[i] is None and shape[i] % n == 0 and shape[i] >= n:
                spec[i] = axis
                break
    return P(*spec)


def _hier_specs(tree: Pytree, mesh):
    """(in_specs, out_specs, fracs) for the shard_map call.

    ``fracs[leaf] = covered / total`` where covered is the product of reduce-
    axis sizes actually sharding the leaf: replicated leaves contribute the
    same partial on every reduce-axis coordinate, so scaling by covered/total
    makes the psum count them exactly once."""
    axes = reduce_axes(mesh)
    total = 1
    for a in axes:
        total *= _axis_size(mesh, a)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    specs = [momentum_pspec(tuple(l.shape), mesh) for l in leaves]
    fracs = []
    for sp in specs:
        covered = 1
        for a in axes:
            if a in sp:
                covered *= _axis_size(mesh, a)
        fracs.append(covered / total)
    unf = partial(jax.tree_util.tree_unflatten, treedef)
    return (unf(specs), unf([P(*sp[1:]) for sp in specs]), unf(fracs), axes)


# ---------------------------------------------------------------------------
# Shard-local kernels (run inside shard_map; ``tree`` leaves are local blocks)
# ---------------------------------------------------------------------------

# Leaf reshaping and the coefficient combine are the SAME computation as the
# single-host stacked path, applied to local blocks — share the code so the
# bit-for-bit fallback equivalence can never drift.
_flat2 = _stk._flat2
_local_combine = _stk._combine


def _global_sqdist(tree: Pytree, y: Pytree, fracs: Pytree, axes) -> Array:
    """THE hierarchical distance pass: this device's frac-scaled partial of
    the shared stacked distance kernel + one (m,)-sized psum over the reduce
    axes — the only cross-pod communication in this module."""
    return lax.psum(_stk.stacked_sqdist(tree, y, fracs), axes)


def _body_mean(tree, s, fracs, axes):
    return _local_combine(tree, s, jnp.sum(s))


def _body_cwmed(tree, s, fracs, axes):
    return _tmap(lambda x: weighted_cwmed(_flat2(x).astype(jnp.float32), s)
                 .reshape(x.shape[1:]), tree)


def _body_cwtm(tree, s, fracs, axes, *, lam: float):
    return _tmap(lambda x: weighted_cwtm(_flat2(x).astype(jnp.float32), s,
                                         lam=lam).reshape(x.shape[1:]), tree)


def _body_gm(tree, s, fracs, axes, *, iters: int = 32, eps: float = 1e-8):
    y0 = _body_cwmed(tree, s, fracs, axes)

    def body(_, y):
        dist = jnp.sqrt(jnp.maximum(_global_sqdist(tree, y, fracs, axes), 0.0))
        invd = s / jnp.maximum(dist, eps)
        return _local_combine(tree, invd, jnp.sum(invd))

    return lax.fori_loop(0, iters, body, y0)


def _body_ctma(tree, s, fracs, axes, *, lam: float, base_body: Callable):
    from repro.kernels.wctma_fused import trim_weights  # pure jnp, no Pallas

    x0 = base_body(tree, s, fracs, axes)
    # the global distances (and hence the trim coefficients) are identical on
    # every device, so the trimmed combine stays shard-local
    kept, thresh = trim_weights(_global_sqdist(tree, x0, fracs, axes), s, lam)
    return _local_combine(tree, kept, jnp.maximum(thresh, 1e-30))


def _body_krum(tree, s, fracs, axes, *, n_byz: int = 1):
    # shared pairwise kernel + scoring with the stacked path; the psum moves
    # (m, m) scalars, never the buffers
    d2 = lax.psum(_stk.stacked_pairwise_sqdist(tree, fracs), axes)
    i = _stk.krum_select(d2, n_byz)
    return _tmap(lambda x: x[i], tree)


# CTMA anchor bodies resolvable by name, with their stacked fallbacks.
_BASE_BODIES = {
    "cwmed": (_body_cwmed, _stk.stacked_cwmed),
    "mean": (_body_mean, _stk.stacked_mean),
    "gm": (_body_gm, _stk.stacked_gm),
    "cwtm": (_body_cwtm, _stk.stacked_cwtm),
}


# ---------------------------------------------------------------------------
# Mesh dispatch
# ---------------------------------------------------------------------------

def _run_hier(body: Callable, tree: Pytree, s: Optional[Array], mesh) -> Pytree:
    m = jax.tree_util.tree_leaves(tree)[0].shape[0]
    w = jnp.ones((m,), jnp.float32) if s is None else s.astype(jnp.float32)
    in_specs, out_specs, fracs, axes = _hier_specs(tree, mesh)
    fn = jax.shard_map(lambda t, sw: body(t, sw, fracs, axes), mesh=mesh,
                       in_specs=(in_specs, P()), out_specs=out_specs,
                       check_vma=False)
    return fn(tree, w)


def _dispatch(body: Callable, fallback: Callable, tree: Pytree,
              s: Optional[Array]) -> Pytree:
    if current_axis_size(POD_AXIS) <= 1:
        return fallback(tree, s)
    return _run_hier(body, tree, s, current_mesh())


def hier_mean(tree: Pytree, s: Optional[Array] = None) -> Pytree:
    return _dispatch(_body_mean, _stk.stacked_mean, tree, s)


def hier_cwmed(tree: Pytree, s: Optional[Array] = None) -> Pytree:
    return _dispatch(_body_cwmed, _stk.stacked_cwmed, tree, s)


def hier_cwtm(tree: Pytree, s: Optional[Array] = None, *,
              lam: float = 0.25) -> Pytree:
    return _dispatch(partial(_body_cwtm, lam=lam),
                     partial(_stk.stacked_cwtm, lam=lam), tree, s)


def hier_gm(tree: Pytree, s: Optional[Array] = None, *, iters: int = 32,
            eps: float = 1e-8) -> Pytree:
    return _dispatch(partial(_body_gm, iters=iters, eps=eps),
                     partial(_stk.stacked_gm, iters=iters, eps=eps), tree, s)


def hier_krum(tree: Pytree, s: Optional[Array] = None, *,
              n_byz: int = 1) -> Pytree:
    return _dispatch(partial(_body_krum, n_byz=n_byz),
                     partial(_stk.stacked_krum, n_byz=n_byz), tree, s)


def hier_ctma(tree: Pytree, s: Optional[Array] = None, *, lam: float,
              base: str = "cwmed",
              base_kw: Optional[dict] = None) -> Pytree:
    """ω-CTMA with the anchor resolved by NAME (the registry composes specs
    as strings and routes the anchor's own parameters — gm's iters/eps,
    cwtm's lam — through ``base_kw``); the stacked twin gets the matching
    callable fallback with identical parameters."""
    if base not in _BASE_BODIES:
        raise KeyError(f"hier ctma base {base!r}; choose from "
                       f"{sorted(_BASE_BODIES)}")
    base_body, base_stacked = _BASE_BODIES[base]
    kw = base_kw or {}
    return _dispatch(
        partial(_body_ctma, lam=lam, base_body=partial(base_body, **kw)),
        partial(_stk.stacked_ctma, lam=lam,
                base=partial(base_stacked, **kw) if kw else base_stacked),
        tree, s)
