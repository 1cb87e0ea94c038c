"""One registry, one resolve path for every robust-aggregation rule.

``resolve(spec, **kw)`` returns a LAYOUT-POLYMORPHIC callable

    agg(X, s=None)      X: (m, d) matrix  -> (d,) vector
    agg(tree, s=None)   tree: stacked pytree, leaves (m, ...) -> pytree

dispatching per input layout:

    flat (m, d) matrix   backend ``jnp``    -> core.aggregators oracles
                         backend ``pallas`` -> kernels.ops fused pipelines
                         backend ``auto``   -> pallas on TPU, jnp elsewhere
    stacked pytree       the leaf-wise ``dist.robust`` path with its single
                         GLOBAL distance pass (no O(m·d) flatten copy); under
                         ``auto``/``hier`` it is additionally mesh-aware —
                         traced inside a multi-pod ``mesh_context`` the rule
                         runs the ``dist.hierarchy`` cross-pod variant
                         (per-pod partial distance sums + an (m,)-sized psum
                         over the ``pod`` axis; no momentum gather)

A rule without a native implementation for some path degrades gracefully:
missing pallas -> the jnp oracle; missing stacked -> a flatten/unflatten
fallback around the flat path (correct, but pays the copy the native stacked
rules avoid — fine for benchmark baselines, wrong for hot paths).

Registering a new rule (e.g. a baseline from related work) is one call:

    register("myrule", flat=lambda sp: my_flat_fn, stacked=..., pallas=...)

Each builder receives the parsed :class:`AggregatorSpec` (λ, iters, extra
params) and returns ``fn(x, s=None)`` for its layout.
"""
from __future__ import annotations

import inspect
import math
from functools import partial
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import aggregators as _flatagg
from repro.kernels.backend import interpret_mode, on_tpu

from .baselines import stacked_zeno, weighted_zeno
from .spec import AggregatorSpec, SpecLike, parse


def _ops():
    """Pallas kernel wrappers, imported ONLY when a pallas builder runs — the
    pure-jnp paths (core.engine with backend='jnp') never pay the kernel
    package import."""
    from repro.kernels import ops
    return ops


def _stk():
    """Stacked-pytree backends, imported ONLY when a stacked builder runs
    (first pytree input) — flat-matrix users never pull in repro.dist."""
    from repro.dist import robust
    return robust


def _hr():
    """Hierarchical cross-pod backends, imported lazily like ``_stk``."""
    from repro.dist import hierarchy
    return hierarchy


Builder = Callable[[AggregatorSpec], Callable]


class Rule(NamedTuple):
    flat: Builder                      # jnp oracle — always present
    pallas: Optional[Builder] = None   # fused kernel path (None -> flat)
    stacked: Optional[Builder] = None  # leaf-wise path (None -> flatten fallback)
    hier: Optional[Builder] = None     # cross-pod shard_map path (None -> stacked)
    composes: bool = False             # accepts a ':base' inner rule
    doc: str = ""


_RULES: Dict[str, Rule] = {}


def register(name: str, flat: Builder, *, pallas: Optional[Builder] = None,
             stacked: Optional[Builder] = None, hier: Optional[Builder] = None,
             composes: bool = False, doc: str = "") -> None:
    """Add (or override) a rule in the global registry."""
    _RULES[name.lower()] = Rule(flat, pallas, stacked, hier, composes, doc)


def rules() -> Dict[str, Rule]:
    return dict(_RULES)


def has_hier(spec: SpecLike, **kw) -> bool:
    """Whether ``spec`` resolves to a rule WITH a hierarchical cross-pod path
    (its stacked branch upgrades under a multi-pod ``mesh_context``). The
    launch layer keys the pod-sharded momentum layout and the dry-run's
    ``agg_hier`` artifact flag on this — a rule that would silently fall back
    to the single-host stacked path must not claim the hierarchical layout."""
    sp = parse(spec, **kw)
    if sp.backend not in ("auto", "hier"):
        return False  # an explicit @jnp/@pallas pin never upgrades
    rule = _RULES.get(sp.rule)
    if rule is None or rule.hier is None:
        return False
    return rule.hier(sp) is not None


def resolve(spec: SpecLike, **kw) -> Callable:
    """Parse ``spec`` and build its layout-polymorphic aggregator.

    ``resolve("ctma:gm@pallas", lam=0.25)(X_or_tree, s)`` — see module doc.
    The parsed spec is attached to the callable as ``.spec``.
    """
    sp = parse(spec, **kw)
    if sp.rule not in _RULES:
        raise KeyError(f"unknown aggregator rule {sp.rule!r} in spec "
                       f"{sp.canonical!r}; registered: {sorted(_RULES)}")
    rule = _RULES[sp.rule]
    if sp.base is not None:
        if not rule.composes:
            raise ValueError(f"rule {sp.rule!r} does not compose with a base "
                             f"(got {sp.canonical!r})")
        if sp.base not in _RULES:
            raise KeyError(f"unknown base rule {sp.base!r} in {sp.canonical!r}")

    backend = _backend(sp)
    if backend == "pallas" and rule.pallas is not None:
        flat_fn = rule.pallas(sp)
    else:
        flat_fn = rule.flat(sp)

    # The stacked branch builds lazily on the first pytree input: flat-only
    # users never import the dist layer, and a stacked builder that declines
    # (returns None — e.g. ctma over a base with no leaf-wise path) falls
    # back to the flatten adapter instead of handing out a broken callable.
    # Under ``auto``/``hier`` a rule with a hier builder gets the mesh-aware
    # dist.hierarchy wrapper, which itself falls back to the single-host
    # stacked path whenever no multi-pod mesh_context is active at trace time.
    # An EXPLICIT ``@hier`` pins that wrapper, so it must fail loudly (here,
    # eagerly) rather than silently hand back a path that would gather the
    # stacked buffers across pods.
    cache: dict = {}
    if sp.backend == "hier":
        hfn = rule.hier(sp) if rule.hier is not None else None
        if hfn is None:
            raise ValueError(
                f"spec {sp.canonical!r}: rule {sp.rule!r} has no hierarchical "
                f"cross-pod path for these parameters; use backend 'auto' for "
                f"graceful single-host fallback, or a rule registered with a "
                f"hier builder")
        cache["hier"] = hfn

    def _stacked_fn():
        if "fn" not in cache:
            fn = rule.stacked(sp) if rule.stacked is not None else None
            fn = fn if fn is not None else _flatten_fallback(flat_fn)
            hfn = cache.get("hier")
            if hfn is None and sp.backend == "auto" and rule.hier is not None:
                hfn = rule.hier(sp)
            if hfn is not None:
                fn = _mesh_aware(hfn, fn)
            cache["fn"] = fn
        return cache["fn"]

    def agg(x, s=None):
        # A pinned ``@hier`` takes the hierarchical wrapper even for a flat
        # (m, d) matrix — the single-leaf stacked case, same values — so the
        # no-cross-pod-gather guarantee is never silently dropped.
        if _is_flat_matrix(x) and sp.backend != "hier":
            return flat_fn(x, s)
        return _stacked_fn()(x, s)

    agg.spec = sp
    agg.__name__ = f"agg<{sp.canonical}>"
    return agg


# ---------------------------------------------------------------------------
# Layout dispatch + generic stacked fallback
# ---------------------------------------------------------------------------

def _is_flat_matrix(x) -> bool:
    """A single (m, d) array takes the flat path; anything else (dicts,
    tuples, or single arrays of other ranks) is a stacked tree. The 2-D
    single-array case is semantically unambiguous: leaf-wise aggregation of
    one (m, d) leaf equals flat aggregation of the matrix."""
    return hasattr(x, "ndim") and x.ndim == 2


def _mesh_aware(hier_fn: Callable, stacked_fn: Callable) -> Callable:
    """The cross-pod variant when traced inside a multi-pod ``mesh_context``,
    else the rule's own stacked path — the one its backend chose, kernels
    included (decided at trace time, like ``dist.hierarchy``'s dispatch)."""
    from repro.dist.context import current_axis_size

    def agg(tree, s=None):
        if current_axis_size(_hr().POD_AXIS) <= 1:
            return stacked_fn(tree, s)
        return hier_fn(tree, s)

    return agg


def _flatten_fallback(flat_fn: Callable) -> Callable:
    """Stacked adapter for rules with no native leaf-wise path: concatenate
    the (m, ...) leaves into one (m, d) matrix, run the flat rule, unflatten.
    Costs the O(m·d) copy the native stacked rules avoid."""
    def agg(tree, s=None):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        m = leaves[0].shape[0]
        x = jnp.concatenate(
            [l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)
        out = flat_fn(x, s)
        pieces, off = [], 0
        for l in leaves:
            n = math.prod(l.shape[1:])
            pieces.append(out[off:off + n].reshape(l.shape[1:]))
            off += n
        return jax.tree_util.tree_unflatten(treedef, pieces)

    return agg


# ---------------------------------------------------------------------------
# Built-in rules
# ---------------------------------------------------------------------------

def _backend(sp: AggregatorSpec) -> str:
    """The spec's backend with ``auto`` decided: pallas on TPU, else jnp."""
    if sp.backend == "auto":
        return "pallas" if on_tpu() else "jnp"
    return sp.backend


def _interp(sp: AggregatorSpec) -> bool:
    """Pallas interpret mode: explicit override, else Mosaic only on TPU."""
    return interpret_mode(sp.interpret)


def _split_kwargs(kw: dict, fn: Callable) -> tuple[dict, dict]:
    """Partition spec extras into (accepted by ``fn``, rest). Composed specs
    carry parameters for BOTH the meta-rule and its base (``ctma:krum`` with
    ``n_byz``): the meta-rule keeps what its signature names, the base builder
    receives the remainder."""
    try:
        params = inspect.signature(fn).parameters.values()
        names = {p.name for p in params
                 if p.kind in (p.KEYWORD_ONLY, p.POSITIONAL_OR_KEYWORD)}
    except (TypeError, ValueError):  # pragma: no cover
        return kw, {}
    return ({k: v for k, v in kw.items() if k in names},
            {k: v for k, v in kw.items() if k not in names})


def _flat_base(sp: AggregatorSpec, default: str, extras: dict) -> Callable:
    name = sp.base or default
    return _RULES[name].flat(sp._replace(rule=name, base=None,
                                         params=tuple(sorted(extras.items()))))


def _stacked_base(sp: AggregatorSpec, default: str,
                  extras: dict) -> Optional[Callable]:
    name = sp.base or default
    entry = _RULES[name]
    if entry.stacked is None:
        return None
    return entry.stacked(sp._replace(rule=name, base=None,
                                     params=tuple(sorted(extras.items()))))


def _cwtm_lam(sp: AggregatorSpec) -> float:
    return max(sp.lam, 1e-3)  # λ=0 would retain everything: degenerate band


def _stacked_cwmed(sp: AggregatorSpec) -> Callable:
    """Leaf-wise ω-CWMed; on the pallas backend each leaf runs the leaf
    median kernel, which reads the leaf in its own dtype and layout and keeps
    its selection in VMEM (the jnp oracle's sort materializes several f32
    copies of every leaf — more than a chip holds for a 10^8-element
    embedding)."""
    if _backend(sp) == "pallas":
        return partial(_stk().stacked_cwmed,
                       median=partial(_ops().wcwmed_leaf, interpret=_interp(sp)))
    return _stk().stacked_cwmed


def _pallas_ctma(sp: AggregatorSpec) -> Callable:
    interp = _interp(sp)
    base = sp.base or "cwmed"
    if not sp.kwargs:  # base extras force the composable jnp path
        if base == "cwmed":
            return partial(_ops().wctma, lam=sp.lam, interpret=interp)
        if base == "gm":
            return partial(_ops().wctma_gm, lam=sp.lam, iters=sp.iters,
                           interpret=interp)
    return _flat_ctma(sp)  # other anchors: no fused pipeline, jnp oracle


def _flat_ctma(sp: AggregatorSpec) -> Callable:
    mine, rest = _split_kwargs(sp.kwargs, _flatagg.weighted_ctma)
    for reserved in ("x", "s", "lam", "base"):
        mine.pop(reserved, None)
    return partial(_flatagg.weighted_ctma, lam=sp.lam,
                   base=_flat_base(sp, "cwmed", rest), **mine)


def _stacked_ctma(sp: AggregatorSpec) -> Optional[Callable]:
    stk = _stk()
    mine, rest = _split_kwargs(sp.kwargs, stk.stacked_ctma)
    for reserved in ("tree", "s", "lam", "base"):
        mine.pop(reserved, None)
    base = _stacked_base(sp, "cwmed", rest)
    if base is None:
        return None
    return partial(stk.stacked_ctma, lam=sp.lam, base=base, **mine)


def _hier_ctma(sp: AggregatorSpec) -> Optional[Callable]:
    hr = _hr()
    base = sp.base or "cwmed"
    if base not in hr._BASE_BODIES:
        return None  # unsupported anchor: resolve falls back to plain stacked
    # Route the anchor's own parameters exactly like the stacked path does
    # (gm: iters/eps; cwtm: the shared λ); any extras this path does not
    # recognize mean PR-2 stacked semantics must win — decline.
    extras = dict(sp.kwargs)
    base_kw = {}
    if base == "gm":
        base_kw = {"iters": sp.iters, "eps": extras.pop("eps", 1e-8)}
    elif base == "cwtm":
        base_kw = {"lam": _cwtm_lam(sp)}
    if extras:
        return None
    return partial(hr.hier_ctma, lam=sp.lam, base=base, base_kw=base_kw)


def _flat_bucketing(sp: AggregatorSpec) -> Callable:
    mine, rest = _split_kwargs(sp.kwargs, _flatagg.bucketing)
    for reserved in ("x", "s", "inner"):  # composition comes from the spec
        mine.pop(reserved, None)
    return partial(_flatagg.bucketing,
                   inner=_flat_base(sp, "cwmed", rest), **mine)


def _register_builtins() -> None:
    register(
        "mean",
        flat=lambda sp: _flatagg.weighted_mean,
        pallas=lambda sp: partial(_ops().wmean, interpret=_interp(sp)),
        stacked=lambda sp: _stk().stacked_mean,
        hier=lambda sp: _hr().hier_mean,
        doc="weighted mean — non-robust baseline",
    )
    register(
        "cwmed",
        flat=lambda sp: _flatagg.weighted_cwmed,
        pallas=lambda sp: partial(_ops().wcwmed, interpret=_interp(sp)),
        stacked=_stacked_cwmed,
        hier=lambda sp: _hr().hier_cwmed,
        doc="ω-CWMed — weighted coordinate-wise median (Lemma C.3)",
    )
    register(
        "gm",
        flat=lambda sp: partial(_flatagg.weighted_gm, iters=sp.iters,
                                **sp.kwargs),
        pallas=lambda sp: partial(_ops().wgm, iters=sp.iters,
                                  interpret=_interp(sp), **sp.kwargs),
        stacked=lambda sp: partial(_stk().stacked_gm, iters=sp.iters,
                                   **sp.kwargs),
        hier=lambda sp: partial(_hr().hier_gm, iters=sp.iters, **sp.kwargs),
        doc="ω-GM / ω-RFA — weighted geometric median (Lemma C.1)",
    )
    register(
        "cwtm",
        flat=lambda sp: partial(_flatagg.weighted_cwtm, lam=_cwtm_lam(sp)),
        stacked=lambda sp: partial(_stk().stacked_cwtm, lam=_cwtm_lam(sp)),
        hier=lambda sp: partial(_hr().hier_cwtm, lam=_cwtm_lam(sp)),
        doc="ω-CWTM — weighted coordinate-wise trimmed mean",
    )
    register(
        "krum",
        flat=lambda sp: partial(_flatagg.krum, **sp.kwargs),
        stacked=lambda sp: partial(_stk().stacked_krum, **sp.kwargs),
        hier=lambda sp: partial(_hr().hier_krum, **sp.kwargs),
        doc="Krum (Blanchard et al. 2017) — unweighted baseline",
    )
    register(
        "ctma",
        flat=_flat_ctma,
        pallas=_pallas_ctma,
        stacked=_stacked_ctma,
        hier=_hier_ctma,
        composes=True,
        doc="ω-CTMA (Alg. 1) — centered trimmed meta-aggregator over :base",
    )
    register(
        "bucketing",
        flat=_flat_bucketing,
        composes=True,
        doc="bucketing meta-rule (Karimireddy et al. 2020) over :base",
    )
    register(
        "zeno",
        flat=lambda sp: partial(weighted_zeno, lam=sp.lam, **sp.kwargs),
        stacked=lambda sp: partial(stacked_zeno, lam=sp.lam, **sp.kwargs),
        doc="Zeno++-style descent scoring (Xie et al.), weighted trim",
    )


_register_builtins()

# Every built-in spec the cross-backend parity suite sweeps.
AGGREGATOR_SPECS = ("mean", "cwmed", "gm", "cwtm", "krum",
                    "ctma:cwmed", "ctma:gm", "bucketing:cwmed", "zeno")
