"""Stacked-pytree aggregators (the distributed form) must agree leaf-for-leaf
with the flat-vector originals in core.aggregators."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (krum, weighted_ctma, weighted_cwmed, weighted_cwtm,
                        weighted_gm, weighted_mean)
from repro.dist.robust import (stacked_cwmed, stacked_ctma, stacked_cwtm,
                               stacked_gm, stacked_krum, stacked_mean)


def _stacked(m=7, seed=0):
    k = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(k, 3)
    tree = {
        "a": jax.random.normal(k1, (m, 4, 6)),
        "b": {"c": jax.random.normal(k2, (m, 10)), "d": jax.random.normal(k3, (m, 2, 3, 2))},
    }
    s = jax.random.uniform(jax.random.fold_in(k, 9), (m,), minval=0.2, maxval=2.0)
    return tree, s


def _flatten(tree, m):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.concatenate([l.reshape(m, -1) for l in leaves], axis=1)


def _flatten_result(res):
    leaves = jax.tree_util.tree_leaves(res)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


@pytest.mark.parametrize("stacked_fn,flat_fn,kw", [
    (stacked_mean, weighted_mean, {}),
    (stacked_cwmed, weighted_cwmed, {}),
    (stacked_gm, weighted_gm, {"iters": 8}),
    (stacked_cwtm, weighted_cwtm, {"lam": 0.2}),
    (stacked_krum, krum, {"n_byz": 2}),
])
def test_stacked_matches_flat(stacked_fn, flat_fn, kw):
    tree, s = _stacked()
    m = s.shape[0]
    got = _flatten_result(stacked_fn(tree, s, **kw) if kw else stacked_fn(tree, s))
    want = flat_fn(_flatten(tree, m), s, **kw) if kw else flat_fn(_flatten(tree, m), s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


@pytest.mark.parametrize("lam", [0.15, 0.35])
def test_stacked_ctma_matches_flat(lam):
    tree, s = _stacked(seed=3)
    m = s.shape[0]
    got = _flatten_result(stacked_ctma(tree, s, lam=lam))
    want = weighted_ctma(_flatten(tree, m), s, lam=lam)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_stacked_ctma_rejects_corrupt_group():
    tree, s = _stacked(seed=5)
    corrupt = jax.tree_util.tree_map(
        lambda x: x.at[0].set(jnp.where(jnp.ones_like(x[0]) > 0, 1e8, x[0])), tree)
    out = stacked_ctma(corrupt, s, lam=0.3)
    assert float(jnp.max(jnp.abs(_flatten_result(out)))) < 100.0


def test_registry():
    from repro.agg import resolve
    tree, s = _stacked()
    for spec in ("mean", "cwmed", "gm", "cwtm", "krum", "zeno",
                 "ctma:cwmed", "ctma:gm", "bucketing:cwmed"):
        out = resolve(spec, lam=0.25)(tree, s)
        assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(tree)


def _qwen2_like(m=4, seed=0):
    """A small tree shaped like qwen2-1.5b-4l's group momenta: the embedding,
    one scanned layer group (matrices, q/k/v biases, norms) and the final
    norm, each leaf with the leading group axis m."""
    d, q, kv, f, v, L = 128, 128, 64, 256, 200, 2
    dims = {"embed": (v, d), "final_norm": (d,),
            "groups": [{"ln1": (L, d), "ln2": (L, d),
                        "mix": {"wq": (L, d, q), "wk": (L, d, kv),
                                "wv": (L, d, kv), "wo": (L, q, d),
                                "bq": (L, q), "bk": (L, kv), "bv": (L, kv)},
                        "mlp": {"wg": (L, d, f), "wu": (L, d, f),
                                "wd": (L, f, d)}}]}
    leaves, treedef = jax.tree_util.tree_flatten(
        dims, is_leaf=lambda t: isinstance(t, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    tree = jax.tree_util.tree_unflatten(treedef, [
        jax.random.normal(k, (m,) + t).astype(jnp.bfloat16)
        for k, t in zip(keys, leaves)])
    return tree, jnp.full((m,), 3.0)


def _kernel_names(fn, *args):
    """The name of every Pallas kernel that ``fn`` launches."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(str(getattr(eqn.params["name"], "name",
                                         eqn.params["name"])))
            for p in eqn.params.values():
                for sub in p if isinstance(p, (tuple, list)) else (p,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def test_pallas_stacked_ctma_reads_leaves_with_the_leaf_median():
    """On the pallas backend ω-CTMA over ω-CWMed of a layer-stacked tree
    gives the jnp backend's result, and every leaf's median is a
    ``wcwmed_leaf`` launch on the leaf itself, none the flat ``wcwmed``."""
    from repro.agg import resolve
    tree, s = _qwen2_like()
    pallas = resolve("ctma:cwmed", lam=0.25, backend="pallas", interpret=True)
    jnp_ = resolve("ctma:cwmed", lam=0.25, backend="jnp")
    np.testing.assert_allclose(np.asarray(_flatten_result(pallas(tree, s))),
                               np.asarray(_flatten_result(jnp_(tree, s))),
                               atol=1e-6, rtol=1e-6)
    names = _kernel_names(pallas, tree, s)
    assert names.count("wcwmed_leaf") == len(jax.tree_util.tree_leaves(tree))
    assert "wcwmed" not in names, names
