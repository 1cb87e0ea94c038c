"""Hierarchical cross-pod aggregation (dist/hierarchy.py).

Parity: on a forced 8-device host mesh (2 pods × 2 data × 2 model) every
hierarchical rule must match the single-host stacked path to allclose —
including non-uniform weights, replicated (indivisible) leaves, and m=1.
HLO: the lowered hierarchical aggregator must contain NO all-gather of the
stacked momentum leaves — the distance reductions communicate only
(m,)-sized partials over the pod axis.

The multi-device tests need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set BEFORE jax initializes, which the tier-1 conftest deliberately does not do
(smoke benches must see the single real CPU). Under plain tier-1 they skip and
``test_hier_parity_subprocess`` re-runs this file in a subprocess with the
flag, so the suite is always exercised. CI additionally runs the in-process
variant directly (see .github/workflows/ci.yml).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import resolve
from repro.dist.context import mesh_context
from repro.launch.mesh import auto_mesh

ROOT = Path(__file__).resolve().parents[1]

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

# (spec, resolve kwargs) — the acceptance sweep plus the anchor rules
SPECS = [
    ("ctma:cwmed", {"lam": 0.25}),
    ("ctma:gm", {"lam": 0.25, "iters": 8}),
    ("gm", {"iters": 8}),
    ("krum", {"n_byz": 2}),
    ("cwmed", {}),
    ("cwtm", {"lam": 0.2}),
    ("mean", {}),
]


def _mesh():
    return auto_mesh((2, 2, 2), ("pod", "data", "model"))


def _tree(m=6, seed=0):
    k = jax.random.PRNGKey(seed)
    tree = {
        "a": jax.random.normal(jax.random.fold_in(k, 1), (m, 4, 8)),
        "b": {"c": jax.random.normal(jax.random.fold_in(k, 2), (m, 10)),
              # 5 divides by neither pod nor model: replicated leaf, exercising
              # the covered/total partial-sum scaling
              "d": jax.random.normal(jax.random.fold_in(k, 3), (m, 5))},
    }
    s = jax.random.uniform(jax.random.fold_in(k, 4), (m,), minval=0.2, maxval=2.5)
    return tree, s


def _flat(tree):
    return jnp.concatenate(
        [l.reshape(-1) for l in jax.tree_util.tree_leaves(tree)])


# ---------------------------------------------------------------------------
# Layout policy (single-device safe)
# ---------------------------------------------------------------------------

def test_momentum_pspec_policy():
    from repro.dist.hierarchy import momentum_pspec

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 4, "model": 2}

    mesh = FakeMesh()
    # pod on the trailing-most divisible dim, model on another, G never sharded
    assert tuple(momentum_pspec((8, 6, 4), mesh)) == (None, "model", "pod")
    # single divisible trailing dim: pod wins, model declines
    assert tuple(momentum_pspec((8, 5, 4), mesh)) == (None, None, "pod")
    # nothing divisible: fully replicated
    assert tuple(momentum_pspec((8, 5), mesh)) == (None, None)


def test_has_hier_capability_probe():
    """The launch layer keys the pod-sharded momentum layout and the dry-run
    agg_hier flag on this probe — it must deny rules whose stacked path would
    silently fall back."""
    from repro.agg import has_hier

    assert has_hier("ctma:cwmed", lam=0.25)
    assert has_hier("ctma:gm", lam=0.25)
    assert has_hier("gm") and has_hier("krum") and has_hier("cwmed")
    assert not has_hier("zeno", lam=0.25)
    assert not has_hier("bucketing:cwmed", lam=0.25)
    assert not has_hier("ctma:krum", lam=0.25)   # unsupported anchor
    assert not has_hier("ctma:cwmed@jnp", lam=0.25)  # pinned single-host
    assert not has_hier("no_such_rule")


def test_hier_pins_flat_matrix_inputs():
    """@hier must honor the pin for flat (m, d) inputs too — they route
    through the hierarchical wrapper as the single-leaf stacked case instead
    of silently taking the flat path."""
    k = jax.random.PRNGKey(2)
    x = jax.random.normal(k, (5, 8))
    s = jax.random.uniform(jax.random.fold_in(k, 1), (5,), minval=0.2, maxval=2.0)
    got = resolve("ctma:cwmed@hier", lam=0.25)(x, s)
    want = resolve("ctma:cwmed@jnp", lam=0.25)(x, s)
    assert got.shape == (8,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


def test_hier_backend_requires_hier_rule():
    """An explicit @hier must fail loudly for rules without a cross-pod path
    (silently degrading to the stacked path would gather the buffers)."""
    with pytest.raises(ValueError, match="hierarchical"):
        resolve("zeno@hier", lam=0.25)
    with pytest.raises(ValueError, match="hierarchical"):
        resolve("bucketing:cwmed@hier", lam=0.25)
    with pytest.raises(ValueError, match="hierarchical"):
        resolve("ctma:krum@hier", lam=0.25)  # unsupported anchor


def test_hier_ctma_routes_base_extras():
    """ctma:gm extras (eps) must reach the anchor on BOTH the hier path and
    its stacked fallback, matching the @jnp stacked routing (they used to be
    silently dropped by the hier builder).

    Anisotropic geometry chosen (checked numerically) so the eps change flips
    the distance RANKING to the anchor — the trim weights depend only on that
    ranking, so the ctma output visibly moves: eps=100 floors every Weiszfeld
    weight (anchor -> weighted mean), eps=1e-8 -> geometric median."""
    k = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(jax.random.fold_in(k, 0), (6, 4))
            * jnp.asarray([1.0, 1.0, 4.0, 8.0])[None, :]}
    s = jax.random.uniform(jax.random.fold_in(k, 1), (6,), minval=0.3, maxval=3.0)
    outs = {}
    for eps in (1e-8, 100.0):
        want = resolve("ctma:gm@jnp", lam=0.35, iters=16, eps=eps)(tree, s)
        outs[eps] = resolve("ctma:gm", lam=0.35, iters=16, eps=eps)(tree, s)
        np.testing.assert_allclose(np.asarray(_flat(outs[eps])),
                                   np.asarray(_flat(want)), atol=1e-6)
    assert float(jnp.max(jnp.abs(_flat(outs[1e-8]) - _flat(outs[100.0])))) > 0.1


def test_hier_falls_back_without_mesh():
    tree, s = _tree()
    for spec, kw in SPECS:
        fn = resolve(f"{spec}@hier", **kw)
        want = resolve(f"{spec}@jnp", **kw)(tree, s)
        np.testing.assert_allclose(np.asarray(_flat(fn(tree, s))),
                                   np.asarray(_flat(want)), atol=1e-6)


# ---------------------------------------------------------------------------
# Multi-device parity (8 forced host devices)
# ---------------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("spec,kw", SPECS, ids=[s for s, _ in SPECS])
def test_hier_matches_stacked(spec, kw):
    tree, s = _tree()
    stacked = resolve(f"{spec}@jnp", **kw)(tree, s)
    with mesh_context(_mesh()):
        hier = resolve(spec, **kw)(tree, s)       # auto: mesh-aware dispatch
    np.testing.assert_allclose(np.asarray(_flat(hier)),
                               np.asarray(_flat(stacked)), atol=2e-4)


@multi_device
@pytest.mark.parametrize("spec,kw", SPECS, ids=[s for s, _ in SPECS])
def test_hier_matches_stacked_uniform_weights(spec, kw):
    tree, _ = _tree(seed=7)
    stacked = resolve(f"{spec}@jnp", **kw)(tree, None)
    with mesh_context(_mesh()):
        hier = resolve(f"{spec}@hier", **kw)(tree, None)
    np.testing.assert_allclose(np.asarray(_flat(hier)),
                               np.asarray(_flat(stacked)), atol=2e-4)


@multi_device
@pytest.mark.parametrize("spec,kw", SPECS, ids=[s for s, _ in SPECS])
def test_hier_single_worker(spec, kw):
    """m=1 must reduce to the identity on the single row."""
    tree, s = _tree(m=1, seed=3)
    with mesh_context(_mesh()):
        hier = resolve(spec, **kw)(tree, s)
    want = resolve(f"{spec}@jnp", **kw)(tree, s)
    np.testing.assert_allclose(np.asarray(_flat(hier)),
                               np.asarray(_flat(want)), atol=2e-4)


@multi_device
def test_hier_rejects_corrupt_group():
    tree, s = _tree(seed=5)
    corrupt = jax.tree_util.tree_map(lambda x: x.at[0].set(1e8), tree)
    with mesh_context(_mesh()):
        out = resolve("ctma:cwmed", lam=0.3)(corrupt, s)
    assert float(jnp.max(jnp.abs(_flat(out)))) < 100.0


@multi_device
def test_hier_hlo_no_momentum_gather():
    """Acceptance: no all-gather of the stacked leaves; distance reductions
    communicate only m-sized partials over the reduce axes."""
    from repro.dist.sharding import hier_momentum_sharding
    from repro.utils import collective_bytes
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _mesh()
    tree, s = _tree()
    m = s.shape[0]
    for spec, kw, passes in [("ctma:cwmed", {"lam": 0.25}, 1),
                             ("gm", {"iters": 8}, 8),
                             ("krum", {"n_byz": 2}, m)]:
        fn = resolve(spec, **kw)
        with mesh_context(mesh):
            jf = jax.jit(fn, in_shardings=(hier_momentum_sharding(mesh, tree),
                                           NamedSharding(mesh, P())))
            cb = collective_bytes(jf.lower(tree, s).compile().as_text())
        assert cb["all-gather"] == 0, (spec, cb)
        # all-reduce bytes: <= passes × (m or m×m) f32 partials × 2 ring phases
        assert cb["all-reduce"] <= passes * m * m * 4 * 2, (spec, cb)
        assert cb["all-reduce"] > 0, (spec, "hier path did not engage")


@multi_device
def test_hier_robust_train_step_two_pods():
    """End-to-end: the robust-DP train step lowered under a multi-pod mesh
    context trains, and its losses stay finite with a Byzantine group."""
    from repro.configs import smoke_config
    from repro.data import lm_batches
    from repro.dist.steps import (RobustDPConfig, init_train_state,
                                  make_robust_train_step)
    from repro.optim import OptConfig

    mesh = _mesh()
    cfg = smoke_config("qwen2-1.5b")
    opt = OptConfig(name="mu2", lr=3e-3, gamma=0.1, beta=0.25)
    rcfg = RobustDPConfig(n_groups=4, agg="ctma:cwmed", lam=0.3,
                          byz_groups=(1,), byz_attack="sign_flip")
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0), rcfg)
    data = lm_batches(cfg, 8, 32)
    with mesh, mesh_context(mesh):
        step = jax.jit(make_robust_train_step(cfg, opt, rcfg))
        for _ in range(3):
            state, metrics = step(state, {k: jnp.asarray(v)
                                          for k, v in next(data).items()})
            assert np.isfinite(float(metrics["loss"]))


# ---------------------------------------------------------------------------
# Tier-1 gates (single-device): run the suite above in a subprocess
# ---------------------------------------------------------------------------

@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="already running in the multi-device variant")
@pytest.mark.skipif(os.environ.get("CI") == "true",
                    reason="CI runs the dedicated in-process parity step")
def test_hier_parity_subprocess():
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", str(Path(__file__)),
         "-k", "not subprocess and not dryrun"],
        env=env, capture_output=True, text=True, timeout=1800, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "passed" in r.stdout, r.stdout   # the parity sweep actually ran


def test_hier_dryrun_multi_pod_robust():
    """launch/dryrun.py end-to-end: the robust multi-pod signature lowers with
    the hierarchical path engaged (asserted via the 'agg=hier' marker — a
    silent fallback to the gathering stacked path would keep the compile
    green but drop the marker)."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               REPRO_DRYRUN_DEVICES="8")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-1.5b",
         "--shape", "train_4k", "--debug-mesh", "--multi-pod", "--robust",
         "--no-probe"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
    assert "agg=hier" in r.stdout
