"""Table 1 / Remark 4.1: wall-clock cost of the weighted aggregation rules —
all are O(dm) (+ log factors), so µs/call should scale ~linearly in d·m.

Also benchmarks the Pallas kernel paths (interpret mode on CPU; Mosaic on
TPU) against the jnp oracles, including the fused vs unfused ω-CTMA pipeline
— the fusion removes one full HBM pass over the (m, d) matrix (3 -> 2), so
``aggpallas_ctma:cwmed_fused_speedup_*`` rows track the bandwidth win across
PRs via BENCH_agg.json (written by benchmarks/run.py).

``run_hier`` (the ``agghier`` bench in benchmarks/run.py) times the
hierarchical cross-pod path (dist/hierarchy.py) against the single-host
stacked path on a 2-pod host mesh and records its collective-bytes / HBM
accounting from the compiled HLO: all-gather must stay 0 — the distance
reductions communicate only (m,)-sized partials over the pod axis. Needs
multiple host devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in a fresh process); with a single device it emits nothing.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp

from repro.agg import resolve
from repro.kernels import ops
from repro.utils import timeit_median

from .common import fmt_row

GRID = [(9, 10_000), (17, 100_000), (33, 1_000_000)]
SPECS = ("mean", "cwmed", "gm", "cwtm", "ctma:cwmed", "ctma:gm", "krum", "bucketing:cwmed")

# Pallas-vs-oracle comparison grid: must include (17, 100_000) — the
# acceptance shape for the fused-CTMA speedup trajectory.
PALLAS_GRID = [(9, 10_000), (17, 100_000)]
PALLAS_SPECS = ("cwmed", "gm", "ctma:cwmed")


def _data(key, m, d):
    k1, k2 = jax.random.split(jax.random.fold_in(key, d + m))
    x = jax.random.normal(k1, (m, d))
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    return x, s


def run(full: bool = False, smoke: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)
    grid = GRID if full else GRID[:2]
    iters, warmup = (2, 1) if smoke else (5, 2)
    # Mosaic on TPU, interpreter elsewhere — otherwise the persisted
    # trajectory would time the interpreter on the hardware fusion targets.
    interp = jax.default_backend() != "tpu"

    # --- jnp aggregator scaling (Table 1 / Remark 4.1) ---------------------
    specs = SPECS[:2] if smoke else SPECS
    for m, d in (grid[:1] if smoke else grid):
        x, s = _data(key, m, d)
        for spec in specs:
            agg = jax.jit(resolve(spec, lam=0.25, backend="jnp"))
            us = timeit_median(lambda: agg(x, s), iters=iters, warmup=warmup) * 1e6
            rows.append(fmt_row(f"aggcost_{spec}_m{m}_d{d}", us,
                                f"bytes_per_call={m * d * 4}"))

    # --- Pallas kernels vs jnp oracles (both smoke and full keep the full
    # PALLAS_GRID: it ends at the acceptance shape m=17, d=100k) ------------
    for m, d in PALLAS_GRID:
        x, s = _data(key, m, d)
        for spec in PALLAS_SPECS:
            oracle = jax.jit(resolve(spec, lam=0.25, backend="jnp"))
            kern = resolve(spec, lam=0.25, backend="pallas", interpret=interp)
            us_o = timeit_median(lambda: oracle(x, s), iters=iters, warmup=warmup) * 1e6
            us_k = timeit_median(lambda: kern(x, s), iters=iters, warmup=warmup) * 1e6
            rows.append(fmt_row(f"aggpallas_{spec}_jnp_m{m}_d{d}", us_o,
                                f"bytes_per_call={m * d * 4}"))
            rows.append(fmt_row(f"aggpallas_{spec}_kernel_m{m}_d{d}", us_k,
                                f"vs_jnp_ratio={us_o / max(us_k, 1e-9):.3f}"))

        # fused vs unfused ω-CTMA: the tentpole fusion (2 vs >=3 HBM passes)
        fused = jax.jit(lambda x, s: ops.wctma(x, s, lam=0.25, fused=True,
                                               interpret=interp))
        unfused = jax.jit(lambda x, s: ops.wctma(x, s, lam=0.25, fused=False,
                                                 interpret=interp))
        us_f = timeit_median(lambda: fused(x, s), iters=iters, warmup=warmup) * 1e6
        us_u = timeit_median(lambda: unfused(x, s), iters=iters, warmup=warmup) * 1e6
        rows.append(fmt_row(f"aggpallas_ctma:cwmed_fused_m{m}_d{d}", us_f,
                            "hbm_passes=2"))
        rows.append(fmt_row(f"aggpallas_ctma:cwmed_unfused_m{m}_d{d}", us_u,
                            "hbm_passes=3"))
        rows.append(fmt_row(f"aggpallas_ctma:cwmed_fused_speedup_m{m}_d{d}",
                            us_u - us_f, f"speedup={us_u / max(us_f, 1e-9):.3f}x"))
    return rows


# ---------------------------------------------------------------------------
# Hierarchical cross-pod path (dist/hierarchy.py) — the ``agghier`` bench
# ---------------------------------------------------------------------------

HIER_GRID = [(9, 10_000), (17, 100_000)]
HIER_SPECS = (("ctma:cwmed", {"lam": 0.25}), ("gm", {"iters": 8}),
              ("krum", {"n_byz": 2}))


def _hier_tree(key, m, d):
    """(m, d) split into a two-leaf stacked tree with pod-divisible dims."""
    x, s = _data(key, m, d)
    return {"a": x[:, : d // 2], "b": x[:, d // 2:]}, s


def run_hier(full: bool = False, smoke: bool = False):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.dist.context import mesh_context
    from repro.dist.sharding import hier_momentum_sharding
    from repro.launch.mesh import auto_mesh
    # NOT from repro.launch.dryrun — importing it would force the 512-device
    # placeholder platform via XLA_FLAGS before jax initializes
    from repro.utils import collective_bytes

    n_dev = jax.device_count()
    if n_dev < 4:
        print("# agghier: skipped — needs a multi-device host platform "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
              file=sys.stderr)
        return []
    mesh = auto_mesh((2, n_dev // 2), ("pod", "data"))
    rows = []
    key = jax.random.PRNGKey(1)
    iters, warmup = (2, 1) if smoke else (5, 2)
    grid = HIER_GRID[:1] if smoke else HIER_GRID
    specs = HIER_SPECS[:1] if smoke else HIER_SPECS
    for m, d in grid:
        tree, s = _hier_tree(key, m, d)
        for spec, kw in specs:
            stacked = jax.jit(resolve(f"{spec}@jnp", **kw))
            us_s = timeit_median(lambda: stacked(tree, s), iters=iters,
                                 warmup=warmup) * 1e6
            hier = resolve(spec, **kw)
            with mesh_context(mesh):
                jf = jax.jit(hier, in_shardings=(
                    hier_momentum_sharding(mesh, tree), NamedSharding(mesh, P())))
                # time the lowered executable directly — calling jf would
                # re-trace and re-compile (lower() does not seed jit's cache)
                compiled = jf.lower(tree, s).compile()
                us_h = timeit_median(lambda: compiled(tree, s), iters=iters,
                                     warmup=warmup) * 1e6
            coll = collective_bytes(compiled.as_text())
            try:
                ca = compiled.cost_analysis()
                ca = ca[0] if isinstance(ca, (list, tuple)) else ca
                hbm = int(float(ca.get("bytes accessed", 0.0)))
            except Exception:  # pragma: no cover
                hbm = 0
            rows.append(fmt_row(
                f"agghier_{spec}_m{m}_d{d}", us_h,
                f"vs_stacked_ratio={us_s / max(us_h, 1e-9):.3f};"
                f"allgather_B={coll['all-gather']};"
                f"allreduce_B={coll['all-reduce']};hbm_B={hbm};n_pod=2"))
            assert coll["all-gather"] == 0, (spec, coll)
    return rows


if __name__ == "__main__":
    print("\n".join(run() + run_hier()))
