"""Drive the main paths once on one TPU chip and check what comes out.

    python chip_smoke.py              # serve, aggregate, train on one chip
    python chip_smoke.py --chips 4    # hierarchical cross-pod aggregation only

Phases (one process; nothing here starts a child):

- serve      qwen2-1.5b at full width and depth (random weights from --seed)
             through ``ServeEngine``: paged cache, the chunked unified step
             and the Mosaic ragged attention kernel. Checks that every
             request finishes with its token count, that the compiled step
             holds a ``tpu_custom_call``, and that one mixed prefill+decode
             tick's logits match the jnp attention path within
             ``SERVE_LOGIT_TOL``.
- aggregate  the paper's server step on an (m, d) = (17, 2^24) f32 matrix:
             ``cwmed``, ``gm``, ``ctma:cwmed`` and ``ctma:gm`` through
             ``repro.agg.resolve`` with ``@pallas`` against ``@jnp``.
- train      ``make_robust_train_step`` (ctma:cwmed, μ²-SGD, 4 groups,
             group 0 Byzantine with sign_flip) on qwen2-1.5b at full width,
             depth cut to ``TRAIN_LAYERS``: 3 donated steps, finite loss.
- hier       (``--chips 4`` only) ``ctma:cwmed@hier`` and ``gm@hier`` on a
             4-device ``pod`` mesh against the single-device stacked result:
             equal values, no all-gather of the momenta, buffers on 4 devices.

Each phase prints one line: its set-up and compile seconds (diagnostics,
not metrics) and the checks it passed. Any failed check raises. The last
line of stdout is the JSON result. Without a TPU, or outside the repository,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Largest relative logit error allowed between the Mosaic attention kernel
# (f32 softmax over bf16 pages) and the jnp path (bf16 scores and weights)
# after 28 bf16 layers: a few bf16 ulps (2^-8) of the logit scale.
SERVE_LOGIT_TOL = 3e-2
# Aggregation parity, relative to max|x|: the median rules select the same
# element on both backends (exact up to the tie average); GM and CTMA sum
# 2^24 f32 terms in different orders.
AGG_TOL = {"cwmed": 1e-6, "gm": 1e-4, "ctma:cwmed": 1e-4, "ctma:gm": 1e-4}
# Robust train step depth at full width. The compile rehearsal against a
# described v5e (compiled.memory_analysis(), batch 4 × 64 tokens) gives
# 9.2 / 10.3 / 11.3 GiB at 2 / 3 / 4 layers: ~1.05 GiB per layer on top of
# the 151936 × 1536 embedding, which every one of the state's 9 parameter-
# sized trees (w, x, x_prev, d, 4 group momenta, the aggregate) carries.
TRAIN_LAYERS = 4
# Single-device hier reference tolerance (the tests' bound), relative.
HIER_TOL = 2e-4


def _import_repo():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "from a checkout of the repository")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def _line(phase: str, setup_s: float, compile_s: float, checks: list) -> None:
    print(f"[{phase}] setup {setup_s:.1f}s compile {compile_s:.1f}s | "
          + "; ".join(checks), flush=True)


def _mosaic(cfg_interpret, flag=True) -> bool:
    """Whether the Pallas kernels lower to Mosaic for these switches."""
    from repro.kernels.backend import interpret_mode, kernels_on
    return kernels_on(flag) and not interpret_mode(cfg_interpret)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _mixed_tick_parity(cfg, params, page_size: int, n_slots: int,
                       max_len: int, seed: int) -> float:
    """Logits of one mixed tick (decode rows for slots 0-1, a fresh prefill
    chunk for slot 2), kernel path vs jnp path, from the same cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.lm import chunk_step
    from repro.serve.cache import (PageAllocator, init_paged_cache,
                                   pages_per_slot)

    C = page_size
    pager = PageAllocator(n_slots, max_len, page_size,
                          n_slots * pages_per_slot(max_len, page_size))
    for slot in range(3):
        pager.alloc(slot, pager.pages_needed(2 * C))
    table = jnp.asarray(pager.table)
    cache = init_paged_cache(cfg, n_slots, max_len, page_size, pager.n_pages)
    rng = np.random.default_rng(seed)
    xla = cfg.with_(use_pallas_decode=False)
    step = jax.jit(lambda c, p, *a: chunk_step(p, c, *a[:-1],
                                               page_table=a[-1]),
                   static_argnums=(0,))
    # tick 1: slots 0 and 1 prefill one chunk each (jnp path builds the cache)
    toks = rng.integers(0, cfg.vocab, (2, C)).astype(np.int32)
    _, cache = step(xla, params, cache, jnp.asarray(toks),
                    jnp.asarray([0, 1], jnp.int32),
                    jnp.asarray([C, C - 3], jnp.int32),
                    jnp.ones((2,), bool), table)
    # tick 2: two decode rows and one fresh chunk row, both paths
    toks = np.zeros((3, C), np.int32)
    toks[:2, 0] = rng.integers(0, cfg.vocab, 2)
    toks[2] = rng.integers(0, cfg.vocab, C)
    args = (jnp.asarray(toks), jnp.asarray([0, 1, 2], jnp.int32),
            jnp.asarray([1, 1, C], jnp.int32),
            jnp.asarray([False, False, True]), table)
    got, _ = step(cfg, params, cache, *args)
    want, _ = step(xla, params, cache, *args)
    return _rel_err(got, want)


def serve_phase(cfg, *, n_requests: int = 8, prompt_lens=(64, 512),
                gen_lens=(16, 32), n_slots: int = 8, page_size: int = 16,
                seed: int = 0, tol: float = SERVE_LOGIT_TOL) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models.lm import init_lm
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.scheduler import synth_workload

    t0 = time.perf_counter()
    max_len = prompt_lens[1] + gen_lens[1]
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    scfg = ServeConfig(paged=True, page_size=page_size, n_slots=n_slots,
                       max_len=max_len)
    eng = ServeEngine(cfg, params, scfg)
    reqs = synth_workload(n_requests, cfg.vocab, seed=seed,
                          prompt_lens=prompt_lens, gen_lens=gen_lens)
    jax.block_until_ready(params)
    setup_s = time.perf_counter() - t0

    rep = eng.run(reqs)
    checks = []
    done = [r for r in reqs if r.done and
            len(rep.outputs[r.uid]) == r.max_new_tokens]
    if len(done) != n_requests:
        raise AssertionError(f"serve: {len(done)}/{n_requests} requests "
                             "finished with their token count")
    checks.append(f"{n_requests}/{n_requests} requests finished with their "
                  f"token count ({rep.gen_tokens} tokens, "
                  f"{rep.prefill_tokens} prompt tokens)")

    # the engine's own jitted step at its mixed shape class: the same
    # program the run compiled, so the compile cache serves it again
    Rn, C = n_slots + eng.chunk_rows, eng.chunk_size
    hlo = eng._unified.lower(
        params, eng.cache, jnp.zeros((Rn, C), jnp.int32),
        jnp.full((Rn,), eng.slots.dump_slot, jnp.int32),
        jnp.ones((Rn,), jnp.int32), jnp.ones((Rn,), bool),
        jnp.zeros((Rn, 2), jnp.uint32), jnp.zeros((Rn,), jnp.int32),
        jnp.asarray(eng.pager.table)).compile().as_text()
    if _mosaic(cfg.pallas_interpret, cfg.use_pallas_decode):
        if "tpu_custom_call" not in hlo:
            raise AssertionError("serve: no tpu_custom_call in the unified step")
        checks.append("tpu_custom_call in the compiled unified step")

    err = _mixed_tick_parity(cfg, params, page_size, n_slots, max_len, seed)
    if not err <= tol:
        raise AssertionError(f"serve: mixed-tick logit error {err:.3e} > {tol}")
    checks.append(f"mixed-tick logits kernel vs jnp rel err {err:.3e} <= {tol}")
    _line("serve", setup_s, rep.compile_s, checks)
    return {"requests": n_requests, "gen_tokens": rep.gen_tokens,
            "logit_rel_err": err}


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------

def aggregate_phase(*, m: int = 17, d: int = 2 ** 24, seed: int = 0,
                    interpret=None, tol: dict = AGG_TOL) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.agg import resolve

    t0 = time.perf_counter()
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    # rows at distinct scales: their distances to any anchor are far apart,
    # so both backends trim the same rows
    scale = 0.5 + jnp.arange(m, dtype=jnp.float32)[:, None] / m
    x = jax.random.normal(k1, (m, d), jnp.float32) * scale
    s = jax.random.uniform(k2, (m,), minval=0.1, maxval=3.0)
    jax.block_until_ready(x)
    setup_s = time.perf_counter() - t0

    compile_s, checks, errs = 0.0, [], {}
    mosaic = _mosaic(interpret)
    for spec in ("cwmed", "gm", "ctma:cwmed", "ctma:gm"):
        kw = {"lam": 0.25, "iters": 8}
        t = time.perf_counter()
        pal = jax.jit(resolve(f"{spec}@pallas", interpret=interpret, **kw)
                      ).lower(x, s).compile()
        ref = jax.jit(resolve(f"{spec}@jnp", **kw)).lower(x, s).compile()
        compile_s += time.perf_counter() - t
        if mosaic and "tpu_custom_call" not in pal.as_text():
            raise AssertionError(f"aggregate: {spec}@pallas has no "
                                 "tpu_custom_call")
        err = _rel_err(pal(x, s), ref(x, s))
        if not err <= tol[spec]:
            raise AssertionError(f"aggregate: {spec} pallas vs jnp rel err "
                                 f"{err:.3e} > {tol[spec]}")
        errs[spec] = err
        checks.append(f"{spec} rel err {err:.1e} <= {tol[spec]:.0e}"
                      + (" (tpu_custom_call)" if mosaic else ""))
    _line(f"aggregate m={m} d={d}", setup_s, compile_s, checks)
    return {"rel_err": errs}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, *, n_layers: int = TRAIN_LAYERS, batch: int = 4,
                seq: int = 64, steps: int = 3, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import lm_batches
    from repro.dist.steps import (RobustDPConfig, init_train_state,
                                  make_robust_train_step)
    from repro.optim.mu2sgd import OptConfig

    print(f"[train] {cfg.name}: full width, depth cut {cfg.n_layers} -> "
          f"{n_layers} layers (TRAIN_LAYERS), batch {batch} x {seq} tokens",
          flush=True)
    t0 = time.perf_counter()
    cfg = cfg.with_(n_layers=n_layers)
    opt = OptConfig(name="mu2", lr=3e-3, gamma=0.1, beta=0.25)
    rcfg = RobustDPConfig(n_groups=4, agg="ctma:cwmed", lam=0.25,
                          byz_groups=(0,), byz_attack="sign_flip")
    state = init_train_state(cfg, opt, jax.random.PRNGKey(seed), rcfg)
    data = lm_batches(cfg, batch, seq, seed=seed)
    batches = [{k: jnp.asarray(v) for k, v in next(data).items()}
               for _ in range(steps)]
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - t0

    t = time.perf_counter()
    step = jax.jit(make_robust_train_step(cfg, opt, rcfg),
                   donate_argnums=(0,)).lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t
    mem = step.memory_analysis()
    gib = 2.0 ** 30
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / gib
    losses = []
    for b in batches:
        state, metrics = step(state, b)      # the compiled program: no retrace
        losses.append(float(metrics["loss"]))
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss {losses}")
    _line("train", setup_s, compile_s, [
        f"{steps} donated steps, loss finite every step "
        f"({', '.join(f'{l:.4f}' for l in losses)})",
        f"compiled footprint {peak:.2f} GiB (args {mem.argument_size_in_bytes / gib:.2f}, "
        f"temp {mem.temp_size_in_bytes / gib:.2f})"])
    return {"losses": losses, "footprint_gib": peak}


# ---------------------------------------------------------------------------
# hier (four chips)
# ---------------------------------------------------------------------------

def hier_leaves(m: int = 8) -> dict:
    """Stacked (m, ...) momenta shaped like a few qwen2-1.5b leaves."""
    return {"wq": (m, 1536, 1536), "w_gate": (m, 1536, 8960),
            "ln": (m, 1536), "bk": (m, 256)}


def hier_phase(devices, *, leaves: dict = None, seed: int = 0,
               tol: float = HIER_TOL) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.agg import resolve
    from repro.dist.context import mesh_context
    from repro.dist.sharding import hier_momentum_sharding
    from repro.launch.mesh import auto_mesh
    from repro.utils import collective_bytes

    leaves = leaves or hier_leaves()
    t0 = time.perf_counter()
    mesh = auto_mesh((len(devices),), ("pod",), devices=devices)
    key = jax.random.PRNGKey(seed)
    tree = {name: jax.random.normal(jax.random.fold_in(key, i), shape)
            for i, (name, shape) in enumerate(sorted(leaves.items()))}
    m = next(iter(leaves.values()))[0]
    s = jax.random.uniform(jax.random.fold_in(key, 99), (m,), minval=0.2,
                           maxval=2.5)
    shard = hier_momentum_sharding(mesh, tree)
    placed = jax.device_put(tree, shard)
    jax.block_until_ready(placed)
    setup_s = time.perf_counter() - t0

    n_dev = len(devices)
    for name, leaf in placed.items():
        owners = {sh.device for sh in leaf.addressable_shards}
        rows = {sh.data.size for sh in leaf.addressable_shards}
        if len(owners) != n_dev or rows != {leaf.size // n_dev}:
            raise AssertionError(f"hier: leaf {name} is not split over "
                                 f"{n_dev} devices ({len(owners)} owners)")
    checks = [f"every momentum leaf split 1/{n_dev} over {n_dev} devices"]
    compile_s = 0.0
    errs = {}
    for spec, kw in (("ctma:cwmed", {"lam": 0.25}), ("gm", {"iters": 8})):
        t = time.perf_counter()
        with mesh_context(mesh):
            hier = jax.jit(resolve(f"{spec}@hier", **kw),
                           in_shardings=(shard, NamedSharding(mesh, P()))
                           ).lower(placed, s).compile()
        ref = jax.jit(resolve(f"{spec}@jnp", **kw)).lower(tree, s).compile()
        compile_s += time.perf_counter() - t
        cb = collective_bytes(hier.as_text())
        if cb["all-gather"] != 0 or cb["all-reduce"] <= 0:
            raise AssertionError(f"hier: {spec} collectives {cb}")
        got = jnp.concatenate([l.reshape(-1) for l in
                               jax.tree_util.tree_leaves(hier(placed, s))])
        want = jnp.concatenate([l.reshape(-1) for l in
                                jax.tree_util.tree_leaves(ref(tree, s))])
        err = _rel_err(got, want)
        if not err <= tol:
            raise AssertionError(f"hier: {spec}@hier vs stacked rel err "
                                 f"{err:.3e} > {tol}")
        errs[spec] = err
        checks.append(f"{spec}@hier = single-device stacked (rel err "
                      f"{err:.1e}), no all-gather, all-reduce "
                      f"{cb['all-reduce']} B")
    _line(f"hier m={m} mesh pod={n_dev}", setup_s, compile_s, checks)
    return {"rel_err": errs}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repo()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    from repro.utils import enable_compile_cache
    enable_compile_cache()

    if args.chips == 4:
        hier_phase(devices[:4], seed=args.seed)
    else:
        from repro.configs import get_config
        cfg = get_config("qwen2-1.5b")
        serve_phase(cfg, seed=args.seed)
        aggregate_phase(seed=args.seed)
        train_phase(cfg, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
