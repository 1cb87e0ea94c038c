"""The paper's asynchronous parameter server (Alg. 2): the program's
``AsyncByzantineEngine.step`` absorbs one worker arrival per call, updates
that worker's momentum buffer, aggregates all m buffers with the weighted
robust rule and applies the AnyTime update.

The model is a flat vector of ``d`` float32 parameters, one shard of the
configuration's model as the traffic file states. The workers' loss is a
seeded quadratic, 1/2 sum h (x - c)^2 + <z, x>, whose gradient is one
elementwise pass over d (h, c from the seed, z from the arrival's noise
word), so the server's O(m d) work is what the window times.

Set-up builds the engine's state on the device from the seed: every worker's
first momentum at x_1 (Alg. 2 line 2) and update counts as after ``warm``
arrivals per worker in the arrival distribution, so the first steps already
aggregate over buffers of unequal weight. The reference follows the first
``check_steps`` arrivals from that state.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.model import key_of, seed_words
from bench.reference import robust as ref_robust

F32 = jnp.float32
U32 = jnp.uint32


def _unit(i, salt):
    """A hash of (index, salt) to [0, 1): uint32 arithmetic, exact on every
    backend, so the program's loss and the reference's agree bit for bit."""
    h = (i.astype(U32) * U32(0x9E3779B1)) ^ salt.astype(U32)
    h = (h ^ (h >> 15)) * U32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * U32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return (h >> 8).astype(F32) * (1.0 / (1 << 24))


def quad_terms(words, noise, d: int, sigma: float):
    """(h, c, z) of the workers' loss for seed words and an arrival's noise
    word: curvature in [0.5, 1.5], centre in [-1, 1], noise in [-sigma, sigma]."""
    i = jnp.arange(d, dtype=U32)
    h = 0.5 + _unit(i, words[0])
    c = 2.0 * _unit(i, words[1] ^ U32(0x5BD1E995)) - 1.0
    z = sigma * (2.0 * _unit(i, noise) - 1.0)
    return h, c, z


def make_loss(d: int, sigma: float):
    def loss(x, batch):
        h, c, z = quad_terms(batch["seed"], batch["y"], d, sigma)
        return 0.5 * jnp.sum(h * (x - c) ** 2) + jnp.sum(z * x)
    return loss


def quad_grad(x, words, noise, d: int, sigma: float):
    h, c, z = quad_terms(words, noise, d, sigma)
    return h * (x - c) + z


def arrival_probs(kind: str, m: int) -> np.ndarray:
    ids = np.arange(1, m + 1, dtype=np.float64)
    p = {"proportional": ids, "squared": ids ** 2,
         "uniform": np.ones_like(ids)}[kind]
    return (p / p.sum()).astype(np.float32)


def warm_counts(t: dict) -> np.ndarray:
    """Update counts after ``warm`` arrivals per worker, drawn in
    proportion to the arrival probabilities (at least one each)."""
    p = arrival_probs(t["arrival"], t["m"])
    return np.maximum(1.0, np.round(p * t["warm"] * t["m"])).astype(np.float32)


def noise_words(words, n: int):
    return jax.random.bits(key_of(words, 2), (n,), U32)


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build(cell, words, fault=None):
    from repro.core.attacks import AttackConfig
    from repro.core.engine import (AsyncByzantineEngine, EngineConfig,
                                   EngineState, engine_init)
    from repro.optim.mu2sgd import OptConfig

    t = cell.traffic
    m, d = t["m"], t["d"]
    cfg = EngineConfig(
        m=m, byz=tuple(t["byz"]), attack=AttackConfig(name=t["attack"]),
        agg=t["agg"], lam=t["lam"], arrival=t["arrival"],
        opt=OptConfig(name="mu2", lr=t["lr"], gamma=t["gamma"], beta=t["beta"]),
        agg_backend=t["agg_backend"])
    eng = AsyncByzantineEngine(cfg, make_loss(d, t["sigma"]))
    if fault == "half_batch":
        full = eng.agg_fn
        half = (m + 1) // 2
        eng.agg_fn = lambda D, s: full(D[:half], s[:half])
    elif fault == "answer_altered":
        full = eng.agg_fn
        eng.agg_fn = lambda D, s: full(D, s).at[0].add(1.0)
    elif fault == "state_unchanged":
        eng._step = jax.jit(lambda s, b: (s, eng._step_impl(s, b)[1]))
    elif fault is not None:
        raise ValueError(f"server has no fault {fault!r}")

    counts = jnp.asarray(warm_counts(t))

    @jax.jit
    def init(words):
        x1 = jax.random.uniform(key_of(words, 0), (d,), F32, -1.0, 1.0)
        batches = {"y": noise_words(words, m),
                   "seed": jnp.broadcast_to(words, (m, 2))}
        st = engine_init(cfg, eng.grad_fn, x1, batches, eng.byz_mask)
        return EngineState(w=st.w, x=st.x, D=st.D, S=counts, Xq=st.Xq,
                           t=st.t, t_byz=st.t_byz, key=key_of(words, 3))

    @jax.jit
    def feed(words):
        ys = noise_words(words, m + t["n_batches"])[m:]
        return [{"y": ys[k], "seed": words} for k in range(t["n_batches"])]

    return eng, init(words), feed(words)


def drive(cell, seed: int, seconds: float, trace: bool, out_dir: Path,
          fault=None, devices=None, t_start=None, setup_box=None) -> tuple:
    t = cell.traffic
    words = seed_words(seed)
    eng, state, batches = build(cell, jnp.asarray(words), fault)
    k = t["check_steps"]
    workers = []
    for i in range(k):
        state, met = eng.step(state, batches[i])
        workers.append(met["worker"])
    got = {"workers": [int(w) for w in workers], "w": np.asarray(state.w),
           "D": np.asarray(state.D)}                       # host copies

    hold = {"state": state, "n": 0}
    tracer = (harness.Tracer(out_dir, seconds * t["trace_from"],
                             seconds * t["trace_from"] + t["trace_seconds"],
                             lambda: jax.block_until_ready(hold["state"]))
              if trace else None)
    with harness.CompileGuard() as guard:
        t0 = harness.now()
        if setup_box is not None:
            setup_box["setup_s"] = t0 - (t_start if t_start is not None else t0)
        prev = None
        while True:
            hold["state"], met = eng.step(
                hold["state"], batches[(k + hold["n"]) % len(batches)])
            hold["n"] += 1
            if prev is not None:
                prev.block_until_ready()
            prev = met["worker"]
            elapsed = harness.now() - t0
            if tracer is not None:
                tracer.poll(elapsed, lambda: {"steps": hold["n"]})
            if elapsed >= seconds and (tracer is None or tracer.state == "done"):
                break
        jax.block_until_ready(hold["state"])
        window_s = harness.now() - t0
    mem = harness.memory_peak(devices) if devices else 0
    snaps = tracer.snapshots if tracer is not None else {}
    rec = {"steps": hold["n"], "window_s": window_s, "m": t["m"], "d": t["d"],
           "compiles_in_window": guard.count,
           "steps_traced": (snaps["stop"]["steps"] - snaps["start"]["steps"]
                            if snaps else 0)}
    del state, hold
    return got, rec, mem


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def reference(cell, seed: int, precision: str = "f32") -> dict:
    """Alg. 2's first ``check_steps`` arrivals from the same state, in plain
    jax.numpy: float32 (``precision="bf16"``: the control, every buffer and
    operation in bfloat16)."""
    t = cell.traffic
    m, d = t["m"], t["d"]
    dt = {"f32": F32, "bf16": jnp.bfloat16}[precision]
    words = jnp.asarray(seed_words(seed))
    probs = jnp.asarray(arrival_probs(t["arrival"], m))
    byz = np.zeros(m, bool)
    byz[list(t["byz"])] = True
    byz = jnp.asarray(byz)
    grad = partial(quad_grad, d=d, sigma=t["sigma"])

    @jax.jit
    def init(words):
        x1 = jax.random.uniform(key_of(words, 0), (d,), F32, -1.0, 1.0)
        ys = noise_words(words, m)
        D = jnp.stack([grad(x1, words, ys[j]) for j in range(m)])
        D = jnp.where(byz[:, None], -D, D).astype(dt)
        x1 = x1.astype(dt)
        return {"w": x1, "x": x1, "D": D,
                "Xq": jnp.broadcast_to(x1, (m, d)).astype(dt),
                "S": jnp.asarray(warm_counts(t)), "key": key_of(words, 3)}

    @partial(jax.jit, donate_argnums=(0,))
    def step(st, noise):
        key, k_arr = jax.random.split(st["key"])
        i = jax.random.categorical(k_arr, jnp.log(probs))
        g = grad(st["x"].astype(F32), words, noise)
        gp = grad(st["Xq"][i].astype(F32), words, noise)
        d_new = ref_robust.corrected_momentum(
            g, gp, st["D"][i].astype(F32), t["beta"], first=False)
        d_new = jnp.where(byz[i], -d_new, d_new)
        S = st["S"].at[i].add(1.0)
        D = st["D"].at[i].set(d_new.astype(dt))
        Xq = st["Xq"].at[i].set(st["x"])
        d_hat = ref_robust.ctma_cwmed({"v": D}, S, t["lam"])["v"]
        w, x = ref_robust.anytime_update(
            {"v": st["w"].astype(F32)}, {"v": st["x"].astype(F32)},
            {"v": d_hat}, t["lr"], t["gamma"])
        return {"w": w["v"].astype(dt), "x": x["v"].astype(dt), "D": D,
                "Xq": Xq, "S": S, "key": key}, i

    st = init(words)
    ys = noise_words(words, m + t["n_batches"])[m:]
    workers = []
    for k in range(t["check_steps"]):
        st, i = step(st, ys[k])
        workers.append(int(i))
    return {"workers": workers, "w": np.asarray(st["w"].astype(F32)),
            "D": np.asarray(st["D"].astype(F32))}


def compare(got: dict, want: dict, cell, seed: int) -> list:
    """Arrivals (exact), and the parameters' change after the first steps
    and the momentum buffers after them, each as an error relative to the
    reference's."""
    t = cell.traffic
    words = jnp.asarray(seed_words(seed))
    w0 = np.asarray(jax.jit(lambda w: jax.random.uniform(
        key_of(w, 0), (t["d"],), F32, -1.0, 1.0))(words), np.float64)
    dg, dw = got["w"].astype(np.float64) - w0, want["w"].astype(np.float64) - w0
    rel_w = float(np.linalg.norm(dg - dw) / np.linalg.norm(dw))
    rel_d = float(np.linalg.norm(got["D"] - want["D"]) / np.linalg.norm(want["D"]))
    miss = sum(a != b for a, b in zip(got["workers"], want["workers"]))
    return [harness.Check("arrivals_differing", float(miss), 0.0),
            harness.Check("param_change_rel_err", rel_w,
                          cell.limit("param_change_rel_err")),
            harness.Check("momenta_rel_err", rel_d,
                          cell.limit("momenta_rel_err"))]


def run(cell, seed: int, seconds: float, trace: bool, out_dir: Path,
        devices=None, fault=None, t_start: Optional[float] = None,
        setup_box: Optional[dict] = None) -> harness.Outcome:
    got, rec, mem = drive(cell, seed, seconds, trace, out_dir, fault, devices,
                          t_start, setup_box)
    t_ref = harness.now()
    want = reference(cell, seed)
    harness.note(rec, reference_s=harness.now() - t_ref)
    checks = compare(got, want, cell, seed)
    del got, want
    checks.append(harness.Check("compiles_in_window",
                                float(rec["compiles_in_window"]), 0.0))
    return harness.Outcome(
        attempted=rec["steps"], failed=0, checks=checks,
        e2e={"server_updates_per_s": rec["steps"] / rec["window_s"]},
        memory_peak_bytes=mem, records=rec, trace_dir=out_dir)


def readings(cell, seed: int, seconds: float, fault=None, devices=None,
             out_dir: Optional[Path] = None) -> dict:
    """The compared numbers of the program (with ``fault`` planted, if one
    is named) and, for the sound program, of the control: the reference
    with its buffers in bfloat16 in the program's place."""
    got, _, _ = drive(cell, seed, seconds, False, out_dir, fault, devices)
    want = reference(cell, seed)
    out = {"program": {c.name: c.value for c in compare(got, want, cell, seed)}}
    del got
    if fault is None:
        ctl = reference(cell, seed, precision="bf16")
        out["control"] = {c.name: c.value for c in compare(ctl, want, cell, seed)}
    return out
