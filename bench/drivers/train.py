"""Robust data-parallel training: the program's ``make_robust_train_step``
(groups of rows, some Byzantine, a robust aggregate of the group momenta,
mu^2-SGD's AnyTime update) on a donated state, driven for the window.

Set-up builds the state and the token batches on the device from the seed,
compiles the step and drives it through its first ``check_steps`` steps, the
steps the reference then follows: each step's loss, the group momenta after
step 1 (the first gradients as the optimizer gets them: their norms, and
their values at coordinates sampled from the seed), the norm of each step's
robust aggregate, the update the optimizer applies, and the parameters'
change (w and x against the initial weights) after the last of them. The
same compiled step and state then run the window.
"""
from __future__ import annotations

from functools import partial
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.model import (canonical_shapes, check_layout, from_program,
                         key_of, make_weights, program_config, seed_words,
                         to_program)
from bench.reference import lm as ref_lm
from bench.reference import robust as ref_robust

F32 = jnp.float32


def make_tokens(c: dict, t: dict, words):
    """(n_batches, rows, seq + 1) token ids whose ranks follow Zipf's law with
    the traffic file's exponent, as words of natural text do; a permutation
    drawn from the seed maps ranks to ids."""
    shape = (t["n_batches"], t["rows"], t["seq"] + 1)
    V = c["vocab_size"]
    cdf = jnp.cumsum(jnp.arange(1, V + 1, dtype=F32) ** -float(t["zipf"]))
    u = jax.random.uniform(key_of(words, 1), shape, F32) * cdf[-1]
    rank = jnp.minimum(jnp.searchsorted(cdf, u, side="right"), V - 1)
    return jax.random.permutation(key_of(words, 2), V)[rank].astype(jnp.int32)


def sample_index(c: dict, seed: int, k: int) -> dict:
    """``k`` flat coordinates of each canonical leaf, drawn from the seed on
    the host, the same for the program and the reference."""
    rng = np.random.default_rng(seed_words(seed).tolist())
    return {n: jnp.asarray(rng.integers(0, int(np.prod(sh)), k), jnp.int32)
            for n, sh in _flat_shapes(canonical_shapes(c)).items()}


def _flat_shapes(shapes: dict, prefix: str = "") -> dict:
    out = {}
    for n, v in shapes.items():
        if isinstance(v, dict):
            out.update(_flat_shapes(v, f"{prefix}{n}/"))
        else:
            out[prefix + n] = v
    return out


def _sample(D: dict, idx: dict) -> dict:
    """The group momenta at the sampled coordinates, (G, k) per leaf."""
    flat = {"/".join(str(getattr(q, "key", q)) for q in path): v
            for path, v in jax.tree_util.tree_flatten_with_path(D)[0]}
    return {n: flat[n].reshape(flat[n].shape[0], -1)[:, i].astype(F32)
            for n, i in idx.items()}


def _leaf_norms(tree):
    """Per-leaf L2 norms of each row of the leading axis."""
    def norm(l):
        l = l.astype(F32)
        return jnp.sqrt(jnp.sum(l.reshape(l.shape[0], -1) ** 2, axis=1))
    return jax.tree_util.tree_map(norm, tree)


def change_norms(w, x, w0) -> dict:
    """Per-leaf L2 norms of the iterate ``w`` and the query point ``x`` less
    the weights ``w0`` they started from, from flat dicts of host arrays,
    exactly: in float64 over the elements whose bits changed. On the host
    because the chip has no room for a second copy of the weights beside
    the state and the step. Each side keeps its own start: weights drawn
    again from the seed in a program that fuses the draw with other work can
    differ in the last bit, by far more than three steps move them."""
    def norm(a, a0):
        a, a0 = a.reshape(-1), a0.reshape(-1)
        bits = np.dtype(f"u{a.itemsize}")
        i = np.flatnonzero(a.view(bits) != a0.view(bits))
        d = a[i].astype(np.float64) - a0[i].astype(np.float64)
        return float(np.sqrt(np.sum(d * d)))
    return {k: {n: norm(a, w0[n]) for n, a in t.items()}
            for k, t in (("w", w), ("x", x))}


def _flat(tree) -> dict:
    """Canonical tree -> {leaf name: numpy value}."""
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = np.asarray(v)
    return out


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def build(cell, words, cfg_overrides: Optional[dict] = None, fault=None):
    """(state, step, batches, readers) of the program, nothing run yet."""
    from repro.dist.steps import (RobustDPConfig, TrainState,
                                  make_robust_train_step)
    from repro.optim.mu2sgd import OptConfig, init_opt

    c, t = cell.config, cell.traffic
    cfg = program_config(c, **(cfg_overrides or {}))
    check_layout(c, cfg)
    opt = OptConfig(name="mu2", lr=t["lr"], gamma=t["gamma"], beta=t["beta"])
    G = t["groups"]
    rcfg = RobustDPConfig(n_groups=G, agg=t["agg"], lam=t["lam"],
                          byz_groups=tuple(t["byz_groups"]),
                          byz_attack=t["attack"])

    @jax.jit
    def init(words):
        params = to_program(make_weights(c, words))
        D = jax.tree_util.tree_map(lambda p: jnp.zeros((G,) + p.shape, p.dtype),
                                   params)
        return TrainState(opt=init_opt(opt, params), D=D,
                          counts=jnp.zeros((G,), F32))

    @jax.jit
    def feed(words):
        toks = make_tokens(c, t, words)
        return [{"tokens": toks[i, :, :-1], "labels": toks[i, :, 1:]}
                for i in range(t["n_batches"])]

    body = make_robust_train_step(cfg, opt, rcfg)
    if fault == "state_unchanged":
        step = jax.jit(lambda s, b: (s, body(s, b)[1]))
    elif fault == "params_unchanged":
        def frozen(s, b):       # the momenta move, the weights do not
            new, met = body(s, b)
            return new._replace(opt=new.opt._replace(
                w=s.opt.w, x=s.opt.x, x_prev=s.opt.x_prev)), met
        step = jax.jit(frozen, donate_argnums=(0,))
    elif fault == "half_batch":
        half = t["rows"] // 2
        step = jax.jit(lambda s, b: body(s, jax.tree_util.tree_map(
            lambda v: v[:half], b)), donate_argnums=(0,))
    elif fault is None:
        step = jax.jit(body, donate_argnums=(0,))
    else:
        raise ValueError(f"train has no fault {fault!r}")

    group_reads = jax.jit(lambda D, idx: (
        _leaf_norms(from_program(D)), _sample(from_program(D), idx)))
    return init(words), step, feed(words), group_reads


def drive(cell, seed: int, seconds: float, trace: bool, out_dir: Path,
          cfg_overrides: Optional[dict] = None, fault=None,
          devices=None, t_start: Optional[float] = None,
          setup_box: Optional[dict] = None) -> tuple:
    """Set-up, the first steps, and the window. Returns (readings of the
    first steps, window records, memory peak)."""
    t = cell.traffic
    words = seed_words(seed)
    state, step, batches, group_reads = build(cell, words, cfg_overrides,
                                              fault)
    idx = sample_index(cell.config, seed, t["grad_samples"])
    w0 = _flat(from_program(state.opt.w))        # the start, on the host
    k = t["check_steps"]
    mets, first = [], None
    for i in range(k):
        state, met = step(state, batches[i])
        mets.append(met)
        if i == 0:
            first, sampled = group_reads(state.D, idx)
    first_reads = {"loss": [float(m["loss"]) for m in mets],
                   "update_norm": [float(m["grad_norm"]) for m in mets],
                   "group_norms": _flat(first),
                   "group_sample": {n: np.asarray(v) for n, v in sampled.items()},
                   "param_change": change_norms(
                       _flat(from_program(state.opt.w)),
                       _flat(from_program(state.opt.x)), w0)}
    del w0

    tokens = t["rows"] * t["seq"]
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
            hold["state"], met = step(hold["state"],
                                      batches[(k + hold["n"]) % len(batches)])
            hold["n"] += 1
            if prev is not None:
                prev.block_until_ready()
            prev = met["loss"]
            elapsed = harness.now() - t0
            if tracer is not None:
                tracer.poll(elapsed, lambda: {"steps": hold["n"]})
            if elapsed >= seconds and (tracer is None or tracer.state == "done"):
                break
        jax.block_until_ready(hold["state"])
        window_s = harness.now() - t0
    if tracer is not None:
        tracer.stop(lambda: {"steps": hold["n"]})
    mem = harness.memory_peak(devices) if devices else 0
    snaps = tracer.snapshots if tracer is not None else {}
    records = {
        "steps": hold["n"], "window_s": window_s,
        "tokens_per_step": tokens, "seq": t["seq"], "groups": t["groups"],
        "state_bytes": jnp.dtype(cell.config["torch_dtype"]).itemsize,
        "compiles_in_window": guard.count,
        "steps_traced": (snaps["stop"]["steps"] - snaps["start"]["steps"]
                         if snaps else 0),
    }
    del state, hold, batches
    return first_reads, records, mem


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def reference(cell, seed: int, precision: str = "f32") -> dict:
    """The first ``check_steps`` steps of the configured algorithm, in plain
    jax.numpy: float32 arithmetic (``precision="fp8"``: the control), the
    state stored in the configuration's dtype as the algorithm keeps it, a
    group's rows in one gradient pass."""
    c, t = cell.config, cell.traffic
    mm = ref_lm.MATMULS[precision]
    dtype = jnp.dtype(c["torch_dtype"])
    G, R, S = t["groups"], t["rows"], t["seq"]
    rows_g = R // G
    words = seed_words(seed)
    w0 = jax.jit(lambda w: make_weights(c, w))(words)
    toks = np.asarray(jax.jit(lambda w: make_tokens(c, t, w))(words))

    @jax.jit
    def mean_grad(p, tk, lb):
        """Mean next-token loss over a group's rows and its gradient."""
        f = lambda q: ref_lm.loss_sum(c, q, tk, lb, mm)
        v, g = jax.value_and_grad(f)(jax.tree_util.tree_map(
            lambda a: a.astype(F32), p))
        n = tk.shape[0] * tk.shape[1]
        return v / n, jax.tree_util.tree_map(lambda a: a * (1.0 / n), g)

    @partial(jax.jit, static_argnames=("first",), donate_argnums=(0,))
    def momentum(D, g, gp, grp, beta, *, first):
        d = jax.tree_util.tree_map(
            lambda dl, gl, gpl: ref_robust.corrected_momentum(
                gl, gpl, dl[grp].astype(F32), beta, first), D, g, gp)
        return jax.tree_util.tree_map(
            lambda dl, nl: dl.at[grp].set(nl.astype(dl.dtype)), D, d)

    @jax.jit
    def server(w, x, D, s):
        d_hat = ref_robust.ctma_cwmed(D, s, t["lam"])
        w_new, x_new = ref_robust.anytime_update(
            _cast(w, F32), _cast(x, F32), d_hat, t["lr"], t["gamma"], dtype)
        norm = jnp.sqrt(sum(jnp.sum(v * v) for v in jax.tree_util.tree_leaves(d_hat)))
        return w_new, x_new, norm

    w = x = xp = w0
    D = jax.tree_util.tree_map(lambda p: jnp.zeros((G,) + p.shape, dtype), w0)
    losses, updates, first_norms = [], [], None
    byz = set(t["byz_groups"])
    if t["attack"] != "label_flip":
        raise ValueError(f"the reference has no attack {t['attack']!r}")
    for step_i in range(t["check_steps"]):
        first = step_i == 0
        step_losses = []
        for grp in range(G):
            tk = toks[step_i, grp * rows_g:(grp + 1) * rows_g]
            lb = tk[:, 1:]
            if grp in byz:
                lb = c["vocab_size"] - 1 - lb
            lval, g = mean_grad(x, tk[:, :-1], lb)
            gp = g if first else mean_grad(xp, tk[:, :-1], lb)[1]
            step_losses.append(float(lval))
            D = momentum(D, g, gp, grp, jnp.float32(t["beta"]), first=first)
            del g, gp
        losses.append(float(np.mean(step_losses)))
        if first:
            first_norms = _flat(jax.jit(_leaf_norms)(D))
            sampled = {n: np.asarray(v) for n, v in jax.jit(_sample)(
                D, sample_index(c, seed, t["grad_samples"])).items()}
        s = jnp.full((G,), float(step_i + 1), F32)
        xp = x
        w, x, norm = server(w, x, D, s)
        updates.append(float(norm))
    del D, xp
    change = change_norms(_flat(w), _flat(x), _flat(w0))
    return {"loss": losses, "update_norm": updates, "group_norms": first_norms,
            "group_sample": sampled,
            "param_change": change}


def _cast(a, dtype):
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), a)


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

def _norm_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Worst leaf: |got - want| over the larger of want and the median
    leaf's want, per group. Arrays are (leaves, groups)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    med = np.median(want, axis=0)
    return float(np.max(np.abs(got - want) / np.maximum(want, med)))


def _rel_gap(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def _sample_gap(got: dict, want: dict, names: list) -> float:
    """The first gradients at the sampled coordinates: per leaf and group,
    the norm of the difference over the larger of the reference's norm and
    the median leaf's, worst leaf and group."""
    diff = np.stack([np.linalg.norm(np.asarray(got[n], np.float64)
                                    - np.asarray(want[n], np.float64), axis=1)
                     for n in names])                              # (leaves, G)
    ref = np.stack([np.linalg.norm(np.asarray(want[n], np.float64), axis=1)
                    for n in names])
    return float(np.max(diff / np.maximum(ref, np.median(ref, axis=0))))


def live_leaves(group_norms: dict) -> list:
    """Leaves whose first gradient in the reference (the largest over the
    groups) is at least a thousandth of the median leaf's. The others, such
    as a key bias under softmax, have no gradient but round-off, and move by
    round-off alone."""
    g = {n: float(np.max(v)) for n, v in group_norms.items()}
    med = float(np.median(list(g.values())))
    return sorted(n for n, v in g.items() if v >= 1e-3 * med)


def _change_gaps(got: dict, want: dict, names: list, k: str) -> np.ndarray:
    """Per-leaf gaps of the change of tree ``k`` (w or x): |got - want| over
    the larger of want and the median leaf's want."""
    g = np.array([got[k][n] for n in names], np.float64)
    w = np.array([want[k][n] for n in names], np.float64)
    return np.abs(g - w) / np.maximum(w, np.median(w))


def change_gap(got: dict, want: dict, names: list, median: bool = False
               ) -> float:
    """The parameters' change: the worst leaf's gap (``median``: the median
    leaf's, which ``bench/control.py`` reports beside it), the larger over w
    and x."""
    pick = np.median if median else np.max
    return max(float(pick(_change_gaps(got, want, names, k)))
               for k in ("w", "x"))


def compare(got: dict, want: dict, cell) -> list:
    """The numbers and their limits: each step's loss; the first gradients'
    norms by the worst leaf, and the first gradients themselves at
    coordinates sampled from the seed; each step's update norm; and the
    parameters' change after the checked steps by the worst live leaf."""
    names = sorted(want["group_norms"])
    gn_w = np.stack([want["group_norms"][n] for n in names])      # (leaves, G)
    gn_g = np.stack([got["group_norms"][n] for n in names])
    live = live_leaves(want["group_norms"])
    return [
        harness.Check("loss_rel_gap", _rel_gap(got["loss"], want["loss"]),
                      cell.limit("loss_rel_gap")),
        harness.Check("first_grad_norm_gap", _norm_gap(gn_g, gn_w),
                      cell.limit("first_grad_norm_gap")),
        harness.Check("first_grad_sample_gap",
                      _sample_gap(got["group_sample"], want["group_sample"],
                                  live),
                      cell.limit("first_grad_sample_gap")),
        harness.Check("update_norm_gap",
                      _rel_gap(got["update_norm"], want["update_norm"]),
                      cell.limit("update_norm_gap")),
        harness.Check("param_change_gap",
                      change_gap(got["param_change"], want["param_change"],
                                 live),
                      cell.limit("param_change_gap")),
    ]


def run(cell, seed: int, seconds: float, trace: bool, out_dir: Path,
        devices=None, cfg_overrides: Optional[dict] = None, fault=None,
        t_start: Optional[float] = None,
        setup_box: Optional[dict] = None) -> harness.Outcome:
    got, rec, mem = drive(cell, seed, seconds, trace, out_dir, cfg_overrides,
                          fault, devices, t_start, setup_box)
    t_ref = harness.now()
    want = reference(cell, seed)
    harness.note(rec, reference_s=harness.now() - t_ref)
    checks = compare(got, want, cell)
    checks.append(harness.Check("compiles_in_window",
                                float(rec["compiles_in_window"]), 0.0))
    return harness.Outcome(
        attempted=rec["steps"], failed=0, checks=checks,
        e2e={"train_tokens_per_s": rec["steps"] * rec["tokens_per_step"]
             / rec["window_s"]},
        memory_peak_bytes=mem, records=rec, trace_dir=out_dir)


def readings(cell, seed: int, seconds: float, fault=None, devices=None,
             out_dir: Optional[Path] = None) -> dict:
    """The compared numbers of the program (with ``fault`` planted, if one
    is named) and, for the sound program, of the control: the reference
    computed with fp8 products in the program's place. Each side also gives
    ``param_change_median_leaf``, the median leaf's change gap, which is
    reported and not compared."""
    got, _, _ = drive(cell, seed, seconds, False, out_dir, fault=fault,
                      devices=devices)
    want = reference(cell, seed)
    live = live_leaves(want["group_norms"])

    def numbers(side):
        n = {c.name: c.value for c in compare(side, want, cell)}
        n["param_change_median_leaf"] = change_gap(
            side["param_change"], want["param_change"], live, median=True)
        return n

    out = {"program": numbers(got)}
    if fault is None:
        out["control"] = numbers(reference(cell, seed, precision="fp8"))
    return out
