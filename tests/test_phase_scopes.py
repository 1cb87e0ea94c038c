"""Device phases (``repro.obs.scopes``): the jitted steps name their work.

The robust train step and the stacked aggregators open
``jax.named_scope``s; these tests read the names back from the
compiled HLO's ``op_name`` metadata, where the profiler's per-op ``tf_op``
stat takes them from. A fusion carries its root instruction's name.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.dist.steps import (RobustDPConfig, init_train_state,
                              make_robust_train_step)
from repro.models import ModelConfig
from repro.obs import scopes
from repro.optim import OptConfig

TINY = ModelConfig(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv=2,
                   d_ff=128, vocab=64)
OPT = OptConfig(name="mu2", lr=5e-3, gamma=0.1, beta=0.25)
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
# instructions that move or name data and do no work of their own; XLA's
# layout and aliasing copies carry no op_name at all
NO_WORK = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "copy"}


def _computations(hlo: str) -> dict:
    """{computation name: [instruction line]}; the entry is under "ENTRY"."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split()
            cur = "ENTRY" if head[0] == "ENTRY" else head[0].lstrip("%")
            comps[cur] = []
        elif line == "}":
            cur = None
        elif cur is not None and " = " in line:
            comps[cur].append(line.strip())
    return comps


def _parse(line: str) -> tuple:
    """(name, opcode, op_name or None, operand names, fused computation)."""
    lhs, rhs = line.split(" = ", 1)
    name = lhs.replace("ROOT ", "").lstrip("%")
    op = OPCODE.search(" " + rhs.split(", metadata=")[0])
    on = OP_NAME.search(line)
    calls = re.search(r"calls=%([\w.\-]+)", rhs)
    return (name, op.group(1) if op else "?", on.group(1) if on else None,
            re.findall(r"%([\w.\-]+)", rhs), calls.group(1) if calls else None)


def _entry_op_names(hlo: str) -> tuple:
    """Entry instructions as {name: (opcode, op_name, operands)}, a fusion
    named by its root, and the operands of the entry's ROOT tuple."""
    comps = _computations(hlo)
    roots = {c: _parse(next(l for l in ls if l.startswith("ROOT ")))
             for c, ls in comps.items() if c != "ENTRY"}
    out, root_ops = {}, []
    for line in comps["ENTRY"]:
        name, op, on, operands, calls = _parse(line)
        if on is None and calls in roots:
            on = roots[calls][2]
        out[name] = (op, on, operands)
        if line.startswith("ROOT "):
            root_ops = operands
    return out, root_ops


def _compiled(step, state, batch) -> str:
    return jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile() \
        .as_text()


@pytest.fixture(scope="module")
def robust_hlo():
    """The robust step (ctma:cwmed, one sign-flipping group, so the attack
    does work) compiled for the CPU at a tiny size."""
    rcfg = RobustDPConfig(n_groups=4, agg="ctma:cwmed", lam=0.25,
                          byz_groups=(0,), byz_attack="sign_flip")
    state = jax.eval_shape(lambda: init_train_state(
        TINY, OPT, jax.random.PRNGKey(0), rcfg))
    batch = {k: jax.ShapeDtypeStruct((8, 16), jnp.int32)
             for k in ("tokens", "labels")}
    return _compiled(make_robust_train_step(TINY, OPT, rcfg), state, batch)


@pytest.mark.parametrize("name", scopes.ROBUST_PHASES)
def test_robust_step_names_every_phase(robust_hlo, name):
    assert any(name in on for on in OP_NAME.findall(robust_hlo)), name


def _reads_input(insts: dict) -> set:
    """Entry instructions that depend on a parameter of the step."""
    memo: dict = {}

    def reads(n):
        if n not in memo:
            memo[n] = False             # operands form a DAG
            op, _, operands = insts[n]
            memo[n] = op == "parameter" or any(
                reads(o) for o in operands if o in insts)
        return memo[n]

    return {n for n in insts if reads(n)}


def _metrics_only(insts: dict, outputs: list) -> set:
    """The metrics outputs and the instructions whose every user is one of
    them: work done for the metrics alone."""
    users: dict = {n: set() for n in insts}
    for n, (_, _, operands) in insts.items():
        for o in operands:
            if o in users:
                users[o].add(n)
    only = {n for n in outputs if n in insts}
    grew = True
    while grew:
        new = {n for n, us in users.items()
               if n not in only and us and us <= only}
        only |= new
        grew = bool(new)
    return only


def test_robust_step_scopes_every_working_instruction(robust_hlo):
    """Every entry instruction that does work is inside a ``robust_step/``
    phase, except:

    - instructions that do no work (``NO_WORK``);
    - the CPU backend's ``wrapped_*`` fusions: lone broadcasts and
      reduce-windows it wraps without metadata;
    - work on constants alone (the RoPE tables, the causal mask): JAX binds
      it outside the vmap and jvp that carry the phase's name;
    - the loss and grad_norm reductions, which feed only the metrics outputs
      (the ROOT tuple's last two: grad_norm, loss)."""
    insts, root_ops = _entry_op_names(robust_hlo)
    scoped = {n for n, (_, on, _) in insts.items()
              if on is not None and f"{scopes.ROBUST_STEP}/" in on}
    metrics_only = _metrics_only(insts, root_ops[-2:]) - scoped
    live = _reads_input(insts)
    stray = sorted(f"{n}: {op} {on}" for n, (op, on, _) in insts.items()
                   if op not in NO_WORK and n not in scoped and n in live
                   and n not in metrics_only
                   and not (op == "fusion" and n.startswith("wrapped_")
                            and on is None))
    assert stray == []
    assert len(scoped) > 50 and metrics_only


@pytest.mark.parametrize("agg,passes", [
    ("ctma:cwmed", (scopes.ANCHOR, scopes.DISTANCE, scopes.COMBINE)),
    ("gm", (scopes.ANCHOR, scopes.WEISZFELD)),
])
def test_stacked_aggregate_names_its_passes(agg, passes):
    from repro.agg import resolve
    fn = resolve(agg, lam=0.25)

    def f(tree, s):
        with scopes.phase(scopes.AGGREGATE):
            return fn(tree, s)

    tree = {"a": jax.ShapeDtypeStruct((5, 8, 16), jnp.float32),
            "b": jax.ShapeDtypeStruct((5, 32), jnp.float32)}
    hlo = jax.jit(f).lower(tree, jax.ShapeDtypeStruct((5,), jnp.float32)) \
        .compile().as_text()
    names = OP_NAME.findall(hlo)
    for p in passes:
        assert any(f"{scopes.AGGREGATE}/{p}/" in on for on in names), p

