"""CPU tests of the phase reduction (bench/scopes.py) and its two readers,
on a synthetic xplane and the Chrome-trace file the profiler writes beside
it."""
from __future__ import annotations

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, scopes

HERE = Path(__file__).resolve().parent
CONFIG = json.loads((HERE / "configs" / "qwen2-1.5b-4l.json").read_text())
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
STEP_MOD, OTHER_MOD = "jit_step(1)", "jit_other(2)"

# (instruction, start us, duration us, program, tf_op, operand): a named
# while with a nested fusion; a copy that XLA adds, read by the momentum's
# fusion.9 but followed by grad_xprev's fusion.3; an unnamed while around
# the median kernel; an unnamed copy whose reader is not in the profile (a
# bitcast between them never runs); a copy read by the metrics' reduction
# alone; and another program that reuses the names fusion.3 (unnamed there)
# and fusion.2
OPS = [
    ("while.1", 0.0, 4.0, STEP_MOD,
     "jit(step)/vmap(robust_step/grad_x)/jvp()/while", "p"),
    ("fusion.2", 1.0, 2.0, STEP_MOD,
     "jit(step)/vmap(robust_step/grad_x)/jvp()/while/body/dot_general", "p"),
    ("copy.8", 4.0, 0.1, STEP_MOD, "", "p"),
    ("fusion.3", 4.1, 0.9, STEP_MOD,
     "jit(step)/vmap(robust_step/grad_xprev)/transpose(jvp())/dot_general",
     "p"),
    ("while.7", 5.0, 2.2, STEP_MOD, "", "p"),     # XLA's loop: no name
    ("wcwmed.4", 5.1, 2.0, STEP_MOD,
     "jit(step)/robust_step/aggregate/anchor/jit(wcwmed_pallas)/wcwmed/"
     "pallas_call", "p"),
    ("copy.5", 7.2, None, STEP_MOD, "", "p"),     # XLA's copy: no name
    ("fusion.9", 7.8, 0.2, STEP_MOD, "jit(step)/robust_step/momentum/add",
     "copy.8"),
    ("fusion.6", 8.0, 1.0, STEP_MOD, "jit(step)/robust_step/update/sub", "p"),
    ("copy.10", 9.0, 0.1, STEP_MOD, "", "p"),
    ("reduce.11", 9.1, 0.1, STEP_MOD, "jit(step)/reduce_sum", "copy.10"),
    ("fusion.3", 9.6, 0.1, OTHER_MOD, "", "p"),
    ("fusion.2", 9.7, 0.1, OTHER_MOD, "jit(other)/add", "p"),
]
MODULES = [(STEP_MOD, 0.0, 9.5), (OTHER_MOD, 9.6, 0.4)]


def _ops(copy_us: float) -> list:
    return [(n, s, copy_us if d is None else d, m, t, o)
            for n, s, d, m, t, o in OPS]


def _text(name: str, operand: str) -> str:
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %{operand})"


def _xplane(copy_us: float):
    """The ops above on one device inside bench.window [0, 10 us]."""
    from jax.profiler import ProfileData
    ops = _ops(copy_us)
    texts = sorted({_text(n, o) for n, *_, o in ops})
    mid = {t: i + 1 for i, t in enumerate(texts)}
    mods = {m: 100 + i for i, (m, _, _) in enumerate(MODULES)}

    def ev(i, start, dur):
        return (f"events {{ metadata_id: {i} offset_ps: {round(start * 1e6)} "
                f"duration_ps: {round(dur * 1e6)} }}")

    meta = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{t}" }} }} '
        for t, i in mid.items())
    meta += "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{m}" }} }} '
                    for m, i in mods.items())
    txt = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {' '.join(ev(mid[_text(n, o)], s, d) for n, s, d, _, _, o in ops)} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {' '.join(ev(mods[m], s, d) for m, s, d in MODULES)} }}
  {meta} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {ev(1, 0, 10)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }} }}
"""
    return txt, ProfileData.from_text_proto(txt)


def _trace_json(copy_us: float) -> dict:
    """The Chrome-trace document the profiler writes beside the xplane: the
    same device events, each op with its ``long_name`` and, where it has
    one, its ``tf_op``."""
    meta = [{"ph": "M", "pid": 3, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 3, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": 701, "name": "process_name",
             "args": {"name": "/host:CPU"}}]
    mods = [{"ph": "X", "pid": 3, "tid": 2, "ts": s, "dur": d, "name": m}
            for m, s, d in MODULES]
    ops = [{"ph": "X", "pid": 3, "tid": 3, "ts": s, "dur": d,
            "name": n.split(".")[0],
            "args": {"long_name": _text(n, o),
                     **({"tf_op": t + ":"} if t else {})}}
           for n, s, d, _, t, o in _ops(copy_us)]
    return {"displayTimeUnit": "ns", "traceEvents": meta + mods + ops}


def _phases(copy_us: float) -> scopes.PhaseTimes:
    _, pd = _xplane(copy_us)
    return scopes.reduce_phases(pd, scopes.op_names(_trace_json(copy_us)))


def test_nested_intervals_count_once():
    pt = _phases(0.1)
    # the while [0, 4] holds fusion.2 [1, 3]: grad_x is 4 us, not 6
    assert pt.seconds(scopes.GRAD_X) == pytest.approx(4e-6)
    assert pt.seconds(scopes.GRAD_X, scopes.GRAD_XPREV) == pytest.approx(4.9e-6)
    # the unnamed while [5, 7.2] around the kernel [5.1, 7.1]
    assert pt.seconds(scopes.AGGREGATE) == pytest.approx(2.2e-6)
    assert pt.seconds(scopes.AGGREGATE, named_only=True) == pytest.approx(2e-6)
    assert pt.seconds(scopes.UPDATE) == pytest.approx(1e-6)
    assert pt.seconds(scopes.ATTACK) == 0.0


def test_busy_matches_trace_reduce_and_bounds_the_phases():
    from bench import trace_reduce
    _, pd = _xplane(0.1)
    pt = _phases(0.1)
    s = trace_reduce.reduce_trace(pd)
    assert pt.busy_s == pytest.approx(s.busy_s)
    assert pt.busy_s == pytest.approx(8.9e-6)
    assert pt.covered_s == pytest.approx(8.5e-6) and pt.covered_s <= pt.busy_s
    # the gate counts the named ops alone: 8.1 of 8.9 us
    assert pt.coverage == pytest.approx(8.1 / 8.9)


def test_unnamed_ops_take_their_readers_else_the_next_named_op():
    """copy.8 is read by the momentum's fusion.9, though grad_xprev runs
    next; copy.5's reader is not in the profile, so it takes the next named
    op's phase, momentum; the while around the kernel takes the kernel's
    pass; copy.10 is read by the metrics' reduction alone, so it stays in no
    phase."""
    pt = _phases(0.1)
    assert pt.seconds(scopes.MOMENTUM) == pytest.approx(0.4e-6)
    assert pt.seconds(scopes.MOMENTUM, named_only=True) == pytest.approx(0.2e-6)
    assert pt.seconds(scopes.GRAD_XPREV) == pytest.approx(0.9e-6)
    got = {k: (p, how, pytest.approx(t)) for k, (p, how, t) in
           pt.unnamed_ops.items()}
    assert got == {
        "copy.8": (scopes.MOMENTUM, "readers", 0.1e-6),
        "copy.5": (scopes.MOMENTUM, "schedule", 0.1e-6),
        "while.7": (f"{scopes.AGGREGATE}/anchor", "schedule", 0.2e-6)}


def test_readers_followed_through_a_long_chain_and_not_a_cycle():
    """An unnamed op takes the phase of a named reader 5000 unnamed ops
    down a chain (no recursion limit); ops that read each other in a cycle,
    or whose readers disagree, are left to the schedule."""
    n = 5000
    names = {(STEP_MOD, f"copy.{i}"): ("", (f"copy.{i - 1}",) if i else ())
             for i in range(n)}
    names[(STEP_MOD, "add.1")] = (f"jit(step)/{scopes.UPDATE}/add",
                                  (f"copy.{n - 1}", "both.1"))
    names[(STEP_MOD, "add.2")] = (f"jit(step)/{scopes.MOMENTUM}/add",
                                  ("both.1",))
    names[(STEP_MOD, "both.1")] = ("", ())
    names[(STEP_MOD, "x.1")] = ("", ("y.1",))
    names[(STEP_MOD, "y.1")] = ("", ("x.1",))
    got = scopes._by_readers(names)
    assert len(got) == n
    assert got[(STEP_MOD, "copy.0")] == (scopes.UPDATE, None)


def test_unscoped_time_and_ops_reported():
    """In no phase: the copy only the metrics' reduction reads, that
    reduction, and the other program's ops, among them its unnamed fusion.3,
    whose name the step's grad_xprev op also has."""
    pt = _phases(0.1)
    assert pt.unscoped_s == pytest.approx(0.4e-6)
    top = dict(pt.top_unscoped())
    assert top == pytest.approx({"copy.10": 0.1e-6, "reduce.11": 0.1e-6,
                                 "fusion.3": 0.1e-6, "fusion.2": 0.1e-6})
    table = pt.table(steps=1)
    for row in ("robust_step/aggregate", "anchor", "copy.10",
                "copy.8", "(readers)", "(schedule)"):
        assert row in table, row


def test_an_op_name_is_looked_up_in_its_own_program():
    """The other program's unnamed fusion.3 does not take the step's
    fusion.3's phase: the map is keyed by (program, instruction) alone."""
    names = scopes.op_names(_trace_json(0.1))
    assert names[(STEP_MOD, "fusion.3")][0].startswith(
        "jit(step)/vmap(robust_step/grad_xprev)")
    assert names[(OTHER_MOD, "fusion.3")] == ("", ("p",))
    assert ("", "fusion.3") not in names
    assert names[(STEP_MOD, "fusion.9")][1] == ("copy.8",)


def test_phase_of_takes_the_outer_phase():
    assert scopes.phase_of("jit(step)/robust_step/aggregate/anchor/x:") \
        == scopes.AGGREGATE
    assert scopes.phase_of("jit(step)/vmap(robust_step/grad_xprev)/jvp()/y") \
        == scopes.GRAD_XPREV
    assert scopes.phase_of("jit(step)/reduce_sum:") is None
    assert scopes.phase_of(None) is None


@pytest.mark.parametrize("tf_op,want", [
    ("jit(step)/robust_step/aggregate/anchor/jit(wcwmed_pallas)/wcwmed:",
     "anchor"),
    ("jit(step)/robust_step/aggregate/distance/reduce_sum:", "distance"),
    ("jit(step)/robust_step/aggregate/combine/m,md->d/dot_general:",
     "combine"),
    ("jit(step)/robust_step/aggregate/weiszfeld/while:", "weiszfeld"),
    ("jit(step)/robust_step/aggregate/reduce_sum:", None),
    ("jit(step)/robust_step/update/anchor/sub:", None),
])
def test_pass_of_names_the_aggregate_pass(tf_op, want):
    got = scopes.pass_of(tf_op)
    assert got == (f"{scopes.AGGREGATE}/{want}" if want else None)


def test_recorded_tpu_trace_by_phase():
    """A profile recorded on one TPU v5 lite: three calls of a jitted step
    whose gradient, median (``aggregate/anchor``) and update are scoped and
    whose norm is not, inside bench.window."""
    from bench import trace_reduce
    d = HERE / "testdata" / "phases"
    pt = scopes.load_phases(d)
    assert pt.n_devices == 1
    assert pt.busy_s == pytest.approx(
        trace_reduce.reduce_trace(trace_reduce.load(d)).busy_s)
    for p in (scopes.GRAD_X, scopes.AGGREGATE, scopes.UPDATE):
        assert pt.seconds(p) > 0, p
    assert pt.seconds(scopes.GRAD_XPREV, scopes.MOMENTUM, scopes.ATTACK) == 0
    assert 0.5 < pt.coverage < 1 and pt.unscoped_s > 0
    assert "multiply_reduce_fusion" in dict(pt.top_unscoped())


def _write_profile(root: Path, cell: str, copy_us: float,
                   with_json: bool = True) -> None:
    from jax.profiler import ProfileData
    txt, _ = _xplane(copy_us)
    d = root / cell / "plugins" / "profile" / "2026_01_01_00_00_00"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(txt))
    if with_json:
        with gzip.open(d / "host.trace.json.gz", "wt") as f:
            json.dump(_trace_json(copy_us), f)


def _ctx(cell: str) -> dict:
    return {"cell": SimpleNamespace(name=cell), "config": CONFIG,
            "peaks": PEAKS, "trace": None,
            "records": {"steps_traced": 2, "tokens_per_step": 6144,
                        "seq": 512, "groups": 4, "state_bytes": 2}}


@pytest.fixture
def trace_root(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    return tmp_path


def test_readers_read_the_phases(trace_root, capsys):
    from bench.counts import param_count, train_flops_per_token
    _write_profile(trace_root, "cell.a", 0.1)               # 91.0 % covered
    ctx = _ctx("cell.a")
    agg = harness.load_reader("agg_roofline.train")(ctx)
    grad = harness.load_reader("grad_mfu.train")(ctx)
    nbytes = 2 * 5 * param_count(CONFIG) * 2
    assert agg == pytest.approx(100 * nbytes / 2.2e-6 / 819e9)
    flops = 2 * 6144 * train_flops_per_token(CONFIG, 512)
    assert grad == pytest.approx(100 * flops / 4.9e-6 / 197e12)
    err = capsys.readouterr().err
    assert err.count("robust_step/grad_xprev") == 1     # the table, once


@pytest.mark.parametrize("copy_us,with_json", [(0.5, True), (0.1, False)])
def test_readers_return_none_without_a_sound_map(trace_root, copy_us,
                                                 with_json):
    """At 87.1 % of busy time in the phases by name, or with no Chrome-trace file
    beside the xplane, the readers give no number and do not raise."""
    _write_profile(trace_root, "cell.b", copy_us, with_json)
    for name in ("agg_roofline.train", "grad_mfu.train"):
        assert harness.load_reader(name)(_ctx("cell.b")) is None


def test_readers_return_none_without_a_trace(trace_root):
    for name in ("agg_roofline.train", "grad_mfu.train"):
        assert harness.load_reader(name)(_ctx("cell.none")) is None
