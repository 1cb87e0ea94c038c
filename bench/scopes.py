"""Device time of the robust train step by phase, from a traced window.

The program names the phases of ``make_robust_train_step`` with
``jax.named_scope`` (``src/repro/obs/scopes.py``): ``robust_step/grad_x``,
``grad_xprev``, ``momentum``, ``attack``, ``aggregate`` and ``update``, and
the aggregate's passes under it (``anchor``, ``distance``, ``combine``,
``weiszfeld``). The names reach each HLO instruction's ``op_name``, and a
fusion takes its root instruction's, so a fusion counts for the phase of
its root. The profiler copies the name into the op's ``tf_op`` stat, but
only in the Chrome-trace file it writes beside the xplane
(``<host>.trace.json.gz``): the xplane that ``ProfileData`` reads on a TPU
holds the op's HLO text and times alone.

So the reduction reads ``trace.json.gz`` into a map {(module, HLO
instruction) -> tf_op}, then walks the xplane's device ``XLA Ops`` events
inside ``bench.window`` as ``trace_reduce`` does. A phase's seconds are the
union of its ops' intervals, so nested events (a ``while`` and its body)
count once; busy time no phase covers is unscoped.

What XLA adds itself (layout copies, broadcasts, async copies) has an empty
``tf_op``. Such an op counts for the phase and pass of the ops that read it,
where the profile shows them (their operands in ``long_name``, followed
through other unnamed ops) and they agree. Where it does not, because a
bitcast between the two never runs and so is not in the profile, the op
counts for the phase of the next named op the device runs in the same
program: XLA schedules an added copy just ahead of the op that reads it.
The 90 % gate below is on the named ops alone, so a broken map still shows.

    python -m bench.scopes <trace dir> [steps]   # prints the phase table
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

from bench.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW_SPAN,
                                _host_spans, _short, _union, find_xplane,
                                load)

ROOT = Path(__file__).resolve().parent.parent
TRACE_ROOT = ROOT / ".bench_trace"       # run.py's traced output, by cell

# the program's names (src/repro/obs/scopes.py), kept here as the yardstick
# reads them, so that a change to the program cannot move them
STEP = "robust_step"
GRAD_X, GRAD_XPREV = f"{STEP}/grad_x", f"{STEP}/grad_xprev"
MOMENTUM, ATTACK = f"{STEP}/momentum", f"{STEP}/attack"
AGGREGATE, UPDATE = f"{STEP}/aggregate", f"{STEP}/update"
PHASES = (GRAD_X, GRAD_XPREV, MOMENTUM, ATTACK, AGGREGATE, UPDATE)
# the aggregate's passes, each counted as "robust_step/aggregate/<pass>"
PASSES = ("anchor", "distance", "combine", "weiszfeld")
# below this share of busy time in the phases, by name, the map is taken
# as broken
MIN_COVERAGE = 0.9
_PHASE = re.compile(rf"{STEP}/(\w+)")
_PASS = re.compile(rf"{AGGREGATE}/({'|'.join(PASSES)})/")
_OPERAND = re.compile(r"%([\w.\-]+)")


@dataclass
class PhaseTimes:
    busy_s: float                 # mean over the devices' op lines
    n_devices: int
    # phase or pass -> per device, its ops' intervals merged (ns), XLA's
    # unnamed ops included; ``named``: the named ops' alone
    intervals: dict = field(default_factory=dict)
    named: dict = field(default_factory=dict)
    # op in no phase -> its device seconds outside every phase (summed
    # over devices)
    unscoped_ops: dict = field(default_factory=dict)
    # unnamed op -> (the phase or pass it was given, "readers" or
    # "schedule", its device seconds outside the named ops' intervals,
    # summed over devices)
    unnamed_ops: dict = field(default_factory=dict)

    def seconds(self, *phases: str, named_only: bool = False) -> float:
        """Device seconds per device in any of ``phases``, overlaps once."""
        table = self.named if named_only else self.intervals
        total = 0.0
        for dev in range(self.n_devices):
            ivs = [iv for p in phases for iv in table.get(p, {}).get(dev, [])]
            total += sum(e - s for s, e in _union(ivs))
        return total * 1e-9 / max(self.n_devices, 1)

    @property
    def covered_s(self) -> float:
        return self.seconds(*PHASES)

    @property
    def unscoped_s(self) -> float:
        return self.busy_s - self.covered_s

    @property
    def coverage(self) -> float:
        """Share of busy time in the phases by the ops' own names."""
        named = self.seconds(*PHASES, named_only=True)
        return named / self.busy_s if self.busy_s > 0 else 0.0

    def top_unscoped(self, n: int = 5) -> list:
        ops = sorted(self.unscoped_ops.items(), key=lambda kv: -kv[1])[:n]
        return [(k, v / max(self.n_devices, 1)) for k, v in ops]

    def top_unnamed(self, n: int = 8) -> list:
        ops = sorted(self.unnamed_ops.items(), key=lambda kv: -kv[1][2])
        return [(k, p, how, t / max(self.n_devices, 1))
                for k, (p, how, t) in ops[:n]]

    def table(self, steps: int) -> str:
        """Seconds a step and share of busy time, per phase and pass, and
        of that the seconds of XLA's unnamed ops given to it."""
        k = max(steps, 1)
        share = (lambda t: 100 * t / self.busy_s) if self.busy_s > 0 \
            else (lambda t: 0.0)
        rows = [f"phases over {steps} steps: busy {self.busy_s / k:.6f} s a "
                f"step, covered {100 * self.coverage:.3f} % by name, "
                f"{share(self.covered_s):.3f} % with XLA's unnamed ops"]

        def row(label, t, unnamed):
            rows.append(f"  {label:<28} {t / k:10.6f} s/step {share(t):8.3f} "
                        f"%  unnamed {unnamed / k:.6f}")

        passes = [f"{AGGREGATE}/{p}" for p in PASSES]
        for p in sorted(set(self.intervals) - set(passes) | set(PHASES)):
            t = self.seconds(p)
            row(p, t, t - self.seconds(p, named_only=True))
            for q in (passes if p == AGGREGATE else []):
                if q in self.intervals:
                    t = self.seconds(q)
                    row("  " + q.rsplit("/", 1)[1], t,
                        t - self.seconds(q, named_only=True))
        row("(all phases)", self.covered_s,
            self.covered_s - self.seconds(*PHASES, named_only=True))
        rows.append(f"  {'(unscoped)':<28} {self.unscoped_s / k:10.6f} s/step "
                    f"{share(self.unscoped_s):8.3f} %")
        for name, t in self.top_unscoped():
            rows.append(f"  unscoped op {name:<28} {t / k:10.6f} s/step")
        for name, p, how, t in self.top_unnamed():
            rows.append(f"  unnamed op {name:<29} {t / k:10.6f} s/step -> "
                        f"{p} ({how})")
        return "\n".join(rows)


def phase_of(tf_op: Optional[str]) -> Optional[str]:
    """The ``robust_step/<phase>`` an op's JAX name stack names first (a
    nested scope such as ``aggregate/anchor`` counts for ``aggregate``)."""
    m = _PHASE.search(tf_op or "")
    return f"{STEP}/{m.group(1)}" if m else None


def pass_of(tf_op: Optional[str]) -> Optional[str]:
    """``robust_step/aggregate/<pass>`` for an op in one of ``PASSES``."""
    m = _PASS.search(tf_op or "")
    return f"{AGGREGATE}/{m.group(1)}" if m else None


def _outside(merged: list, starts: list, s: float, e: float) -> float:
    """Length of [s, e] outside the disjoint sorted intervals ``merged``
    (``starts`` their starts)."""
    out, i = e - s, max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        out -= max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return out


def _enclosing(starts: list, spans: list, t: float) -> str:
    """Name of the span in ``spans`` (sorted by start) that contains t."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return spans[i][2]
    return ""


def op_names(doc: dict) -> dict:
    """{(module, HLO instruction): (tf_op, operands)} of a Chrome-trace
    document's device ops, the module being the program run that encloses
    the op; tf_op "" for an op that XLA added without a name."""
    pname, tname = {}, {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pname[e.get("pid")] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            tname[(e.get("pid"), e.get("tid"))] = e["args"]["name"]
    modules, ops = defaultdict(list), []
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or not str(pname.get(e.get("pid"))).startswith(
                "/device:"):
            continue
        line = tname.get((e["pid"], e.get("tid")))
        if line == MODULES_LINE:
            modules[e["pid"]].append((e["ts"], e["ts"] + e["dur"], e["name"]))
        elif line == OPS_LINE:
            ops.append(e)
    starts = {}
    for pid, spans in modules.items():
        spans.sort()
        starts[pid] = [s[0] for s in spans]
    out: dict = {}
    for e in ops:
        args = e.get("args") or {}
        mod = _enclosing(starts.get(e["pid"], []), modules.get(e["pid"], []),
                         e["ts"])
        text = args.get("long_name") or e["name"]
        out[(mod, _short(text))] = (
            args.get("tf_op") or "",
            tuple(_OPERAND.findall(text.split(" = ", 1)[-1])))
    return out


def _by_readers(names: dict) -> dict:
    """{(module, instruction): (phase, pass)} of each op XLA added without a
    name whose readers in the profile, followed through other unnamed ops,
    all lie in one phase and pass (phase None: outside every phase). An op
    is decided once all its unnamed readers are, so a chain of any length
    is followed and a cycle is left undecided."""
    users = defaultdict(set)
    for (mod, name), (_, operands) in names.items():
        for o in operands:
            if (mod, o) in names:
                users[(mod, o)].add((mod, name))
    label = {k: (phase_of(t), pass_of(t)) for k, (t, _) in names.items() if t}
    pending = {k: sum(u not in label for u in users[k])
               for k, (t, _) in names.items() if not t}
    ready = [k for k, n in pending.items() if n == 0]
    while ready:
        k = ready.pop()
        got = {label.get(u) for u in users[k]}
        label[k] = got.pop() if len(got) == 1 else None
        for o in set(names[k][1]):
            p = (k[0], o)
            if p in pending and p not in label:
                pending[p] -= 1
                if pending[p] == 0:
                    ready.append(p)
    return {k: label[k] for k in pending if label.get(k) is not None}


def _labels(events: list, names: dict, readers: dict) -> list:
    """(phase, pass, how) of each event of one device's op line, in the
    list's order, ``how`` "name" for a named op. An op XLA added without a
    name takes its readers' labels (``readers``, from :func:`_by_readers`),
    else those of the next named op of its program (``events`` sorted by
    start, a ``while`` before its body)."""
    out, nxt = [None] * len(events), {}
    for i in range(len(events) - 1, -1, -1):
        _, _, name, mod = events[i]
        tf_op = names.get((mod, name), (None,))[0]
        if tf_op:
            nxt[mod] = out[i] = (phase_of(tf_op), pass_of(tf_op), "name")
        elif tf_op == "" and (mod, name) in readers:
            out[i] = readers[(mod, name)] + ("readers",)
        elif tf_op == "" and nxt.get(mod):
            out[i] = nxt[mod][:2] + ("schedule",)
    return out


def reduce_phases(pd, names: dict) -> PhaseTimes:
    """Device time inside ``bench.window`` of an xplane ``pd`` by phase,
    each op named through ``names`` (from :func:`op_names`)."""
    windows = [(s, e) for n, s, e in _host_spans(pd) if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0][0], windows[-1][1]
    planes = [p for p in pd.planes if p.name.startswith("/device:")
              and any(l.name == OPS_LINE for l in p.lines)]
    intervals: dict = defaultdict(dict)
    named: dict = defaultdict(dict)
    unscoped: dict = defaultdict(float)
    unnamed: dict = {}
    readers = _by_readers(names)
    busy = 0.0
    for dev, plane in enumerate(planes):
        lines = {l.name: l for l in plane.lines}
        spans = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in (lines[MODULES_LINE].events
                                  if MODULES_LINE in lines else []))
        starts = [s[0] for s in spans]
        events = sorted(
            ((ev.start_ns, ev.start_ns + ev.duration_ns, _short(ev.name),
              _enclosing(starts, spans, ev.start_ns))
             for ev in lines[OPS_LINE].events),
            key=lambda x: (x[0], -x[1]))
        every, mine, mine_named, orphans, given = [], defaultdict(list), \
            defaultdict(list), [], []
        for (s, e, name, _), label in zip(events,
                                          _labels(events, names, readers)):
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            every.append((s, e))
            phase, pas, how = label or (None, None, "")
            if phase is None:
                orphans.append((name, s, e))
                continue
            for key in (phase, pas) if pas else (phase,):
                mine[key].append((s, e))
                if how == "name":
                    mine_named[key].append((s, e))
            if how != "name":
                given.append((name, pas or phase, how, s, e))
        busy += sum(e - s for s, e in _union(every))
        for phase, ivs in mine.items():
            intervals[phase][dev] = _union(ivs)
        for phase, ivs in mine_named.items():
            named[phase][dev] = _union(ivs)
        covered = _union([iv for p in PHASES for iv in mine.get(p, [])])
        cstarts = [c[0] for c in covered]
        for name, s, e in orphans:      # a while counts what its body leaves
            unscoped[name] += _outside(covered, cstarts, s, e) * 1e-9
        by_name = _union([iv for p in PHASES for iv in mine_named.get(p, [])])
        bstarts = [c[0] for c in by_name]
        for name, key, how, s, e in given:  # the time it adds to the named
            t = unnamed.get(name, (key, how, 0.0))[2]
            unnamed[name] = (key, how, t + _outside(by_name, bstarts, s, e)
                             * 1e-9)
    n = len(planes)
    return PhaseTimes(busy_s=busy * 1e-9 / max(n, 1), n_devices=n,
                      intervals=dict(intervals), named=dict(named),
                      unscoped_ops={k: v for k, v in unscoped.items() if v > 0},
                      unnamed_ops={k: v for k, v in unnamed.items()
                                   if v[2] > 0})


def load_phases(trace_dir) -> Optional[PhaseTimes]:
    """The phase reduction of the profile under ``trace_dir``, or None where
    it holds no profile or no Chrome-trace file beside it."""
    try:
        xp = find_xplane(trace_dir)
        tj = xp.with_name(xp.name.replace(".xplane.pb", ".trace.json.gz"))
        with gzip.open(tj, "rt") as f:
            names = op_names(json.load(f))
        return reduce_phases(load(xp), names)
    except (OSError, ValueError, KeyError) as e:
        # missing, unreadable, or not in the profiler's format
        print(f"bench.scopes: no phases from {trace_dir}: {e}",
              file=sys.stderr)
        return None


def phase_seconds(ctx: dict, *phases: str) -> Optional[float]:
    """Device seconds of ``phases`` (their union) in the cell's traced
    window, XLA's unnamed ops included by schedule, or None where the ops
    named in a phase cover less than ``MIN_COVERAGE`` of busy time: a
    broken map shows as a missing number, never a wrong one.
    The readers of one run share ``ctx``: the profile is reduced, and its
    table printed to stderr, by the first of them."""
    if "phases" not in ctx:
        pt = ctx["phases"] = load_phases(TRACE_ROOT / ctx["cell"].name)
        if pt is not None:
            print(pt.table(int(ctx["records"].get("steps_traced") or 0)),
                  file=sys.stderr, flush=True)
    pt = ctx["phases"]
    if pt is None or pt.coverage < MIN_COVERAGE:
        return None
    t = pt.seconds(*phases)
    return t if t > 0 else None


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    pt = load_phases(argv[0])
    if pt is None:
        return 1
    print(pt.table(int(argv[1]) if len(argv) > 1 else 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
