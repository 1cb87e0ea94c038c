"""Reduce a profiler trace (xplane) to device busy and idle time, device time
per operation, and idle gaps attributed to the harness's own spans.

The harness wraps its measured window in ``jax.profiler.TraceAnnotation``
spans whose names start with ``bench.``; they land on the host planes of the
same trace, on the same clock as the device's operations. Of a device plane
(``/device:...``) the line ``XLA Ops`` holds one event per operation run;
busy time is the union of those intervals inside the window span, so
operations that overlap count once.

    python bench/trace_reduce.py <trace dir or .xplane.pb>   # prints a summary
"""
from __future__ import annotations

import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# stats whose text helps to recognise an operation (the HLO op, its JAX
# source scope, and for a Pallas kernel the kernel's name)
_LABEL_STATS = ("hlo_op", "long_name", "tf_op", "name", "kernel_name",
                "source", "hlo_category")


@dataclass
class OpTotal:
    seconds: float = 0.0
    count: int = 0
    label: str = ""


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over the devices' op lines
    n_devices: int
    ops: dict = field(default_factory=dict)      # name -> OpTotal (summed over devices)
    modules: dict = field(default_factory=dict)  # name -> OpTotal (summed over devices)
    idle_gaps: dict = field(default_factory=dict)  # span name -> seconds (mean over devices)

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds per device of every operation whose name matches
        the regular expression ``pattern`` (a Pallas kernel's custom call is
        named after the jitted function that calls it)."""
        rx = re.compile(pattern)
        return sum(t.seconds for n, t in self.ops.items()
                   if rx.search(n)) / self.n_devices

    def op_count(self, pattern: str) -> int:
        rx = re.compile(pattern)
        return sum(t.count for n, t in self.ops.items() if rx.search(n))

    def module_seconds(self, pattern: str) -> tuple[float, int]:
        """(device seconds per device, calls per device) of the compiled
        programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        hit = [t for n, t in self.modules.items() if rx.search(n)]
        return (sum(t.seconds for t in hit) / self.n_devices,
                sum(t.count for t in hit) // max(self.n_devices, 1))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)[:top]
        gaps = sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, t.seconds / self.n_devices] for n, t in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(path) -> Path:
    p = Path(path)
    if p.is_file():
        return p
    hits = sorted(p.rglob("*.xplane.pb"))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {p}")
    return hits[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(find_xplane(path)))


def _union(intervals: list) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _short(name: str) -> str:
    """An XLA op event's name is its HLO instruction text on a TPU
    (``%wcwmed_pallas.14 = f32[...] custom-call(...)``): keep the name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _label(ev) -> str:
    """The event's full text and the stats that name its source."""
    parts = [ev.name] if " = " in ev.name else []
    for k, v in ev.stats:
        if k in _LABEL_STATS:
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _host_spans(pd) -> list:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def _attribute(gap: tuple, spans: list) -> str:
    """Innermost harness span, other than the window, that covers the gap's
    midpoint; the window itself where no other does."""
    mid = 0.5 * (gap[0] + gap[1])
    inner = [(e - s, n) for n, s, e in spans
             if s <= mid <= e and n != WINDOW_SPAN]
    if inner:
        return min(inner)[1]
    return WINDOW_SPAN


def reduce_trace(pd) -> TraceSummary:
    spans = _host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    dev_planes = [p for p in pd.planes if p.name.startswith("/device:")
                  and any(l.name == OPS_LINE for l in p.lines)]
    if not dev_planes:
        raise ValueError("trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = windows[0][0], windows[-1][1]
    ops: dict = defaultdict(OpTotal)
    modules: dict = defaultdict(OpTotal)
    gaps: dict = defaultdict(float)
    busy_total = 0.0
    for plane in dev_planes:
        intervals = []
        for line in plane.lines:
            table = (ops if line.name == OPS_LINE
                     else modules if line.name == MODULES_LINE else None)
            if table is None:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                t = table[_short(ev.name)]
                t.seconds += (e - s) * 1e-9
                t.count += 1
                if not t.label:
                    t.label = _label(ev)
                if table is ops:
                    intervals.append((s, e))
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps[_attribute((a, b), spans)] += (b - a) * 1e-9
    n = len(dev_planes)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total * 1e-9 / n, n_devices=n,
        ops=dict(ops), modules=dict(modules),
        idle_gaps={k: v / n for k, v in gaps.items()})


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    s = reduce_trace(load(argv[0]))
    print(json.dumps({"window_s": s.window_s, "busy_s": s.busy_s,
                      "n_devices": s.n_devices, "idle_frac": s.idle_frac,
                      **s.breakdown(25),
                      "modules": sorted(([n, t.seconds, t.count] for n, t in
                                         s.modules.items()),
                                        key=lambda r: -r[1])[:25],
                      "op_labels": {n: t.label for n, t in sorted(
                          s.ops.items(), key=lambda kv: -kv[1].seconds)[:25]}},
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
