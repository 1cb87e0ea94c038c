"""Host-side span tracer with Chrome-trace / Perfetto JSON export.

Collects timeline events while an engine runs — complete spans
(``ph="X"``: prefill calls, decode steps, warmup), instant events
(``ph="i"``: quarantine transitions, request retirement), counter tracks
(``ph="C"``: queue depth, slot occupancy) and async request lifetimes
(``ph="b"``/``"e"`` keyed by request uid) — and exports them as the Chrome
trace-event JSON Perfetto loads directly (``ui.perfetto.dev`` → open file).
Timestamps are microseconds from tracer construction on
``time.perf_counter``. Each span is also a ``jax.profiler.TraceAnnotation``
of the same name: while a profile is being taken (``jax.profiler.trace``)
it lands on the profile's host plane, on the clock of the device's ops.

XLA compiles are folded in as first-class trace events:
:meth:`Tracer.attach_compile_events` registers a ``jax.monitoring``
duration listener on the same events as
:mod:`repro.lint_runtime.compile_count` (backend compiles + jaxpr traces),
so every compile shows up as a span on its own track — warmup cost and any
mid-run recompile are visible on the exact timeline the serving spans live
on, instead of being a bare counter in a test.

The tracer is append-only and lock-guarded (the monitoring listener fires
from whatever thread compiled), and export is a plain ``json.dump`` — no
engine ever blocks on tracing beyond the list append.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Union

from jax.profiler import TraceAnnotation

from repro.lint_runtime import (BACKEND_COMPILE_EVENT, TRACE_EVENT,
                                _unregister)

# track (tid) layout of the exported timeline
TID_ENGINE = 1          # prefill / decode / warmup spans + counters
TID_COMPILE = 2         # XLA backend compiles + jaxpr traces
TID_REQUESTS = 3        # async request lifetimes
_TID_NAMES = {TID_ENGINE: "engine", TID_COMPILE: "xla_compile",
              TID_REQUESTS: "requests"}


class Tracer:
    """Chrome-trace event collector; one instance per observed run."""

    def __init__(self, path: Optional[Union[str, Path]] = None,
                 pid: int = 1):
        self.path = Path(path) if path is not None else None
        self.pid = pid
        self.events: List[dict] = []
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._compile_listener = None
        for tid, name in _TID_NAMES.items():
            self._push({"name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": name}})

    # -- low-level ---------------------------------------------------------

    def now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _push(self, ev: dict) -> None:
        with self._lock:
            self.events.append(ev)

    # -- event kinds -------------------------------------------------------

    @contextmanager
    def span(self, name: str, cat: str = "engine", tid: int = TID_ENGINE,
             **args: Any) -> Iterator[None]:
        """Complete event around a block of work, and the profiler's
        annotation of it (a no-op unless a profile is being taken)."""
        ts = self.now_us()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            self._push({"name": name, "cat": cat, "ph": "X", "ts": ts,
                        "dur": self.now_us() - ts, "pid": self.pid,
                        "tid": tid, "args": args})

    def complete(self, name: str, ts_us: float, dur_us: float,
                 cat: str = "engine", tid: int = TID_ENGINE,
                 **args: Any) -> None:
        """Record an already-timed span (e.g. a compile whose duration the
        listener reports after the fact)."""
        self._push({"name": name, "cat": cat, "ph": "X", "ts": ts_us,
                    "dur": dur_us, "pid": self.pid, "tid": tid,
                    "args": args})

    def instant(self, name: str, cat: str = "engine",
                tid: int = TID_ENGINE, **args: Any) -> None:
        self._push({"name": name, "cat": cat, "ph": "i", "s": "t",
                    "ts": self.now_us(), "pid": self.pid, "tid": tid,
                    "args": args})

    def counter(self, name: str, **values: float) -> None:
        """Counter track sample, e.g. ``counter("queue", depth=3)``."""
        self._push({"name": name, "cat": "engine", "ph": "C",
                    "ts": self.now_us(), "pid": self.pid, "tid": TID_ENGINE,
                    "args": {k: float(v) for k, v in values.items()}})

    def begin_async(self, name: str, aid: int, cat: str = "request",
                    **args: Any) -> None:
        self._push({"name": name, "cat": cat, "ph": "b", "id": int(aid),
                    "ts": self.now_us(), "pid": self.pid,
                    "tid": TID_REQUESTS, "args": args})

    def end_async(self, name: str, aid: int, cat: str = "request",
                  **args: Any) -> None:
        self._push({"name": name, "cat": cat, "ph": "e", "id": int(aid),
                    "ts": self.now_us(), "pid": self.pid,
                    "tid": TID_REQUESTS, "args": args})

    # -- compile events (lint_runtime fold-in) -----------------------------

    def attach_compile_events(self) -> None:
        """Record every XLA backend compile / jaxpr trace as a span on the
        compile track until :meth:`detach_compile_events` (or close)."""
        if self._compile_listener is not None:
            return
        from jax import monitoring

        names = {BACKEND_COMPILE_EVENT: "xla_backend_compile",
                 TRACE_EVENT: "jaxpr_trace"}

        def listener(event: str, duration: float, **_kw: Any) -> None:
            label = names.get(event)
            if label is None:
                return
            dur_us = duration * 1e6
            # the listener fires at completion: backdate the span start
            self.complete(label, ts_us=max(self.now_us() - dur_us, 0.0),
                          dur_us=dur_us, cat="compile", tid=TID_COMPILE)

        monitoring.register_event_duration_secs_listener(listener)
        self._compile_listener = listener

    def detach_compile_events(self) -> None:
        if self._compile_listener is not None:
            _unregister(self._compile_listener)
            self._compile_listener = None

    # -- export ------------------------------------------------------------

    def export(self, path: Optional[Union[str, Path]] = None) -> dict:
        """Write (and return) the Chrome-trace JSON document."""
        with self._lock:
            doc: Dict[str, Any] = {"traceEvents": list(self.events),
                                   "displayTimeUnit": "ms"}
        out = Path(path) if path is not None else self.path
        if out is not None:
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(doc))
        return doc

    def close(self) -> None:
        self.detach_compile_events()
        self.export()


def validate_trace(path: Union[str, Path]) -> List[str]:
    """Cheap Perfetto-loadability check of an exported trace file: valid
    JSON, a ``traceEvents`` list, and every event carrying the required
    ``ph``/``name``/``ts`` (metadata events excepted for ``ts``)."""
    try:
        doc = json.loads(Path(path).read_text())
    except json.JSONDecodeError as e:
        return [f"{path}: invalid JSON ({e.msg})"]
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        return [f"{path}: missing traceEvents list"]
    errors = []
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict) or "ph" not in ev or "name" not in ev:
            errors.append(f"{path}: event {i} missing ph/name")
            continue
        if ev["ph"] != "M" and not isinstance(ev.get("ts"), (int, float)):
            errors.append(f"{path}: event {i} ({ev['name']}) missing ts")
        if ev["ph"] == "X" and not isinstance(ev.get("dur"), (int, float)):
            errors.append(f"{path}: event {i} ({ev['name']}) missing dur")
    return errors
