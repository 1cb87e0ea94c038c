"""What every cell shares: the manifest, the device check, the compile cache,
the measured window with its compile guard and trace, the per-layer metric
readers, and the result line.

A cell is one entry of ``workloads`` in BENCHMARK.json. Its traffic file
(``traffic/<traffic>.json``) names the driver (``drivers/<driver>.py``) that
builds the system under test from the configuration file and drives it; a
per-layer metric is ``metrics/<name>.py`` with a ``read(ctx)`` function.
Adding a cell, a mix of an existing driver, or a metric adds files only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list

    def limit(self, key: str) -> float:
        return float(self.traffic["limits"][self.config["name"]][key])


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, root: Path = ROOT, manifest: Optional[dict] = None) -> Cell:
    man = manifest or load_manifest(root)
    wl = {w["name"]: w for w in man["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(name=name, config=config, traffic=traffic,
                chips=int(w["chips"]),
                end_to_end=[m for m in man["end_to_end"] if mine(m)],
                per_layer=[m for m in man["per_layer"] if mine(m)])


def tpu_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one; every program is cached, however
    quickly it compiled."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileGuard:
    """Counts XLA backend compiles while it is active."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event: str, duration: float, **_kw) -> None:
        if self._on and event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        from jax._src import monitoring
        with contextlib.suppress(ValueError, AssertionError):
            monitoring.unregister_event_duration_listener(self._listen)
        return False


class Tracer:
    """Starts the profiler at ``start_s`` into the window and stops it at
    ``stop_s``; ``poll(elapsed)`` is called by the cell's loop between
    calls. The ``bench.window`` span brackets the traced part, so the trace
    reduction measures exactly it."""

    def __init__(self, out_dir: Path, start_s: float, stop_s: float,
                 sync: Callable[[], Any]):
        self.out_dir, self.start_s, self.stop_s = out_dir, start_s, stop_s
        self.sync = sync
        self.state = "idle"
        self._span = None
        self.snapshots: dict = {}

    def poll(self, elapsed: float, snapshot: Callable[[], dict] = dict) -> None:
        import jax
        if self.state == "idle" and elapsed >= self.start_s:
            self.sync()
            jax.profiler.start_trace(str(self.out_dir))
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.snapshots["start"] = snapshot()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_s:
            self.stop(snapshot)

    def stop(self, snapshot: Callable[[], dict] = dict) -> None:
        import jax
        if self.state != "on":
            return
        self.sync()
        self.snapshots["stop"] = snapshot()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back. ``e2e`` holds the end-to-end readings,
    ``records`` whatever the per-layer readers need besides the trace."""
    attempted: int
    failed: int
    checks: list
    e2e: dict
    memory_peak_bytes: int
    records: dict = dataclasses.field(default_factory=dict)
    trace_dir: Optional[Path] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def load_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, out: Outcome, summary, peaks: dict) -> dict:
    ctx = {"cell": cell, "records": out.records, "trace": summary,
           "peaks": peaks, "config": cell.config}
    got = {}
    for m in cell.per_layer:
        v = load_reader(m["name"])(ctx)
        if v is not None:
            got[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return got


def device_dict(devices, mem: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": mem}


def driver(cell: Cell):
    return importlib.import_module(f"bench.drivers.{cell.traffic['driver']}")


def emit(line: dict, checks: list) -> None:
    """Checks as the last lines of stderr, then the result line on stdout."""
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def result_line(cell: Cell, out: Outcome, devices, setup_s: float,
                trace: bool, peaks: dict, extra_device: Optional[dict] = None
                ) -> dict:
    dev = device_dict(devices, out.memory_peak_bytes)
    line: dict = {"correct": out.correct, "attempted": out.attempted,
                  "failed": out.failed}
    if trace:
        from bench import trace_reduce
        summary = trace_reduce.reduce_trace(trace_reduce.load(out.trace_dir))
        line["metrics"] = per_layer(cell, out, summary, peaks)
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        line["device"] = dev
        line["breakdown"] = summary.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        vals = dict(out.e2e, setup_s=setup_s)
        line["metrics"] = {k: {"value": float(vals[k]), "unit": units[k]}
                           for k in units}
        line["device"] = dev
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    return line


def now() -> float:
    return time.perf_counter()


def note(records: dict, **kw) -> None:
    """Add timings to a run's records and print them to stderr."""
    records.update(kw)
    print("timing " + " ".join(f"{k}={v:.3f}" for k, v in kw.items()),
          file=sys.stderr, flush=True)
