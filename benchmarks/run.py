"""Benchmark orchestrator — one bench per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--full] [--smoke] [--only fig2,...]

Prints ``name,value,unit,derived`` CSV rows (stdout) — ``unit`` names what
the value column measures (``us``, ``tok_s``, ``ms``, ``frac``, ``ratio``,
``kb``, ``steps``) — mirroring the paper's experimental panels:

    fig2_*      Fig. 2/5  weighted vs non-weighted robust aggregators
    fig3_*      Fig. 3/6  ω-CTMA effect on base aggregators
    fig4_*      Fig. 4/7  μ²-SGD vs momentum vs SGD
    thm42_*     Thm. 4.2  1/√T excess-loss decay under attack
    aggcost_*   Table 1 / Remark 4.1 aggregator cost scaling
    aggpallas_* Pallas kernel paths vs jnp oracles (fused vs unfused CTMA)
    agghier_*   hierarchical cross-pod path vs single-host stacked, with
                collective-bytes/HBM accounting (needs a multi-device host —
                run under XLA_FLAGS=--xla_force_host_platform_device_count=8)
    kernel_*    Pallas kernel timings (interpret mode)
    roofline_*  §Roofline terms from the dry-run artifacts
    serve_*     static vs continuous-batching decode A/B (tok/s, p50/p99
                latency, slot occupancy, decode speedup) — the value column
                carries the metric, not microseconds
    robustserve_* Byzantine-tolerant replicated decode: honest-baseline
                tok/s + replication overhead, per-attack token accuracy vs
                the honest stream, quarantine latency (value = metric)
    robust_*    repro.fleet adversarial robustness matrix — one row per
                attack × aggregator × arrival × heterogeneity cell; value =
                standalone aggregator µs/call, derived packs final loss vs
                the honest envelope + breakdown fraction (bisection over
                Byzantine mass on one compiled vmapped step)

Aggregation rows additionally persist to ``BENCH_agg.json`` at the repo root
so successive PRs accumulate a perf trajectory (``--smoke`` runs the reduced
aggcost + agghier grids only — the CI fast path — and still records the
fused-CTMA speedup at the acceptance shape m=17, d=100k). Serve rows persist
the same way to ``BENCH_serve.json`` (``--only serve --smoke`` is the CI
serve step), replicated-serving rows to ``BENCH_robust_serve.json``
(``--only robust-serve --smoke`` is the CI serving-robustness step), and
training-side robustness-matrix rows to ``BENCH_robust.json``
(``--only robust --smoke`` is the CI training-robustness step).
"""
from __future__ import annotations

import argparse
import inspect
import json
import re
import sys
import time
from pathlib import Path

BENCHES = {
    "aggcost": "benchmarks.bench_agg_cost",
    "agghier": "benchmarks.bench_agg_cost:run_hier",
    "fig2": "benchmarks.bench_weighted_vs_unweighted",
    "fig3": "benchmarks.bench_ctma_effect",
    "fig4": "benchmarks.bench_optimizers",
    "thm42": "benchmarks.bench_convergence",
    "kernels": "benchmarks.bench_kernels",
    "roofline": "benchmarks.bench_roofline",
    "serve": "benchmarks.bench_serve",
    "robust-serve": "benchmarks.bench_robust_serve",
    "robust": "benchmarks.bench_robust",
}

BENCH_AGG_PATH = Path(__file__).resolve().parents[1] / "BENCH_agg.json"
BENCH_SERVE_PATH = Path(__file__).resolve().parents[1] / "BENCH_serve.json"
BENCH_ROBUST_SERVE_PATH = (Path(__file__).resolve().parents[1]
                           / "BENCH_robust_serve.json")
BENCH_ROBUST_PATH = Path(__file__).resolve().parents[1] / "BENCH_robust.json"


_UNIT_RE = re.compile(r"[A-Za-z][A-Za-z0-9_]*")


def _parse_row(row: str) -> dict:
    """Parse a bench row into its persisted dict.

    Canonical rows are 4-field ``name,value,unit,derived``; legacy 3-field
    ``name,value,derived`` rows (pre-unit writers) are still accepted with
    ``unit="us"``. The unit slot is only claimed when it looks like a bare
    unit token — legacy ``derived`` text can itself contain commas, so the
    discriminator is the field shape, not the comma count. ``us_per_call``
    is kept as a back-compat alias, but only for rows whose value really is
    microseconds — accuracy/ratio rows no longer masquerade as durations."""
    name, value, rest = row.split(",", 2)
    unit, sep, derived = rest.partition(",")
    if not (sep and _UNIT_RE.fullmatch(unit)):
        unit, derived = "us", rest
    out = {"name": name, "value": float(value), "unit": unit,
           "derived": derived}
    if unit == "us":
        out["us_per_call"] = out["value"]
    return out


def _persist(path: Path, prefixes: tuple, rows: list[str], tag: str) -> None:
    """Append matching rows to a trajectory file, keeping the last 20 runs."""
    matched = [_parse_row(r) for r in rows if r.startswith(prefixes)]
    if not matched:
        return
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text()).get("runs", [])
        except (json.JSONDecodeError, AttributeError):
            history = []
    history.append({"unix_time": int(time.time()), "rows": matched})
    path.write_text(json.dumps({"runs": history[-20:]}, indent=1))
    print(f"# wrote {len(matched)} {tag} rows to {path.name}", file=sys.stderr)


def persist_agg(rows: list[str]) -> None:
    """Append this run's aggregation rows to BENCH_agg.json (perf trajectory)."""
    _persist(BENCH_AGG_PATH, ("aggcost_", "aggpallas_", "agghier_"), rows, "agg")


def persist_serve(rows: list[str]) -> None:
    """Append this run's serve rows to BENCH_serve.json (tokens/s, p50/p99
    latency, slot occupancy, static-vs-continuous decode speedup)."""
    _persist(BENCH_SERVE_PATH, ("serve_",), rows, "serve")


def persist_robust_serve(rows: list[str]) -> None:
    """Append this run's replicated-serving rows to BENCH_robust_serve.json
    (honest-baseline tok/s + replication overhead, per-attack token accuracy
    vs the honest stream, quarantine latency in decode steps)."""
    _persist(BENCH_ROBUST_SERVE_PATH, ("robustserve_",), rows, "robust-serve")


def persist_robust(rows: list[str]) -> None:
    """Append this run's robustness-matrix rows to BENCH_robust.json — one
    cell per row: aggregator µs/call in the value column; final loss, honest
    envelope, breakdown fraction and engine step cost in ``derived``."""
    _persist(BENCH_ROBUST_PATH, ("robust_",), rows, "robust")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset: reduced aggcost + agghier grids")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench name(s) {unknown}; choose from {list(BENCHES)}")
    if args.smoke and not args.only:
        names = ["aggcost", "agghier"]

    print("name,value,unit,derived")
    failures = 0
    all_rows: list[str] = []
    for name in names:
        mod_name, _, attr = BENCHES[name].partition(":")
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            fn = getattr(mod, attr or "run")
            if "smoke" in inspect.signature(fn).parameters:
                rows = fn(full=args.full, smoke=args.smoke)
            else:  # benches that predate the smoke flag
                rows = fn(full=args.full)
            for row in rows:
                print(row, flush=True)
            all_rows.extend(rows)
            print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", file=sys.stderr)
    persist_agg(all_rows)
    persist_serve(all_rows)
    persist_robust_serve(all_rows)
    persist_robust(all_rows)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
