"""Fleet launcher: run an adversarial scenario grid from the command line.

    PYTHONPATH=src python -m repro.launch.fleet --smoke
    PYTHONPATH=src python -m repro.launch.fleet --problem classifier \
        --attacks sign_flip,adaptive_scale --aggs ctma:cwmed,cwmed \
        --arrivals proportional,squared --alphas inf,0.3 \
        --m 9 --byz-frac 0.22 --steps 100 --breakdown --json matrix.json

Builds the attack × aggregator × arrival × heterogeneity cross-product with
`repro.fleet.matrix_scenarios`, runs it through the batched vmapped engine
(`run_scenarios`), and — with ``--breakdown`` — bisects every cell's
breakdown point and times the resolved aggregators
(`repro.fleet.breakdown_matrix`). Prints one line per cell; ``--json`` dumps
the full structured rows. ``--smoke`` is the quadratic-family quick check.
"""
from __future__ import annotations

import argparse
import json
import math
import sys


def _csv(s: str) -> list:
    return [x.strip() for x in s.split(",") if x.strip()]


def _alphas(s: str) -> tuple:
    return tuple(math.inf if a in ("inf", "iid") else float(a)
                 for a in _csv(s))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--problem", default="classifier",
                    choices=("classifier", "quadratic"))
    ap.add_argument("--attacks", default="sign_flip,little,empire,"
                                         "adaptive_scale")
    ap.add_argument("--aggs", default="ctma:cwmed,ctma:gm,cwmed")
    ap.add_argument("--arrivals", default="proportional,squared")
    ap.add_argument("--alphas", default="inf,0.3",
                    help="Dirichlet label-skew levels; 'inf' = IID")
    ap.add_argument("--m", type=int, default=9)
    ap.add_argument("--byz-frac", type=float, default=2.0 / 9.0)
    ap.add_argument("--lam", type=float, default=0.38)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seeds", default="0")
    ap.add_argument("--breakdown", action="store_true",
                    help="bisect each cell's breakdown point (slower)")
    ap.add_argument("--bisect-steps", type=int, default=0,
                    help="shorter horizon for breakdown probes (0 = full)")
    ap.add_argument("--json", default="", help="write structured rows here")
    ap.add_argument("--smoke", action="store_true",
                    help="quadratic family, short horizons")
    ap.add_argument("--obs-dir", default="",
                    help="write repro.obs telemetry here: "
                         "<dir>/fleet.metrics.jsonl + <dir>/fleet.trace.json "
                         "(per-scenario loss trajectories + engine.* device "
                         "metrics; summarize with python -m repro.launch.obs)")
    args = ap.parse_args(argv)
    from repro.utils import enable_compile_cache
    enable_compile_cache()

    from repro.fleet import (breakdown_matrix, matrix_scenarios,
                             run_scenarios)

    kw = dict(problem=args.problem, attacks=tuple(_csv(args.attacks)),
              aggs=tuple(_csv(args.aggs)),
              arrivals=tuple(_csv(args.arrivals)),
              alphas=_alphas(args.alphas), m=args.m, byz_frac=args.byz_frac,
              lam=args.lam, steps=args.steps, batch=args.batch,
              seeds=tuple(int(s) for s in _csv(args.seeds)))
    if args.smoke:
        kw.update(problem="quadratic", steps=min(args.steps, 60), batch=4)
    scenarios = matrix_scenarios(**kw)
    print(f"# {len(scenarios)} scenarios", file=sys.stderr)

    obs = None
    if args.obs_dir:
        from repro.obs import RunObs
        obs = RunObs.open(args.obs_dir, "fleet")

    if args.breakdown:
        rows = breakdown_matrix(scenarios,
                                bisect_steps=args.bisect_steps or None)
        for r in rows:
            acc = f" acc={r['acc']:.3f}" if "acc" in r else ""
            print(f"{r['cell']}: loss={r['final_loss']:.4f} "
                  f"(honest {r['honest_loss']:.4f}){acc} "
                  f"breakdown={r['breakdown_count']}/{r['m']} "
                  f"agg_us={r['agg_us_per_call']:.1f}")
    else:
        results = run_scenarios(scenarios, obs=obs)
        rows = []
        for res in results:
            ev = {k: float(v) for k, v in res.eval.items()}
            rows.append({"cell": res.scenario.label, **ev,
                         "lambda_emp": res.lambda_emp,
                         "engine_us_per_step": res.us_per_step})
            print(f"{res.scenario.label}: " +
                  " ".join(f"{k}={v:.4f}" for k, v in ev.items()) +
                  f" lambda={res.lambda_emp:.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if obs is not None:
        obs.close()
        print(f"# obs: wrote {args.obs_dir}/fleet.metrics.jsonl + "
              f"fleet.trace.json", file=sys.stderr)


if __name__ == "__main__":
    main()
