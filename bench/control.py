"""Readings that set a cell's limits: the program's compared numbers and the
control's, on several seeds in one process, at the cell's own size.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 [--fault <name>]

Prints one JSON line per seed. The benchmark's own runs never run this; the
numbers go into PERF.md beside the limits set from them (the lower reading is
the largest the sound program gives, the upper the smallest the control or a
planted fault gives).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(ROOT)
    drv = harness.driver(cell)
    for seed in args.seeds:
        r = drv.readings(cell, seed, args.seconds, fault=args.fault,
                         devices=devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "fault": args.fault, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
