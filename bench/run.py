"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json. The run needs a TPU
with as many chips as the cell asks for; without one it exits non-zero and
prints no result. Set-up (weights and inputs from the seed, compilation,
warm-up) is timed as ``setup_s``; then the window runs for ``--seconds``
with nothing compiling inside it. With ``--trace 1`` part of the window is
profiled and the result carries the cell's per-layer metrics instead of its
end-to-end ones. The last line of stdout is the JSON result; the checks that
decide ``correct`` are the last lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for need in (ROOT / "BENCHMARK.json", ROOT / "src" / "repro"):
        if not need.exists():
            print(f"bench: {need} is missing; run from a checkout of the "
                  "repository", file=sys.stderr)
            return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    from bench.counts import load_peaks

    cell = harness.load_cell(args.workload, ROOT)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    peaks = load_peaks(devices[0].device_kind)
    harness.enable_compile_cache(ROOT)
    out_dir = ROOT / ".bench_trace" / args.workload
    shutil.rmtree(out_dir, ignore_errors=True)

    drv = harness.driver(cell)
    setup_box = {}
    out = drv.run(cell, args.seed, args.seconds, bool(args.trace), out_dir,
                  devices=devices, t_start=T_START, setup_box=setup_box)
    line = harness.result_line(cell, out, devices, setup_box["setup_s"],
                               bool(args.trace), peaks)
    harness.emit(line, out.checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
