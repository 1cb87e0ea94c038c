"""replint driver — JAX/Pallas-aware static analysis over the repro tree.

    python tools/lint.py src/repro                      # all groups, exit 1 on findings
    python tools/lint.py src/repro --report lint_report.json   # CI artifact
    python tools/lint.py --only docs                    # old docs_check behavior
    python tools/lint.py --only pallas --vmem-budget 8  # tighter kernel budget
    python tools/lint.py --write-kernel-table           # refresh kernels/README.md
    python tools/lint.py --check-kernel-table           # CI drift gate

Groups: ``ast`` (RL101–RL105 JAX hazards), ``pallas`` (RP301–RP304 kernel
VMEM/grid/naming audit + generated VMEM table), ``docs`` (RD201/RD202, the folded
``tools/docs_check.py``). Rule catalog: ``tools/lint/README.md``.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lint import (AST_RULES, DEFAULT_VMEM_BUDGET, GROUPS, audit_paths,
                  build_report, docs_findings, emit, iter_python_files,
                  lint_files, render_readme, vmem_table)
from lint.engine import REPO_ROOT, apply_suppressions

KERNELS_README = REPO_ROOT / "src" / "repro" / "kernels" / "README.md"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="lint.py", description=__doc__)
    ap.add_argument("paths", nargs="*", default=[],
                    help="files/dirs to lint (default: src/repro)")
    ap.add_argument("--only", choices=GROUPS, action="append",
                    help="run only this rule group (repeatable)")
    ap.add_argument("--report", metavar="FILE",
                    help="write the JSON lint report here")
    ap.add_argument("--vmem-budget", type=float, default=None, metavar="MIB",
                    help=f"Pallas per-kernel VMEM budget in MiB "
                         f"(default {DEFAULT_VMEM_BUDGET / 2**20:.0f})")
    ap.add_argument("--write-kernel-table", action="store_true",
                    help="regenerate the VMEM table in kernels/README.md")
    ap.add_argument("--check-kernel-table", action="store_true",
                    help="fail if the kernels/README.md VMEM table is stale")
    args = ap.parse_args(argv)

    groups = tuple(args.only) if args.only else GROUPS
    paths = [Path(p) for p in args.paths] or [REPO_ROOT / "src" / "repro"]
    budget = int(args.vmem_budget * 2**20) if args.vmem_budget \
        else DEFAULT_VMEM_BUDGET

    files = iter_python_files(paths)
    active, suppressed, sups = [], [], []
    extra = {}

    if "ast" in groups:
        a, s, sp = lint_files(files, AST_RULES)
        active += a
        suppressed += s
        sups += sp

    if "pallas" in groups:
        sites, pf = audit_paths(paths, budget)
        # pallas findings honor the same line-level suppressions
        pa, ps = apply_suppressions(pf, sups)
        # drop RL000 duplicates re-raised by the second apply pass
        pa = [f for f in pa if f.code != "RL000"]
        active += pa
        suppressed += ps
        extra["kernels"] = [{
            "path": s.path, "line": s.line, "kernel": s.func,
            "grid": s.grid_src, "vmem_bytes": s.vmem_bytes,
            "assumed": s.assumed,
        } for s in sorted(sites, key=lambda s: (s.path, s.line))]
        # per-file rollup over the whole kernels package, zero-site files
        # included, so the report accounts for every kernel file
        kdir = REPO_ROOT / "src" / "repro" / "kernels"
        by_file = {}
        for s in sites:
            by_file.setdefault(s.path.rsplit("/", 1)[-1], []).append(s)
        extra["kernel_files"] = [{
            "file": p.name,
            "sites": len(by_file.get(p.name, [])),
            "max_vmem_bytes": max((s.vmem_bytes
                                   for s in by_file.get(p.name, [])),
                                  default=0),
        } for p in sorted(kdir.glob("*.py")) if p.name != "__init__.py"]

        table = vmem_table(sites, budget)
        if args.write_kernel_table or args.check_kernel_table:
            current = KERNELS_README.read_text() \
                if KERNELS_README.exists() else ""
            desired = render_readme(current, table)
            if args.check_kernel_table and desired != current:
                from lint.engine import Finding
                active.append(Finding(
                    "RP300", "src/repro/kernels/README.md", 1,
                    "VMEM table is stale — regenerate with "
                    "'python tools/lint.py --write-kernel-table'"))
            if args.write_kernel_table and desired != current:
                KERNELS_README.write_text(desired)
                print(f"updated {KERNELS_README.relative_to(REPO_ROOT)}")

    if "docs" in groups:
        active += docs_findings()

    active.sort(key=lambda f: (f.path, f.line, f.code))
    report = build_report(active, suppressed, sups, groups=list(groups),
                          files=files, extra=extra)
    return emit(report, args.report)


if __name__ == "__main__":
    raise SystemExit(main())
