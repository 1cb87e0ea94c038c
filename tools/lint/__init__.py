"""replint — JAX/Pallas-aware static analysis for the repro codebase.

Rule groups (select with ``--only``):

- ``ast``    — RL101–RL105 JAX hazard rules (:mod:`tools.lint.rules_ast`)
- ``pallas`` — RP301–RP304 kernel VMEM/grid/naming audit (:mod:`tools.lint.pallas_audit`)
- ``docs``   — RD201/RD202 markdown links + module docstrings, RD203 obs
  metric-catalog coverage (:mod:`tools.lint.docs_rules`, absorbed from
  ``tools/docs_check.py``)

Driver: ``python tools/lint.py [paths] [--only GROUP] [--report FILE]``.
See ``tools/lint/README.md`` for the full rule catalog and suppression
syntax, and ``src/repro/lint_runtime.py`` for the runtime compile-count
sentinel that complements these static checks.
"""
from .engine import (Finding, ModuleUnderLint, Suppression, build_report,
                     emit, iter_python_files, lint_files)
from .rules_ast import AST_RULES
from .pallas_audit import (ASSUMED_DIMS, DEFAULT_VMEM_BUDGET, KernelSite,
                           audit_paths, render_readme, vmem_table)
from .docs_rules import (check_docstrings, check_links,
                         check_metric_catalog, docs_findings,
                         registered_obs_names)

GROUPS = ("ast", "pallas", "docs")

__all__ = [
    "AST_RULES", "ASSUMED_DIMS", "DEFAULT_VMEM_BUDGET", "Finding", "GROUPS",
    "KernelSite", "ModuleUnderLint", "Suppression", "audit_paths",
    "build_report", "check_docstrings", "check_links",
    "check_metric_catalog", "docs_findings", "emit", "iter_python_files",
    "lint_files", "registered_obs_names", "render_readme", "vmem_table",
]
