"""replint self-tests: every rule fires on its bad fixture and never on the
clean twin; suppressions require justification and are counted; the Pallas
auditor covers every kernel file within budget; and the full src/repro tree
lints clean (the CI gate, pinned here so tier-1 catches drift first).

The engine-regression tests lint MUTATED copies of the real serve/fleet
sources — the exact one-line regressions the linter exists to catch (drop a
donated-cache rebind, branch on a traced arg) — so rule coverage is tied to
the real codebase, not just synthetic fixtures.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "tools"))

from lint import (AST_RULES, DEFAULT_VMEM_BUDGET, audit_paths, lint_files,
                  vmem_table)
from lint.engine import ModuleUnderLint

FIXTURES = ROOT / "tools" / "lint" / "fixtures"
AST_CODES = ["RL101", "RL102", "RL103", "RL104", "RL105"]
PALLAS_CODES = ["RP301", "RP302", "RP303", "RP304"]


# ---------------------------------------------------------------------------
# fixtures: each rule fires exactly on its bad twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("code", AST_CODES)
def test_ast_rule_fires_on_bad_fixture_only(code):
    bad, _, _ = lint_files([FIXTURES / f"{code.lower()}_bad.py"], AST_RULES)
    clean, _, _ = lint_files([FIXTURES / f"{code.lower()}_clean.py"],
                             AST_RULES)
    assert {f.code for f in bad} == {code}, [f.render() for f in bad]
    assert clean == [], [f.render() for f in clean]


@pytest.mark.parametrize("code", PALLAS_CODES)
def test_pallas_rule_fires_on_bad_fixture_only(code):
    _, bad = audit_paths([FIXTURES / f"{code.lower()}_bad.py"])
    _, clean = audit_paths([FIXTURES / f"{code.lower()}_clean.py"])
    assert {f.code for f in bad} == {code}, [f.render() for f in bad]
    assert clean == [], [f.render() for f in clean]


def test_fixture_set_is_complete():
    for code in AST_CODES + PALLAS_CODES:
        assert (FIXTURES / f"{code.lower()}_bad.py").exists()
        assert (FIXTURES / f"{code.lower()}_clean.py").exists()


# ---------------------------------------------------------------------------
# suppressions: justified ones count, unjustified ones are findings
# ---------------------------------------------------------------------------

def _lint_source(tmp_path, source):
    p = tmp_path / "mod.py"
    p.write_text(source)
    return lint_files([p], AST_RULES)


def test_justified_suppression_silences_and_is_counted(tmp_path):
    active, suppressed, sups = _lint_source(tmp_path, (
        "import numpy as np\n"
        "x = np.random.randn(4)"
        "  # replint: disable=RL104 -- fixture data, determinism irrelevant\n"
    ))
    assert active == []
    assert [f.code for f in suppressed] == ["RL104"]
    assert len(sups) == 1 and sups[0].justification.startswith("fixture")


def test_unjustified_suppression_is_its_own_finding(tmp_path):
    active, suppressed, _ = _lint_source(tmp_path, (
        "import numpy as np\n"
        "x = np.random.randn(4)  # replint: disable=RL104\n"
    ))
    assert [f.code for f in suppressed] == ["RL104"]
    assert [f.code for f in active] == ["RL000"]   # naked opt-out surfaces


def test_suppression_covers_only_its_own_line(tmp_path):
    active, _, _ = _lint_source(tmp_path, (
        "import numpy as np\n"
        "a = np.random.randn(4)  # replint: disable=RL104 -- seeded upstream\n"
        "b = np.random.randn(4)\n"
    ))
    assert [f.code for f in active] == ["RL104"]
    assert active[0].line == 3


# ---------------------------------------------------------------------------
# regression guards on the REAL sources: the one-line mistakes the linter
# must catch in serve/fleet code, pinned against mutated copies
# ---------------------------------------------------------------------------

def _mutated(tmp_path, src_path: Path, old: str, new: str) -> Path:
    src = src_path.read_text()
    assert old in src, f"pattern drifted out of {src_path.name}: {old!r}"
    out = tmp_path / src_path.name
    out.write_text(src.replace(old, new, 1))
    return out


def test_engine_insert_handoff_use_after_donation_detected(tmp_path):
    """Dropping the ``self.cache =`` rebind on the donated insert→decode
    handoff in serve/engine.py is the exact regression RL101 exists for."""
    bugged = _mutated(
        tmp_path, ROOT / "src" / "repro" / "serve" / "engine.py",
        "                self.cache = self._insert(self.cache, pcache, "
        "slot_ids)",
        "                self._insert(self.cache, pcache, slot_ids)")
    active, _, _ = lint_files([bugged], AST_RULES)
    assert any(f.code == "RL101" and "self.cache" in f.message
               for f in active), [f.render() for f in active]


def test_fleet_vstep_loop_use_after_donation_detected(tmp_path):
    """fleet/batched.py donates the stacked engine state into the vmapped
    step every loop iteration; dropping the rebind must flag RL101."""
    bugged = _mutated(
        tmp_path, ROOT / "src" / "repro" / "fleet" / "batched.py",
        "            state, metrics = self._vstep(state, batch, probs, "
        "masks, weighted)",
        "            out, metrics = self._vstep(state, batch, probs, "
        "masks, weighted)")
    active, _, _ = lint_files([bugged], AST_RULES)
    assert any(f.code == "RL101" and "'state'" in f.message
               for f in active), [f.render() for f in active]


def test_real_sources_are_currently_clean():
    for rel in ("src/repro/serve/engine.py", "src/repro/fleet/batched.py",
                "src/repro/serve/replicated.py", "src/repro/core/engine.py"):
        active, _, _ = lint_files([ROOT / rel], AST_RULES)
        assert active == [], [f.render() for f in active]


# ---------------------------------------------------------------------------
# Pallas auditor over the real kernels
# ---------------------------------------------------------------------------

def test_pallas_audit_covers_every_kernel_file_within_budget():
    kdir = ROOT / "src" / "repro" / "kernels"
    sites, findings = audit_paths([kdir])
    assert findings == [], [f.render() for f in findings]
    kernel_files = {p.name for p in kdir.glob("*.py")
                    if p.name != "__init__.py"}
    # every kernel file with pallas_call sites is audited (ops.py and pad.py
    # are jit wrappers / padding helpers with no kernel launches of their own)
    audited = {s.path.rsplit("/", 1)[-1] for s in sites}
    assert audited == {"ssd.py", "swa.py", "wctma_fused.py", "wcwmed.py",
                       "wreduce.py"}
    assert audited <= kernel_files
    # ... every site has a computed footprint, and all are under budget
    assert len(sites) >= 8
    for s in sites:
        assert s.vmem_bytes > 0, s
        assert s.vmem_bytes <= DEFAULT_VMEM_BUDGET, s


def test_vmem_table_lists_every_site_and_matches_readme():
    kdir = ROOT / "src" / "repro" / "kernels"
    sites, _ = audit_paths([kdir])
    table = vmem_table(sites)
    for s in sites:
        assert f"`{s.func}`" in table
    readme = (kdir / "README.md").read_text()
    assert table in readme, ("kernels/README.md VMEM table is stale — run "
                             "python tools/lint.py --write-kernel-table")


def test_dump_page_invariant_holds_in_serve_cache():
    sites, findings = audit_paths([ROOT / "src" / "repro" / "serve"])
    assert [f for f in findings if f.code == "RP303"] == []


# ---------------------------------------------------------------------------
# the CI gate: full src/repro runs clean through the driver
# ---------------------------------------------------------------------------

def test_full_src_repro_lint_exits_zero(tmp_path):
    report = tmp_path / "lint_report.json"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py"), "src/repro",
         "--report", str(report)],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    data = json.loads(report.read_text())
    assert data["n_findings"] == 0
    assert data["groups"] == ["ast", "pallas", "docs"]
    assert len(data["kernels"]) >= 8     # the VMEM audit rides the report
    # the per-file rollup accounts for EVERY kernel file, sites or not
    rollup = {k["file"] for k in data["kernel_files"]}
    kdir = ROOT / "src" / "repro" / "kernels"
    assert rollup == {p.name for p in kdir.glob("*.py")
                      if p.name != "__init__.py"}


def test_check_kernel_table_mode_passes_on_current_tree():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "lint.py"), "src/repro",
         "--only", "pallas", "--check-kernel-table"],
        cwd=ROOT, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_parent_map_and_suppression_parsing():
    mod = ModuleUnderLint(FIXTURES / "rl101_bad.py")
    assert mod.suppressions() == []
    fn = [n for n in __import__("ast").walk(mod.tree)
          if n.__class__.__name__ == "FunctionDef"]
    assert fn and mod.enclosing_function(fn[0].body[0]) is fn[0]
