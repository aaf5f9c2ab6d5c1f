"""The lint gate as a tier-1 test: the production tree lints clean
with every rule active, and the guarded-by contract coverage holds.

``tools/graftlint.py`` is the CI spelling of this gate; running the
same engine in-process here means a tree that regresses any rule
(R1–R8) fails the ordinary test run too — nobody has to remember to
run the linter. The coverage floor stops the R8 contract from rotting
by deletion: suppress-or-declare triage must keep a critical mass of
threaded classes declaring ``GUARDED_BY``.
"""

from __future__ import annotations

import ast
import json
import os
import subprocess
import sys

from siddhi_tpu.analysis import default_rules, load_modules, run_lint

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
ROOTS = ("siddhi_tpu", "tools", "chip_smoke.py",
         "__graft_entry__.py")


def _production_modules():
    return load_modules(ROOTS, REPO)


def test_full_gate_zero_findings():
    """Every rule, every production file, zero findings."""
    modules = _production_modules()
    rules = default_rules()
    assert [r.id for r in rules] == [f"R{i}" for i in range(1, 9)]
    findings = run_lint(modules, rules=rules)
    assert not findings, "\n".join(f.format() for f in findings)


def test_guarded_by_coverage_floor():
    """At least 8 production classes declare a non-empty GUARDED_BY —
    the R8 contract is load-bearing, not vestigial."""
    declaring = []
    for mod in _production_modules():
        if not mod.path.startswith("siddhi_tpu/"):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            for stmt in node.body:
                if (isinstance(stmt, ast.Assign)
                        and any(isinstance(t, ast.Name)
                                and t.id == "GUARDED_BY"
                                for t in stmt.targets)
                        and isinstance(stmt.value, ast.Dict)
                        and stmt.value.keys):
                    declaring.append(f"{mod.path}:{node.name}")
    assert len(declaring) >= 8, declaring


def test_json_gate_output():
    """--json emits machine-readable records with the same exit-code
    contract as the text mode."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py"),
         "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["findings"] == []
    assert doc["files"] > 100
    assert doc["rules"] == [f"R{i}" for i in range(1, 9)]
