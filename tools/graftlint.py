"""graftlint — static analysis for this repo's own regression classes.

Runs the AST rule suite in ``siddhi_tpu/analysis/`` over the production
tree (``siddhi_tpu/`` + ``tools/`` + the repo-root entry points) and
exits nonzero on any finding:

  R1  no backend init at import (module-level jnp / eager jax calls)
  R2  typed config-knob discipline (siddhi_tpu.* reads outside knobs.py,
      knobs declared but never read)
  R3  metric-registration parity (undeclared families, unpaired gauges)
  R4  lock-order discipline (acquisitions inverting lockorder.py)
  R5  no host pulls in jitted step code
  R6  device-instrument parity
  R7  actuator parity
  R8  guarded-by lock coverage (GUARDED_BY field contracts)

Usage:
    python tools/graftlint.py            # lint the tree, exit 0/1
    python tools/graftlint.py --list     # print the rule set
    python tools/graftlint.py --json     # findings as JSON records
    python tools/graftlint.py PATH...    # lint specific roots

Suppress a deliberate exception with ``# graftlint: disable=R1`` on the
line (or ``disable-file=R1`` anywhere in the file) — suppressions are
reviewable, silent drift is not. No jax import, no backend: the linter
runs in milliseconds anywhere.
"""

from __future__ import annotations

import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

if "siddhi_tpu" not in sys.modules:
    # stub the package so `siddhi_tpu.analysis` loads WITHOUT running
    # siddhi_tpu/__init__.py (which imports jax and mutates XLA_FLAGS):
    # the lint engine and rules are stdlib-only on purpose, and the
    # linter must run in milliseconds in jax-less environments too
    _pkg = types.ModuleType("siddhi_tpu")
    _pkg.__path__ = [os.path.join(REPO, "siddhi_tpu")]
    sys.modules["siddhi_tpu"] = _pkg

DEFAULT_ROOTS = ("siddhi_tpu", "tools", "chip_smoke.py",
                 "__graft_entry__.py")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    from siddhi_tpu.analysis import default_rules, load_modules, run_lint

    rules = default_rules()
    if "--list" in argv:
        for r in rules:
            print(f"{r.id}  {r.title}")
        return 0
    as_json = "--json" in argv
    roots = [a for a in argv if not a.startswith("-")] or list(DEFAULT_ROOTS)
    missing = [r for r in roots if not os.path.exists(os.path.join(REPO, r))]
    if missing:
        print(f"graftlint: root(s) do not exist: {missing}")
        return 2
    try:
        modules = load_modules(roots, REPO)
    except SyntaxError as e:
        # a mid-edit broken file gets the finding format, not a traceback
        print(f"{e.filename}:{e.lineno}: parse: {e.msg}")
        return 1
    if not modules:
        # a gate that checks nothing must not report success
        print(f"graftlint: no Python files under {roots}")
        return 2
    findings = run_lint(modules, rules=rules)
    if as_json:
        # machine-readable gate output (CI annotations, editor plugins):
        # one record per finding + a trailing summary object. Exit codes
        # are identical to the text mode.
        import json

        print(json.dumps({
            "findings": [{"rule": f.rule, "path": f.path, "line": f.line,
                          "message": f.message} for f in findings],
            "files": len(modules),
            "rules": [r.id for r in rules],
        }, indent=2))
        return 1 if findings else 0
    for f in findings:
        print(f.format())
    n = len(findings)
    print(f"graftlint: {n} finding{'s' if n != 1 else ''} across "
          f"{len(modules)} files ({', '.join(r.id for r in rules)})")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
