"""Tests of the benchmark itself. Not part of tier-1 (``pytest tests/``);
run them with ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
Everything here runs on the CPU backend at rehearsal sizes and proves
control flow and the comparisons, nothing about the chip."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.fixture
def run_cell(capsys):
    """Runs one cell's rehearsal in this process; returns (exit code,
    the parsed last line of stdout or None, everything else)."""
    from benchmarks import run

    def go(workload, *extra, seed=11, seconds=0.5):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), *extra])
        cap = capsys.readouterr()
        lines = [ln for ln in cap.out.splitlines() if ln.startswith("{")]
        last = json.loads(lines[-1]) if lines else None
        return rc, last, cap
    return go
