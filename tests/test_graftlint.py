"""graftlint self-tests: each rule flags its known-bad fixture, and the
real tree is clean.

The fixtures under ``tests/fixtures/lint/`` are loaded with SYNTHETIC
repo-relative paths (a production-looking location per rule) so the
rules' path scoping — R5 only looks at hot-path packages, R2 skips
tests/ — applies exactly as it would in the tree."""

from __future__ import annotations

import os

import pytest

from siddhi_tpu.analysis import default_rules, load_modules, run_lint
from siddhi_tpu.analysis.engine import LintContext, ModuleInfo

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures", "lint")

# fixture file -> virtual repo path (rule scoping applies to the path)
FIXTURE_PATHS = {
    "r1_backend_init.py": "siddhi_tpu/parallel/bad_backend.py",
    "r2_adhoc_knob.py": "siddhi_tpu/core/bad_knobs.py",
    "r3_metric_family.py": "siddhi_tpu/observability/bad_metrics.py",
    "r3_stage_family.py": "siddhi_tpu/observability/bad_stage_metrics.py",
    "r4_lock_order.py": "siddhi_tpu/core/query/bad_locks.py",
    "r5_host_pull.py": "siddhi_tpu/core/query/bad_steps.py",
    "r6_instruments.py": "siddhi_tpu/core/query/bad_instruments.py",
    "r7_actuators.py": "siddhi_tpu/autopilot/bad_actuators.py",
    "r8_guards.py": "siddhi_tpu/core/query/bad_guards.py",
}


def _load_fixture(name: str) -> ModuleInfo:
    return ModuleInfo.load(os.path.join(FIXTURES, name),
                           FIXTURE_PATHS[name])


def _lint_fixture(name: str):
    # the real export.py supplies the R3 declarations
    export = ModuleInfo.load(
        os.path.join(REPO, "siddhi_tpu/observability/export.py"),
        "siddhi_tpu/observability/export.py")
    mods = [_load_fixture(name), export]
    findings = run_lint(mods)
    # only findings against the fixture itself (export.py may report
    # dead prefixes in this tiny two-file tree — not under test here)
    return [f for f in findings if f.path == FIXTURE_PATHS[name]]


@pytest.mark.parametrize("name,rule,min_hits", [
    ("r1_backend_init.py", "R1", 3),   # module const, jax.devices, default
    ("r2_adhoc_knob.py", "R2", 3),     # f-string key, literal key, env var
    ("r3_metric_family.py", "R3", 3),  # prefix x2 + family literal
    # critical-path profiler families (stage.* / siddhi_stage_ms):
    # unremoved gauge under the new prefix + family literal
    ("r3_stage_family.py", "R3", 2),
    ("r4_lock_order.py", "R4", 2),     # pump->owner and owner->barrier
    ("r5_host_pull.py", "R5", 4),      # float, .item, np.asarray, bool
    # undeclared data slot + consumer-less check slot
    ("r6_instruments.py", "R6", 2),
    # untyped knob + dead actuator + undeclared actuation path
    ("r7_actuators.py", "R7", 3),
    # stale declaration, unlocked write, unlocked read, undeclared
    # thread-spawning class
    ("r8_guards.py", "R8", 4),
])
def test_rule_flags_its_fixture(name, rule, min_hits):
    findings = _lint_fixture(name)
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) >= min_hits, (
        f"{name}: wanted >= {min_hits} {rule} findings, got "
        f"{[f.format() for f in findings]}")


def test_fixture_findings_are_single_rule():
    # each fixture is crafted for exactly one rule — cross-rule noise
    # would mean the fixtures (or rules) drifted
    for name, path in FIXTURE_PATHS.items():
        rule = name[:2].upper()
        wrong = [f for f in _lint_fixture(name) if f.rule != rule]
        assert not wrong, (
            f"{name} tripped other rules: "
            f"{[f.format() for f in wrong]}")


def test_clean_tree_zero_findings():
    """The acceptance bar: the repaired production tree lints clean."""
    modules = load_modules(
        ("siddhi_tpu", "tools", "chip_smoke.py",
         "__graft_entry__.py"), REPO)
    findings = run_lint(modules)
    assert not findings, "\n".join(f.format() for f in findings)


def test_suppression_comments():
    import tempfile

    src = ("import jax.numpy as jnp\n"
           "X = jnp.int64(1)  # graftlint: disable=R1\n"
           "Y = jnp.int64(2)\n")

    with tempfile.NamedTemporaryFile("w", suffix=".py", delete=False) as f:
        f.write(src)
        tmp = f.name
    try:
        m = ModuleInfo.load(tmp, "siddhi_tpu/s.py")
        findings = run_lint([m])
        r1 = [f for f in findings if f.rule == "R1"]
        assert len(r1) == 1 and r1[0].line == 3, \
            [f.format() for f in findings]
        # file-scope suppression silences both
        with open(tmp, "w") as fh:
            fh.write("# graftlint: disable-file=R1\n" + src)
        m = ModuleInfo.load(tmp, "siddhi_tpu/s.py")
        assert not [f for f in run_lint([m]) if f.rule == "R1"]
    finally:
        os.unlink(tmp)


def test_rule_registry_lists_eight_rules():
    rules = default_rules()
    assert [r.id for r in rules] == ["R1", "R2", "R3", "R4", "R5", "R6",
                                     "R7", "R8"]


def test_instrument_parity_bidirectional():
    """A DEVICE_SLOTS entry no Slot(...) produces — and a check slot no
    _consume_check_slot handles — are findings too (fixture export.py,
    the real one stays untouched)."""
    import ast

    exp_src = ('TELEMETRY_PREFIXES = ("device",)\n'
               'PROCESS_LIFETIME_GAUGES = ("device.*",)\n'
               'DEVICE_SLOTS = ("win_fill", "never_computed")\n'
               'DEVICE_CHECK_SLOTS = ("seq",)\n')
    reg_src = ('from siddhi_tpu.observability.instruments import Slot\n'
               'def wire(tel, q):\n'
               '    tel.gauge(f"device.{q}.win_fill", lambda: 0)\n'
               'def spec():\n'
               '    return [Slot("win_fill"), Slot("seq", kind="check")]\n'
               'class R:\n'
               '    def _consume_check_slot(self, name, vals):\n'
               '        if name == "seq":\n'
               '            pass\n')
    mods = [
        ModuleInfo(path="siddhi_tpu/observability/export.py", src=exp_src,
                   tree=ast.parse(exp_src)),
        ModuleInfo(path="siddhi_tpu/core/wire.py", src=reg_src,
                   tree=ast.parse(reg_src)),
    ]
    findings = [f for f in run_lint(mods) if f.rule == "R6"]
    dead = [f for f in findings if "never_computed" in f.message]
    assert dead, [f.format() for f in findings]
    # the matched pair raises nothing else
    assert all("never_computed" in f.message for f in findings), \
        [f.format() for f in findings]


def test_metric_prefix_parity_bidirectional():
    """A declared-but-unused prefix is a finding too (dead declaration),
    using a fixture export.py so the real one stays untouched."""
    import ast

    exp_src = ('TELEMETRY_PREFIXES = ("junction", "ghost")\n'
               'PROCESS_LIFETIME_GAUGES = ("junction.*",)\n')
    reg_src = ('def wire(tel, sid):\n'
               '    tel.gauge(f"junction.{sid}.queue_depth", lambda: 0)\n')
    mods = [
        ModuleInfo(path="siddhi_tpu/observability/export.py", src=exp_src,
                   tree=ast.parse(exp_src)),
        ModuleInfo(path="siddhi_tpu/core/wire.py", src=reg_src,
                   tree=ast.parse(reg_src)),
    ]
    findings = run_lint(mods)
    ghosts = [f for f in findings if "ghost" in f.message]
    assert ghosts, [f.format() for f in findings]


def test_knob_parity_bidirectional():
    """A knob declared in the registry that no production code reads is
    a finding — in both consumption styles (attr=None needs a
    read_knob literal, attr='x' needs the attribute consumed). Uses a
    fixture knobs.py so the real registry stays untouched."""
    import ast

    reg_src = ('KNOBS = _declare(\n'
               '    Knob("window_capacity", "int",'
               ' attr="window_capacity"),\n'
               '    Knob("ghost_attr", "int", attr="ghost_attr"),\n'
               '    Knob("quota_queue_depth", "int"),\n'
               '    Knob("ghost_key", "float"),\n'
               ')\n')
    use_src = ('def wire(ctx, cm):\n'
               '    cap = getattr(ctx, "window_capacity", 4096)\n'
               '    depth = read_knob(cm, "quota_queue_depth")\n'
               '    return cap, depth\n')
    mods = [
        ModuleInfo(path="siddhi_tpu/core/util/knobs.py", src=reg_src,
                   tree=ast.parse(reg_src)),
        ModuleInfo(path="siddhi_tpu/core/wire.py", src=use_src,
                   tree=ast.parse(use_src)),
    ]
    findings = [f for f in run_lint(mods) if f.rule == "R2"]
    msgs = [f.message for f in findings]
    assert any("ghost_attr" in m for m in msgs), msgs
    assert any("ghost_key" in m for m in msgs), msgs
    # the two consumed knobs raise nothing
    assert not any("window_capacity" in m or "quota_queue_depth" in m
                   for m in msgs), msgs


def test_step_registry_resolves():
    """Every declared jitted step builder still exists where declared
    (hlo_audit trusts this registry for its coverage assertion)."""
    from siddhi_tpu.analysis.step_registry import JIT_STEP_BUILDERS, resolve

    # the exact set: a builder that disappears, or appears, is seen
    assert sorted(JIT_STEP_BUILDERS) == [
        "device_join", "device_routed", "fused_fanout",
        "gspmd_replicated_batch", "query_step", "sharded_agg"]
    for name in JIT_STEP_BUILDERS:
        assert resolve(name) is not None


def test_hlo_audit_covers_the_registry():
    """``tools/hlo_audit.py`` has one ``@audit`` for every registered
    builder and none for a builder that is gone (its ``main`` asserts the
    same before it lowers anything)."""
    import importlib.util

    from siddhi_tpu.analysis.step_registry import JIT_STEP_BUILDERS

    spec = importlib.util.spec_from_file_location(
        "hlo_audit", os.path.join(REPO, "tools", "hlo_audit.py"))
    hlo_audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hlo_audit)
    assert sorted(hlo_audit.AUDITS) == sorted(JIT_STEP_BUILDERS)


def test_graftlint_driver_exits_zero():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "graftlint.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout
