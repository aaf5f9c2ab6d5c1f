"""chip_smoke.py's CPU rehearsal, in this process: the same code the chip
runs, at a tiny size. Proves control flow and the comparisons, nothing
about the chip."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_rehearsal_runs_all_three_phases(capsys):
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    lines = _lines(capsys)
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert sorted(phases) == ["A", "B", "C"]
    for ln in phases.values():
        assert ln["equal_to_reference"] is True
        assert ln["compiles_after_warmup"] == 0
        assert ln["errors_logged"] == []
        assert ln["warmup"]["programs_compiled"] > 0
        assert ln["rows_out"] > 0
    assert phases["B"]["rings_wrapped"] > 0
    assert phases["C"]["pending_found_expired"] > 0
    assert lines[0]["string_encoder"] == "native"
    # a rehearsal says so, and never under a TPU's name
    assert lines[-1] == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}


def test_rehearsal_of_the_four_chip_phase(capsys):
    """On four of the suite's virtual CPU devices: the one routed run,
    equal to the unsharded run of the same feed."""
    assert chip_smoke.main(["--cpu-rehearsal", "--chips", "4"]) == 0
    lines = _lines(capsys)
    by_phase = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert sorted(by_phase) == ["mesh/routed", "mesh/unsharded"]
    routed, unsharded = by_phase["mesh/routed"], by_phase["mesh/unsharded"]
    assert routed["devices"] == 4 and unsharded["devices"] == 1
    assert len(set(routed["mesh_device_ids"])) == 4
    assert routed["outcome"] == "equal to unsharded, row for row"
    assert routed["rows_not_bit_equal"] == 0
    assert routed["rows_out"] == unsharded["rows_out"] > 0
    assert routed["compiles_after_warmup"] == 0
    assert len(routed["memory"]) == 4
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4


def test_refuses_to_run_without_a_tpu(capsys):
    """No option, no chip: non-zero, and no result line."""
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs 1 tpu device" in out.err


@pytest.mark.parametrize("phase,reference", [
    ("A", "reference_global_window"),
    ("B", "reference_keyed_window"),
    ("C", "reference_pattern"),
])
def test_a_failed_comparison_prints_no_result(capsys, monkeypatch, phase,
                                              reference):
    real = getattr(chip_smoke, reference)

    def off_by_one(*args):
        out = list(real(*args))
        out[1] = np.asarray(out[1]).copy()
        out[1][-1] += 1
        return tuple(out)

    monkeypatch.setattr(chip_smoke, reference, off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure, match=f"phase {phase}"):
        chip_smoke.main(["--cpu-rehearsal"])
    assert '"ok"' not in capsys.readouterr().out


def test_a_routed_run_that_differs_prints_no_result(capsys, monkeypatch):
    """The four-chip phase fails like every other: one routed row off
    the unsharded run's is a ``SmokeFailure`` and no result line."""
    real = chip_smoke._drive_stock

    def one_row_off(*args, route=None):
        out, *rest = real(*args, route=route)
        if route is not None:
            out.parts["totalVolume"][-1][-1] += 1
        return (out, *rest)

    monkeypatch.setattr(chip_smoke, "_drive_stock", one_row_off)
    with pytest.raises(chip_smoke.SmokeFailure, match="phase mesh/routed"):
        chip_smoke.main(["--cpu-rehearsal", "--chips", "4"])
    assert '"ok"' not in capsys.readouterr().out


def test_references_on_a_hand_worked_case():
    """The plain references, checked by hand — they are what the chip's
    answers are held to."""
    sym = np.array([0, 1, 0, 0])
    price = np.array([1.0, 2.0, 3.0, 5.0])
    vol = np.array([1, 2, 3, 4])
    avg, tot, _ = chip_smoke.reference_global_window(sym, price, vol, 2)
    # window of the last 2 events: [0] [0,1] [1,0] [0,0]
    assert avg.tolist() == [1.0, 2.0, 3.0, 4.0]
    assert tot.tolist() == [1, 2, 3, 7]
    avg, tot, facts = chip_smoke.reference_keyed_window(sym, price, vol, 2)
    # per-key windows: key 0 holds [1] [1,3] [3,5]; key 1 holds [2]
    assert avg.tolist() == [1.0, 2.0, 2.0, 4.0]
    assert tot.tolist() == [1, 2, 4, 7]
    assert facts == {"rings_wrapped": 1, "rings_wrapped_share": 0.5}
    v1, v2, by, expired = chip_smoke.reference_pattern(
        np.array([0, 0, 1, 0, 1, 1]), np.array([7, 7, 7, 7, 7, 7]),
        np.array([5.0, 1.0, 3.0, 9.0, 4.0, 10.0]),
        np.array([0, 10, 20, 30, 5011, 5020]), 5000)
    # B=3 takes A=1 only; B=4 @5011 finds A=5 (t=0) expired; B=10 takes 9
    assert list(zip(v1, v2, by)) == [(1.0, 3.0, 2), (9.0, 10.0, 5)]
    assert expired == 1


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from siddhi_tpu.core.util import compile_cache

    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    # set in the environment: JAX reads it itself, the program sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache.place_compile_cache() == "/some/where"
    assert seen == []
    # unset: one fixed path under the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert compile_cache.place_compile_cache() == want
    assert seen == [("jax_compilation_cache_dir", want)] * 2
