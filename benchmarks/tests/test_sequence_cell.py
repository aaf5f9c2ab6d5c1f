"""The cell ``sequence_peak_10k.hot20_walk16k``: its control fails at
rehearsal size, its three metrics are its own, and their readers on
made-up journeys and trace events whose answer is plain. (The engine held
to the reference row for row, and the feed kind ``walk``: tier-1,
``tests/test_sequence_peak.py`` and ``tests/test_benchmark_feeds.py``.)
"""

import json

import pytest

from benchmarks import generator, manifest
from benchmarks.metrics import (nfa_rounds_hbm_roofline, nfa_rounds_per_batch,
                                step_nfa_rounds_ms)
from benchmarks.tracereduce import SEND

CELL = "sequence_peak_10k.hot20_walk16k"
NEW = ("nfa_rounds_per_batch", "step_nfa_rounds_ms", "nfa_rounds_hbm_roofline")


def test_the_cell_reports_the_three_and_no_other_cell_does():
    bench = manifest.Cell(CELL).bench
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "events_per_s"
            assert entry["layer"] == "query step (kernels)"
    assert set(NEW) <= {e["name"] for e, _ in manifest.Cell(CELL).per_layer()}
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {e["name"] for e, _ in
                     manifest.Cell(other["name"]).per_layer()}
            assert not names & set(NEW)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_fails_the_comparison(seed):
    """Prices in bfloat16: ties extend runs float32 breaks and every
    price is rounded, so rows differ."""
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    feed = generator.make_feed(cell.config, sizes, traffic, seed)
    want = cell.family.reference(cell.config, sizes, feed, 60)
    ctl = cell.family.reference(cell.config, sizes, feed, 60,
                                dtype=cell.config["control_precision"])
    assert all(v <= lim for _, v, lim in
               cell.family.compare(cell.config, want, want))
    over = [n for n, v, lim in cell.family.compare(cell.config, want, ctl)
            if v > lim]
    assert set(over) == {"rows_missing", "order_mismatch_rows"}


def test_a_control_run_is_not_correct_by_its_control_numbers(run_cell):
    rc, last, cap = run_cell(CELL, "--trace", "0", "--cpu-rehearsal",
                             "--control")
    assert rc == 0 and last["correct"] is True
    facts = [json.loads(ln) for ln in cap.out.splitlines()
             if ln.startswith('{"workload"')][-1]
    assert {n for n, v, lim in facts["control"] if v > lim} == {
        "rows_missing", "order_mismatch_rows"}
    assert facts["reference"]["pending_per_key_max"] == 1


def test_rounds_a_batch_is_the_journeys_mean():
    ctx = {"journeys": [{"nfa_rounds": 17}, {"nfa_rounds": 19},
                        {"nfa_rounds": None}, {}]}
    assert nfa_rounds_per_batch.read(ctx) == 18.0
    assert nfa_rounds_per_batch.read({"journeys": [{"x": 1}]}) is None


def _events(rounds):
    return {"host": [[SEND, 0.0, 100e6], [SEND, 100e6, 100e6]],
            "rounds": rounds}


def test_the_loops_time_is_the_union_of_its_operations_in_the_window():
    """A loop's own event may span its body's operations: counted once.
    Two planes: their mean. Outside the sends' window: left out."""
    one = [[10e6, 50e6], [12e6, 5e6], [20e6, 10e6],      # a while over two
           [120e6, 40e6], [190e6, 30e6]]                 # 10 ms past the end
    got = step_nfa_rounds_ms.attribute(_events({"/device:TPU:0": one}))
    assert got["sends"] == 2
    assert got["scope_s"] == pytest.approx((50e6 + 40e6 + 10e6) / 1e9)
    two = step_nfa_rounds_ms.attribute(_events({
        "/device:TPU:0": one, "/device:TPU:1": [[0.0, 20e6]]}))
    assert two["scope_s"] == pytest.approx((0.1 + 0.02) / 2)
    assert step_nfa_rounds_ms.attribute(_events({})) is None
    assert step_nfa_rounds_ms.attribute(
        {"host": [], "rounds": {"/device:TPU:0": one}}) is None


def test_the_roofline_is_the_rounds_least_bytes_over_the_loops_time(
        monkeypatch):
    cell = manifest.Cell(CELL)
    sizes, _ = cell.sized(False)
    least = cell.family.round_bytes_per_batch(cell.config, sizes, 16_384)
    # 16,384 rows x 24 B, 4,690 keys x 32 slots x 21 B twice, 4,096 x 21 B
    assert least == 6_776_979
    monkeypatch.setattr(step_nfa_rounds_ms, "read", lambda ctx: 50.0)
    ctx = {"peaks": {"hbm_bytes_per_s": 819e9}, "chips": 1}
    assert nfa_rounds_hbm_roofline.read(ctx) == pytest.approx(
        100 * least / 819e9 / 0.05)
    monkeypatch.setattr(step_nfa_rounds_ms, "read", lambda ctx: None)
    assert nfa_rounds_hbm_roofline.read(ctx) is None
