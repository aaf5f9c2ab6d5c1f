"""The cell ``join_time10s_len100.hot20_tweets1k``: a rehearsal is correct
and its control fails, its three metrics are its own, their readers on
made-up journeys and trace events, and the least bytes at the cell's
sizes. (The engine held to the reference row for row, the reference to
upstream's rules an event at a time and the feed kind ``two_stream_rows``:
tier-1, ``tests/test_join_cell.py`` and ``tests/test_benchmark_feeds.py``.)
"""

import json

import pytest

from benchmarks import generator, manifest
from benchmarks.metrics import (join_matches_per_batch,
                                join_probe_hbm_roofline, step_join_probe_ms)
from benchmarks.tracereduce import SEND

CELL = "join_time10s_len100.hot20_tweets1k"
NEW = ("step_join_probe_ms", "join_probe_hbm_roofline",
       "join_matches_per_batch")


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


@pytest.mark.parametrize("seed", [1, 2])
def test_the_control_fails_the_comparison_by_rows(seed):
    """Prices in bfloat16: every price is rounded, so rows differ."""
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    feed = generator.make_feed(cell.config, sizes, traffic, seed)
    want = cell.family.reference(cell.config, sizes, feed, 40)
    ctl = cell.family.reference(cell.config, sizes, feed, 40,
                                dtype=cell.config["control_precision"])
    assert all(v <= lim for _, v, lim in
               cell.family.compare(cell.config, want, want))
    over = [n for n, v, lim in cell.family.compare(cell.config, want, ctl)
            if v > lim]
    assert set(over) == {"rows_missing", "order_mismatch_rows"}


def test_a_rehearsal_is_correct_and_its_control_is_not(run_cell):
    rc, last, cap = run_cell(CELL, "--trace", "0", "--cpu-rehearsal",
                             "--control", seconds=1.0)
    assert rc == 0 and last["correct"] is True
    assert set(last["metrics"]) == {"events_per_s", "result_latency_p95_ms",
                                    "setup_s"}
    facts = [json.loads(ln) for ln in cap.out.splitlines()
             if ln.startswith('{"workload"')][-1]
    assert {n for n, v, lim in facts["control"] if v > lim} == {
        "rows_missing", "order_mismatch_rows"}


def test_matches_are_a_mean_over_the_sent_batches_not_the_timer_steps():
    journeys = [{"join_matches": 2000}, {"join_matches": 0, "timer_steps": 1},
                {"join_matches": 870_000}, {"join_matches": None},
                {"nfa_rounds": 3}]
    assert join_matches_per_batch.read({"journeys": journeys}) == 436_000
    assert join_matches_per_batch.read({"journeys": journeys[3:]}) is None


def test_probe_time_is_the_union_of_its_scope_a_send():
    events = {"host": [[SEND, 0.0, 10e6], [SEND, 10e6, 10e6]],
              "rounds": {"/device:TPU:0": [[1e6, 4e6], [3e6, 4e6],
                                           [12e6, 1e6]]}}
    got = step_join_probe_ms.step_nfa_rounds_ms.attribute(events)
    assert got == {"scope_s": 7e-3, "sends": 2}


def test_the_least_probe_bytes_follow_the_expected_pairs():
    cell = manifest.Cell(CELL)
    sizes, _traffic = cell.sized(False)
    *_, pairs_tick, pairs_tweet, window = cell.family._round(cell.config,
                                                             sizes)
    assert window == 2_621_440
    assert pairs_tick == pytest.approx(2_130, rel=0.01)
    assert pairs_tweet == pytest.approx(872_400, rel=0.01)
    probe = cell.family.probe_bytes_per_batch(cell.config, sizes)
    whole = cell.family.bytes_per_batch(cell.config, sizes, 65536)
    assert 5e6 < probe < whole < 8e6
    # a roofline share from a probe time: bytes over time, in percent
    ctx = {"peaks": {"hbm_bytes_per_s": 819e9}, "chips": 1}
    step_join_probe_ms._of_file.cache_clear()
    assert join_probe_hbm_roofline.read(dict(ctx, journeys=[])) is None
