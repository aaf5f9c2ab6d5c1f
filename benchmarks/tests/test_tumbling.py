"""The tumbling family (``timebatch_1s_10k.hot20_tick250``): its reference
held to the event-at-a-time loop, its control, its least bytes, and the
three readers of what a flush leaves in the trace and in the journeys.

On a cut of a real trace: the first twelve sends of the cell on one v5e
chip from PR 31's first traced chip run (three flushes among them), as
``_flush.load`` gives them, times from the first send, kept beside this
file. On the older cuts and on journeys of any other query every reader
returns nothing. On made-up events whose answer is plain.
"""

import gzip
import json
import os

import numpy as np
import pytest

from benchmarks import generator, manifest
from benchmarks.metrics import _flush
from benchmarks.references import tumbling

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "timebatch_1s_10k.hot20_tick250"
NEW = ("step_flush_ms", "timer_steps_per_window", "flush_rows_per_window")


def _cut(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


def _feed(seed, **traffic_over):
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(rehearsal=True)
    traffic.update(traffic_over)
    return cell, sizes, generator.make_feed(cell.config, sizes, traffic, seed)


def _readers():
    return {entry["name"]: reader
            for entry, reader in manifest.Cell(CELL).per_layer()}


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 77])
@pytest.mark.parametrize("round_ms", [250, 400, 1300])
def test_reference_equals_the_loop_event_at_a_time(seed, round_ms):
    """Windows of four batches, of two and a half (a boundary inside a
    gap), and batches further apart than a window (every send closes one,
    every other boundary closes nothing)."""
    n = 23
    cell, sizes, feed = _feed(seed, round_ms=round_ms)
    want = tumbling.reference(cell.config, sizes, feed, n)
    hist = feed.history(0, n)
    send = np.repeat(np.arange(n), feed.rows)
    by, key, cnt, lo, hi, ts = tumbling.loop_reference(
        send, hist["key"], hist["cols"]["price"].astype(np.float64),
        hist["ts"], sizes["window_ms"])
    assert len(key) and want["facts"]["flushes"] >= 4
    for name, col in (("key", key), ("n", cnt), ("lo", lo), ("hi", hi),
                      ("ts", ts)):
        assert np.array_equal(want[name], col), name
    assert np.array_equal(want["rows_per_batch"],
                          np.bincount(by, minlength=n))
    assert not any(n for n, v, lim in tumbling.compare(
        cell.config, want, want) if v > lim)


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 77])
def test_the_control_fails_by_a_number_about_values_alone(seed):
    cell, sizes, feed = _feed(seed)
    want = tumbling.reference(cell.config, sizes, feed, 40)
    below = tumbling.reference(cell.config, sizes, feed, 40,
                               dtype=cell.config["control_precision"])
    over = {n: v for n, v, lim in tumbling.compare(cell.config, want, below)
            if v > lim}
    assert set(over) == {"minmax_max_abs_err"}
    # bfloat16 keeps 8 bits: half a unit of 0.5 at most, from 64 up
    assert 0.05 < over["minmax_max_abs_err"] <= 0.25


@pytest.mark.parametrize("fault,number", [
    ("a_row_lost", "rows_missing"),
    ("a_key_swapped", "key_mismatch_rows"),
    ("two_rows_exchanged", "order_mismatch_rows"),
    ("a_count_off", "sum_mismatch_rows"),
    ("a_min_off", "minmax_max_abs_err"),
])
def test_compare_names_what_is_wrong(fault, number):
    cell, sizes, feed = _feed(5)
    want = tumbling.reference(cell.config, sizes, feed, 40)
    got = {k: want[k].copy() for k in ("key", "n", "lo", "hi")}
    if fault == "a_row_lost":
        got = {k: v[:-1] for k, v in got.items()}
    elif fault == "a_key_swapped":
        absent = np.setdiff1d(np.arange(sizes["keys"] + 1),
                              want["key"][want["flush"] == 0])[0]
        got["key"][3] = absent
    elif fault == "two_rows_exchanged":
        for col in got.values():
            col[[2, 3]] = col[[3, 2]]
    elif fault == "a_count_off":
        got["n"][7] += 1
    else:
        got["lo"][7] -= 1e-5        # one float32 ulp at 100 is 7.6e-6
    over = {n for n, v, lim in tumbling.compare(cell.config, want, got)
            if v > lim}
    assert number in over
    if fault == "two_rows_exchanged":   # matched by key, all is there
        assert over == {"order_mismatch_rows"}


def test_least_bytes_of_a_batch_at_the_cells_sizes():
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(rehearsal=False)
    assert (sizes["keys"], traffic["batch_rows"]) == (10_000, 65_536)
    # 65,536 x (4 + 4 + 8) in, 10,000 x 32 of accumulators read and
    # written; the flush (10,000 x 44 a window) is left out: a floor
    assert tumbling.bytes_per_batch(cell.config, sizes, 65_536) == 1_368_576
    assert tumbling.bytes_per_batch(cell.config, sizes, 4_096) \
        == 4_096 * 16 + 4_096 * 32


def test_the_cell_reports_the_three_and_no_other_cell_does():
    bench = manifest.Cell(CELL).bench
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == "events_per_s"
    assert set(NEW) <= set(_readers())
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {e["name"] for e, _ in
                     manifest.Cell(other["name"]).per_layer()}
            assert not names & set(NEW)


# ------------------------------------------------------------ the readers

def test_a_real_trace_of_the_cell_gives_its_flushes():
    """Twelve sends of 65,536 rows: three windows closed among them, each
    flush a run of ``siddhi.flush`` operations on the one device plane."""
    recorded = _cut("trace_v5e_tumbling_cut.json.gz")
    assert len(recorded["host"]) == 12
    (ops,) = recorded["flush"].values()
    assert {scope for scope, _s, _d in ops} == {"flush"}
    got = _flush.attribute(recorded)
    assert got["flushes"] == 3
    per_flush_ms = got["flush_s"] / got["flushes"] * 1e3
    assert per_flush_ms == pytest.approx(
        sum(d for _sc, _s, d in ops) / 3 / 1e6)
    assert 0.05 < per_flush_ms < 5.0        # a v5e's, not a CPU's


@pytest.mark.parametrize("cut", ["trace_v5e_partition_cut.json.gz",
                                 "trace_v5e_pattern_spans_cut.json.gz",
                                 "trace_v5e_x4_route_cut.json.gz"])
def test_a_trace_of_another_cell_names_no_flush(cut):
    assert _flush.attribute(_cut(cut)) is None


def test_flushes_are_runs_of_operations_and_planes_are_averaged():
    send = [["bench.send_columns", 0.0, 100e6]]
    one = [["flush", 10e6, 1e5], ["flush", 10.2e6, 3e5],      # a flush
           ["flush", 50e6, 2e5], ["flush", 50.3e6, 2e5],      # another
           ["flush", 200e6, 9e9]]                  # outside the window
    got = _flush.attribute({"host": send, "flush": {"/device:TPU:0": one}})
    assert got == {"flush_s": pytest.approx(8e5 / 1e9), "flushes": 2}
    two = _flush.attribute({"host": send, "flush": {
        "/device:TPU:0": one, "/device:TPU:1": one[:2]}})
    assert two == {"flush_s": pytest.approx(12e5 / 2 / 1e9), "flushes": 1.5}
    assert _flush.attribute({"host": [], "flush": {"p": one}}) is None
    assert _flush.attribute({"host": send, "flush": {}}) is None
    assert _flush.attribute({"host": send}) is None


def test_journeys_of_another_query_read_as_nothing():
    readers = _readers()
    plain = {"pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0,
             "flush_rows": None, "timer_steps": None}
    older = {"pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0}
    for journeys in ([], [plain], [older, older]):
        for name in NEW[1:]:
            assert readers[name].read({"journeys": journeys}) is None, name


def test_journeys_of_the_cell_read_per_flush():
    """Eight data steps, two TIMER steps that flushed, one that found its
    window empty (a send two boundaries ahead)."""
    data = {"flush_rows": None, "timer_steps": None}
    journeys = ([data] * 4 + [{"flush_rows": 9_990, "timer_steps": 1}]
                + [data] * 4 + [{"flush_rows": 9_994, "timer_steps": 1},
                                {"flush_rows": None, "timer_steps": 1}])
    readers = _readers()
    ctx = {"journeys": journeys}
    assert readers["flush_rows_per_window"].read(ctx) == 9_992.0
    assert readers["timer_steps_per_window"].read(ctx) == 1.5
