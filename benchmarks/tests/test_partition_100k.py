"""The cell ``partition_len1k_100k.hot20_bulk_100k``: its rehearsal grows
key capacity in set-up as the chip run does, and the three readers of what
the growth and the whole-ring passes leave in the journeys and the trace.

On a cut of a real trace: one step of the cell on one v5e chip from PR
33's traced chip run (key capacity 131,072, rings of 131,072,000 slots),
as ``_ring_pass.load`` gives it, times from the send's start, kept beside
this file. On the older cuts and on journeys of a program without the
fields every reader returns nothing. On made-up events whose answer is
plain.
"""

import gzip
import json
import os

import pytest

from benchmarks import drive, manifest
from benchmarks.metrics import _ring_pass

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "partition_len1k_100k.hot20_bulk_100k"
NEW = ("step_ring_pass_ms", "grow_s", "state_bytes_per_slot")
SLOTS = 131_072_000


def _cut(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


def _readers():
    return {entry["name"]: reader
            for entry, reader in manifest.Cell(CELL).per_layer()}


def test_the_cell_reports_the_three_and_no_other_cell_does():
    bench = manifest.Cell(CELL).bench
    moves = {"step_ring_pass_ms": "events_per_s", "grow_s": "setup_s",
             "state_bytes_per_slot": "events_per_s"}
    for entry in bench["per_layer"]:
        if entry["name"] in NEW:
            assert entry["workloads"] == [CELL]
            assert entry["moves"] == moves[entry["name"]]
    assert set(NEW) <= set(_readers())
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {e["name"] for e, _ in
                     manifest.Cell(other["name"]).per_layer()}
            assert not names & set(NEW)


def test_the_configuration_is_cell_ones_at_ten_times_the_keys():
    big = manifest.Cell(CELL)
    small = manifest.Cell("partition_len1k_10k.hot20_bulk")
    for key in ("family", "app", "inputs", "output", "aggregates",
                "stated_precision", "control_precision", "limits",
                "guarantees"):
        assert big.config[key] == small.config[key], key
    assert big.config["sizes"] == {"keys": 100_000, "window": 1_000}
    assert big.config["reduced"] == [] and "route" not in big.config
    same = {k: v for k, v in big.traffic.items()
            if k not in ("fill", "rehearsal", "why")}
    assert same == {k: small.traffic[k] for k in same}
    assert big.traffic["fill"] == {"until": "hot_rings_wrapped",
                                   "at_most_batches": 1024}


def test_the_rehearsal_grows_in_its_fill_and_the_readers_say_so(
        run_cell, monkeypatch):
    """600 keys against a warm batch of 256 rows: the warm batch sizes
    the state for 256 keys before any exists (no growth: nothing to
    move), the pool grows it to 512 or 1,024 with its rings live, as
    65,536 -> 131,072 on the chip: two step programs, one growth."""
    apps = []
    build_app = drive.build_app

    def keeping(*args):
        apps.append(build_app(*args))
        return apps[-1]

    monkeypatch.setattr(drive, "build_app", keeping)
    rc, last, cap = run_cell(CELL, "--trace", "1", "--cpu-rehearsal")
    assert rc == 0 and last["correct"], cap.err[-2000:]
    metrics = last["metrics"]
    assert metrics["grow_s"]["value"] > 0
    # eight ring slots a key: 35 B of ring columns a slot, and a key's
    # count and aggregates (8 + 2 x 16 B) spread over its eight
    assert metrics["state_bytes_per_slot"]["value"] == 35 + 40 / 8
    assert "step_ring_pass_ms" not in metrics       # no device trace here
    (_manager, rt, _collector), = apps
    snap = rt.app_context.telemetry.snapshot()
    assert snap["counters"]["state.bench.grows"] == 1
    assert snap["jit"]["query.bench.step"]["compiles"] == 2
    q = rt.query_runtimes["bench"]
    # (not every one of the 480 cold keys comes in eight small batches)
    assert q.key_capacity() in (512, 1_024)
    assert q.state_slots() == 8 * q.key_capacity()


# ------------------------------------------------------------ the readers

def test_a_real_trace_of_the_cell_gives_one_steps_ring_passes():
    """One send of 65,536 rows at key capacity 131,072. By hand, from the
    listing of the step's operations over ``[131,072,000]`` (PERF.md
    section 5): each of the two int64 rings (``volume``, ``__ts__``) is
    split by two ``X64Split*`` copies of 3.05-3.06 ms, its high plane
    passed over in 1.59 and its words re-joined in 3.18-3.19 (the scope),
    and combined by an ``X64Combine`` copy of 5.83-5.84: 2 x (2 x 3.05 +
    5.83) = 23.88 and 2 x (1.59 + 3.18) = 9.55, 33.43 ms a step. The 19
    ``X64*`` calls on batch-wide and keys-wide columns beside them are
    microseconds each and are not ring passes."""
    recorded = _cut("trace_v5e_ring_pass_cut.json.gz")
    assert len(recorded["host"]) == 1
    (ops,) = recorded["ring"].values()
    assert len(ops) == 29
    ring = [op for op in ops if op[0] == "scope" or op[1] == SLOTS]
    assert [op[0] for op in ring] == ["x64", "scope", "x64", "scope", "x64",
                                      "x64", "scope", "x64", "scope", "x64"]
    assert max(op[3] for op in ops if op not in ring) < 10e3       # ns
    got = _ring_pass.attribute(recorded, SLOTS)
    assert got["sends"] == 1
    assert got["kind_s"]["x64"] * 1e3 == pytest.approx(23.876, abs=1e-3)
    assert got["kind_s"]["scope"] * 1e3 == pytest.approx(9.555, abs=1e-3)
    by_hand = (3.0588 + 3.0517 + 5.8370 + 3.0523 + 3.0510 + 5.8254
               + 1.5937 + 3.1857 + 1.5925 + 3.1831)
    assert sum(got["kind_s"].values()) * 1e3 == pytest.approx(by_hand,
                                                              abs=1e-3)
    assert 10 < by_hand < 100               # a v5e's, not a CPU's
    # a program from a compile cache filled before the scope existed
    # (the cache's key leaves scopes out) shows the copies alone
    unscoped = {"host": recorded["host"], "ring": {
        "p": [op for op in ops if op[0] != "scope"]}}
    assert _ring_pass.attribute(unscoped, SLOTS)["kind_s"]["scope"] == 0.0


@pytest.mark.parametrize("cut", ["trace_v5e_partition_cut.json.gz",
                                 "trace_v5e_pattern_spans_cut.json.gz",
                                 "trace_v5e_x4_route_cut.json.gz",
                                 "trace_v5e_tumbling_cut.json.gz"])
def test_an_older_cut_names_no_ring_pass(cut):
    assert _ring_pass.attribute(_cut(cut), SLOTS) is None


def test_journeys_without_the_fields_read_as_nothing():
    readers = _readers()
    older = {"batch": 7, "pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0}
    unkeyed = dict(older, grow_ms=None, state_bytes=1_000, state_slots=None)
    for journeys in ([], [older], [unkeyed, unkeyed]):
        ctx = {"journeys": journeys}
        assert readers["state_bytes_per_slot"].read(ctx) is None
        assert readers["step_ring_pass_ms"].read(ctx) is None


def test_state_bytes_per_slot_is_the_windows_first_batch():
    j = {"batch": 9, "state_bytes": 4_000, "state_slots": 100}
    later = dict(j, batch=10, state_bytes=8_000)
    ctx = {"journeys": [{"batch": 8}, j, later]}
    assert _readers()["state_bytes_per_slot"].read(ctx) == 40.0


def test_grow_s_is_set_ups_growths_from_the_engines_ring():
    from siddhi_tpu.observability import journey

    def rec(batch, grow_ms):
        return {"batch": batch, "grow_ms": grow_ms}

    journey.enable()
    try:
        journey._RING.clear()
        read = _readers()["grow_s"].read
        assert read({"journeys": []}) is None
        journey._RING.extend([rec(1, 1_500.0), rec(2, None), rec(3, 250.0),
                              rec(4, None), rec(5, 4_000.0)])
        window = [rec(4, None), rec(5, 4_000.0)]
        assert read({"journeys": window}) == 1.75     # batches 1 and 3
        assert read({"journeys": []}) == 5.75         # no window: all
        journey._RING.clear()
        journey._RING.extend([{"batch": 1}, {"batch": 2}])   # the parent's
        assert read({"journeys": [{"batch": 2}]}) is None
    finally:
        journey._RING.clear()
        journey.disable()


def test_ring_passes_are_the_scope_and_the_copies_of_a_ring_column():
    send = [["bench.send_columns", 0.0, 100e6]]
    one = [["scope", 500, 10e6, 2e6],        # the scope, whatever its shape
           ["x64", 1_000, 20e6, 3e6],        # a copy of a ring column
           ["x64", 64, 30e6, 5e6],           # of a batch column: not a pass
           ["x64", 1_000, 200e6, 9e9]]       # outside the window
    got = _ring_pass.attribute({"host": send, "ring": {"p0": one}}, 1_000)
    assert got == {"kind_s": {"scope": pytest.approx(2e-3),
                              "x64": pytest.approx(3e-3)}, "sends": 1}
    # the ring's length not known (no journey states it): the scope alone
    got = _ring_pass.attribute({"host": send, "ring": {"p0": one}}, None)
    assert got["kind_s"] == {"scope": pytest.approx(2e-3), "x64": 0.0}
    # planes are averaged; one with nothing to count is not a plane
    two = _ring_pass.attribute({"host": send, "ring": {
        "p0": one, "p1": one[:1], "p2": one[2:3]}}, 1_000)
    assert two["kind_s"] == {"scope": pytest.approx(2e-3),
                             "x64": pytest.approx(1.5e-3)}
    assert _ring_pass.attribute({"host": [], "ring": {"p": one}}, 1_000) \
        is None
    assert _ring_pass.attribute({"host": send, "ring": {}}, 1_000) is None
    assert _ring_pass.attribute({"host": send}, 1_000) is None
