"""The readers of the routed cell (``partition_len1k_40k.hot20_bulk_x4``):
``_route.py`` and the five metrics that only a device-routed query feeds.

On a cut of a real trace: the first two sends of the cell on a four-chip
v5e host from PR 27's first traced chip run (four device planes), as
``_spans.load`` gives them with ``_route.scoped_ops`` beside
(``routed``), operation names shortened, times from the first send, kept
beside this file. On the one-chip cuts and on journeys of an unrouted
query every reader returns nothing. On made-up events whose answer is
plain. And the cell's rehearsal on four virtual CPU devices.
"""

import gzip
import json
import os

import pytest

from benchmarks import manifest, tracereduce
from benchmarks.metrics import _route, _spans
from test_spans import _msg

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "partition_len1k_40k.hot20_bulk_x4"
NEW = ("step_route_ms", "step_merge_ms", "route_prep_ms_per_batch",
       "route_pieces_per_batch", "route_fullest_shard_pct")


def _cut(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    return _cut("trace_v5e_x4_route_cut.json.gz")


def _readers():
    return {entry["name"]: reader
            for entry, reader in manifest.Cell(CELL).per_layer()}


def test_the_cell_reports_the_five_and_no_other_cell_does():
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


@pytest.mark.parametrize("cut", ["trace_v5e_partition_cut.json.gz",
                                 "trace_v5e_pattern_spans_cut.json.gz"])
def test_a_one_chip_trace_names_neither_scope(cut):
    """The cuts of PR 24 and PR 25: no ``routed`` key, as a trace of an
    unrouted program gives none; nothing to read, nothing returned."""
    assert _route.attribute(_cut(cut)) is None


def test_journeys_of_an_unrouted_query_read_as_nothing():
    readers = _readers()
    plain = {"pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0,
             "route_prep_ms": None, "route_pieces": None,
             "shard_rows_max": None, "shard_capacity": None}
    older = {"pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0}
    for journeys in ([], [plain], [older, older]):
        ctx = {"journeys": journeys}
        for name in NEW[2:]:
            assert readers[name].read(ctx) is None, name


def test_journeys_of_a_routed_query_read_as_their_means():
    readers = _readers()
    ctx = {"journeys": [
        {"route_prep_ms": 3.0, "route_pieces": 1, "shard_rows_max": 60,
         "shard_capacity": 80.0},
        {"route_prep_ms": 5.0, "route_pieces": 2, "shard_rows_max": 80,
         "shard_capacity": 80.0},
        {"route_prep_ms": None, "route_pieces": None,
         "shard_rows_max": None, "shard_capacity": None}]}
    assert readers["route_prep_ms_per_batch"].read(ctx) == 4.0
    assert readers["route_pieces_per_batch"].read(ctx) == 1.5
    assert readers["route_fullest_shard_pct"].read(ctx) == 87.5


def test_the_two_scopes_are_read_from_the_raw_file(tmp_path):
    """An ``XSpace`` written field by field: two device planes whose
    event metadata name ``siddhi.route`` (by value) and ``siddhi.merge``
    (by reference) in ``tf_op``; an inner-step operation
    (``siddhi.state``) that is not this reader's."""
    stat_names = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                  _msg((1, 9), (2, _msg(
                      (1, 9), (2, "jit(siddhi_device_routed_x4)/shard_map/"
                                  "siddhi.merge/sort:"))))]
    route = _msg((1, 1), (2, "%all-to-all.1 = ..."),
                 (5, _msg((1, 7), (5, "jit(siddhi_device_routed_x4)/shard_map/"
                                      "siddhi.route/all_to_all:"))))
    merge = _msg((1, 2), (2, "%sort.2 = ..."), (5, _msg((1, 7), (7, 9))))
    state = _msg((1, 3), (2, "%fusion.3 = ..."),
                 (5, _msg((1, 7), (5, "jit(siddhi_device_routed_x4)/shard_map/"
                                      "siddhi.state/gather:"))))
    events = [_msg((1, 1), (2, 5_000_000), (3, 2_000_000)),
              _msg((1, 3), (2, 7_000_000), (3, 1_000_000)),
              _msg((1, 2), (2, 8_000_000), (3, 4_000_000))]

    def device(n):
        return _msg(
            (1, n), (2, f"/device:TPU:{n}"),
            (3, _msg((1, 2), (2, "XLA Ops"), (3, 1000),
                     *[(4, e) for e in events])),
            *[(4, _msg((1, k), (2, m))) for k, m in
              ((1, route), (2, merge), (3, state))],
            *[(5, s) for s in stat_names])

    path = tmp_path / "made.xplane.pb"
    path.write_bytes(_msg((1, device(0)), (1, device(1))))
    ops = [["route", 1000 + 5000.0, 2000.0], ["merge", 1000 + 8000.0, 4000.0]]
    assert _route.scoped_ops(str(path)) == {
        "/device:TPU:0": ops, "/device:TPU:1": ops}
    assert _spans.scoped_ops(str(path)) == {
        f"/device:TPU:{n}": [["state", 1000 + 7000.0, 1000.0]]
        for n in (0, 1)}
    # one send that ends inside the merge: clipped; the mean of the planes
    got = _route.attribute({
        "host": [["bench.send_columns", 0.0, 10_000.0]],
        "routed": _route.scoped_ops(str(path))})
    assert got["planes"] == 2 and got["sends"] == 1
    assert got["scope_s"] == pytest.approx(
        {"route": 2000e-9, "merge": 1000e-9})
    assert _route.attribute({"host": [], "routed": {"p": ops}}) is None


def test_the_rehearsal_on_four_virtual_devices_ends_correct(run_cell):
    rc, last, _cap = run_cell(CELL, "--trace", "1", "--cpu-rehearsal")
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # the journey's three are read on any backend; the two scopes come
    # off a device plane, which a CPU trace has not
    assert m["route_prep_ms_per_batch"] > 0
    assert m["route_pieces_per_batch"] >= 1.0
    assert 25.0 <= m["route_fullest_shard_pct"] <= 100.0
    assert "step_route_ms" not in m and "step_merge_ms" not in m
    assert last["metrics"]["route_fullest_shard_pct"]["unit"] == "%"


def test_the_recorded_routed_trace_reads_as_it_was_read_by_hand(recorded):
    """By hand (PERF.md section 5): two sends of 306.9 ms; each runs ONE
    program, ``jit_siddhi_device_routed_x4``, on each of the four chips
    for 245.0 ms; of them 21.2 ms in ``siddhi.route`` (twelve bucket
    scatters, the ``all_to_all`` 0.3) and 138.5 in ``siddhi.merge``
    (fourteen gathers of 655,360 elements; the sort 1.6, the
    ``all_gather`` 0.35), the four planes within 0.5% of each other."""
    got = _route.attribute(recorded)
    assert got["sends"] == 2 and got["planes"] == 4
    assert got["window_s"] == pytest.approx(0.6142, abs=1e-4)
    assert got["scope_s"] == pytest.approx(
        {"route": 42.332e-3, "merge": 277.003e-3}, abs=1e-6)
    for ops in recorded["routed"].values():
        for scope, want in (("route", 42.33e6), ("merge", 277.0e6)):
            assert sum(d for k, _s, d in ops if k == scope) == \
                pytest.approx(want, rel=1e-2)
    # the reducer and the span reader see the same window and sends
    reduced = tracereduce.reduce(recorded)
    inner = _spans.attribute(recorded)
    assert reduced["sends"] == inner["sends"] == 2
    assert reduced["window_s"] == inner["window_s"] == got["window_s"]
    assert reduced["device_planes"] == 4
    ((program, seconds),) = reduced["programs"]
    assert program.startswith("jit_siddhi_device_routed_x4(")
    assert seconds / 2 == pytest.approx(245.05e-3, rel=1e-3)
    # the five scopes account for the program's device time but for
    # 1.5% (copies, parameters' own operations): none nests in another
    scoped = sum(got["scope_s"].values()) + sum(inner["scope_s"].values())
    assert 0.98 * reduced["busy_s"] < scoped <= reduced["busy_s"]
    assert inner["scope_s"] == pytest.approx(
        {"state": 130.736e-3, "select": 30.636e-3, "meta": 2.016e-3},
        abs=1e-6)
    # every routed operation is one of the reducer's operations
    for plane, ops in recorded["routed"].items():
        starts = {int(s) for _n, s, _d in
                  recorded["devices"][plane]["XLA Ops"]}
        assert all(int(s) in starts for _k, s, _d in ops)
    # the host side: ``siddhi.route.prepare`` (kept beside the spans of
    # ``_spans.LAYER_OF``, which does not know it) lies inside its
    # batch's ``siddhi.query.step``, so the idle under it is dispatch's
    steps = {b: (s, s + d) for n, s, d, b in recorded["spans"]
             if n == "siddhi.query.step"}
    prepares = recorded["route_prepare"]
    assert [b for _n, _s, _d, b in prepares] == sorted(steps) == [52, 53]
    for _n, s, d, b in prepares:
        assert steps[b][0] <= s and s + d <= steps[b][1]
        assert 8.9e6 < d < 9.4e6
