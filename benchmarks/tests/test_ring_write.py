"""``step_ring_write_ms``: the device's time in ``siddhi.ring_write``, the
keyed length window's ring writes, in the four partition cells.

On a cut of a real trace: one step of ``partition_len1k_100k.hot20_bulk_100k``
on one v5e chip from PR 36's traced chip run (key capacity 131,072, eleven
ring leaves of 131,072,000 slots), as ``step_ring_write_ms.load`` gives it,
times from the send's start, kept beside this file. On the older cuts
(programs without the scope) the reader returns nothing. On made-up events
whose answer is plain.
"""

import gzip
import json
import os

import pytest

from benchmarks import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "step_ring_write_ms"
CELLS = ["partition_len1k_10k.hot20_bulk", "partition_len1k_40k.hot20_bulk_x4",
         "partition_len1k_100k.hot20_bulk_100k",
         "partition_len1k_10k.zipf_scrambled"]


def _cut(name):
    with gzip.open(os.path.join(HERE, name), "rt") as f:
        return json.load(f)


def _reader():
    return dict((entry["name"], reader) for entry, reader
                in manifest.Cell(CELLS[0]).per_layer())[NAME]


def test_the_partition_cells_report_it_and_no_other_cell_does():
    bench = manifest.Cell(CELLS[0]).bench
    (entry,) = [e for e in bench["per_layer"] if e["name"] == NAME]
    assert entry["workloads"] == CELLS
    assert (entry["moves"], entry["source"], entry["better"]) == (
        "events_per_s", "device_trace", "lower")
    assert entry["layer"] == next(
        e["layer"] for e in bench["per_layer"] if e["name"] == "step_state_ms")
    for cell in bench["workloads"]:
        names = {e["name"] for e, _ in manifest.Cell(cell["name"]).per_layer()}
        assert (NAME in names) == (cell["name"] in CELLS)


def test_a_real_trace_of_cell_6_gives_one_steps_ring_writes():
    """One send of 65,536 rows into eleven leaves of 131,072,000 slots. By
    hand, from the listing of the step's operations under
    ``siddhi.state/siddhi.ring_write`` (PERF.md section 5): eleven
    scatters, the eight 4-byte leaves at 1.857-1.859 ms and the three null
    masks at 1.044 ms (windows of each ring passed through fast memory),
    eleven sorts of 65,536 (slot, word) pairs at 0.033-0.034 ms, and
    thirteen operations of under a microsecond (the ``select_n`` that
    keeps an out-of-range slot out of range): 8 x 1.858 + 3 x 1.044 + 11
    x 0.0335 = 18.36 ms. (Update by update the same eleven took 64.8 ms:
    5.97-6.00 and 5.51-5.53 each.)"""
    recorded = _cut("trace_v5e_ring_write_cut.json.gz")
    assert len(recorded["host"]) == 1
    (ops,) = recorded["ring_write"].values()
    by_ms = sorted(d / 1e6 for _s, d in ops)
    assert len(ops) == 35
    rest, sorts, masks, columns = (by_ms[:-22], by_ms[-22:-11],
                                   by_ms[-11:-8], by_ms[-8:])
    assert max(rest) < 0.001
    assert all(0.033 < d < 0.034 for d in sorts), sorts
    assert all(1.043 < d < 1.045 for d in masks), masks
    assert all(1.856 < d < 1.860 for d in columns), columns
    got = _reader().attribute(recorded)
    assert got["sends"] == 1
    assert got["scope_s"] * 1e3 == pytest.approx(sum(by_ms), abs=1e-6)
    assert got["scope_s"] * 1e3 == pytest.approx(
        8 * 1.858 + 3 * 1.044 + 11 * 0.0335, abs=0.01)


@pytest.mark.parametrize("cut", ["trace_v5e_partition_cut.json.gz",
                                 "trace_v5e_x4_route_cut.json.gz",
                                 "trace_v5e_tumbling_cut.json.gz"])
def test_an_older_cut_names_no_ring_write(cut):
    assert _reader().attribute(_cut(cut)) is None


def test_ring_writes_are_the_scopes_operations_inside_the_window():
    attribute = _reader().attribute
    sends = [["bench.send_columns", 0.0, 40e6],
             ["bench.send_columns", 50e6, 50e6]]
    one = [[10e6, 2e6],              # inside
           [60e6, 3e6],
           [95e6, 9e6],              # the window ends at 100e6: 5e6 of it
           [200e6, 9e9]]             # outside
    got = attribute({"host": sends, "ring_write": {"p0": one}})
    assert got == {"scope_s": pytest.approx(10e-3), "sends": 2}
    # planes are averaged
    two = attribute({"host": sends, "ring_write": {"p0": one, "p1": one[:1]}})
    assert two["scope_s"] == pytest.approx(6e-3)
    assert attribute({"host": [], "ring_write": {"p": one}}) is None
    assert attribute({"host": sends, "ring_write": {}}) is None
    assert attribute({"host": sends}) is None
