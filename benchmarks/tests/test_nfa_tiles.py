"""``nfa_tiles_per_batch``: the sequence cell's alone, and its reader on
made-up journeys whose answer is plain (the count itself is held to the
batch's keys in tier-1, ``tests/test_sequence_peak.py``)."""

from benchmarks import manifest
from benchmarks.metrics import nfa_tiles_per_batch

CELL = "sequence_peak_10k.hot20_walk16k"


def test_the_sequence_cell_reports_it_and_no_other_cell_does():
    cell = manifest.Cell(CELL)
    entry, = [e for e, _ in cell.per_layer()
              if e["name"] == "nfa_tiles_per_batch"]
    assert entry["workloads"] == [CELL]
    assert (entry["moves"], entry["layer"], entry["source"]) == (
        "events_per_s", "query step (kernels)", "program_counter")
    for other in cell.bench["workloads"]:
        if other["name"] != CELL:
            assert "nfa_tiles_per_batch" not in {
                e["name"] for e, _ in manifest.Cell(other["name"]).per_layer()}


def test_tiles_a_batch_is_the_journeys_mean():
    ctx = {"journeys": [{"nfa_tiles": 20}, {"nfa_tiles": 22},
                        {"nfa_tiles": None}, {"nfa_rounds": 17}]}
    assert nfa_tiles_per_batch.read(ctx) == 21.0
    # a program without the field (the parent of the PR that brought it)
    assert nfa_tiles_per_batch.read({"journeys": [{"nfa_rounds": 17}]}) is None
