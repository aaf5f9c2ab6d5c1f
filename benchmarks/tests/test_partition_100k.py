"""The cell ``partition_len1k_100k.hot20_bulk_100k``: its rehearsal grows
key capacity in set-up as the chip run does, and the two readers of what
the growth leaves in the journeys. On journeys of a program without the
fields every reader returns nothing. On made-up journeys whose answer is
plain. (What the ring writes cost there: ``test_ring_write.py``.)
"""

from benchmarks import drive, manifest

CELL = "partition_len1k_100k.hot20_bulk_100k"
NEW = ("grow_s", "state_bytes_per_slot")


def _readers():
    return {entry["name"]: reader
            for entry, reader in manifest.Cell(CELL).per_layer()}


def test_the_cell_reports_the_two_and_no_other_cell_does():
    bench = manifest.Cell(CELL).bench
    moves = {"grow_s": "setup_s", "state_bytes_per_slot": "events_per_s"}
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
    (_manager, rt, _collector), = apps
    snap = rt.app_context.telemetry.snapshot()
    assert snap["counters"]["state.bench.grows"] == 1
    assert snap["jit"]["query.bench.step"]["compiles"] == 2
    q = rt.query_runtimes["bench"]
    # (not every one of the 480 cold keys comes in eight small batches)
    assert q.key_capacity() in (512, 1_024)
    assert q.state_slots() == 8 * q.key_capacity()


# ------------------------------------------------------------ the readers

def test_journeys_without_the_fields_read_as_nothing():
    readers = _readers()
    older = {"batch": 7, "pack_ms": 1.0, "dispatch_ms": 2.0, "emit_ms": 3.0}
    unkeyed = dict(older, grow_ms=None, state_bytes=1_000, state_slots=None)
    for journeys in ([], [older], [unkeyed, unkeyed]):
        ctx = {"journeys": journeys}
        assert readers["state_bytes_per_slot"].read(ctx) is None


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
