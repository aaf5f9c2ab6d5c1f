"""``BENCHMARK.json``'s configuration ``sequence_peak_10k`` as a
deployment: upstream's rising-run-then-drop SEQUENCE
(``SequencePartitionTestCase.testSequencePartitionQuery11``: a Kleene
``+`` whose condition reads its own ``e2[last]``), partitioned by symbol,
through ``SiddhiManager`` + ``send_columns`` with the engine's defaults,
on the generic NFA engine. Held to the plain reference of the benchmark
(``benchmarks/references/sequence.py``: numpy only, nothing of the
program), whose event-at-a-time loop is held to the rows upstream's test
expects. The app text, the feed kind (``walk``) and the key mix are the
configuration's and the cell's own files; the sizes are their rehearsal
entries (40 keys, 256-row batches), and, where a round has to be wider
than the engine's tile, 500 keys and 1,024-row batches. CPU backend,
seeded.
"""

import numpy as np
import pytest

from benchmarks import drive, generator, manifest, run
from benchmarks.references import sequence
from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.observability import journey
from siddhi_tpu.ops import nfa
from siddhi_tpu.observability.export import prometheus_text

CELL = "sequence_peak_10k.hot20_walk16k"
PATTERN_CELL = "pattern_ab_10k.rounds_bulk"


def _loop_rows(key, price):
    rows, _most = sequence.loop_reference(np.asarray(key),
                                          np.asarray(price, np.float64))
    return [(k, e1, e2a, e2b, e3) for _i, k, e1, e2a, e2b, e3 in rows]


def _f32(*xs):
    return [float(np.float32(x)) for x in xs]


# ---------------------------------------------------------------- (a)

def test_the_loop_gives_upstreams_rows_for_query_11():
    """testSequencePartitionQuery11's events, partitioned by volume as
    upstream's test partitions them: one match a key."""
    events = [("WSO2", 29.6, 100), ("WSO2", 35.6, 100), ("WSO2", 57.6, 100),
              ("IBM", 47.6, 100), ("WSO2", 129.6, 10), ("WSO2", 135.6, 10),
              ("WSO2", 157.6, 10), ("IBM", 147.6, 10)]
    price = np.array([p for _s, p, _v in events], np.float32)
    rows = _loop_rows([v for _s, _p, v in events], price)
    assert rows == [(100, *_f32(29.6, 35.6, 57.6, 47.6)),
                    (10, *_f32(129.6, 135.6, 157.6, 147.6))]


@pytest.mark.parametrize("prices,want", [
    # a failed extension kills the match: 28 is below e1 = 30, so the
    # match of 30 dies before it has an e2; 28 starts its own
    ([30, 28, 29, 27], [(28, 29, None, 27)]),
    # below e2[last] is the e3 that completes the match, not a kill
    ([30, 31, 32, 31.5], [(30, 31, 32, 31.5)]),
    # every re-arms on the event that a pending match consumed as its
    # e3 (31.5 completes one match and starts the next), but not on one
    # a pending match absorbed (31 and 32 start nothing)
    ([30, 31, 32, 31.5, 33, 31.7], [(30, 31, 32, 31.5),
                                    (31.5, 33, None, 31.7)]),
    # e2[1] reads null after a one-event run
    ([25, 26, 24], [(25, 26, None, 24)]),
    # an e1 must be above 20; a run that starts below takes its first
    # event above 20 as e1
    ([10, 19, 21, 22, 20.5], [(21, 22, None, 20.5)]),
    # a tie extends the run (>=), a fall after e1 with no e2 kills it
    ([40, 40, 40, 39, 38], [(40, 40, 40, 39)]),
])
def test_the_loop_by_hand(prices, want):
    price = np.array(prices, np.float32)
    rows = _loop_rows(np.zeros(len(prices), np.int64), price)
    assert [r[1:] for r in rows] == [
        tuple(None if x is None else float(np.float32(x)) for x in w)
        for w in want]


def test_one_key_holds_one_pending_match_at_most():
    """Under these rules an event leaves at most one partial match a key
    pending: far under ``nfa_slots`` (32)."""
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    feed = generator.make_feed(cell.config, sizes, traffic, 5)
    hist = feed.history(0, len(feed.warm) + len(feed.pool))
    _rows, most = sequence.loop_reference(hist["key"], hist["cols"]["price"])
    assert most == 1


# ---------------------------------------------------------------- (b)

def _feed(seed):
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    return cell, sizes, generator.make_feed(cell.config, sizes, traffic,
                                            seed)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 11])
def test_the_batch_reference_equals_its_loop(seed):
    cell, sizes, feed = _feed(seed)
    n = len(feed.warm) + 3 * len(feed.pool)          # the pool goes round
    want = sequence.reference(cell.config, sizes, feed, n)
    hist = feed.history(0, n)
    rows, _most = sequence.loop_reference(hist["key"], hist["cols"]["price"])
    assert len(rows) > 500
    assert want["key"].tolist() == [r[1] for r in rows]
    for role, j in (("price1", 2), ("price2", 3), ("price4", 5)):
        assert want[role].tolist() == [r[j] for r in rows]
    assert want["price3_null"].tolist() == [r[4] is None for r in rows]
    assert want["price3"][~want["price3_null"]].tolist() == [
        r[4] for r in rows if r[4] is not None]
    # the batch of every row: the batch of its e3 event
    per_row = np.repeat(np.arange(n), feed.rows)
    assert np.array_equal(np.bincount(per_row[[r[0] for r in rows]],
                                      minlength=n), want["rows_per_batch"])
    assert want["rounds_per_batch"].tolist() == [
        int(np.bincount(feed.batch(i).keys).max()) for i in range(n)]
    same = sequence.compare(cell.config, want, want)
    assert all(v == 0 for _n, v, _lim in same)


# --------------------------------------------------------- (c) and (d)

def _drive(cell, sizes, feed, n):
    journey.enable()
    try:
        manager, rt, collector = drive.build_app(cell.config, sizes, 1)
        sender = drive.Sender(rt, feed)
        for i in range(n):
            sender.send(i)
        got = run._delivered(cell.config, collector, rt, feed)
        got["key_capacity"] = rt.query_runtimes["seq"]._win_keys \
            if "seq" in rt.query_runtimes else None
        counters = rt.app_context.telemetry.snapshot()["counters"]
        scraped = prometheus_text(manager)
        ring = [j for j in journey.ring()
                if j["queries"] == list(rt.query_runtimes)]
        manager.shutdown()
    finally:
        journey.disable()
    return got, counters, scraped, ring


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 3])
def test_the_system_equals_the_reference_row_for_row(seed):
    cell, sizes, feed = _feed(seed)
    n = len(feed.warm) + feed.fill_batches + 2 * len(feed.pool)
    got, counters, scraped, ring = _drive(cell, sizes, feed, n)
    want = sequence.reference(cell.config, sizes, feed, n)
    assert len(want["key"]) > 500
    assert -1 not in got["key"]                      # keys decoded
    assert sequence.compare(cell.config, want, got) == [
        ("rows_missing", 0, 0), ("order_mismatch_rows", 0, 0)]
    # every step ran the generic engine, and told its rounds: on each
    # batch's journey and summed on /metrics
    rounds = want["rounds_per_batch"]
    assert [j["nfa_rounds"] for j in ring] == rounds.tolist()
    assert counters["nfa.seq.generic_steps"] == n
    assert counters["nfa.seq.rounds"] == rounds.sum()
    assert f'name="nfa.seq.rounds"}} {rounds.sum()}' in scraped


def test_the_two_step_kernel_reports_no_generic_step_and_no_round():
    cell = manifest.Cell(PATTERN_CELL)
    sizes, traffic = cell.sized(True)
    feed = generator.make_feed(cell.config, sizes, traffic, 11)
    n = len(feed.warm) + feed.fill_batches + 4
    _got, counters, scraped, ring = _drive(cell, sizes, feed, n)
    assert counters["nfa.nfa.generic_steps"] == 0
    assert counters["nfa.nfa.rounds"] == 0
    assert 'name="nfa.nfa.generic_steps"} 0' in scraped
    assert len(ring) == n and all(j["nfa_rounds"] == 0 for j in ring)


def test_the_rounds_are_a_meta_lane_whatever_the_instruments():
    """``profile_device_instruments`` off: the round count still rides
    the meta (its own lane, before ``nfa_runs``) and reaches the journey;
    the instrument lane is gone."""
    cell, sizes, feed = _feed(9)
    journey.enable()
    try:
        manager, rt, _collector = drive.build_app(cell.config, sizes, 1)
        q = rt.query_runtimes["seq"]
        on = [s.name for s in q.instrument_slots()]
        rt.app_context.profile_device_instruments = False
        q._instr_spec = None
        q._steps.clear()
        sender = drive.Sender(rt, feed)
        for i in range(3):
            sender.send(i)
        off = [s.name for s in q.instrument_slots()]
        ring = [j for j in journey.ring() if j["queries"] == ["seq"]]
        manager.shutdown()
    finally:
        journey.disable()
    assert on == ["nfa_rounds", "nfa_runs"] and off == ["nfa_rounds"]
    assert [j["nfa_rounds"] for j in ring] == [
        int(np.bincount(feed.batch(i).keys).max()) for i in range(3)]


# ------------------------------------------------------------------- (e)

def _rounds_and_tiles(keys, W):
    """A batch's rounds (its most rows of one key) and the tiles they take:
    the sum over rounds r of ceil(n_r / W), n_r the keys with more than r
    rows."""
    counts = np.bincount(keys)
    n_r = [int((counts > r).sum()) for r in range(counts.max())]
    return len(n_r), sum(-(-n // W) for n in n_r), n_r[0]


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 5])
def test_a_round_wider_than_a_tile_runs_as_several(seed):
    """500 keys, 1,024-row batches: round 0 holds some 260 keys, over
    twice the tile, and the rows still equal the reference row for row;
    each batch's tiles are what its keys make them, its rounds unmoved."""
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    sizes["keys"] = 500
    sizes["batch_rows"] = traffic["batch_rows"] = 1_024
    feed = generator.make_feed(cell.config, sizes, traffic, seed)
    n = len(feed.warm) + feed.fill_batches + 2 * len(feed.pool)
    got, counters, scraped, ring = _drive(cell, sizes, feed, n)
    want = sequence.reference(cell.config, sizes, feed, n)
    assert len(want["key"]) > 500
    assert sequence.compare(cell.config, want, got) == [
        ("rows_missing", 0, 0), ("order_mismatch_rows", 0, 0)]
    W = nfa._tile_rows(1_024, got["key_capacity"])
    reckoned = [_rounds_and_tiles(feed.batch(i).keys, W) for i in range(n)]
    assert max(first for _r, _t, first in reckoned) > 2 * W
    rounds = [r for r, _t, _f in reckoned]
    tiles = [t for _r, t, _f in reckoned]
    assert rounds == want["rounds_per_batch"].tolist()
    assert [j["nfa_rounds"] for j in ring] == rounds
    assert [j["nfa_tiles"] for j in ring] == tiles
    assert sum(tiles) > sum(rounds)
    assert counters["nfa.seq.rounds"] == sum(rounds)
    assert counters["nfa.seq.tiles"] == sum(tiles)
    assert f'name="nfa.seq.tiles"}} {sum(tiles)}' in scraped


class _Rows(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(e.data for e in events)


# upstream's CountPatternTestCase.testQuery17 (tests/test_pattern_corpus.py):
# AABB AABB A, four seconds, ABB AABB gives three matches
_EVERY_A2_B = """@app:playback define stream InputStream (name string);
    @info(name = 'q')
    from every e1=InputStream[(e1.name == 'A')]<2>
       -> e2=InputStream[(e2.name == 'B')]
       within 3 seconds
    select 'rule1' as ruleId, count() as numOfEvents
    insert into OutputStream;
"""
_Q17 = list("AABBAABBA") + list("ABBAABB")
_Q17_TS = np.array([1_000 + 100 * i + (4_000 if i >= 9 else 0)
                    for i in range(len(_Q17))], np.int64)


def _q17_rows(one_batch: bool):
    journey.enable()
    try:
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(_EVERY_A2_B)
        got = _Rows()
        rt.add_callback("OutputStream", got)
        h = rt.get_input_handler("InputStream")
        if one_batch:
            h.send_columns({"name": np.array(_Q17, dtype=object)},
                           timestamps=_Q17_TS)
        else:
            for name, t in zip(_Q17, _Q17_TS):
                h.send(int(t), [name])
        counters = rt.app_context.telemetry.snapshot()["counters"]
        ring = [j for j in journey.ring() if j["queries"] == ["q"]]
        manager.shutdown()
    finally:
        journey.disable()
    return got.rows, counters, ring


def test_an_unpartitioned_count_pattern_takes_a_row_a_tile():
    """K = 1: the tile is one row, so a batch of the one key takes a tile
    a row, as many as its rounds, and gives the rows the same events give
    one at a time (upstream's three matches)."""
    rows, counters, ring = _q17_rows(one_batch=True)
    one_by_one, _c, _r = _q17_rows(one_batch=False)
    assert rows == one_by_one and len(rows) == 3
    assert [(j["nfa_rounds"], j["nfa_tiles"]) for j in ring] == [
        (len(_Q17), len(_Q17))]
    assert counters["nfa.q.tiles"] == counters["nfa.q.rounds"] == len(_Q17)
