"""The two-stream window join of ``BASELINE.json`` configs[4] through the
normal path (``send_columns`` on both input handlers, the device join
engine, the callback) at a small size: ``time(1 sec)`` of 1,024-row tick
batches against ``length(100)`` of 32-row tweet batches over 200 symbols
(the cell ``join_time10s_len100.hot20_tweets1k`` at its rehearsal sizes;
``time(4 sec)`` where the ring has to grow three times),
held row for row and in order to the benchmark's plain reference
(``benchmarks/references/join.py``), which is held to upstream's rules an
event at a time. The time window's ring starts at 32 slots and grows
with its content; the keyed probe's output width grows with the pairs.
"""

import numpy as np
import pytest

from benchmarks import drive, generator, manifest, run
from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.util.config import InMemoryConfigManager

CELL = "join_time10s_len100.hot20_tweets1k"


def _cell(seed, **sized):
    cell = manifest.Cell(CELL)
    sizes, traffic = cell.sized(True)
    sizes.update(sized)
    return cell, sizes, generator.make_feed(cell.config, sizes, traffic, seed)


def _app(config, sizes, capacity):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.window_capacity": str(capacity)}))
    rt = m.create_siddhi_app_runtime(config["app"].format(**sizes))
    out = config["output"]
    collector = drive.make_collector(tuple(out["columns"].values()))
    rt.add_callback(out["stream"], collector)
    return m, rt, collector


def _events(feed, n):
    """The feed's first n batches as upstream's events: (stream, ts, key,
    price or tweet index), a row at a time."""
    out = []
    for i in range(n):
        b = feed.batch(i)
        val = b.cols["price"] if b.stream == 0 else b.codes["tweet"]
        out += zip([b.stream] * len(b.keys), feed.timestamps(i).tolist(),
                   b.keys.tolist(), val.tolist())
    return out


@pytest.mark.parametrize("seed", [5, 2_900_000_011])
def test_the_reference_is_upstreams_rules_an_event_at_a_time(seed):
    cell, sizes, feed = _cell(seed)
    n = len(feed.warm) + feed.fill_batches + 5
    want = cell.family.reference(cell.config, sizes, feed, n)
    rows = cell.family.loop_reference(
        _events(feed, n), sizes["time_s"] * 1000, sizes["length"])
    assert len(rows) > 10_000
    assert rows == list(zip(want["key"].tolist(), want["tweet"].tolist(),
                            want["price"].tolist()))
    assert want["rows_per_batch"].sum() == len(rows)


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_app_is_the_reference_row_for_row_while_its_ring_grows(seed):
    cell, sizes, feed = _cell(seed, time_s=4)
    m, rt, collector = _app(cell.config, sizes, 32)
    q = rt.query_runtimes["join"]
    stage = q.sides["left"].window_stage
    assert q.engine is not None and q.engine.keyed == {"left": True,
                                                       "right": True}
    sender = drive.Sender(rt, feed)
    n = len(feed.warm) + feed.fill_batches + 12
    capacities, widths = [], []
    try:
        for i in range(n):
            sender.send(i)
            capacities.append(stage.capacity)
            widths.append(dict(q.engine.width))
        rt.app_context.completion_pump.flush()
        got = run._delivered(cell.config, collector, rt, feed)
        tel = rt.app_context.telemetry
        counters, gauges = dict(tel.counters), tel.read_gauges()
    finally:
        m.shutdown()
    want = cell.family.reference(cell.config, sizes, feed, n)
    assert [(name, v) for name, v, _lim in
            cell.family.compare(cell.config, want, got)] == [
        ("rows_missing", 0), ("order_mismatch_rows", 0)]
    # four seconds of ticks are sixteen 1,024-row batches: 32 -> 1,024 ->
    # 8,192 -> 65,536 slots, each the power of two the rows needed and at
    # least eight times the last, then no more
    assert sorted(set(capacities)) == [1024, 8192, 65536]
    assert capacities[-1] == 65536 and counters["state.join.grows"] == 3
    assert gauges["window.join.capacity"] == 65536
    # tweet batches whose pairs outgrew the width the last one needed
    grew = [i for i in range(1, n) if feed.batch(i).stream == 1
            and widths[i]["right"] > widths[i - 1]["right"]]
    assert len(grew) >= 2
    assert gauges["join.join.out_width"] in {w["left"] for w in widths} | {
        w["right"] for w in widths}
    # the ticks probe 100 tweets, a ring that never grows: their width is
    # the power of two of their most pairs, no more
    tick_pairs = max(int(want["rows_per_batch"][i]) for i in range(n)
                     if feed.batch(i).stream == 0)
    assert widths[-1]["left"] == max(1024, 1 << (tick_pairs - 1).bit_length())
    # every pair the steps matched was delivered, once
    assert counters["join.join.matches"] == len(want["key"])


EDGES = """
@app:playback
define stream cseEventStream (symbol string, price float, volume int);
define stream twitterStream (user string, tweet string, company string);
@info(name = 'join')
from cseEventStream#window.time(1 sec) join twitterStream#window.length(3)
  on cseEventStream.symbol == twitterStream.company
select cseEventStream.symbol as symbol, twitterStream.tweet as tweet,
       cseEventStream.price as price
insert into outputStream;
"""


SENDS = [  # (stream, ts, symbols, prices or tweets)
    (0, 10_000, ["A", "B", "A"], [1.5, 2.5, 3.5]),
    (1, 10_999, ["A"], ["t0"]),              # both A ticks, oldest first
    (1, 11_000, ["A", "B"], ["t1", "t2"]),   # the 10,000 ticks are gone
    (0, 11_000, ["C", "C", "A", "D", "B"], [4.5, 5.5, 6.5, 7.5, 8.5]),
    (0, 11_200, ["E", "F"], [9.5, 10.5]),    # no tweet of E or F
    (1, 11_500, ["C", "A", "B", "C"], ["t3", "t4", "t5", "t6"]),
    (0, 11_600, ["C", "A"], [11.5, 12.5]),   # meets t4 t5 t6 only
    # the length(3) ring wraps: t9, t7, t8 by slot, t7, t8, t9 by arrival
    (1, 11_700, ["A", "A", "A"], ["t7", "t8", "t9"]),
    (0, 11_800, ["A"], [13.5]),              # meets t7 t8 t9, in that order
]


def _edges(app, engine, capacity, sends=SENDS):
    """Sends ``sends`` through ``app`` on the ``engine`` join engine with
    a time ring of ``capacity`` slots; returns (rows, events, the time
    ring's slots at the end, the join runtime)."""
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.window_capacity": str(capacity),
         "siddhi_tpu.join_engine": engine}))
    rt = m.create_siddhi_app_runtime(app)
    collector = drive.make_collector(("symbol", "tweet", "price"))
    rt.add_callback("outputStream", collector)
    ticks, tweets = (rt.get_input_handler("cseEventStream"),
                     rt.get_input_handler("twitterStream"))
    events = []
    try:
        for stream, ts, keys, vals in sends:
            n = len(keys)
            if stream == 0:
                ticks.send_columns(
                    {"symbol": np.array(keys, dtype=object),
                     "price": np.array(vals, np.float32),
                     "volume": np.ones(n, np.int32)},
                    timestamps=np.full(n, ts, np.int64))
            else:
                tweets.send_columns(
                    {"user": np.array(["u"] * n, dtype=object),
                     "tweet": np.array(vals, dtype=object),
                     "company": np.array(keys, dtype=object)},
                    timestamps=np.full(n, ts, np.int64))
            events += [(stream, ts, k, v) for k, v in zip(keys, vals)]
        rt.app_context.completion_pump.flush()
        decode = rt.app_context.string_dictionary.decode
        got = list(zip([decode(int(i)) for i in collector.column("symbol")],
                       [decode(int(i)) for i in collector.column("tweet")],
                       collector.column("price").tolist()))
        q = rt.query_runtimes["join"]
        capacity = q.sides["left"].window_stage.capacity
    finally:
        m.shutdown()
    return got, events, capacity, q


def test_edges_are_upstreams_an_event_at_a_time():
    """A tick exactly 1 s old when a tweet arrives has left the window; a
    tick batch whose symbols no tweet has matches nothing; a tweet batch
    that evicts tweets of its own; several ticks of one symbol meet a
    tweet oldest first, and several tweets of a wrapped ring meet a tick
    oldest first; the ring grows past its 4 slots."""
    from benchmarks.references import join

    got, events, capacity, q = _edges(EDGES, "device", 4)
    assert q.engine is not None and all(q.engine.keyed.values())
    want = join.loop_reference(events, 1_000, 3)
    assert got == want
    assert ("A", "t0", 1.5) in got and ("A", "t1", 1.5) not in got
    assert not [r for r in got if r[0] in ("E", "F")]
    assert got[-3:] == [("A", "t7", 13.5), ("A", "t8", 13.5),
                        ("A", "t9", 13.5)]
    assert capacity == 32           # 4 -> 32 slots: ten ticks live


# an ``on`` with no equality conjunct that means the same: every engine
# probes the other side's whole window with the broadcast compare
BROADCAST = EDGES.replace(
    "on cseEventStream.symbol == twitterStream.company",
    "on not (cseEventStream.symbol != twitterStream.company)")


@pytest.mark.parametrize("engine,app", [
    ("legacy", EDGES), ("device", BROADCAST), ("legacy", BROADCAST)],
    ids=["legacy-keyed", "device-broadcast", "legacy-broadcast"])
def test_every_probe_meets_a_wrapped_ring_in_arrival_order(engine, app):
    """The legacy engine, and the broadcast probe of either engine, emit
    the same rows in the same order as upstream's loop: a trigger meets
    the other side's rows oldest first, also once the length(3) ring has
    wrapped."""
    from benchmarks.references import join

    got, events, _capacity, q = _edges(app, engine, 16)
    if engine == "legacy":
        assert q.engine is None
    else:
        assert q.engine is not None and not any(q.engine.keyed.values())
    assert got == join.loop_reference(events, 1_000, 3)
    assert got[-3:] == [("A", "t7", 13.5), ("A", "t8", 13.5),
                        ("A", "t9", 13.5)]


PARTITIONED = EDGES.replace("@info(name = 'join')", """partition with (
  symbol of cseEventStream, company of twitterStream)
begin
@info(name = 'join')""").replace("insert into outputStream;",
                                 "insert into outputStream;\nend;")


def test_a_partitioned_join_meets_each_keys_wrapped_ring_in_arrival_order():
    """Inside a partition each key has windows of its own: the legacy
    keyed probe reads a key's rings oldest first too, so each key's rows
    are upstream's loop over that key's events, also once its length(3)
    ring has wrapped (A's: t10, t8, t9 by slot)."""
    from benchmarks.references import join

    sends = SENDS + [(1, 11_900, ["A"], ["t10"]), (0, 11_950, ["A"], [14.5])]
    got, events, _capacity, q = _edges(PARTITIONED, "device", 16, sends)
    assert q.engine is None and q.partition_ctx is not None
    for key in "ABC":
        mine = [e for e in events if e[2] == key]
        assert [r for r in got if r[0] == key] == join.loop_reference(
            mine, 1_000, 3), key
    assert got[-3:] == [("A", "t8", 14.5), ("A", "t9", 14.5),
                        ("A", "t10", 14.5)]
