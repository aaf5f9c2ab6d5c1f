"""The routed deployment of ``BENCHMARK.json``'s configuration
``partition_len1k_40k`` (per-key ``length`` rings sharded by key over a
mesh, rows exchanged on the device, ordered re-merge), at a small size on
virtual CPU devices, through the normal path: ``SiddhiManager`` ->
``device_route_query_step`` -> ``send_columns`` -> ``StreamCallback``.

Held to (a) the plain reference of the family, the deque-per-key loop of
``benchmarks/references/keyed_window.py`` (every row: keys and integer
sums exactly, averages to the configuration's own limit), and (b) the
unsharded run of the same app, row for row in delivery order: a routed
deployment owes the unsharded answers in the unsharded order. The app
text, the aggregates, the limit and the traffic's hot set are the
configuration's and the cell's own files; only the sizes are a test's.
"""

import functools
import json
import os

import numpy as np
import pytest

from benchmarks import drive, generator, run
from benchmarks.references import keyed_window
from siddhi_tpu import SiddhiManager
from siddhi_tpu.observability import journey
from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, "benchmarks", *path),
              encoding="utf-8") as f:
        return json.load(f)


CONFIG = _json("configs", "partition_len1k_40k.json")
TRAFFIC = _json("traffic", "hot20_bulk_x4.json")
ROWS, WINDOW, SEED = 256, 8, 27
# case -> (keys, route_slack): a slack of 4.0 makes a shard's quota a
# source's whole slice, so nothing can split; 1.25 is the cell's own
CASES = {"no_split": (40, 4.0), "split": (40, 1.25), "growth": (200, 4.0)}


def _batches(case):
    """The key indices and value columns of every batch sent, from the
    cell's generator (20% of the keys take 80% of the events, drawn from
    the seed), and the key names."""
    keys, _slack = CASES[case]
    sizes = {"keys": keys, "window": WINDOW}
    traffic = dict(TRAFFIC, batch_rows=ROWS, pool_batches=6)
    feed = generator.make_feed(CONFIG, sizes, traffic, SEED)
    sent = [(b.keys, {c: v for c, v in b.cols.items()
                      if c != feed.key_attr}) for b in feed.pool * 2]
    if case == "split":
        # one key, so one owner: every source's rows go to one shard and
        # the pair's quota (ROWS / n * 1.25 / n) cannot hold them
        sent.insert(3, (np.zeros(ROWS, np.int64), sent[3][1]))
    if case == "growth":
        # a dozen keys first: the engine's least key capacity (16) holds
        # them; the fourth batch brings the rest and the capacity grows
        # with the rings live
        sent = [(k % 12, c) for k, c in sent[:3]] + sent[3:]
    return feed, sent


@functools.lru_cache(maxsize=None)
def _deployed(case, shards):
    """One run of the configuration's app over ``shards`` virtual devices
    (0: unsharded): what the callback received by role, the route counters
    of every batch's journey, and the per-shard key capacity before and
    after."""
    keys, slack = CASES[case]
    feed, sent = _batches(case)
    sizes = {"keys": keys, "window": WINDOW}
    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(CONFIG["app"].format(**sizes))
    collector = drive.make_collector(tuple(CONFIG["output"]["columns"]
                                           .values()))
    rt.add_callback(CONFIG["output"]["stream"], collector)
    q = rt.query_runtimes[CONFIG["route"]["query"]]
    capacity = []
    if shards:
        rt.start()
        device_route_query_step(
            q, make_mesh(shards), rows_per_shard=int(ROWS / shards * slack),
            exchange=CONFIG["route"]["exchange"])
        capacity.append(q._route_layout.localK)
    handler = rt.get_input_handler(feed.streams[0])
    journey.enable()
    try:
        for i, (k, cols) in enumerate(sent):
            handler.send_columns(
                {feed.key_attr: feed.names[k], **cols},
                timestamps=np.arange(i * ROWS, (i + 1) * ROWS,
                                     dtype=np.int64))
        ring = journey.ring()
    finally:
        journey.disable()
    got = run._delivered(CONFIG, collector, rt, feed)
    if shards:
        capacity.append(q._route_layout.localK)
    manager.shutdown()
    return {"got": got, "capacity": capacity,
            "pieces": [j["route_pieces"] for j in ring],
            "fullest": [(j["shard_rows_max"], j["shard_capacity"])
                        for j in ring]}


def _history(case):
    _feed, sent = _batches(case)
    price, volume = (CONFIG["aggregates"][a] for a in ("avg", "sum"))
    return (np.concatenate([k for k, _ in sent]),
            np.concatenate([c[price] for _, c in sent]),
            np.concatenate([c[volume] for _, c in sent]))


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_routed_answers_equal_the_plain_reference(case, shards):
    key, price, volume = _history(case)
    want_avg, want_sum = keyed_window.loop_reference(
        key, price, volume, WINDOW)
    got = _deployed(case, shards)["got"]
    # one output row per arriving event, in arrival order
    assert len(got["key"]) == len(key)
    assert np.array_equal(got["key"], key)
    assert np.array_equal(got["sum"], want_sum)
    assert np.abs(got["avg"] - want_avg).max() \
        <= CONFIG["limits"]["avg_max_abs_err"]


@pytest.mark.parametrize("shards", [1, 2, 4, 8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_routed_rows_equal_the_unsharded_run_in_order(case, shards):
    routed, plain = _deployed(case, shards), _deployed(case, 0)
    for role in ("key", "sum", "avg"):
        assert np.array_equal(routed["got"][role], plain["got"][role]), role
    # an unrouted query's journeys carry no route counter
    assert set(plain["pieces"]) == {None}
    assert set(plain["fullest"]) == {(None, None)}


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_routed_batches_split_only_where_a_pair_exceeds_its_quota(
        case, shards):
    got = _deployed(case, shards)
    pieces = got["pieces"]
    assert len(pieces) == 12 + (case == "split")
    if case == "split" and shards > 1:
        # the one-key batch; the hot set may split a neighbour as well
        assert pieces[3] > 1
    else:
        assert set(pieces) == {1}
    quota = int(ROWS / shards * CASES[case][1]) // shards
    for rows, room in got["fullest"]:
        assert room == shards * quota and 0 < rows <= room
    before, after = got["capacity"]
    if case == "growth":
        assert after > before          # re-laid out with the rings live
    else:
        assert after >= CASES[case][0] // shards
