"""Key-capacity growth mid-stream (``QueryRuntime._ensure_capacity``,
``grow_state`` / ``grow_leaf``): the grown state answers exactly as a state
that was that size from the start, the growth never holds the grown state
twice, and a leaf that does not grow is not copied.

Three apps of the benchmark's families, each through ``SiddhiManager`` +
``send_columns`` with the engine's defaults: the partitioned ``length(W)``
avg / sum of ``partition_len1k_*`` (capacity 16 -> 64 -> 256 with its rings
live), the grouped ``length(W)`` of ``groupby_len1k_10k`` and the grouped
``timeBatch`` of ``timebatch_1s_10k`` (each grown once). Held to the
families' event-at-a-time loops (``benchmarks/references``: numpy only,
nothing of the program) and, bit for bit, to the same app PRE-SIZED: its
capacities set to the final ones before the first batch, so that nothing
ever grows. CPU backend, small sizes, seeded.
"""

import functools
import gc

import jax
import numpy as np
import pytest

from benchmarks.references import global_window, keyed_window, tumbling
from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.query import runtime as query_runtime
from siddhi_tpu.core.util.statistics import pytree_nbytes

WINDOW, ROWS, SEED = 8, 256, 33
STOCK = "define stream StockStream (symbol string, price float, volume long);"
APPS = {
    "partition": STOCK + """
        partition with (symbol of StockStream)
        begin
          @info(name = 'bench')
          from StockStream#window.length({W})
          select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
          insert into OutStream;
        end;""",
    "groupby": STOCK + """
        @info(name = 'bench')
        from StockStream#window.length({W})
        select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
        group by symbol
        insert into OutStream;""",
    "timebatch": "@app:playback\n" + STOCK + """
        @info(name = 'bench')
        from StockStream#window.timeBatch(1 sec)
        select symbol, count() as n, min(price) as lo, max(price) as hi
        group by symbol
        insert into OutStream;""",
}
# the keys each batch may draw from: the engine's least capacity (16)
# holds the first stage; every later stage outgrows what came before, and
# its first batch brings every one of its keys
STAGES = {"partition": (12, 12, 60, 60, 250, 250),
          "groupby": (12, 12, 200, 200),
          "timebatch": (12, 12, 12, 12, 200, 200, 200, 200, 200)}
GROWTHS = {"partition": [(16, 64), (64, 256)], "groupby": [(16, 256)],
           "timebatch": [(16, 256)]}


class Rows(StreamCallback):
    """Every delivered row with the send that was under way."""

    def __init__(self):
        self.rows, self.send = [], -1

    def receive(self, events):
        self.rows += [(self.send, *e.data) for e in events]


def _sent(app):
    """The batches of one run, from the seed: key indices, prices,
    volumes, and one timestamp a batch (250 ms apart: four a window)."""
    rng = np.random.default_rng(SEED)
    stages = STAGES[app]
    return [(rng.permutation(ROWS) % n if n != stages[i - 1] or i == 0
             else rng.integers(0, n, ROWS),     # a stage's first: every key
             (rng.random(ROWS) * 100).astype(np.float32),
             rng.integers(1, 1000, ROWS), 1_000 + 250 * i)
            for i, n in enumerate(stages)]


@functools.lru_cache(maxsize=None)
def _run(app, presized):
    """One run: the delivered rows, and what every growth did (the
    capacities, the old and new leaves, the device's live bytes around
    each leaf's construction)."""
    growths = []
    grow_state, grow_leaf = query_runtime.grow_state, query_runtime.grow_leaf

    def live():
        return sum(a.nbytes for a in jax.live_arrays())

    def spying_state(init_state, grown, old_leaves):
        gc.collect()
        g = {"old": list(old_leaves), "live": [], "live_before": live(),
             "old_shapes": [a.shape for a in old_leaves],
             "old_bytes": pytree_nbytes(old_leaves)}
        growths.append(g)
        g["new"] = grow_state(init_state, grown, old_leaves)
        g["dropped"] = all(a is None for a in old_leaves)
        return g["new"]

    def spying_leaf(init_state, index, old):
        # the leaf constructor: the old leaf and everything not yet
        # moved are alive here, and on return its successor is too
        growths[-1]["live"].append(live())
        new = grow_leaf(init_state, index, old)
        growths[-1]["live"].append(live())
        return new

    query_runtime.grow_state = spying_state
    query_runtime.grow_leaf = spying_leaf
    try:
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(APPS[app].format(W=WINDOW))
        out = Rows()
        rt.add_callback("OutStream", out)
        q = rt.query_runtimes["bench"]
        if presized:
            q.selector_plan.num_keys = GROWTHS[app][-1][1]
            if q.partition_ctx is not None:
                q._win_keys = GROWTHS[app][-1][1]
        h = rt.get_input_handler("StockStream")
        names = np.array([f"S{i}" for i in range(256)], dtype=object)
        capacities = []
        for i, (key, price, volume, ts) in enumerate(_sent(app)):
            out.send = i
            h.send_columns({"symbol": names[key], "price": price,
                            "volume": volume},
                           timestamps=np.full(ROWS, ts, np.int64))
            capacities.append(q.key_capacity())
        counters = rt.app_context.telemetry.snapshot()["counters"]
        manager.shutdown()
    finally:
        query_runtime.grow_state = grow_state
        query_runtime.grow_leaf = grow_leaf
    return {"rows": out.rows, "growths": growths, "capacities": capacities,
            "grows": counters.get("state.bench.grows", 0)}


def _history(app):
    sent = _sent(app)
    return (np.concatenate([np.full(ROWS, i) for i in range(len(sent))]),
            *(np.concatenate([b[c] for b in sent]) for c in range(3)),
            np.concatenate([np.full(ROWS, b[3], np.int64) for b in sent]))


@pytest.mark.parametrize("app", list(APPS))
def test_a_grown_state_answers_as_the_plain_reference(app):
    got = _run(app, presized=False)
    send, key, price, volume, ts = _history(app)
    rows = got["rows"]
    assert got["capacities"][0] == 16
    assert got["capacities"][-1] == GROWTHS[app][-1][1]
    assert got["grows"] == len(GROWTHS[app]) == len(got["growths"])
    if app == "timebatch":
        w_send, w_key, w_n, w_lo, w_hi, _ts = tumbling.loop_reference(
            send, key, price.astype(np.float64), ts, 1_000)
        assert len(rows) == len(w_key) > 0
        assert [r[0] for r in rows] == w_send.tolist()
        assert [int(r[1][1:]) for r in rows] == w_key.tolist()
        assert [r[2] for r in rows] == w_n.tolist()
        assert [r[3] for r in rows] == w_lo.tolist()      # a min or max
        assert [r[4] for r in rows] == w_hi.tolist()      # selects: exact
        return
    loop = (keyed_window if app == "partition" else global_window)
    w_avg, w_sum = loop.loop_reference(key, price, volume, WINDOW)
    assert len(rows) == len(key)
    assert [int(r[1][1:]) for r in rows] == key.tolist()
    assert [r[3] for r in rows] == w_sum.tolist()
    assert np.abs(np.array([r[2] for r in rows]) - w_avg).max() < 1e-9


@pytest.mark.parametrize("app", list(APPS))
def test_a_grown_state_answers_bit_for_bit_as_one_that_never_grew(app):
    grown, sized = _run(app, presized=False), _run(app, presized=True)
    assert sized["growths"] == [] and sized["grows"] == 0
    assert set(sized["capacities"]) == {GROWTHS[app][-1][1]}
    assert len(grown["rows"]) == len(sized["rows"])
    for a, b in zip(grown["rows"], sized["rows"]):
        assert a[:2] == b[:2]
        # floats by their bits: -0.0, a NaN's payload and the last ulp
        assert np.asarray(a[2:], np.float64).tobytes() \
            == np.asarray(b[2:], np.float64).tobytes(), (a, b)


# which leaves a growth finds: the partitioned rings and aggregates are
# all as wide as the keys; the grouped length window of the benchmark is
# fused (``ops/fused_agg.py``: one ring for all keys, no K-wide state: its
# growth only compiles the step again); the tumbling accumulators grow
# beside two scalars
GROWS, KEEPS = ("partition", "timebatch"), ("groupby", "timebatch")


@pytest.mark.parametrize("app", list(APPS))
def test_a_growth_never_holds_the_grown_state_twice(app):
    growths = _run(app, presized=False)["growths"]
    assert len(growths) == len(GROWTHS[app])
    for g in growths:
        new_bytes = pytree_nbytes(g["new"])
        assert g["dropped"]
        if app not in GROWS:
            assert new_bytes == g["old_bytes"] and not g["live"]
            continue
        largest = max(a.nbytes for a in g["new"])
        assert new_bytes > g["old_bytes"] and g["live"]
        # whatever else the process holds is in ``live_before``, with
        # the old state; the growth may add the new state and, by the
        # issue's allowance, one leaf more. The parent's re-layout (a
        # whole fresh state, then the copies) added twice the new state.
        over = max(g["live"]) - g["live_before"]
        assert over <= new_bytes + largest, (over, new_bytes, largest)
        assert over < 2 * new_bytes
        # and when the last leaf exists, the old ones that grew are gone
        assert g["live"][-1] - g["live_before"] <= new_bytes


@pytest.mark.parametrize("app", list(APPS))
def test_a_leaf_of_unchanged_shape_is_the_same_buffer(app):
    growths = _run(app, presized=False)["growths"]
    same = grew = 0
    for g in growths:
        for old, shape, new in zip(g["old"], g["old_shapes"], g["new"]):
            if new.shape == shape:
                assert new is old
                same += 1
            else:
                assert new is not old
                assert all(n >= o for n, o in zip(new.shape, shape))
                grew += 1
    assert (grew > 0) == (app in GROWS), (grew, app)
    assert (same > 0) == (app in KEEPS), (same, app)
