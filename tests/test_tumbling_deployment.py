"""``BASELINE.json`` configs[2] as a deployment: ``timeBatch(1 sec)`` ->
count / min / max over symbols, through ``SiddhiManager`` + ``send_columns``
with the engine's defaults, held row for row to the plain event-at-a-time
reference of the benchmark (``benchmarks/references/tumbling.py``
``loop_reference``: numpy only, nothing of the program). The plan folds
the window into accumulators as wide as its keys (``ops/tumbling_agg.py``)
where the query's shape allows it and keeps the buffered stage elsewhere.
CPU backend, small sizes, seeded.
"""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.references.tumbling import loop_reference  # noqa: E402
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.eligibility import (SURFACE_FUSION,  # noqa: E402
                                         SURFACE_ROUTE, ReasonCode)
from siddhi_tpu.core.stream.junction import FatalQueryError  # noqa: E402
from siddhi_tpu.observability.export import prometheus_text  # noqa: E402
from siddhi_tpu.ops import tumbling_agg  # noqa: E402
from siddhi_tpu.ops.tumbling_agg import TumblingAggStage  # noqa: E402
from siddhi_tpu.ops.windows import TimeBatchWindowStage  # noqa: E402

KEYS = 40
NAMES = np.array([f"S{i}" for i in range(KEYS)], dtype=object)
SELECT = "select symbol, count() as n, min(price) as lo, max(price) as hi"
APP = """{playback}
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.timeBatch({window})
{select}
{tail}
insert {events}into OutStream;
"""


def app(select=SELECT, tail="group by symbol", window="1 sec",
        playback="@app:playback", events=""):
    return APP.format(playback=playback, window=window, select=select,
                      tail=tail, events=events)


class Rows(StreamCallback):
    """Every delivered event with the send that was under way."""

    def __init__(self):
        self.rows, self.send = [], -1

    def receive(self, events):
        self.rows += [(self.send, e.timestamp, *e.data) for e in events]


def build(text):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(text)
    got = Rows()
    rt.add_callback("OutStream", got)
    return m, rt, got


def send_all(rt, got, batches, first=0):
    h = rt.get_input_handler("StockStream")
    for i, (keys, price, ts) in enumerate(batches, start=first):
        got.send = i
        h.send_columns(
            {"symbol": NAMES[keys], "price": price,
             "volume": np.ones(len(keys), np.int64)},
            timestamps=np.full(len(keys), ts, np.int64))


def stream(seed, dist, times, rows=48):
    """One batch a timestamp in ``times``: (key indices, prices, ts)."""
    rng = np.random.default_rng(seed)
    hot = rng.permutation(KEYS)[:KEYS // 5]
    out = []
    for ts in times:
        keys = rng.integers(0, KEYS, rows)
        if dist == "hot_set":
            keys = np.where(rng.random(rows) < 0.8,
                            hot[rng.integers(0, len(hot), rows)], keys)
        out.append((keys.astype(np.int64),
                    (rng.random(rows) * 100).astype(np.float32), int(ts)))
    return out


def reference_rows(batches, window_ms=1000):
    send = np.concatenate([np.full(len(k), i) for i, (k, _p, _t)
                           in enumerate(batches)])
    key = np.concatenate([k for k, _p, _t in batches])
    price = np.concatenate([p for _k, p, _t in batches]).astype(np.float64)
    ts = np.concatenate([np.full(len(k), t) for k, _p, t in batches])
    by, k, n, lo, hi, at = loop_reference(send, key, price, ts, window_ms)
    return [(int(s), int(t), f"S{g}", int(c), float(a), float(b))
            for s, t, g, c, a, b in zip(by, at, k, n, lo, hi)]


# windows of 3, 4 and 5 batches; a window some keys miss (12-row batches);
# a jump over a whole window (it closes empty and answers nothing); a
# batch two boundaries ahead of the last (two timers, the second empty)
TIMES = {
    "steady": 10_000 + np.cumsum([0] + [334] * 8 + [250] * 8 + [200] * 10),
    "keys_absent": 10_000 + 250 * np.arange(14),
    "empty_window": np.r_[10_000 + 250 * np.arange(6),
                          13_100 + 250 * np.arange(6)],
    "two_boundaries": np.r_[10_000 + 250 * np.arange(5), 13_000, 13_100,
                            14_050, 15_000],
}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("dist", ["hot_set", "uniform"])
@pytest.mark.parametrize("case", list(TIMES))
def test_the_system_equals_the_loop_reference_row_for_row(case, dist, seed):
    batches = stream(seed, dist, TIMES[case],
                     rows=12 if case == "keys_absent" else 48)
    m, rt, got = build(app())
    assert type(rt.query_runtimes["bench"].window_stage) is TumblingAggStage
    send_all(rt, got, batches)
    m.shutdown()
    want = reference_rows(batches)
    assert want and got.rows == want
    if case == "keys_absent":
        assert min(np.bincount([r[0] for r in want])) < KEYS
    if case in ("empty_window", "two_boundaries"):
        # the send that jumped delivered one window's rows, not two
        sends = {r[0] for r in want}
        assert len(sends) < len({int(t) // 1000 for t in TIMES[case]})


QUERIES = {
    "count_min_max": (SELECT, "group by symbol"),
    "having": (SELECT, "group by symbol having n > 2"),
    "no_group_by": ("select count() as n, max(price) as hi", ""),
    "count_of_an_attribute": (
        "select symbol, count(volume) as n, min(volume) as lo",
        "group by symbol"),
    "an_expression_of_aggregates": (
        "select symbol, max(price) - min(price) as spread", "group by symbol"),
}


@pytest.mark.parametrize("query", list(QUERIES))
def test_folded_and_buffered_stages_answer_alike(monkeypatch, query):
    """The same query through the buffered stage (the plan's fold switched
    off) and through the folded one: the same events, timestamps and order."""
    select, tail = QUERIES[query]
    batches = stream(7, "hot_set", TIMES["steady"])
    runs = []
    for fold in (True, False):
        if not fold:
            monkeypatch.setattr(tumbling_agg, "plan_tumbling_fold",
                                lambda *a, **k: None)
        m, rt, got = build(app(select, tail))
        want_stage = TumblingAggStage if fold else TimeBatchWindowStage
        assert type(rt.query_runtimes["bench"].window_stage) is want_stage
        send_all(rt, got, batches)
        m.shutdown()
        runs.append(got.rows)
    assert runs[0] and runs[0] == runs[1]


def test_null_arguments_leave_the_aggregates_alone():
    m, rt, got = build(app(
        "select symbol, count() as n, count(price) as np, min(price) as lo"))
    h = rt.get_input_handler("StockStream")
    h.send(10_000, ["A", 5.0, 1])
    h.send(10_100, ["A", None, 1])
    h.send(10_200, ["B", None, 1])
    h.send(10_300, ["A", 3.0, 1])
    h.send(11_000, ["C", 1.0, 1])
    m.shutdown()
    assert [r[2:] for r in got.rows] == [("B", 1, 0, None), ("A", 3, 2, 3.0)]


FALLS_BACK = {
    "select_all": app("select *", ""),
    "expired_output": app(events="all events "),
    "a_sum": app("select symbol, sum(volume) as v"),
    "a_non_key_attribute": app("select symbol, volume, count() as n"),
    "order_by": app(tail="group by symbol order by n"),
    "stream_current_events": app(window="1 sec, true"),
}


@pytest.mark.parametrize("why", list(FALLS_BACK))
def test_a_query_that_needs_the_rows_keeps_the_buffered_stage(why):
    m, rt, _got = build(FALLS_BACK[why])
    assert type(rt.query_runtimes["bench"].window_stage) \
        is TimeBatchWindowStage
    m.shutdown()


def test_a_batch_larger_than_window_capacity_runs_under_the_defaults():
    rows = 8192
    batches = stream(5, "uniform", [10_000, 10_250, 11_000, 12_000],
                     rows=rows)
    m, rt, got = build(app())
    assert rt.app_context.window_capacity < rows
    send_all(rt, got, batches)
    m.shutdown()
    assert got.rows == reference_rows(batches)
    assert sum(r[3] for r in got.rows) == 3 * rows


def test_select_all_from_the_same_window_still_overflows_with_its_message():
    batches = stream(5, "uniform", [10_000, 10_250], rows=8192)
    m, rt, got = build(app("select *", ""))
    with pytest.raises(FatalQueryError, match="window_capacity"):
        send_all(rt, got, batches)
    m.shutdown()


def test_snapshot_and_restore_carry_the_accumulators():
    batches = stream(9, "hot_set", 10_000 + 250 * np.arange(12))
    m, rt, got = build(app())
    send_all(rt, got, batches[:6])          # mid-window: 2 of 4 batches in
    snap = rt.snapshot()
    before = list(got.rows)
    m.shutdown()
    m, rt, got = build(app())
    rt.restore(snap)
    send_all(rt, got, batches[6:], first=6)
    m.shutdown()
    assert before + got.rows == reference_rows(batches)


def test_wall_clock_flushes_agree_with_playback():
    """No ``@app:playback``: the flush comes from the scheduler's timer
    thread. Three batches sent just after a boundary make one window; its
    answer is what playback gives for the same three."""
    batches = stream(11, "hot_set", [0, 0, 0])
    m, rt, got = build(app(window="2 sec", playback=""))

    def flushes():
        return len({r[0] for r in got.rows})

    def wait_for(n):
        until = time.time() + 30
        while flushes() < n and time.time() < until:
            time.sleep(0.01)
        assert flushes() >= n

    h = rt.get_input_handler("StockStream")
    warm = {"symbol": NAMES[:1], "price": np.ones(1, np.float32),
            "volume": np.ones(1, np.int64)}
    got.send = -2
    h.send_columns(warm)            # compiles; its window closes at once
    wait_for(1)
    got.send = -1
    h.send_columns(warm)
    wait_for(2)                     # a boundary has just passed
    got.rows.clear()
    got.send = 0
    for keys, price, _ts in batches:
        h.send_columns({"symbol": NAMES[keys], "price": price,
                        "volume": np.ones(len(keys), np.int64)})
    wait_for(1)
    live = [r[2:] for r in got.rows]
    m.shutdown()
    m, rt, got = build(app(window="2 sec"))
    send_all(rt, got, [(k, p, 10_000 + i) for i, (k, p, _t)
                       in enumerate(batches)] + [(batches[0][0][:1],
                                                  batches[0][1][:1], 12_000)])
    m.shutdown()
    assert live and live == [r[2:] for r in got.rows]


def test_eligibility_census_of_the_query():
    """A timer-driven window whose order keys are not global-aware: no
    second chip, no fused fan-out (ROADMAP C1, C3); no surface answers
    UNKNOWN."""
    m, rt, _got = build(app())
    by_surface = {s: c for s, c, _d in rt.eligibility_census["bench"]}
    assert by_surface[SURFACE_ROUTE] is ReasonCode.WINDOW_NOT_GLOBAL_AWARE
    assert by_surface[SURFACE_FUSION] is ReasonCode.SCHEDULER_WINDOW
    assert all(c is not ReasonCode.UNKNOWN for c in by_surface.values())
    m.shutdown()


def test_flushes_and_timer_steps_are_counted():
    batches = stream(3, "uniform", 10_000 + 250 * np.arange(9))
    m, rt, got = build(app())
    send_all(rt, got, batches)
    counters = rt.app_context.telemetry.snapshot()["counters"]
    scraped = prometheus_text(m)
    m.shutdown()
    assert counters["window.bench.flushes"] == 2
    assert counters["window.bench.timer_steps"] == 2
    for name in ("flushes", "timer_steps"):         # and on /metrics
        assert f'name="window.bench.{name}"}} 2' in scraped
