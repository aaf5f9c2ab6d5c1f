"""The one span primitive (observability/tracing.py) on the profiler's
clock: while tracing is on, the engine's stages are ``siddhi.<name>``
events of a ``jax.profiler`` trace, nested as the layers are, sharing a
``batch`` id; the journey's counters say what the pulled arrays say;
step programs are named from their family and trace their body in the
three scopes. CPU backend: what is in the trace, never how long it took.
"""

import glob
import os

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.observability import journey
from siddhi_tpu.observability.instruments import (META_SCOPE, SELECT_SCOPE,
                                                  STATE_SCOPE)
from siddhi_tpu.observability.tracing import TRACER, spans_on

RING_KEYS = {"app", "queries", "pack_ms", "queue_ms", "dispatch_ms",
             "device_service_ms", "device_queue_ms", "emit_ms", "t",
             "batch", "meta_pull_ms", "pull_ms", "rows_out", "rows_padded",
             "route_prep_ms", "route_pieces", "shard_rows_max",
             "shard_capacity", "flush_rows", "timer_steps", "grow_ms",
             "state_bytes", "state_slots", "key_ms", "launch_ms",
             "h2d_bytes"}
FLUSH_KEYS = ("flush_rows", "timer_steps")
ROUTE_KEYS = ("route_prep_ms", "route_pieces", "shard_rows_max",
              "shard_capacity")

TWO_QUERIES = """
define stream S (k string, v long);
@info(name='q1')
from S#window.length(8) select k, sum(v) as total group by k insert into O;
@info(name='q2')
from S[v > 1] select k, v insert into P;
"""

PATTERN = """
@app:playback
define stream AStream (k string, v double);
define stream BStream (k string, v double);
partition with (k of AStream, k of BStream)
begin
  @info(name = 'nfa')
  from every e1=AStream -> e2=BStream[e2.v > e1.v] within 5 sec
  select e1.v as v1, e2.v as v2
  insert into MatchStream;
end;
"""

PARTITIONED = """
define stream S (k string, v long);
partition with (k of S)
begin
  @info(name='pq')
  from S#window.length(4) select k, sum(v) as total insert into O;
end;
"""

JOIN = """
define stream L (k string, v long);
define stream R (k string, w long);
@info(name='jq')
from L#window.length(4) join R#window.length(4) on L.k == R.k
select L.k as k, v, w insert into J;
"""


class Columns(StreamCallback):
    """Reads every output column of every delivery, as the benchmark's
    collector does: the pull happens here, inside the engine's emit."""

    def __init__(self):
        self.pulled = []

    def receive_batch(self, batch, junction=None):
        self.pulled.append({k: np.asarray(batch.cols[k])
                            for k in list(batch.cols)})


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    journey.disable(force=True)
    TRACER.enabled = False
    TRACER.clear()


def _manager(**config):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {f"siddhi_tpu.{k}": str(v) for k, v in config.items()}))
    return m


def _send(handler, i, n=3):
    handler.send_columns(
        {"k": np.array(["a", "b", "c"][:n], object),
         "v": np.arange(1, n + 1) + i})


def _engine_spans(trace_dir):
    """{thread line: [(start, end, name, stats)]} of the ``siddhi.*``
    events in the trace written under ``trace_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    by_line = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            got = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name,
                          dict(e.stats)) for e in line.events
                         if e.name.startswith("siddhi."))
            if got:
                by_line[line.name] = got
    return by_line


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.mark.parametrize("depth", [1, 2])
def test_a_profiler_trace_holds_the_engines_stages(tmp_path, depth):
    m = _manager(pipeline_depth=depth, fuse_fanout="false")
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)
    rt.add_callback("O", Columns())
    rt.add_callback("P", Columns())
    h = rt.get_input_handler("S")
    _send(h, 0)                     # compiles, outside the trace
    rt.start_trace(str(tmp_path))
    assert journey.enabled() and spans_on()
    for i in range(1, 4):
        _send(h, i)
    rt.stop_trace()
    assert not journey.enabled() and not spans_on()
    m.shutdown()

    (spans,) = _engine_spans(tmp_path).values()   # the sender's thread
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[2], []).append(sp)
    assert {"siddhi.pack", "siddhi.junction.dispatch", "siddhi.query.step",
            "siddhi.meta_pull", "siddhi.emit",
            "siddhi.pull"} <= set(by_name)
    # one batch id a batch, given at pack and shared by its spans
    packs = by_name["siddhi.pack"]
    ids = [sp[3]["batch"] for sp in packs]
    assert len(ids) == 3 and len(set(ids)) == 3
    for name in ("siddhi.query.step", "siddhi.meta_pull", "siddhi.emit",
                 "siddhi.pull"):
        assert {sp[3]["batch"] for sp in by_name[name]} == set(ids), name
    # both queries step, pull their meta and emit for every batch
    assert len(by_name["siddhi.query.step"]) == 6
    assert len(by_name["siddhi.emit"]) == 6
    assert sorted(sp[3]["query"] for sp in by_name["siddhi.emit"]) == \
        ["q1"] * 3 + ["q2"] * 3
    # nesting: pack before the junction; a step inside the input
    # junction's dispatch; the output pull inside an emit; the meta pull
    # and the emit in the step's synchronous tail at depth 1, at the
    # pump's drain once the delivery has returned at depth 2
    in_dispatch = [sp for sp in by_name["siddhi.junction.dispatch"]
                   if sp[3]["stream"] == "S"]
    assert len(in_dispatch) == 3
    for pack, disp in zip(packs, in_dispatch):
        assert pack[1] <= disp[0] and pack[3]["batch"] == disp[3]["batch"]
    for step in by_name["siddhi.query.step"]:
        assert any(_inside(step, d) for d in in_dispatch)
    for pull in by_name["siddhi.pull"]:
        assert any(_inside(pull, e) for e in by_name["siddhi.emit"])
        assert pull[3]["bytes"] > 0 and pull[3]["arrays"] > 0
    for tail in by_name["siddhi.meta_pull"] + by_name["siddhi.emit"]:
        in_step = any(_inside(tail, s) for s in by_name["siddhi.query.step"])
        assert in_step == (depth == 1), tail


def test_an_nfa_app_leaves_journeys_with_every_ring_key():
    m = _manager(pipeline_depth=2)
    rt = m.create_siddhi_app_runtime(PATTERN)
    out = Columns()
    rt.add_callback("MatchStream", out)
    a, b = rt.get_input_handler("AStream"), rt.get_input_handler("BStream")
    keys = np.array([f"K{i}" for i in range(6)], object)

    def round_(t, v):
        a.send_columns({"k": keys, "v": np.zeros(6)},
                       timestamps=np.full(6, t, np.int64))
        b.send_columns({"k": keys, "v": np.full(6, v)},
                       timestamps=np.full(6, t + 1, np.int64))

    round_(1_000, 1.0)              # compiles both steps
    journey.enable()
    round_(2_000, 1.0)
    round_(3_000, 1.0)
    ring = journey.ring()
    journey.disable()
    m.shutdown()
    assert len(ring) == 4           # A, B, A, B
    for rec in ring:
        assert set(rec) == RING_KEYS
        assert rec["queries"] == ["nfa"] and rec["pack_ms"] > 0
        # the counters of a device-routed query: None for every other
        assert [rec[k] for k in ROUTE_KEYS] == [None] * 4
        # and those of a folded tumbling window
        assert [rec[k] for k in FLUSH_KEYS] == [None] * 2
        assert rec["dispatch_ms"] > 0 and rec["meta_pull_ms"] > 0
    assert len({rec["batch"] for rec in ring}) == 4
    heads, tails = ring[0::2], ring[1::2]
    # a head batch arms and emits nothing: no callback, so no pull
    assert all(r["rows_out"] == 0 and r["pull_ms"] is None for r in heads)
    assert all(r["rows_out"] == 6 and r["pull_ms"] > 0 for r in tails)
    assert sum(len(p["v2"]) > 0 for p in out.pulled) >= 2


TUMBLING = """
@app:playback
define stream S (k string, v float);
@info(name='bars')
from S#window.timeBatch(1 sec)
select k, count() as n, min(v) as lo, max(v) as hi group by k
insert into O;
"""


@pytest.mark.parametrize("depth", [1, 2])
def test_a_flush_is_a_timer_step_of_the_send_that_crossed(tmp_path, depth):
    """A folded tumbling window under playback: the send whose timestamp
    crosses the boundary fires the scheduler's timer while it advances the
    clock, so ``siddhi.timer`` (with the flush's step, meta pull, emit and
    pull inside it) carries THAT send's batch id, and so do the journey's
    ``timer_steps`` and ``flush_rows``."""
    m = _manager(pipeline_depth=depth)
    rt = m.create_siddhi_app_runtime(TUMBLING)
    out = Columns()
    rt.add_callback("O", out)
    h = rt.get_input_handler("S")

    def send(t):
        h.send_columns({"k": np.array(["a", "b", "a"], object),
                        "v": np.array([1.0, 2.0, 3.0], np.float32)},
                       timestamps=np.full(3, t, np.int64))

    send(1_000)
    send(2_000)                     # compiles the TIMER step too
    rt.start_trace(str(tmp_path))
    for t in (2_400, 2_800, 3_000, 3_500):
        send(t)
    ring = journey.ring()
    rt.stop_trace()
    counters = rt.app_context.telemetry.snapshot()["counters"]
    m.shutdown()

    (spans,) = _engine_spans(tmp_path).values()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[2], []).append(sp)
    (timer,) = by_name["siddhi.timer"]        # one window closed: at 3,000
    # the sends' packs (the TIMER chunk is packed too, inside its span)
    ids = [sp[3]["batch"] for sp in by_name["siddhi.pack"]
           if not _inside(sp, timer)]
    assert len(ids) == 4
    assert timer[3]["batch"] == ids[2] and timer[3]["query"] == "bars"
    assert timer[3]["ts"] == 3_000
    stages = {"siddhi.query.step", "siddhi.meta_pull", "siddhi.emit",
              "siddhi.pull"}
    inside = [sp for sp in spans if sp[2] in stages and _inside(sp, timer)]
    assert {sp[2] for sp in inside} == stages
    assert {sp[3]["batch"] for sp in inside} == {ids[2]}
    # the timer fired before that send's own step
    own = [sp for sp in by_name["siddhi.query.step"]
           if sp[3]["batch"] == ids[2] and not _inside(sp, timer)]
    assert len(own) == 1 and timer[1] <= own[0][0]

    assert all(set(rec) == RING_KEYS for rec in ring)
    assert len(ring) == 5           # four data steps and the TIMER step
    (flush,) = [rec for rec in ring if rec["flush_rows"]]
    assert flush["timer_steps"] == 1 and flush["batch"] == ids[2]
    assert flush["flush_rows"] == flush["rows_out"] == 2      # a and b
    assert [rec["timer_steps"] for rec in ring if rec is not flush] \
        == [None] * 4
    assert sorted(rec["batch"] for rec in ring if rec is not flush) \
        == sorted(ids)
    assert counters["window.bars.flushes"] == 2     # at 2,000 and 3,000
    assert counters["window.bars.timer_steps"] == 2
    (answer,) = [p for p in out.pulled[1:] if p["__valid__"].any()]
    assert answer["n"][answer["__valid__"]].tolist() == [3, 6]   # b, a


def test_a_growth_is_one_grow_span_with_what_it_moved(tmp_path):
    """Key capacity outgrown mid-stream: ``siddhi.grow`` once a growth,
    inside the step of the batch that forced it, with the capacities and
    the state's bytes on both sides; the same on the journey
    (``grow_ms``, ``state_bytes``, ``state_slots``) and on /metrics
    (``state.<query>.grows`` / ``.key_capacity`` / ``.bytes``)."""
    from siddhi_tpu.observability.export import prometheus_text

    m = _manager(pipeline_depth=2)
    rt = m.create_siddhi_app_runtime(PARTITIONED)
    rt.add_callback("O", Columns())
    h = rt.get_input_handler("S")

    def send(keys):
        h.send_columns({"k": np.array([f"k{i}" for i in range(keys)], object),
                        "v": np.arange(keys)})

    send(10)                        # the least capacity, 16, holds these
    q = rt.query_runtimes["pq"]
    bytes_16 = q.state_bytes()
    rt.start_trace(str(tmp_path))
    send(10)
    send(40)                        # 16 -> 64
    send(40)
    send(200)                       # 64 -> 256
    ring = journey.ring()
    rt.stop_trace()
    snap = rt.app_context.telemetry.snapshot()
    text = prometheus_text(m)
    bytes_256 = q.state_bytes()
    m.shutdown()

    (spans,) = _engine_spans(tmp_path).values()
    grows = [sp for sp in spans if sp[2] == "siddhi.grow"]
    assert [(g[3]["from_keys"], g[3]["to_keys"]) for g in grows] \
        == [(16, 64), (64, 256)]
    assert {g[3]["query"] for g in grows} == {"pq"}
    assert grows[0][3]["bytes_before"] == bytes_16
    assert grows[0][3]["bytes_after"] == grows[1][3]["bytes_before"]
    assert grows[1][3]["bytes_after"] == bytes_256 == 16 * bytes_16
    steps = [sp for sp in spans if sp[2] == "siddhi.query.step"]
    for g in grows:
        assert sum(_inside(g, st) for st in steps) == 1
    assert all(set(rec) == RING_KEYS for rec in ring)
    assert [rec["grow_ms"] is not None for rec in ring] \
        == [False, True, False, True]
    for rec, g in zip([r for r in ring if r["grow_ms"]], grows):
        assert rec["grow_ms"] == pytest.approx((g[1] - g[0]) / 1e6, rel=0.2)
    # the state as each batch's step left it: 4 ring slots a key
    assert [rec["state_slots"] for rec in ring] == [64, 256, 256, 1024]
    assert [rec["state_bytes"] for rec in ring] \
        == [bytes_16, 4 * bytes_16, 4 * bytes_16, bytes_256]
    assert snap["counters"]["state.pq.grows"] == 2
    assert snap["gauges"]["state.pq.key_capacity"] == 256
    assert snap["gauges"]["state.pq.bytes"] == bytes_256
    for name in ("grows", "key_capacity", "bytes"):
        assert f'name="state.pq.{name}"' in text


def test_pull_counters_equal_what_the_arrays_say():
    m = _manager(pipeline_depth=2)
    rt = m.create_siddhi_app_runtime(TWO_QUERIES.split("@info(name='q2')")[0])
    out = Columns()
    rt.add_callback("O", out)
    h = rt.get_input_handler("S")
    _send(h, 0, n=3)
    journey.enable()
    TRACER.start()                  # the span's arguments, in its ring
    _send(h, 1, n=3)
    (rec,) = journey.ring()
    (pull,) = [e for e in TRACER.stop()["traceEvents"]
               if e["name"] == "pull"]
    journey.disable()
    m.shutdown()
    cols = out.pulled[-1]
    lengths = {len(v) for v in cols.values()}
    assert len(lengths) == 1        # every column at the padded length
    assert rec["rows_padded"] == lengths.pop()
    assert rec["rows_out"] == int(cols["__valid__"].sum()) == 3
    assert pull["args"]["bytes"] == sum(v.nbytes for v in cols.values())
    assert pull["args"]["arrays"] == len(cols)
    assert pull["args"]["batch"] == rec["batch"]
    # emit holds the pull: its self time is emit_ms - pull_ms
    assert 0 < rec["pull_ms"] <= rec["emit_ms"]


def test_a_trace_gives_back_its_own_hold_once(tmp_path, monkeypatch):
    """``stop_trace`` releases the hold ``start_trace`` took even when
    the profiler fails to stop, and a retry cannot release a second one
    (another holder's); shutdown stops a trace left running."""
    m = _manager()
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)
    journey.enable()                # somebody else's hold
    rt.start_trace(str(tmp_path))
    stop = jax.profiler.stop_trace

    def failing_stop():
        stop()
        raise RuntimeError("the profiler could not write")

    monkeypatch.setattr(jax.profiler, "stop_trace", failing_stop)
    with pytest.raises(RuntimeError, match="could not write"):
        rt.stop_trace()
    with pytest.raises(RuntimeError, match="no trace is running"):
        rt.stop_trace()
    assert journey.enabled() and spans_on()      # the other hold stands
    journey.disable()
    assert not journey.enabled() and not spans_on()
    monkeypatch.undo()
    rt.start_trace(str(tmp_path / "second"))
    m.shutdown()
    assert not journey.enabled() and not spans_on()


def test_off_the_columns_pull_without_a_span_or_a_journey(tmp_path):
    """Spans off while a profiler trace runs (``jax.profiler`` taken
    directly): no ``siddhi.*`` event in it, no ring record."""
    m = _manager(pipeline_depth=2)
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)
    out = Columns()
    rt.add_callback("O", out)
    h = rt.get_input_handler("S")
    _send(h, 0)
    assert not spans_on()
    ring_before = journey.ring()     # what an earlier enable left
    jax.profiler.start_trace(str(tmp_path))
    _send(h, 1)
    jax.profiler.stop_trace()
    m.shutdown()
    assert len(out.pulled) == 2 and _engine_spans(tmp_path) == {}
    assert journey.ring() == ring_before


def _routed(depth=2, shards=4, rows_per_shard=16):
    """``PARTITIONED`` with its query routed over ``shards`` of the
    virtual devices; one batch sent, so the routed step is compiled."""
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m = _manager(pipeline_depth=depth)
    rt = m.create_siddhi_app_runtime(PARTITIONED)
    out = Columns()
    rt.add_callback("O", out)
    rt.start()
    device_route_query_step(rt.query_runtimes["pq"], make_mesh(shards),
                            rows_per_shard=rows_per_shard,
                            exchange="all_to_all")
    h = rt.get_input_handler("S")
    _send(h, 0)
    return m, rt, h, out


@pytest.mark.parametrize("depth", [1, 2])
def test_a_routed_query_prepares_inside_its_step(tmp_path, depth):
    """``siddhi.route.prepare`` (the host side of the device-routed
    dispatch) nests in ``siddhi.query.step`` under the batch's id, and
    the batch's journey keeps its duration, the pieces, and at the drain
    the fullest shard's rows beside a shard's capacity."""
    m, rt, h, _out = _routed(depth)
    rt.start_trace(str(tmp_path))
    for i in range(1, 4):
        _send(h, i)
    ring = journey.ring()
    rt.stop_trace()
    m.shutdown()
    (spans,) = _engine_spans(tmp_path).values()
    prepares = [sp for sp in spans if sp[2] == "siddhi.route.prepare"]
    steps = [sp for sp in spans if sp[2] == "siddhi.query.step"]
    packs = [sp for sp in spans if sp[2] == "siddhi.pack"]
    assert len(prepares) == len(steps) == len(packs) == 3
    for pack, prep, step in zip(packs, prepares, steps):
        assert _inside(prep, step)
        assert prep[3]["batch"] == step[3]["batch"] == pack[3]["batch"]
        assert prep[3]["query"] == "pq"
    assert len(ring) == 3
    for rec, prep in zip(ring, prepares):
        assert set(rec) == RING_KEYS and rec["queries"] == ["pq"]
        assert rec["batch"] == prep[3]["batch"]
        assert 0 < rec["route_prep_ms"] <= rec["dispatch_ms"]
        assert rec["route_pieces"] == 1
        # three keys, three rows: a, b, c go to shards 0, 1, 2
        assert rec["shard_rows_max"] == 1
        assert rec["shard_capacity"] == 16      # 4 shards x quota 4


def test_a_split_batch_says_so_in_its_journey():
    m, _rt, h, out = _routed(rows_per_shard=8)     # quota 2 a pair
    journey.enable()
    h.send_columns({"k": np.array(["a"] * 16, object), "v": np.arange(16)})
    (rec,) = journey.ring()
    journey.disable()
    m.shutdown()
    # 16 rows of one key: every source's four rows go to shard 0
    assert rec["route_pieces"] == 2 and rec["route_prep_ms"] > 0
    assert sum(int(p["__valid__"].sum()) for p in out.pulled[1:]) == 16


@pytest.mark.parametrize("depth", [1, 2])
def test_a_split_batch_launches_once_a_piece_and_keeps_the_sum(depth):
    """Each piece of a split batch is a ``siddhi.launch`` of its own
    under the batch's id; the journey keeps their sum, also where the
    first piece's emit finished it before the second was launched."""
    m, _rt, h, _out = _routed(depth, rows_per_shard=8)     # quota 2 a pair
    journey.enable()
    TRACER.start()
    h.send_columns({"k": np.array(["a"] * 16, object), "v": np.arange(16)})
    (rec,) = journey.ring()
    events = TRACER.stop()["traceEvents"]
    journey.disable()
    m.shutdown()
    launches = [e for e in events if e["name"] == "launch"]
    (step,) = [e for e in events if e["name"] == "query.step"]
    assert rec["route_pieces"] == len(launches) == 2
    for e in launches:
        assert e["args"]["batch"] == rec["batch"]
        assert e["args"]["query"] == "pq" and e["args"]["h2d_bytes"] > 0
        assert step["ts"] <= e["ts"] \
            and e["ts"] + e["dur"] <= step["ts"] + step["dur"]
    assert rec["launch_ms"] == pytest.approx(
        sum(e["dur"] for e in launches) / 1e3, rel=1e-3)
    assert rec["h2d_bytes"] == sum(e["args"]["h2d_bytes"] for e in launches)


def test_off_a_routed_query_leaves_no_span_and_no_journey(tmp_path):
    m, _rt, h, out = _routed()
    assert not spans_on()
    ring_before = journey.ring()
    jax.profiler.start_trace(str(tmp_path))
    _send(h, 1)
    jax.profiler.stop_trace()
    m.shutdown()
    assert len(out.pulled) == 2 and _engine_spans(tmp_path) == {}
    assert journey.ring() == ring_before


@pytest.mark.parametrize("depth", [1, 2])
def test_key_and_launch_nest_in_the_step_in_that_order(tmp_path, depth):
    """The two sub-stages of dispatch: ``siddhi.key`` then
    ``siddhi.launch``, both inside the batch's ``siddhi.query.step`` and
    under its id; the launch has closed before the meta pull opens; a
    key-capacity growth is a child of the key span; the journey's
    ``key_ms`` and ``launch_ms`` are those spans' durations, inside
    ``dispatch_ms``."""
    m = _manager(pipeline_depth=depth)
    rt = m.create_siddhi_app_runtime(PARTITIONED)
    rt.add_callback("O", Columns())
    h = rt.get_input_handler("S")

    def send(keys):
        h.send_columns({"k": np.array([f"k{i}" for i in range(keys)], object),
                        "v": np.arange(keys)})

    send(10)
    rt.start_trace(str(tmp_path))
    send(10)
    send(40)                        # 16 -> 64: grows, and compiles anew
    ring = journey.ring()
    rt.stop_trace()
    m.shutdown()
    (spans,) = _engine_spans(tmp_path).values()
    by_name = {}
    for sp in spans:
        by_name.setdefault(sp[2], []).append(sp)
    steps, keys = by_name["siddhi.query.step"], by_name["siddhi.key"]
    launches, pulls = by_name["siddhi.launch"], by_name["siddhi.meta_pull"]
    assert len(steps) == len(keys) == len(launches) == len(pulls) == 2
    for step, key, launch, pull, rec in zip(steps, keys, launches, pulls,
                                            ring):
        assert _inside(key, step) and _inside(launch, step)
        assert key[1] <= launch[0] and launch[1] <= pull[0]
        assert key[3]["batch"] == launch[3]["batch"] == step[3]["batch"] \
            == pull[3]["batch"] == rec["batch"]
        assert key[3]["query"] == launch[3]["query"] == "pq"
        assert rec["key_ms"] == pytest.approx((key[1] - key[0]) / 1e6,
                                              rel=0.2)
        assert rec["launch_ms"] == pytest.approx(
            (launch[1] - launch[0]) / 1e6, rel=0.2)
        assert rec["key_ms"] + rec["launch_ms"] <= rec["dispatch_ms"]
        assert rec["h2d_bytes"] == launch[3]["h2d_bytes"] > 0
    assert [(k[3]["rows"], k[3]["keys"], k[3]["new_keys"]) for k in keys] \
        == [(16, 10, 0), (64, 40, 30)]
    (grow,) = by_name["siddhi.grow"]
    assert _inside(grow, keys[1])


def test_launch_counts_the_numpy_leaves_that_cross():
    """``h2d_bytes`` / ``h2d_arrays``: the numpy columns handed to the
    step and its clock; what is already on the device counts nothing."""
    import jax.numpy as jnp

    from siddhi_tpu.core.event import launch_step

    m = _manager(fuse_fanout="false")
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)
    seen = {}
    _spy(rt.query_runtimes["q1"], seen)
    h = rt.get_input_handler("S")
    _send(h, 0)
    journey.enable()
    TRACER.start()
    _send(h, 1)
    (rec,) = [r for r in journey.ring() if r["queries"] == ["q1"]]
    state = {"a": jnp.zeros(4), "b": (jnp.zeros(2), np.zeros(3, np.int32))}
    out = launch_step(lambda st, cols, now: (st, len(cols)), state,
                      {"x": np.zeros(8, np.int64), "y": jnp.ones(8)},
                      np.int64(5), query="made")
    events = {e["args"]["query"]: e["args"]
              for e in TRACER.stop()["traceEvents"] if e["name"] == "launch"}
    journey.disable()
    m.shutdown()
    assert out == (state, 2)
    assert events["made"] == {
        "query": "made", "h2d_bytes": 64 + 8 + 12, "h2d_arrays": 3,
        "state_leaves": 3, "batch": None}
    _step, (q_state, cols, now) = seen["q1"]
    up = [v for v in list(cols.values()) + [now]
          if isinstance(v, (np.ndarray, np.generic))]
    assert len(up) == len(cols) + 1         # a sent batch is all numpy
    assert events["q1"]["h2d_arrays"] == len(up)
    assert events["q1"]["h2d_bytes"] == sum(v.nbytes for v in up) \
        == rec["h2d_bytes"]
    assert events["q1"]["state_leaves"] == len(
        jax.tree_util.tree_leaves(q_state))
    assert events["q1"]["batch"] == rec["batch"]


def _nfa_round():
    m = _manager()
    rt = m.create_siddhi_app_runtime(PATTERN)
    rt.add_callback("MatchStream", Columns())
    keys = np.array(["x", "y"], object)

    def send(t):
        rt.get_input_handler("AStream").send_columns(
            {"k": keys, "v": np.zeros(2)}, timestamps=np.full(2, t))
        rt.get_input_handler("BStream").send_columns(
            {"k": keys, "v": np.ones(2)}, timestamps=np.full(2, t + 1))

    return m, send, "query.step", ["nfa"], 2


def _join_round():
    m = _manager()
    rt = m.create_siddhi_app_runtime(JOIN)
    rt.add_callback("J", Columns())
    one = np.array(["a"], object)

    def send(t):
        rt.get_input_handler("L").send_columns({"k": one, "v": np.array([t])})
        rt.get_input_handler("R").send_columns({"k": one, "w": np.array([t])})

    return m, send, "query.step", ["jq"], 2


def _fanout_round():
    m = _manager()
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)     # fuses q1 and q2
    rt.add_callback("O", Columns())
    h = rt.get_input_handler("S")
    return m, lambda t: _send(h, t), "fanout.step", ["q1", "q2"], 1


@pytest.mark.parametrize("family", ["nfa", "join", "fanout"])
def test_every_step_family_opens_key_and_launch(family):
    """The NFA, join and fused fan-out runtimes dispatch through the same
    two sub-stages: one ``key`` and one ``launch`` a step, in that order
    inside the step's span, under the batch's id, and on the journey."""
    m, send, outer, queries, steps = {
        "nfa": _nfa_round, "join": _join_round,
        "fanout": _fanout_round}[family]()
    send(1_000)                     # compiles
    journey.enable()
    TRACER.start()
    send(2_000)
    ring = journey.ring()
    events = [e for e in TRACER.stop()["traceEvents"] if e.get("ph") == "X"]
    journey.disable()
    m.shutdown()
    outers = [e for e in events if e["name"] == outer]
    keys = [e for e in events if e["name"] == "key"]
    launches = [e for e in events if e["name"] == "launch"]
    assert len(outers) == len(keys) == len(launches) == len(ring) == steps
    for o, key, launch, rec in zip(outers, keys, launches, ring):
        assert o["ts"] <= key["ts"] \
            and key["ts"] + key["dur"] <= launch["ts"] \
            and launch["ts"] + launch["dur"] <= o["ts"] + o["dur"]
        assert key["args"]["batch"] == launch["args"]["batch"] \
            == rec["batch"] == o["args"]["batch"]
        assert rec["queries"] == queries and set(rec) == RING_KEYS
        assert rec["key_ms"] == pytest.approx(key["dur"] / 1e3, rel=1e-3)
        assert rec["launch_ms"] == pytest.approx(launch["dur"] / 1e3,
                                                 rel=1e-3)
        assert rec["h2d_bytes"] == launch["args"]["h2d_bytes"] > 0
        assert launch["args"]["h2d_arrays"] > 0
        assert launch["args"]["state_leaves"] > 0


def test_off_the_launch_is_the_call_and_reads_no_argument(monkeypatch):
    """Spans off: ``launch_step`` is ``step(state, *args)`` after one flag
    check; no leaf is flattened, no attribute computed."""
    from siddhi_tpu.core import event

    def no_flatten(_tree):
        raise AssertionError("an attribute was evaluated")

    monkeypatch.setattr(jax.tree_util, "tree_leaves", no_flatten)
    assert not spans_on()
    got = event.launch_step(lambda st, a, b: (st, a + b), "state", 1, 2,
                            query="q")
    assert got == ("state", 3)
    assert journey.keying(None, "q", 8, no_flatten) is journey.keying(
        None, "q", 8, no_flatten)           # the shared no-op: never sized
    TRACER.enabled = True
    with pytest.raises(AssertionError, match="an attribute was evaluated"):
        event.launch_step(lambda st: st, "state", query="q")


def _spy(q, seen, key=None):
    """Records the jitted step and the arguments of every dispatch of a
    (single-stream or join) runtime."""
    finish = q._finish_device_batch

    def spying_finish(step, cols, overflow_msg):
        def spy(*args):
            seen[key or q.name] = (step, args)
            return step(*args)

        return finish(spy, cols, overflow_msg)

    q._finish_device_batch = spying_finish


class _SpyingSteps(dict):
    """``NFAQueryRuntime._steps`` stand-in: remembers the last call of
    each jitted step (from its second lookup on)."""

    def __init__(self, seen):
        super().__init__()
        self.seen = seen

    def __setitem__(self, key, step):
        def spy(*args):
            self.seen[key] = (step, args)
            return step(*args)

        super().__setitem__(key, spy)


def _abstract(seen):
    return {k: (step, jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.result_type(x)),
        args)) for k, (step, args) in seen.items()}


def _query_step():
    m = _manager(fuse_fanout="false")
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)
    seen = {}
    _spy(rt.query_runtimes["q1"], seen)
    _send(rt.get_input_handler("S"), 0)
    m.shutdown()
    return _abstract(seen), {"q1": "jit_siddhi_query_step"}


def _nfa_steps():
    m = _manager()
    rt = m.create_siddhi_app_runtime(PATTERN)
    seen = {}
    rt.query_runtimes["nfa"]._steps = _SpyingSteps(seen)
    keys = np.array(["x", "y"], object)
    for t in (1_000, 2_000):
        rt.get_input_handler("AStream").send_columns(
            {"k": keys, "v": np.zeros(2)}, timestamps=np.full(2, t))
        rt.get_input_handler("BStream").send_columns(
            {"k": keys, "v": np.ones(2)}, timestamps=np.full(2, t + 1))
    m.shutdown()
    return _abstract(seen), {
        ("AStream", False): "jit_siddhi_nfa_step_AStream",
        ("BStream", False): "jit_siddhi_nfa_step_BStream"}


def _join_sides():
    m = _manager()
    rt = m.create_siddhi_app_runtime(JOIN)
    q = rt.query_runtimes["jq"]
    one = np.array(["a"], object)
    rt.get_input_handler("L").send_columns({"k": one, "v": np.array([1])})
    rt.get_input_handler("R").send_columns({"k": one, "w": np.array([2])})
    m.shutdown()
    return dict(q._steps), {"left": "siddhi_device_join_left",
                            "right": "siddhi_device_join_right"}


@pytest.mark.parametrize("family", ["query_step", "nfa_step"])
def test_step_programs_are_named_by_family_and_carry_the_scopes(family):
    steps, want = {"query_step": _query_step, "nfa_step": _nfa_steps}[family]()
    assert set(steps) == set(want)
    for key, (step, args) in steps.items():
        lowered = step.lower(*args)
        text = lowered.as_text(debug_info=True)
        assert f"module @{want[key]}" in text, key
        # the three scopes, the same in every family
        for scope in (STATE_SCOPE, SELECT_SCOPE, META_SCOPE):
            assert scope in text, (key, scope)
        # and in what XLA keeps after optimisation: op_name metadata
        assert STATE_SCOPE in lowered.compile().as_text()


def test_fused_and_join_programs_are_named_by_family():
    m = _manager()
    rt = m.create_siddhi_app_runtime(TWO_QUERIES)     # fuses q1 and q2
    _send(rt.get_input_handler("S"), 0)
    (group,) = {q._fanout_group for q in rt.query_runtimes.values()}
    assert group._step.__name__ == "siddhi_fused_fanout"
    m.shutdown()
    steps, want = _join_sides()
    assert {side: step.__name__ for side, step in steps.items()} == want
