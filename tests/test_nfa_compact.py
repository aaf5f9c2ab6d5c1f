"""An NFA stream step's compacted output (``ops/compact.py``): the rows a
callback receives are the valid rows of the padded output, value for
value and in order, and the host pulls the narrow columns whenever the
meta's count fits them, the padded ones when it does not, nothing when no
row is valid; a query downstream sees one width either way. CPU backend:
what is delivered and pulled, never how long it took.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.event import LazyColumns
from siddhi_tpu.core.query import nfa_runtime
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.observability.tracing import TRACER
from siddhi_tpu.ops.compact import (compact_columns, compact_width,
                                    valid_row_indices)
from siddhi_tpu.ops.expressions import PADDED_KEY, VALID_KEY
from test_nfa_fast_differential import PATTERNS, _random_feeds

AB = """
@app:playback
define stream A (k string, v double);
define stream B (k string, v double);
partition with (k of A, k of B)
begin
  @info(name = 'q')
  from every e1=A -> e2=B[e2.v > e1.v] within 5 sec
  select e1.v as v1, e2.v as v2
  insert into M;
end;
"""


class Rows(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


class Columns(StreamCallback):
    """Reads ONE column, as a columnar consumer would: the first touch
    pulls every pending device column of the batch."""

    def __init__(self):
        super().__init__()
        self.lengths = []
        self.v1 = []

    def receive_batch(self, batch, junction=None):
        v1 = np.asarray(batch.cols["v1"])
        self.lengths.append(len(v1))
        self.v1.append(v1[np.asarray(batch.cols[VALID_KEY])])


def _app(app, slots=32, **knobs):
    m = SiddhiManager()
    conf = {"siddhi_tpu.nfa_slots": str(slots)}
    conf.update({f"siddhi_tpu.{k}": str(v) for k, v in knobs.items()})
    m.set_config_manager(InMemoryConfigManager(conf))
    return m, m.create_siddhi_app_runtime(app)


def _counters(rt, query="q"):
    c = rt.app_context.telemetry.snapshot()["counters"]
    return (c.get(f"pull.{query}.compacted", 0),
            c.get(f"pull.{query}.padded", 0))


def _send(h, keys, vals, ts):
    h.send_columns({"k": np.array(keys, dtype=object),
                    "v": np.array(vals, dtype=np.float64)},
                   timestamps=np.full(len(keys), ts, np.int64))


def _drive(app, feeds, fast, compact, monkeypatch, slots=16):
    with monkeypatch.context() as mp:
        if not compact:
            mp.setattr(nfa_runtime, "compact_width", lambda b, p: None)
        m, rt = _app(app, slots=slots)
        c = Rows()
        rt.add_callback("M", c)
        q = next(iter(rt.query_runtimes.values()))
        q.stage.fast_enabled = fast
        for stream, cols, ts in feeds:
            rt.get_input_handler(stream).send_columns(cols, timestamps=ts)
        pulls = _counters(rt, q.name)
        m.shutdown()
    return c.rows, pulls


# ------------------------------------------------------------ the device half

@pytest.mark.parametrize("n,width,share", [
    (33, 2, 0.03), (33, 2, 0.5), (31, 8, 0.1), (64, 64, 1.0), (1000, 64, 0.05),
    (4224, 256, 0.0), (4224, 256, 0.03), (4224, 256, 0.2), (67584, 4096, 0.03),
])
def test_indices_are_those_of_the_set_rows_in_order(n, width, share):
    rng = np.random.default_rng(n + width)
    valid = rng.random(n) < share
    want = np.nonzero(valid)[0]
    got = np.asarray(jax.jit(valid_row_indices, static_argnums=1)(
        jnp.asarray(valid), width))
    assert got.dtype == np.int32 and got.shape == (width,)
    if len(want) <= width:       # exact while the set rows fit
        assert (got[:len(want)] == want).all() and not got[len(want):].any()
    out = {VALID_KEY: jnp.asarray(valid),
           "v": jnp.asarray(rng.random(n)),
           "set": jnp.asarray(rng.integers(0, 9, (n, 3)))}
    twins = jax.jit(compact_columns, static_argnums=1)(out, width)
    assert {k: v.shape for k, v in twins.items()} == {
        VALID_KEY: (width,), "v": (width,), "set": (width, 3)}
    if 0 < len(want) <= width:
        assert int(np.asarray(twins[VALID_KEY]).sum()) == len(want)
        assert (np.asarray(twins["v"])[:len(want)]
                == np.asarray(out["v"])[want]).all()
        assert (np.asarray(twins["set"])[:len(want)]
                == np.asarray(out["set"])[want]).all()
    else:                        # skipped: nothing gathered
        assert not np.asarray(twins["v"]).any()


@pytest.mark.parametrize("batch,padded,width", [
    (1, 33, 2), (8, 264, 16), (128, 4224, 256), (16_384, 540_672, 32_768),
    (100, 3300, 256), (16_384, 16_384 * 12, 32_768),
    (8, 24, None), (8, 63, None), (128, 128 * 5, None),
    (16_384, 16_384 * 11, None),
])
def test_width_follows_from_the_shapes(batch, padded, width):
    assert compact_width(batch, padded) == width


# -------------------------- (a) compacted == the valid rows of the padded

@pytest.mark.parametrize("fast", [True, False], ids=["two-step", "generic"])
@pytest.mark.parametrize("name", sorted(PATTERNS))
def test_delivered_rows_equal_the_padded_outputs(name, fast, monkeypatch):
    app = PATTERNS[name].replace(
        "from ", "partition with (k of A, k of B) begin @info(name='q') from ",
        1).replace("insert into M;", "insert into M; end;")
    # the same feed for both engines, and one the pattern matches on at all
    rng = np.random.default_rng(
        zlib.crc32(name.encode()) + (5 if name == "nonevery-sequence" else 0))
    feeds = _random_feeds(rng, n_batches=30, max_rows=8, n_keys=4,
                          ts_jump_ms=700)
    narrow, pulls = _drive(app, feeds, fast, True, monkeypatch)
    wide, wide_pulls = _drive(app, feeds, fast, False, monkeypatch)
    assert narrow == wide and len(wide) > 0
    # compacted pulls, and padded ones where one B met more pending A's
    # than the width holds (no `within`): the rows are the same either way
    assert pulls[0] > 0
    assert wide_pulls == (0, 0)               # no twins: counted as neither


# -------------------------------------------- (b) the fall-back is exact

def test_more_matches_than_the_width_fall_back_to_the_padded_pull():
    m, rt = _app(AB)
    c = Rows()
    rt.add_callback("M", c)
    a, b = rt.get_input_handler("A"), rt.get_input_handler("B")
    for i in range(3):           # 24 pending A's of one key, 8 a batch
        _send(a, ["K"] * 8, [i * 8 + j for j in range(8)], 1_000 + i)
    _send(b, ["K"], [5.5], 2_000)         # 6 matches: fits the width (16)
    assert _counters(rt) == (1, 0) and len(c.rows) == 6
    _send(b, ["K"], [100.0], 2_001)       # the other 18: does not
    assert _counters(rt) == (1, 1)
    m.shutdown()
    assert sorted(r[1][0] for r in c.rows[:6]) == [0, 1, 2, 3, 4, 5]
    assert sorted(r[1][0] for r in c.rows[6:]) == list(range(6, 24))
    assert all(r[1][1] == 100.0 for r in c.rows[6:])


# ------------------------------------ (c) nothing valid: nothing pulled

@pytest.mark.parametrize("depth", [1, 2], ids=["sync", "pump"])
def test_a_head_batch_and_an_all_miss_batch_pull_nothing(depth):
    m, rt = _app(AB, pipeline_depth=depth)
    c = Rows()
    rt.add_callback("M", c)
    a, b = rt.get_input_handler("A"), rt.get_input_handler("B")
    _send(a, ["K0", "K1"], [5.0, 5.0], 1_000)     # compiles
    _send(b, ["K0", "K1"], [1.0, 1.0], 1_001)
    TRACER.start()
    _send(a, ["K2", "K3"], [5.0, 5.0], 1_002)     # a head batch
    _send(b, ["K2", "K3"], [1.0, 1.0], 1_003)     # every row misses
    pulls = [e for e in TRACER.stop()["traceEvents"] if e["name"] == "pull"]
    assert _counters(rt) == (0, 0) and c.rows == []
    m.shutdown()
    assert pulls == []


# ------------------- (d) one touched column pulls the chosen set only

def _one_round(rt, n_match, a_ts):
    a, b = rt.get_input_handler("A"), rt.get_input_handler("B")
    keys = [f"K{i}" for i in range(8)]
    _send(a, keys, [1.0] * 8, a_ts)
    _send(b, keys, [2.0] * n_match + [0.0] * (8 - n_match), a_ts + 1)


@pytest.mark.parametrize("path", ["sync", "pump", "deferred"])
def test_touching_one_column_pulls_only_the_chosen_set(path):
    knobs = {"sync": {"pipeline_depth": 1}, "pump": {"pipeline_depth": 2},
             "deferred": {"pipeline_depth": 1, "defer_meta": 2}}[path]
    m, rt = _app(AB, **knobs)
    cb = Columns()
    rt.add_callback("M", cb)
    _one_round(rt, 8, 1_000)              # compiles both steps
    TRACER.start()
    _one_round(rt, 5, 2_000)
    rt.query_runtimes["q"].flush_deferred()   # the hold-N tail's drain
    pulls = [e["args"] for e in TRACER.stop()["traceEvents"]
             if e["name"] == "pull"]
    m.shutdown()
    assert cb.lengths[-1] == 16 and list(cb.v1[-1]) == [1.0] * 5
    # one pull, of the twins alone: the 9 columns at 16 rows, not at 264
    (pull,) = pulls
    assert pull["rows"] == 16 and pull["arrays"] == 9
    assert pull["bytes"] == 16 * (4 + 4 + 8 + 1 + 1 + 8 + 1 + 8 + 1)


def test_an_output_without_twins_is_left_as_it_is():
    cols = LazyColumns({VALID_KEY: np.ones(4, bool), "v": np.arange(4)})
    before = dict(cols)
    assert cols.choose(2) is None and dict(cols) == before


def _both_widths():
    padded = {VALID_KEY: jnp.ones(8, bool), "v": jnp.arange(8.0)}
    return LazyColumns({VALID_KEY: jnp.arange(2) < 1, "v": jnp.zeros(2),
                        PADDED_KEY: padded})


def _pulls_of(touch):
    TRACER.start()
    touch()
    return [e["args"] for e in TRACER.stop()["traceEvents"]
            if e["name"] == "pull"]


@pytest.mark.parametrize("count,chosen,rows", [
    (1, True, 2), (2, True, 2), (3, False, 8), (None, False, 8)])
def test_the_count_settles_which_columns_a_pull_moves(count, chosen, rows):
    cols = _both_widths()
    assert PADDED_KEY not in cols             # held aside, out of a pull
    assert cols.choose(count) is chosen and cols.choose(count) is None
    assert cols.fell_back_from == (None if chosen else 2)
    (pull,) = _pulls_of(lambda: cols["v"])
    assert pull["arrays"] == 2 and pull["rows"] == rows == len(cols["v"])


def test_a_pull_nobody_chose_for_moves_the_padded_columns():
    cols = _both_widths()
    (pull,) = _pulls_of(lambda: cols["v"])
    assert pull["rows"] == 8 and list(cols["v"]) == list(range(8))
    assert cols[VALID_KEY].all()


# --------------------------------------- (e) a double keeps its bits

def test_a_subnormal_and_a_negative_zero_pass_bit_for_bit(monkeypatch):
    odd = [5e-324, -0.0, 2.2250738585072014e-308, -5e-324, 1e-30, 0.1, -1e308]
    keys = [f"K{i}" for i in range(len(odd))]
    got = {}
    for compact in (True, False):
        with monkeypatch.context() as mp:
            if not compact:
                mp.setattr(nfa_runtime, "compact_width", lambda b, p: None)
            m, rt = _app(AB)
            cb = Columns()
            rt.add_callback("M", cb)
            _send(rt.get_input_handler("A"), keys, odd, 1_000)
            _send(rt.get_input_handler("B"), keys, [1e309] * len(odd), 1_001)
            m.shutdown()
        got[compact] = (cb.lengths, np.concatenate(cb.v1).view(np.int64))
    assert got[True][0] == [16] and got[False][0] == [8 * 33]
    assert list(got[True][1]) == list(got[False][1]) \
        == list(np.array(odd).view(np.int64))


# ------------------------ (f) where it does not engage, on purpose

def _spy_outputs(q):
    seen = []
    run_step = q._run_nfa_step

    def spy(run, allow_pipeline=True):
        def spied():
            state, out = run()
            seen.append((allow_pipeline, PADDED_KEY in out,
                         out[VALID_KEY].shape[0]))
            return state, out

        return run_step(spied, allow_pipeline)

    q._run_nfa_step = spy
    return seen


def test_a_gspmd_sharded_step_delivers_uncompacted():
    from siddhi_tpu.parallel.mesh import make_mesh, shard_query_step

    m, rt = _app(AB)
    c = Rows()
    rt.add_callback("M", c)
    q = rt.query_runtimes["q"]
    shard_query_step(q, make_mesh(4))
    seen = _spy_outputs(q)
    _one_round(rt, 5, 1_000)
    assert _counters(rt) == (0, 0)
    m.shutdown()
    assert len(c.rows) == 5
    assert seen == [(True, False, 264), (True, False, 264)]


def test_the_timer_step_delivers_uncompacted():
    app = """
    @app:playback
    define stream A (k string, v double);
    define stream B (k string, v double);
    @info(name = 'q')
    from every e1=A -> not B[v > e1.v] for 200 milliseconds
    select e1.v as v1 insert into M;
    """
    m, rt = _app(app)
    c = Rows()
    rt.add_callback("M", c)
    seen = _spy_outputs(rt.query_runtimes["q"])
    a = rt.get_input_handler("A")
    a.send(1_000, ["x", 1.0])
    a.send(1_500, ["x", 2.0])      # past the first deadline: a timer sweep
    m.shutdown()
    assert [r[1] for r in c.rows][:1] == [(1.0,)]
    timers = [s for s in seen if not s[0]]
    assert timers and not any(compacted for _p, compacted, _n in timers)
    assert any(compacted for p, compacted, _n in seen if p)


def test_a_selector_with_an_overflow_scalar_still_compacts():
    # distinctCount's saturation flag is a 0-d entry of the selector's
    # output: it rides the meta, the columns beside it are compacted
    app = AB.replace("e2.v as v2", "distinctCount(e2.v) as n")
    m, rt = _app(app)
    c = Rows()
    rt.add_callback("M", c)
    seen = _spy_outputs(rt.query_runtimes["q"])
    _one_round(rt, 5, 1_000)
    assert _counters(rt) == (1, 0)
    m.shutdown()
    assert sorted(r[1] for r in c.rows) == [(1.0, 1)] * 5
    assert [compacted for _p, compacted, _n in seen] == [True, True]


def test_the_split_keyer_path_and_small_slot_counts_stay_padded():
    grouped = AB.replace("select e1.v as v1, e2.v as v2",
                         "select e1.v as v1, sum(e2.v) as v2 group by e1.v")
    for app, slots in ((grouped, 32), (AB, 2)):
        m, rt = _app(app, slots=slots)
        c = Rows()
        rt.add_callback("M", c)
        seen = _spy_outputs(rt.query_runtimes["q"])
        _one_round(rt, 5, 1_000)
        assert _counters(rt) == (0, 0)
        m.shutdown()
        assert len(c.rows) == 5
        assert seen and not any(compacted for _p, compacted, _n in seen)


# ------------------ a query downstream sees one width, fall-back or not

def test_a_chained_query_sees_one_width_across_a_fall_back():
    app = AB + """
    @info(name = 'down')
    from M[v2 > 0.0] select v1, v2 insert into O;
    """
    m, rt = _app(app)
    c = Rows()
    rt.add_callback("O", c)
    seen = []
    down = rt.query_runtimes["down"]
    receive = down.receive_batch
    down.receive_batch = lambda batch, *a, **k: (
        seen.append((batch.capacity, batch.size)), receive(batch, *a, **k))[1]
    a, b = rt.get_input_handler("A"), rt.get_input_handler("B")
    for i in range(6):           # 24 pending A's of each of two keys
        _send(a, ["KL"[i % 2]] * 8, [(i // 2) * 8 + j for j in range(8)],
              1_000 + i)
    _send(b, ["K"], [5.5], 2_000)         # 6 matches: fits the width (16)
    _send(b, ["K", "L"], [100.0, 100.0], 2_001)     # the other 18, and 24
    assert _counters(rt) == (1, 1)
    compiles = down._step._fn._cache_size()
    m.shutdown()
    # the fall-back went on in pieces of the width downstream compiled for
    assert seen == [(16, 6), (16, 16), (16, 16), (16, 10)]
    assert compiles == 1
    assert sorted(r[1][0] for r in c.rows[:6]) == [0, 1, 2, 3, 4, 5]
    assert sorted(r[1][0] for r in c.rows[6:24]) == list(range(6, 24))
    assert sorted(r[1][0] for r in c.rows[24:]) == list(range(24))
    assert all(r[1][1] == 100.0 for r in c.rows[6:])


# ------- a fault planted in the step's columns reaches the answers

def test_a_value_altered_in_the_steps_output_is_delivered(monkeypatch):
    """What the benchmark's ``answer_altered`` fault relies on: the step's
    top-level columns ARE what a callback receives."""
    m, rt = _app(AB)
    c = Rows()
    rt.add_callback("M", c)
    q = rt.query_runtimes["q"]
    build = q.build_stream_step_fn

    def altered(*args, **kw):
        real = build(*args, **kw)

        def step(state, cols, now):
            new, out = real(state, cols, now)
            first = out[VALID_KEY].argmax()
            return new, {**out, "v1": out["v1"].at[first].add(1.0)}
        return step

    monkeypatch.setattr(q, "build_stream_step_fn", altered)
    _one_round(rt, 5, 1_000)
    assert _counters(rt) == (1, 0)
    m.shutdown()
    assert sorted(r[1] for r in c.rows) == [(1.0, 2.0)] * 4 + [(2.0, 2.0)]
