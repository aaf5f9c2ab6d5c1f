"""Keyed (inside-partition) externalTime, timeLength, and delay windows —
per-key instances of ExternalTimeWindowProcessor / TimeLengthWindowProcessor
/ DelayWindowProcessor (partitions give every key its own window)."""

import collections

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.ops import keyed_windows as KW
from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
from siddhi_tpu.ops.windows import CURRENT, EXPIRED


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.events = []

    def receive(self, events):
        self.events.extend(events)


def build(app, out="OutStream"):
    manager = SiddhiManager()
    runtime = manager.create_siddhi_app_runtime(app)
    collector = Collector()
    runtime.add_callback(out, collector)
    return manager, runtime, collector


STREAM = "@app:playback define stream S (sym string, v int);\n"


def test_keyed_external_time_sliding_sum():
    # per-key clock: A's rows only expire when A gets new events
    m, rt, c = build("""@app:playback define stream S (sym string, ets long, v int);
        partition with (sym of S) begin
        from S#window.externalTime(ets, 1 sec)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 1000, 10])
    h.send(1200, ["B", 1200, 100])
    h.send(1500, ["A", 1500, 20])     # A window: 10+20
    h.send(2300, ["A", 2300, 30])     # 1000+1000<=2300: row 10 expires -> 20+30
    h.send(5000, ["B", 5000, 1])      # B: row 100 expired -> 1
    m.shutdown()
    got = {}
    for e in c.events:
        got[e.data[0]] = e.data[1]
    by_seq = [tuple(e.data) for e in c.events]
    assert ("A", 30) in by_seq       # after first A
    assert by_seq[-2:] == [("A", 50), ("B", 1)] or got == {"A": 50, "B": 1}


def test_keyed_external_time_expired_keep_timestamps():
    m, rt, c = build("""@app:playback define stream S (sym string, ets long, v int);
        partition with (sym of S) begin
        from S#window.externalTime(ets, 1 sec)
        select sym, v insert all events into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 1000, 1])
    h.send(2500, ["A", 2500, 2])     # expires row 1
    m.shutdown()
    # arrival, expiry (original timestamp — ExternalTimeWindowProcessor
    # keeps event time), then the new current
    got = [(e.timestamp, tuple(e.data)) for e in c.events]
    assert got == [(1000, ("A", 1)), (1000, ("A", 1)), (2500, ("A", 2))]


def test_keyed_external_time_nonmonotone_clock_degrades_gracefully():
    # a backwards external timestamp must not corrupt expiry: the per-key
    # running max (segmented cummax) treats the stalled clock as "no
    # advance", mirroring the unkeyed stage and the reference's behavior
    # of never expiring on a clock that goes backwards
    m, rt, c = build("""@app:playback define stream S (sym string, ets long, v int);
        partition with (sym of S) begin
        from S#window.externalTime(ets, 1 sec)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    from siddhi_tpu.core.event import Event
    h = rt.get_input_handler("S")
    # one batch, A's clock goes 2000 -> 1500 (backwards) -> 3500
    h.send([Event(timestamp=2000, data=["A", 2000, 1]),
            Event(timestamp=2100, data=["A", 1500, 2]),
            Event(timestamp=2200, data=["B", 9000, 100]),
            Event(timestamp=2300, data=["A", 3500, 4])])
    m.shutdown()
    a_totals = [e.data[1] for e in c.events if e.data[0] == "A"]
    # rows 1 and 2 expire exactly once each (at clock 3500: 2000+1000 and
    # 1500+1000 are both covered); no arbitrary expiry from the backwards
    # tick — final A total is 4, never negative or duplicated
    assert a_totals[-1] == 4
    assert all(t >= 0 for t in a_totals)
    b_totals = [e.data[1] for e in c.events if e.data[0] == "B"]
    assert b_totals == [100]


def test_keyed_timelength_evicts_by_count_and_time():
    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.timeLength(10 sec, 2)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 1])
    h.send(1100, ["A", 2])      # A live: 1,2
    h.send(1200, ["A", 4])      # count cap 2: evict 1 -> total 6
    h.send(1300, ["B", 100])    # B independent
    h.send(1400, ["A", 8])      # evict 2 -> total 12
    m.shutdown()
    last = {}
    for e in c.events:
        last[e.data[0]] = e.data[1]
    assert last == {"A": 12, "B": 100}


def test_keyed_timelength_time_expiry_still_works():
    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.timeLength(1 sec, 10)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 5])
    h.send(2500, ["A", 7])      # row 5 expired by time
    m.shutdown()
    assert [tuple(e.data) for e in c.events][-1] == ("A", 7)


def test_keyed_batch_window_per_key_chunks():
    from siddhi_tpu.core.event import Event

    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.batch()
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    # chunk 1: A{1,2}, B{10}
    h.send([Event(timestamp=1000, data=["A", 1]),
            Event(timestamp=1000, data=["A", 2]),
            Event(timestamp=1000, data=["B", 10])])
    # chunk 2: A{5} — replaces A's batch; B untouched
    h.send(1100, ["A", 5])
    m.shutdown()
    rows = [tuple(e.data) for e in c.events]
    # batch-mode sums per flush: chunk1 A->3, B->10; chunk2 A->5
    assert rows[-1] == ("A", 5)
    assert ("A", 3) in rows and ("B", 10) in rows


def test_keyed_lengthbatch_multi_key_chunk_emits_every_key():
    # regression: a single chunk flushing several keys' batches must emit
    # one row per key, not just the chunk's last row
    from siddhi_tpu.core.event import Event

    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.lengthBatch(2)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send([Event(timestamp=1000, data=["A", 1]),
            Event(timestamp=1000, data=["A", 2]),
            Event(timestamp=1000, data=["B", 10]),
            Event(timestamp=1000, data=["B", 20])])
    m.shutdown()
    rows = sorted(tuple(e.data) for e in c.events)
    assert rows == [("A", 3), ("B", 30)]


def test_keyed_batch_window_join_side_probes_latest_chunk():
    m, rt, c = build("""
        define stream S (sym string, v int);
        define stream R (sym string, w int);
        partition with (sym of S, sym of R) begin
        from S#window.batch() join R#window.length(4)
             on S.sym == R.sym
        select S.sym as sym, S.v as v, R.w as w insert into OutStream; end;
    """)
    rt.get_input_handler("S").send(["A", 1])
    rt.get_input_handler("R").send(["A", 7])   # probes A's latest batch {1}
    m.shutdown()
    assert ("A", 1, 7) in [tuple(e.data) for e in c.events]


def test_keyed_hopping_window_per_key_phase():
    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.hopping(3 sec, 1 sec)
        select sym, sum(v) as total insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 1])        # A arms: first hop at 2000
    h.send(1500, ["B", 10])       # B arms: first hop at 2500
    h.send(2100, ["A", 2])        # A's hop at 2000 fired via timer/arrival
    h.send(2600, ["B", 20])       # B's hop fired
    h.send(3100, ["A", 4])        # A's 2nd hop (3000): trailing {1,2}
    m.shutdown()
    rows = [tuple(e.data) for e in c.events]
    assert ("A", 1) in rows       # A's first hop: {1}
    assert ("B", 10) in rows      # B's first hop: {10}
    assert ("A", 3) in rows       # A's second hop: {1,2}


def test_keyed_delay_releases_after_time():
    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.delay(1 sec)
        select sym, v insert into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["A", 1])
    h.send(1100, ["B", 2])
    assert c.events == []        # still held
    h.send(2200, ["A", 3])       # clock passes 2000: A1 and B2 release
    m.shutdown()
    got = [tuple(e.data) for e in c.events]
    # A1 and B2 released once the clock passed their +1s deadlines; A3's
    # deadline (3200) never arrives before shutdown, so it stays held
    assert got == [("A", 1), ("B", 2)]


def test_keyed_session_with_latency_per_key_host_instances():
    m, rt, c = build(STREAM + """
        partition with (sym of S) begin
        from S#window.session(2 sec, sym, 1 sec)
        select sym, v insert all events into OutStream; end;
    """)
    h = rt.get_input_handler("S")
    h.send(1000, ["u1", 1])
    h.send(3500, ["u2", 9])     # u1's session parked (latency hold)
    h.send(3700, ["u1", 2])     # late event revives u1
    h.send(9000, ["u2", 0])     # everything expires
    m.shutdown()
    u1 = [tuple(e.data) for e in c.events if e.data[0] == "u1"]
    # both rows appear twice (CURRENT + one joint EXPIRED emission)
    assert u1.count(("u1", 1)) == 2 and u1.count(("u1", 2)) == 2


# ---------------------------------------------------------------------------
# The keyed length window's ring write hands the scatter slots that are
# sorted and unique (``ops/keyed_windows.py`` ``_ring_write``): a row that
# is not written carries an out-of-range slot of its own. The stage alone,
# batch by batch, against the event-at-a-time loop: every emitted row in
# its order and every live ring entry, bit for bit.

_RING_SPECS = {"v": np.int64, "v?": np.bool_, "d": np.float64,
               "s": np.int32, TS_KEY: np.int64, PK_KEY: np.int32}
# a double's bits that arithmetic would not keep, an int64's that a
# float64 cannot hold (above 2**53)
_DOUBLES = np.array([0x8000000000000000, 0x7FF8000000000001,
                     0xFFF0DEADBEEF0001, 0x7FF0000000000000,
                     0x0000000000000001, 0x3FF0000000000000],
                    np.uint64).view(np.float64)        # -0.0, two NaNs, inf ...
_LONGS = np.array([2**53 + 1, -(2**53) - 1, 2**63 - 1, -(2**63),
                   0x00000001FFFFFFFF, -1], np.int64)


def _ring_batch(rng, n, pk, *, valid=None, types=None):
    B = len(pk)
    return {
        "v": _LONGS[(np.arange(B) + 5 * n) % len(_LONGS)] ^ np.int64(n),
        "v?": rng.random(B) < 0.2,
        "d": _DOUBLES[(np.arange(B) + 3 * n) % len(_DOUBLES)],
        "s": rng.integers(-2**31, 2**31 - 1, B).astype(np.int32),
        TS_KEY: np.int64(1_791_000_000_000) + 1000 * n + np.arange(B),
        PK_KEY: np.asarray(pk, np.int32),
        TYPE_KEY: np.full(B, CURRENT, np.int8) if types is None else types,
        VALID_KEY: np.ones(B, bool) if valid is None else valid,
    }


def _edge_batches(case):
    """Three batches of 24 rows over 5 key slots, ``W`` = 4."""
    rng = np.random.default_rng(36)
    B, K = 24, 5
    spread = [rng.integers(0, K, B) for _ in range(3)]
    if case == "one key takes more than W rows":
        # key 2: 3 rows, then 19 of 24 (only its last 4 are written: the
        # other 15 carry out-of-range slots of their own), then 9
        pks = [np.where(np.arange(B) < 3, 2, 4),
               np.where(np.arange(B) % 5 == 0, spread[1], 2),
               np.where(np.arange(B) < 9, 2, spread[2])]
        return [_ring_batch(rng, n, pk) for n, pk in enumerate(pks)]
    if case == "invalid and EXPIRED-typed rows mixed in":
        return [_ring_batch(
            rng, n, pk, valid=rng.random(B) < 0.7,
            types=np.where(rng.random(B) < 0.3, EXPIRED, CURRENT).astype(np.int8))
            for n, pk in enumerate(spread)]
    if case == "a batch whose every row is invalid":
        return [_ring_batch(rng, n, pk, valid=np.full(B, n != 1))
                for n, pk in enumerate(spread)]
    assert case == "every row of one key, a double and an int64 bit for bit"
    return [_ring_batch(rng, n, np.full(B, 3)) for n in range(3)]


def _event_at_a_time(W, K, batches, keys):
    """The loop: per valid CURRENT event, in arrival order, the key's
    oldest entry leaves (EXPIRED, stamped now) once the ring is full, then
    the event enters and is emitted. -> (emitted rows per batch, the rings
    as ``{slot: row}`` per key)."""
    rings = [collections.OrderedDict() for _ in range(K)]
    total = [0] * K
    emitted = []
    for n, cols in enumerate(batches):
        now, rows = 1_791_000_000_000 + 1000 * n, []
        for i in range(len(cols[VALID_KEY])):
            if not (cols[VALID_KEY][i] and cols[TYPE_KEY][i] == CURRENT):
                continue
            k = int(cols[PK_KEY][i])
            row = {c: cols[c][i] for c in keys}
            if total[k] >= W:
                old = dict(rings[k].pop((total[k] - W) % W))
                old[TS_KEY] = np.int64(now)
                rows.append((EXPIRED, old))
            rings[k][total[k] % W] = row
            total[k] += 1
            rows.append((CURRENT, row))
        emitted.append(rows)
    return emitted, rings


def _row_bits(row, keys):
    return tuple(np.asarray(row[c]).tobytes() for c in keys)


@pytest.mark.parametrize("case", [
    "one key takes more than W rows",
    "invalid and EXPIRED-typed rows mixed in",
    "a batch whose every row is invalid",
    "every row of one key, a double and an int64 bit for bit",
])
def test_keyed_length_ring_write_against_the_event_loop(case):
    W, K = 4, 5
    batches = _edge_batches(case)
    keys = sorted(_RING_SPECS)
    want_rows, want_rings = _event_at_a_time(W, K, batches, keys)
    stage = KW.KeyedLengthWindowStage(W, _RING_SPECS)
    step = jax.jit(stage.apply)
    state = stage.init_state(K)
    for n, cols in enumerate(batches):
        before = state
        state, out = step(state, cols,
                          {"current_time": 1_791_000_000_000 + 1000 * n})
        out = {k: np.asarray(v) for k, v in out.items()}
        live = np.flatnonzero(out[VALID_KEY])
        assert live.tolist() == list(range(len(live)))      # valid rows first
        got = [(int(out[TYPE_KEY][i]), _row_bits(
            {c: out[c][i] for c in keys}, keys)) for i in live]
        assert got == [(t, _row_bits(row, keys)) for t, row in want_rows[n]]
        if not want_rows[n]:                  # nothing valid: nothing written
            assert all(np.asarray(a).tobytes() == np.asarray(b).tobytes()
                       for a, b in zip(jax.tree_util.tree_leaves(before),
                                       jax.tree_util.tree_leaves(state)))
    ring, valid = stage.contents(state)
    ring = {c: np.asarray(v) for c, v in ring.items()}
    assert ring["v"].dtype == np.int64 and ring["d"].dtype == np.float64
    total = np.asarray(state["total"])
    for k in range(K):
        assert int(np.asarray(valid)[k].sum()) == len(want_rings[k]) == min(
            W, int(total[k]))
        for slot, row in want_rings[k].items():
            assert _row_bits({c: ring[c][k, slot] for c in keys},
                             keys) == _row_bits(row, keys), (k, slot)


def _ring_scatters(jaxpr, slots):
    """Every scatter into a ``[slots]``-long operand, sub-jaxprs too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter" and eqn.invars[0].aval.shape == (slots,):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _ring_scatters(sub, slots)
    return found


@pytest.mark.parametrize("B,told", [(7, True), (8, False)])
def test_ring_slots_beyond_31_bits_take_the_plain_write(B, told):
    """``K*W + B`` = 2**31 - 1 still fits the int32 slots the sorted write
    needs (the largest slot handed out is ``K*W + B - 1``); one more row
    does not, and the stage keeps its int64 slots, the one shared
    out-of-range slot and the plain write. On shapes alone: nothing of
    that size is allocated."""
    W, K = 8, 268_435_455                     # K*W = 2**31 - 8
    assert K * W + B == 2**31 - 1 + (not told)
    stage = KW.KeyedLengthWindowStage(W, _RING_SPECS)
    state = jax.eval_shape(lambda: stage.init_state(K))
    cols = {k: jax.ShapeDtypeStruct((B,), dt) for k, dt in _RING_SPECS.items()}
    cols[TYPE_KEY] = jax.ShapeDtypeStruct((B,), np.int8)
    cols[VALID_KEY] = jax.ShapeDtypeStruct((B,), np.bool_)
    jaxpr = jax.make_jaxpr(
        lambda s, c: stage.apply(s, c, {"current_time": 0}))(state, cols)
    writes = _ring_scatters(jaxpr.jaxpr, K * W)
    assert len(writes) == len(jax.tree_util.tree_leaves(state["buf"])) == 8
    for eqn in writes:
        assert eqn.params["indices_are_sorted"] is told
        assert eqn.params["unique_indices"] is told
