"""A 64-bit integer ring column is HELD as its two 32-bit words.

``KeyedLengthWindowStage`` keeps an int64 ring column (``__ts__``, a
``long`` attribute) as two ``uint32[K*W]`` leaves ``(low, high)`` under
``buf[name]`` and writes each by a one-operand 32-bit scatter; the routed
exchange buckets an int64 column word by word too
(``tests/test_mesh_routing.py`` holds that half). The layout the stage
had before, ONE ``int64[K*W]`` leaf written by one scatter, is kept HERE
as the reference: every emitted row, and the rings re-joined to int64
(``contents()``, the snapshot's canonical form), must be bit-equal to it.
A ``double`` column is not split: it must come through untouched.
"""

import contextlib
import pickle
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.query.runtime import grow_state
from siddhi_tpu.core.util import snapshot
from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore
from siddhi_tpu.ops import keyed_windows as KW
from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
from siddhi_tpu.ops.windows import CURRENT, EXPIRED
from siddhi_tpu.parallel import mesh as M

I64 = np.iinfo(np.int64)
# what breaks a careless split: the sign, a low word whose top bit is set
# (it must not sign-extend into the high word), both words all ones
NASTY = np.array([
    0, 1, -1, I64.min, I64.max, I64.min + 1, I64.max - 1,
    0x80000000, 0xFFFFFFFF, -0x80000000, -0xFFFFFFFF, 0x7FFFFFFF,
    0x100000000, -0x100000000, 0x7FFFFFFF80000000, -0x7FFFFFFF80000000,
    0x00000001FFFFFFFF, -0x00000001FFFFFFFF, 0x123456789ABCDEF0 - (1 << 63),
], dtype=np.int64)
EPOCH_MS = 1_791_000_000_000          # above 2**40


# the reference, as the stage was: an int64 ring column is ONE leaf,
# gathered and scattered whole-valued, whatever the dtype
_INT64_LAYOUT = {
    "_new_ring": lambda slots, dtype: jnp.zeros((slots,), dtype),
    "_ring_read": lambda ring, at: ring[at],
    "_ring_write": lambda ring, slot, col: ring.at[slot].set(col, mode="drop"),
}


def _int64_layout(on=True):
    """``with _int64_layout(on):`` the stage builds, reads and writes its
    rings the old way while ``on``."""
    return (mock.patch.multiple(KW, **_INT64_LAYOUT) if on
            else contextlib.nullcontext())


def _bits(tree):
    """Every leaf as (dtype, shape, bytes): ``-0.0`` is not ``0.0`` here."""
    return [(str(np.asarray(x).dtype), np.shape(x), np.asarray(x).tobytes())
            for x in jax.tree_util.tree_leaves(tree)]


def _rejoined(state):
    """``state`` with every ring column that is held as ``(low, high)``
    words put back together as int64, by numpy and not by the code under
    test: the form in which the two layouts must agree bit for bit."""
    def ring(v):
        if not isinstance(v, tuple):
            return np.asarray(v)
        low, high = (np.asarray(w) for w in v)
        assert low.dtype == high.dtype == np.uint32 and low.shape == high.shape
        return ((high.astype(np.uint64) << np.uint64(32))
                | low.astype(np.uint64)).view(np.int64)

    out = dict(state)
    for wk in ("win", "lwin", "rwin"):
        if wk in out and "buf" in out[wk]:
            out[wk] = dict(out[wk], buf={k: ring(v)
                                         for k, v in out[wk]["buf"].items()})
    return out


@pytest.mark.parametrize("value", NASTY.tolist())
def test_words_round_trip(value):
    v = jnp.asarray([value, ~value], jnp.int64)
    low, high = KW.int64_words(v)
    assert low.dtype == high.dtype == jnp.uint32
    assert np.asarray(low).tolist() == [int(x) & 0xFFFFFFFF for x in (value, ~value)]
    assert np.asarray(high).tolist() == [(int(x) >> 32) & 0xFFFFFFFF
                                         for x in (value, ~value)]
    assert _bits(KW.int64_from_words(low, high)) == _bits(v)


# ---------------------------------------------------------------- the stage

_SPECS = {"volume": np.int64, "volume?": np.bool_, "price": np.float64,
          "price?": np.bool_, "sym": np.int32, TS_KEY: np.int64,
          "__gk__": np.int32, PK_KEY: np.int32}


def _batch(rng, B, K, n, *, keys=None, invalid=0.0):
    """Batch ``n`` of ``B`` rows over ``K`` key slots (``keys``: the ids
    that occur)."""
    pk = rng.choice(np.arange(K) if keys is None else np.asarray(keys), B)
    return {
        "volume": NASTY[(np.arange(B) + 7 * n) % len(NASTY)],
        "volume?": rng.random(B) < 0.1,
        "price": rng.standard_normal(B) * 10.0 ** rng.integers(-300, 300, B),
        "price?": np.zeros(B, bool),
        "sym": rng.integers(-2**31, 2**31 - 1, B).astype(np.int32),
        TS_KEY: EPOCH_MS + 1000 * n + np.sort(rng.integers(0, 1000, B)),
        "__gk__": pk.astype(np.int32),
        PK_KEY: pk.astype(np.int32),
        TYPE_KEY: np.where(rng.random(B) < invalid / 2, EXPIRED,
                           CURRENT).astype(np.int8),
        VALID_KEY: rng.random(B) >= invalid,
    }


def _run_stage(W, K, batches, *, reference=False, grow_to=None):
    """The bits of (the ring as ``contents()`` shows it + the counts, the
    emitted rows) after ``batches``; ``grow_to``: after the second batch
    the key capacity grows from ``K`` to that, leaf by leaf as the engine
    does it."""
    with _int64_layout(reference):
        stage = KW.KeyedLengthWindowStage(W, _SPECS)
        step = jax.jit(stage.apply)
        state, outs = stage.init_state(K), []
        for n, cols in enumerate(batches):
            if grow_to is not None and n == 2:
                grown = jax.tree_util.tree_leaves(
                    jax.eval_shape(lambda: stage.init_state(grow_to)))
                leaves, treedef = jax.tree_util.tree_flatten(state)
                state = jax.tree_util.tree_unflatten(treedef, grow_state(
                    lambda: stage.init_state(grow_to), grown, leaves))
            state, out = step(state, cols, {"current_time": EPOCH_MS + 1000 * n})
            outs.append(out)
        ring, live = stage.contents(state)
        return state, _bits((ring, live, state["total"])), _bits(outs)


@pytest.mark.parametrize("W,K,B,kw", [
    pytest.param(5, 16, 64, {}, id="nasty_values_several_batches"),
    pytest.param(4, 8, 96, {"keys": [3]}, id="ring_wraps_inside_one_batch"),
    pytest.param(2, 4, 32, {}, id="evictee_inserted_earlier_in_the_batch"),
    pytest.param(3, 16, 64, {"invalid": 0.5}, id="invalid_and_expired_rows"),
    pytest.param(3, 64, 48, {"keys": [0, 17, 63]}, id="absent_keys"),
    pytest.param(7, 1, 40, {}, id="one_key"),
    pytest.param(1000, 3, 64, {}, id="ring_wider_than_the_batch"),
])
def test_ring_and_rows_equal_the_int64_layout(W, K, B, kw):
    rng = np.random.default_rng(W * 1000 + K)
    batches = [_batch(rng, B, K, n, **kw) for n in range(5)]
    state, ring, rows = _run_stage(W, K, batches)
    ref_state, ref_ring, ref_rows = _run_stage(W, K, batches, reference=True)
    assert rows == ref_rows
    assert ring == ref_ring
    # and the leaves themselves: the reference's int64 leaf taken apart by
    # numpy is the pair of word leaves, bit for bit
    assert _bits(_rejoined({"win": state})) == _bits({"win": ref_state})


def test_an_int64_ring_column_is_two_word_leaves():
    """What ``init_state`` lays down: ``(low, high)`` ``uint32[K*W]`` for
    an int64 column, never ``[K*W, 2]``; every other dtype one leaf."""
    buf = KW.KeyedLengthWindowStage(5, _SPECS).init_state(8)["buf"]
    for name, dtype in _SPECS.items():
        if dtype is np.int64:
            assert isinstance(buf[name], tuple) and len(buf[name]) == 2
            assert [(w.dtype, w.shape) for w in buf[name]] == [
                (jnp.uint32, (40,))] * 2
        else:
            assert (buf[name].dtype, buf[name].shape) == (dtype, (40,))


def test_a_double_ring_column_is_one_scatter():
    """The exception, written down: a ``double`` has no bits to take on
    the chip, so its ring write stays one scatter of the float64 column;
    each int64 column is two uint32 ones, and no operation of the step
    is as long as a ring but the writes."""
    stage = KW.KeyedLengthWindowStage(7, _SPECS)
    cols = _batch(np.random.default_rng(0), 8, 3, 0)
    jaxpr = jax.make_jaxpr(stage.apply)(
        stage.init_state(3), cols, {"current_time": 0})
    ring_long = [(e.primitive.name, str(e.outvars[0].aval.dtype))
                 for e in jaxpr.eqns
                 if any(v.aval.shape == (21,) for v in e.outvars)]
    assert sorted(ring_long) == sorted(("scatter", dt) for dt in (
        ["uint32"] * 4 + ["float64", "bool", "bool", "int32", "int32", "int32"]))


@pytest.mark.parametrize("W,K,grow_to,B", [
    pytest.param(5, 8, 16, 64, id="capacity_doubles"),
    pytest.param(3, 4, 32, 48, id="capacity_times_eight"),
    pytest.param(1000, 2, 4, 64, id="ring_wider_than_the_batch"),
])
def test_growth_over_the_word_leaves_answers_as_a_state_never_grown(
        W, K, grow_to, B):
    """``grow_state`` lays each word leaf over its successor's prefix like
    any ring leaf (key-major, ``K*W`` long): rows, rings and counts are
    those of a state that had the grown capacity from the start, and of
    the int64 layout grown the same way."""
    rng = np.random.default_rng(W + K)
    batches = [_batch(rng, B, grow_to, n,
                      keys=None if n >= 2 else np.arange(K)) for n in range(5)]
    _s, ring, rows = _run_stage(W, K, batches, grow_to=grow_to)
    _s, never_ring, never_rows = _run_stage(W, grow_to, batches)
    assert rows == never_rows
    assert ring == never_ring
    _s, ref_ring, ref_rows = _run_stage(W, K, batches, grow_to=grow_to,
                                        reference=True)
    assert (ring, rows) == (ref_ring, ref_rows)


# --------------------------------------------------------------- the engine

class _Rows(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(
            (e.timestamp, e.is_expired,
             tuple(x.hex() if isinstance(x, float) else x for x in e.data))
            for e in events)


_APP = """
@app:playback
define stream S (k string, volume long, price double);
{purge}
partition with (k of S)
begin
  @info(name = 'q')
  from S#window.length(3)
  select k, volume, price, sum(volume) as total, max(volume) as top
  insert all events into Out;
end;
"""

_JOIN_APP = """
@app:playback
define stream L (k string, volume long, price double);
define stream R (k string, volume long);
partition with (k of L, k of R)
begin
  @info(name = 'q')
  from L#window.length(3) join R#window.length(2)
    on L.volume != R.volume
  select L.k, L.volume as lv, R.volume as rv, L.price
  insert all events into Out;
end;
"""


def _send(rt, stream, n0, n1, n_keys, wide=False):
    """Rows ``n0..n1`` of one deterministic feed, as batches of 16."""
    h = rt.get_input_handler(stream)
    for lo in range(n0, n1, 16):
        i = np.arange(lo, min(lo + 16, n1))
        cols = {"k": np.array([f"K{j % n_keys}" for j in i], dtype=object),
                "volume": NASTY[i % len(NASTY)]}
        if wide:
            cols["price"] = np.where(i % 5 == 0, -0.0, i * 1e-310 + i)
        h.send_columns(cols, timestamps=EPOCH_MS + i)


def _engine(reference, scenario):
    """Run ``scenario(manager) -> (rows, runtime)`` (in the int64 layout
    if ``reference``); returns the rows and the bits of every query's
    final state with its rings re-joined."""
    with _int64_layout(reference):
        manager = SiddhiManager()
        manager.set_persistence_store(InMemoryPersistenceStore())
        try:
            rows, runtime = scenario(manager)
            state = {name: _bits(_rejoined(q._state))
                     for name, q in runtime.query_runtimes.items()}
            return rows, state
        finally:
            manager.shutdown()


def _start(manager, app):
    rt = manager.create_siddhi_app_runtime(app)
    out = _Rows()
    rt.add_callback("Out", out)
    rt.start()
    return rt, out


def _scenario_growth(manager):
    rt, out = _start(manager, _APP.format(purge=""))
    _send(rt, "S", 0, 96, 5, wide=True)
    k0 = rt.query_runtimes["q"]._state["win"]["total"].shape[0]
    _send(rt, "S", 96, 400, 3 * k0 + 1, wide=True)    # key capacity doubles
    assert rt.query_runtimes["q"]._state["win"]["total"].shape[0] > k0
    _send(rt, "S", 400, 480, 5, wide=True)
    return out.rows, rt


def _scenario_purge(manager):
    rt, out = _start(manager, _APP.format(
        purge="@purge(enable='true', interval='10 sec', idle.period='1 hour')"))
    _send(rt, "S", 0, 96, 5, wide=True)
    pctx = rt.partition_contexts[0]
    pctx.keyspace.last_seen = dict.fromkeys(pctx.keyspace.last_seen, 0)
    assert len(pctx.purge()) == 5
    _send(rt, "S", 96, 192, 7, wide=True)   # the keys come back, rings clean
    return out.rows, rt


def _scenario_join(manager):
    rt, out = _start(manager, _JOIN_APP)
    for lo in range(0, 96, 16):
        _send(rt, "L", lo, lo + 16, 4, wide=True)
        _send(rt, "R", lo + 3, lo + 19, 4)
    return out.rows, rt


def _scenario_snapshot(manager):
    rt, out = _start(manager, _APP.format(purge=""))
    _send(rt, "S", 0, 96, 5, wide=True)
    rt.persist()
    rt.shutdown()
    rt2, out2 = _start(manager, _APP.format(purge=""))
    rt2.restore_last_revision()
    _send(rt2, "S", 96, 192, 5, wide=True)
    return out.rows + out2.rows, rt2


@pytest.mark.parametrize("scenario", [
    pytest.param(_scenario_growth, id="key_capacity_doubles_between_batches"),
    pytest.param(_scenario_purge, id="purge_then_re_arrival"),
    pytest.param(_scenario_join, id="contents_through_a_partitioned_join"),
    pytest.param(_scenario_snapshot, id="snapshot_restore_continue"),
])
def test_engine_rows_and_state_equal_the_int64_layout(scenario):
    rows, state = _engine(False, scenario)
    ref_rows, ref_state = _engine(True, scenario)
    assert len(rows) > 90
    assert rows == ref_rows
    assert state == ref_state


# ------------------------------------------------------------- the snapshot

def _persisted(store):
    """The one revision ``store`` holds, unpickled."""
    (revision,) = store.revisions("wordsapp")
    return revision, pickle.loads(store.load("wordsapp", revision))


def test_a_snapshot_holds_the_word_leaves_and_restores():
    """``FORMAT_VERSION`` 5: the canonical form stores the two leaves as
    they are; restored, they are the state that was persisted, bit for
    bit, and the run goes on as one that was never interrupted."""
    app = "@app:name('wordsapp')" + _APP.format(purge="")
    manager = SiddhiManager()
    store = InMemoryPersistenceStore()
    manager.set_persistence_store(store)
    try:
        whole_rt, whole = _start(manager, app)
        _send(whole_rt, "S", 0, 192, 5, wide=True)
        whole_rt.shutdown()

        rt, out = _start(manager, app)
        _send(rt, "S", 0, 96, 5, wide=True)
        before = _bits(rt.query_runtimes["q"]._state)
        rt.persist()
        rt.shutdown()
        _revision, obj = _persisted(store)
        assert obj["version"] == snapshot.FORMAT_VERSION == 5
        buf = obj["queries"]["q"]["state"]["win"]["buf"]
        for name in ("volume", TS_KEY):
            low, high = buf[name]
            assert low.dtype == high.dtype == np.uint32 and low.ndim == 1
        assert buf["price"].dtype == np.float64
        # the stamps are beyond 2**32 and the volumes use sign and high word
        assert np.asarray(buf[TS_KEY][1]).max() == EPOCH_MS >> 32
        assert np.asarray(buf["volume"][1]).max() == 0xFFFFFFFF

        rt2, out2 = _start(manager, app)
        rt2.restore_last_revision()
        assert _bits(rt2.query_runtimes["q"]._state) == before
        _send(rt2, "S", 96, 192, 5, wide=True)
        assert out.rows + out2.rows == whole.rows
    finally:
        manager.shutdown()


def test_a_version_4_snapshot_is_refused():
    """A version-4 file holds an int64 ring as one leaf, which the step
    no longer takes: refused by the version check, whatever is in it."""
    app = "@app:name('wordsapp')" + _APP.format(purge="")
    manager = SiddhiManager()
    store = InMemoryPersistenceStore()
    manager.set_persistence_store(store)
    try:
        with _int64_layout():
            rt, _out = _start(manager, app)
            _send(rt, "S", 0, 96, 5, wide=True)
            rt.persist()
            rt.shutdown()
        revision, obj = _persisted(store)
        assert obj["queries"]["q"]["state"]["win"]["buf"]["volume"].dtype \
            == np.int64
        obj["version"] = 4
        store.save("wordsapp", revision, pickle.dumps(obj))
        rt2, _out2 = _start(manager, app)
        with pytest.raises(ValueError, match="snapshot format 4 is not "
                                             "supported .expected 5."):
            rt2.restore_last_revision()
    finally:
        manager.shutdown()


# --------------------------------------------------------- the routed state

def _routed(manager, app, shards):
    rt, out = _start(manager, app)
    if shards:
        M.device_route_query_step(rt.query_runtimes["q"], M.make_mesh(shards),
                                  rows_per_shard=64)
    return rt, out


@pytest.mark.parametrize("shards", [2, 4])
def test_routed_word_leaves_to_canonical_and_back(shards):
    """Shard-major word leaves are re-laid out leaf by leaf like every
    ring leaf (``canonical_route_state`` / ``_canonical_to_routed``): the
    canonical form re-joins to the unsharded run's rings, goes back to
    the routed state bit for bit, and a revision persisted at ``shards``
    restores into the other shard count and into no mesh at all, each
    continuing as the unsharded run does, a key-capacity growth included. The int64 column uses its high
    word and its sign; ``insert all events`` shows it as it leaves a ring."""
    app = "@app:name('wordsapp')" + _APP.format(purge="")
    manager = SiddhiManager()
    store = InMemoryPersistenceStore()
    manager.set_persistence_store(store)
    try:
        whole_rt, whole = _routed(manager, app, None)
        _send(whole_rt, "S", 0, 96, 7, wide=True)
        plain = _rejoined(jax.device_get(whole_rt.query_runtimes["q"]._state))
        _send(whole_rt, "S", 96, 192, 7, wide=True)
        _send(whole_rt, "S", 192, 448, 70, wide=True)
        whole_rt.shutdown()

        rt, out = _routed(manager, app, shards)
        _send(rt, "S", 0, 96, 7, wide=True)
        q = rt.query_runtimes["q"]
        canonical = M.canonical_route_state(q)
        low, high = canonical["win"]["buf"]["volume"]
        assert low.dtype == high.dtype == np.uint32
        # key for key, the canonical rings are the unsharded run's (its
        # capacity may be another: compare the keys both hold)
        W = 3
        keys = min(canonical["win"]["total"].shape[0],
                   plain["win"]["total"].shape[0])
        assert keys >= 7
        joined = _rejoined(canonical)["win"]
        for name in ("volume", TS_KEY, "price", "volume?"):
            assert (joined["buf"][name][:keys * W].tobytes()
                    == plain["win"]["buf"][name][:keys * W].tobytes()), name
        assert (joined["total"][:keys] == plain["win"]["total"][:keys]).all()
        again = M._canonical_to_routed(q, q._route_layout, canonical)
        assert _bits(again) == _bits(jax.device_get(q._state))
        rt.persist()
        rt.shutdown()
        assert out.rows == whole.rows[:len(out.rows)]

        for other in (6 - shards, None):
            rt2, out2 = _routed(manager, app, other)
            rt2.restore_last_revision()
            _send(rt2, "S", 96, 192, 7, wide=True)
            # and ten times the keys: the routed growth re-lays the word
            # leaves out through the canonical form (ensure_routed_capacity)
            q2 = rt2.query_runtimes["q"]
            capacity = q2.key_capacity()
            _send(rt2, "S", 192, 448, 70, wide=True)
            assert q2.key_capacity() > capacity
            rt2.shutdown()
            assert out.rows + out2.rows == whole.rows, (
                f"{shards} shards restored into {other or 'no mesh'}")
    finally:
        manager.shutdown()
