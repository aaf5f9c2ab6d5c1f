"""A 64-bit integer column is scattered as its two 32-bit words.

``KeyedLengthWindowStage.apply`` writes an int64 ring column (``__ts__``,
a ``long`` attribute) by two one-operand 32-bit scatters, and the routed
exchange buckets an int64 column the same way (``tests/test_mesh_routing.py``
holds that half). The one-scatter int64 write the stage had before is kept
HERE as the reference: rings, emitted rows and snapshots must be bit-equal
to it. A ``double`` column is not split: it must come through untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore
from siddhi_tpu.ops import keyed_windows as KW
from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
from siddhi_tpu.ops.windows import CURRENT, EXPIRED

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


def _one_scatter_write(ring, slot, col):
    """The ring write as it was: ONE scatter, whatever the dtype."""
    return ring.at[slot].set(col, mode="drop")


def _bits(tree):
    """Every leaf as (dtype, shape, bytes): ``-0.0`` is not ``0.0`` here."""
    return [(str(np.asarray(x).dtype), np.shape(x), np.asarray(x).tobytes())
            for x in jax.tree_util.tree_leaves(tree)]


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


def _run_stage(write, W, K, batches):
    stage = KW.KeyedLengthWindowStage(W, _SPECS)
    saved, KW._ring_write = KW._ring_write, write
    try:
        step = jax.jit(stage.apply)
        state, outs = stage.init_state(K), []
        for n, cols in enumerate(batches):
            state, out = step(state, cols, {"current_time": EPOCH_MS + 1000 * n})
            outs.append(out)
        return _bits(state), _bits(outs)
    finally:
        KW._ring_write = saved


@pytest.mark.parametrize("W,K,B,kw", [
    pytest.param(5, 16, 64, {}, id="nasty_values_several_batches"),
    pytest.param(4, 8, 96, {"keys": [3]}, id="ring_wraps_inside_one_batch"),
    pytest.param(2, 4, 32, {}, id="evictee_inserted_earlier_in_the_batch"),
    pytest.param(3, 16, 64, {"invalid": 0.5}, id="invalid_and_expired_rows"),
    pytest.param(3, 64, 48, {"keys": [0, 17, 63]}, id="absent_keys"),
    pytest.param(7, 1, 40, {}, id="one_key"),
    pytest.param(1000, 3, 64, {}, id="ring_wider_than_the_batch"),
])
def test_ring_and_rows_equal_the_one_scatter_write(W, K, B, kw):
    rng = np.random.default_rng(W * 1000 + K)
    batches = [_batch(rng, B, K, n, **kw) for n in range(5)]
    ring, rows = _run_stage(KW._ring_write, W, K, batches)
    ref_ring, ref_rows = _run_stage(_one_scatter_write, W, K, batches)
    assert ring == ref_ring
    assert rows == ref_rows


def test_a_double_ring_column_is_one_scatter():
    """The exception, written down: a ``double`` has no bits to take on
    the chip, so its ring write stays one scatter of the float64 column;
    each int64 column is two uint32 ones."""
    stage = KW.KeyedLengthWindowStage(4, _SPECS)
    cols = _batch(np.random.default_rng(0), 16, 4, 0)
    jaxpr = jax.make_jaxpr(stage.apply)(
        stage.init_state(4), cols, {"current_time": 0})
    ring_writes = [str(e.outvars[0].aval.dtype) for e in jaxpr.eqns
                   if e.primitive.name == "scatter"
                   and e.outvars[0].aval.shape == (16,)]
    assert sorted(ring_writes) == sorted(
        ["uint32"] * 4 + ["float64", "bool", "bool", "int32", "int32", "int32"])


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


def _engine(write, scenario):
    """Run ``scenario(manager) -> (rows, runtime)`` with ``write`` as the
    ring write; returns the rows and the bits of every query's final
    state."""
    saved, KW._ring_write = KW._ring_write, write
    manager = SiddhiManager()
    manager.set_persistence_store(InMemoryPersistenceStore())
    try:
        rows, runtime = scenario(manager)
        state = {name: _bits(q._state)
                 for name, q in runtime.query_runtimes.items()}
        return rows, state
    finally:
        manager.shutdown()
        KW._ring_write = saved


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
def test_engine_rows_and_state_equal_the_one_scatter_write(scenario):
    rows, state = _engine(KW._ring_write, scenario)
    ref_rows, ref_state = _engine(_one_scatter_write, scenario)
    assert len(rows) > 90
    assert rows == ref_rows
    assert state == ref_state
