"""Device-side repartitioning (parallel/mesh.device_route_query_step).

Round-6 contract: a keyed query's batch routing happens INSIDE the jitted
step (dense all_to_all under shard_map), the group-by key rides a dense-id
space SEPARATE from the partition key (the old host router's GK == PK
restriction is lifted), and emitted rows re-merge across shards into the
exact unsharded emission order — every test here asserts bit-identity
against an unsharded run of the same feed, through the full engine path
(junction -> process_batch -> CompletionPump -> callbacks).
"""

import re
import struct

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.stream.junction import FatalQueryError
from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore
from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

DISTINCT_GK_APP = """
    @app:name('routeapp')
    define stream S (symbol string, side string, price double, volume long);
    partition with (symbol of S)
    begin
      @info(name = 'q')
      from S#window.length(8)
      select symbol, side, avg(price) as ap, sum(volume) as tv
      group by side
      insert into Out;
    end;
"""


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


def _build(app):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app)
    c = Collector()
    rt.add_callback("Out", c)
    return m, rt, c


def _feed(rt, lo, hi, n_sym=13, n_side=5):
    rng = np.random.default_rng(42)
    syms = rng.integers(0, n_sym, 2000)
    sides = rng.integers(0, n_side, 2000)
    h = rt.get_input_handler("S")
    for i in range(lo, hi):
        h.send([f"SYM{syms[i]}", f"SIDE{sides[i]}",
                float(i % 17) + 0.25, int(i)])


def _spy_step_calls(q):
    """Record every ``(step, (state, cols, now))`` the runtime dispatches
    from here on."""
    seen, finish = [], q._finish_device_batch

    def spying_finish(step, cols, overflow_msg):
        def spy(*args):
            seen.append((step, args))
            return step(*args)

        return finish(spy, cols, overflow_msg)

    q._finish_device_batch = spying_finish
    return seen


def _run_unsharded(app, lo=0, hi=400):
    m, rt, c = _build(app)
    _feed(rt, lo, hi)
    m.shutdown()
    return c.rows


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_distinct_group_key_bit_identical(n_dev):
    """The case the host router hard-rejected: a partitioned query whose
    group-by key differs from the partition key runs sharded and yields
    output bit-identical to the unsharded run."""
    ref = _run_unsharded(DISTINCT_GK_APP)
    m, rt, c = _build(DISTINCT_GK_APP)
    q = rt.query_runtimes["q"]
    device_route_query_step(q, make_mesh(n_dev), rows_per_shard=256)
    assert q._route_layout.n == n_dev   # conftest pins an 8-device mesh
    _feed(rt, 0, 400)
    m.shutdown()
    assert len(ref) == 400
    assert c.rows == ref


def test_out_of_order_emission_remerges():
    """Keys are fed in an order that makes consecutive rows land on
    DIFFERENT shards every time (round-robin over the shard owners), so
    any merge that concatenates per-shard output instead of re-merging by
    the global emission-order key would interleave wrongly. Window
    evictions (EXPIRED rows) must also stay glued before the CURRENT row
    that displaced them."""
    app = """
        define stream S (k string, v double);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(2) select k, v, sum(v) as s insert into Out;
        end;
    """
    def feed(rt):
        h = rt.get_input_handler("S")
        # 16 keys; adjacent sends always hit different shards at n=4
        for i in range(240):
            h.send([f"P{i % 16}", float(i)])

    m1, rt1, c1 = _build(app)
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app)
    device_route_query_step(rt2.query_runtimes["q"], make_mesh(4),
                            rows_per_shard=256)
    feed(rt2)
    m2.shutdown()
    assert len(c1.rows) > 0
    assert c2.rows == c1.rows


def _bits(row):
    """A delivered row with its doubles as bit patterns: ``-0.0`` is not
    ``0.0`` here, and a NaN equals itself."""
    return tuple(struct.pack("<d", x) if isinstance(x, float) else x
                 for x in row)


@pytest.mark.parametrize("n_dev", [2, 4])
def test_remerge_with_holes_keeps_every_bit(n_dev):
    """``test_out_of_order_emission_remerges`` with what a sum across
    shards could lose: the ``having`` leaves holes among each shard's
    emitted rows, and the payload carries ``-0.0``, a NaN and integers
    above 2^53 (no double holds them) through the merge."""
    app = """
        define stream S (k string, d double, big long, v long);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(2)
          select k, d, big, sum(v) as s
          having s > 40
          insert into Out;
        end;
    """
    odd = [-0.0, float("nan"), 0.0, 1.0 / 3.0, -1e300, 5e-324]

    def feed(rt):
        h = rt.get_input_handler("S")
        for i in range(240):
            h.send([f"P{i % 16}", odd[i % len(odd)],
                    2 ** 53 + 1 + i * (2 ** 40 + 1), i % 37])

    m1, rt1, c1 = _build(app)
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app)
    device_route_query_step(rt2.query_runtimes["q"], make_mesh(n_dev),
                            rows_per_shard=256)
    feed(rt2)
    m2.shutdown()
    assert 0 < len(c1.rows) < 240          # the having dropped some
    assert {struct.pack("<d", r[1]) for r in c1.rows} >= {
        struct.pack("<d", -0.0), struct.pack("<d", 0.0)}
    assert any(r[1] != r[1] for r in c1.rows)
    assert [_bits(r) for r in c2.rows] == [_bits(r) for r in c1.rows]


@pytest.mark.parametrize("n_dev", [2, 4])
def test_merge_places_every_row_padded_tail_included(n_dev):
    """The egress permutation itself, over ALL ``n * L`` rows: what each
    shard scatters of its own rows and the shards sum (the doubles: what
    rides the sort) equals ``all_gather(column)[order]`` bit for bit,
    invalid rows and the padded tail included, with holes between a
    shard's valid rows."""
    from siddhi_tpu.parallel.mesh import KEY_AXIS, _ordered_merge

    L, big = 96, np.int64(2 ** 62)
    rng = np.random.default_rng(n_dev)
    okey = rng.permutation(n_dev * L).astype(np.int64)
    valid = rng.random(n_dev * L) < 0.6      # holes anywhere, not a tail
    okey = np.where(valid, okey, big)
    f64 = rng.standard_normal(n_dev * L)
    f64[:6] = [-0.0, np.nan, np.inf, 5e-324, 1e-30, -np.inf]
    f64[~valid] = -0.0                       # the tail's bits count too
    cols = {
        "__valid__": valid,
        "__type__": rng.integers(-128, 127, n_dev * L).astype(np.int8),
        "sym": rng.integers(-2 ** 31, 2 ** 31 - 1, n_dev * L,
                            dtype=np.int32),
        "big": rng.integers(2 ** 53 + 1, 2 ** 63 - 1, n_dev * L,
                            dtype=np.int64),
        "neg": -rng.integers(2 ** 53 + 1, 2 ** 63 - 1, n_dev * L,
                             dtype=np.int64),
        "f64": f64,
        "f32": rng.standard_normal(n_dev * L).astype(np.float32) * -0.0,
        "wide": rng.integers(0, 2 ** 62, (n_dev * L, 3), dtype=np.int64),
        "f64x2": np.stack([f64[::-1], -f64], axis=1),
    }

    got = jax.jit(jax.shard_map(
        lambda okey, cols: _ordered_merge(okey, cols, n_dev),
        mesh=make_mesh(n_dev), in_specs=(P(KEY_AXIS), P(KEY_AXIS)),
        out_specs=P(), check_vma=False))(okey, cols)
    order = np.argsort(okey, kind="stable")
    assert set(got) == set(cols)
    for name, col in cols.items():
        want, have = col[order], np.asarray(got[name])
        assert have.dtype == want.dtype and have.shape == want.shape, name
        assert have.tobytes() == want.tobytes(), name


_KEYED_APP = """
    define stream S (k string, v double, n long);
    partition with (k of S)
    begin
      @info(name = 'q')
      from S#window.length(4)
      select k, avg(v) as a, sum(n) as t insert into Out;
    end;
"""
_JOIN_APP = """
    define stream L (sym string, lv long);
    define stream R (sym string, rv long);
    partition with (sym of L, sym of R)
    begin
      @info(name = 'q') from L#window.length(8) join R#window.length(8)
        on L.lv > R.rv
        select L.sym as sym, L.lv as lv, R.rv as rv insert into Out;
    end;
"""
_LOC_DEF = re.compile(r'^(#loc\d+) = loc\("([^"]*)"')
_HLO_OP = re.compile(
    r'"?stablehlo\.(all_gather|gather|scatter|sort)"?\(([^)]*)\)')
_HLO_SIG = re.compile(r': \(([^()]*)\) -> (.*?) loc\((#loc\d+)\)\s*$')


def _scope_ops(text, scope):
    """``(op, n_operands, operand types, result types)`` of every
    all_gather, gather, scatter and sort of a lowered program (StableHLO
    with debug info) that was traced under ``scope``. An operation with a
    region (a scatter, a sort) carries its types on the line that closes
    it."""
    names = {m[1]: m[2] for m in map(_LOC_DEF.match, text.splitlines()) if m}
    found, open_ops = [], []
    for line in text.splitlines():
        stripped = line.strip()
        op = _HLO_OP.search(line)
        head = (op[1], op[2].count("%")) if op else None
        if stripped.startswith("})"):
            head = open_ops.pop()
        if stripped.endswith("({"):
            open_ops.append(head)
            continue
        sig = _HLO_SIG.search(line)
        if head and sig and f"{scope}/" in names.get(sig[3], ""):
            found.append((head[0], head[1], sig[1], sig[2]))
    assert not open_ops
    return found


@pytest.mark.parametrize("app,stream,row", [
    (_KEYED_APP, "S", lambda i: [f"P{i % 16}", float(i), i]),
    (_JOIN_APP, "L", lambda i: [f"P{i % 16}", i]),
], ids=["keyed_window", "join_side"])
def test_egress_moves_own_rows_only(app, stream, row):
    """The routed program as lowered: under ``siddhi.merge`` the only
    ``all_gather``s a row wide are the order keys' and the float64
    columns' (which ride the ONE sort), no ``gather`` reads an ``n *
    L``-row operand (no column is permuted by ``[order]``), and every
    scatter is one 32-bit operand with one update: a 64-bit value
    scattered as one operand becomes a two-plane scatter on the chip,
    which gets none of the compiler's sorted path (PERF.md section 5)."""
    m, rt, _c = _build(app)
    q = rt.query_runtimes["q"]
    device_route_query_step(q, make_mesh(4), rows_per_shard=64)
    seen = _spy_step_calls(q)
    h = rt.get_input_handler(stream)
    for i in range(32):
        h.send(row(i))
    step, (state, cols, now) = seen[-1]
    lowered = step._routed_raw.lower(
        state, cols, q._route_layout.device_luts(), now)
    m.shutdown()
    emitted = [a for k, a in lowered.out_info[1].items() if k != "__meta__"]
    rows = emitted[0].shape[0]                            # n * L
    doubles = sum(a.dtype == np.float64 for a in emitted)
    text = lowered.as_text(debug_info=True)
    ops = _scope_ops(text, "siddhi.merge")
    wide = f"tensor<{rows}x"
    gathered = sorted(o[3] for o in ops
                      if o[0] == "all_gather" and wide in o[3])
    assert gathered == ([f"tensor<{rows}xf64>"] * doubles
                        + [f"tensor<{rows}xi64>"]), gathered
    assert not [o for o in ops if o[0] == "gather"
                and o[2].startswith(wide)], ops
    # the rank's scatter, and one for every 32-bit word of a column that
    # does not ride the sort
    scatters = [o for o in ops if o[0] == "scatter"]
    words = sum(max(a.dtype.itemsize // 4, 1) for a in emitted
                if a.dtype != np.float64)
    assert len(scatters) == words + 1, scatters
    for _op, n_operands, operands, result in scatters:
        assert n_operands == 3, (operands, result)
        assert result == f"tensor<{rows}xi32>", (operands, result)
    # ONE sort: the order keys, the slots' numbers, the doubles
    sorts = [o for o in ops if o[0] == "sort"]
    assert [o[1] for o in sorts] == [2 + doubles], sorts


_WORDS_APP = """
    @app:playback
    define stream S (k string, d double, big long, v long);
    partition with (k of S)
    begin
      @info(name = 'q')
      from S#window.length(3)
      select k, d, big, v, sum(v) as s
      insert all events into Out;
    end;
"""
_I64 = np.iinfo(np.int64)
# the sign, a low word with its top bit set, both words all ones
_NASTY = np.array([
    0, -1, _I64.min, _I64.max, 0x80000000, 0xFFFFFFFF, -0x80000000,
    0x100000000, 0x7FFFFFFF80000000, -0x00000001FFFFFFFF, 2 ** 53 + 1,
], dtype=np.int64)
_ODD = np.array([-0.0, np.nan, 0.0, 1.0 / 3.0, -1e300, 5e-324])


def _feed_words(rt, hot):
    """Fifteen 16-row batches whose ``long`` columns and timestamps
    (epoch milliseconds, above 2^40) need both of their words; ``hot``
    of every five rows land on ONE key."""
    h = rt.get_input_handler("S")
    for lo in range(0, 240, 16):
        i = np.arange(lo, lo + 16)
        h.send_columns(
            {"k": np.array([f"P{0 if j % 5 < hot else j % 16}" for j in i],
                           dtype=object),
             "d": _ODD[i % len(_ODD)], "big": _NASTY[i % len(_NASTY)],
             "v": _NASTY[(3 * i + 1) % len(_NASTY)] >> 8},
            timestamps=1_791_000_000_000 + i)


@pytest.mark.parametrize("n_dev", [2, 4])
@pytest.mark.parametrize("rows_per_shard,hot", [(256, 0), (8, 4)],
                         ids=["within_quota", "over_quota_splits"])
def test_int64_columns_cross_the_exchange_as_words(n_dev, rows_per_shard, hot):
    """The exchange buckets a 64-bit integer column as its two 32-bit
    words (``mesh.py`` ``exch``): every ``long``, the timestamps and the
    row index arrive bit for bit, the ``double`` beside them (which is
    not split) too, also when a shard's quota forces the batch apart."""
    m1, rt1, c1 = _build(_WORDS_APP)
    _feed_words(rt1, hot)
    m1.shutdown()
    m2, rt2, c2 = _build(_WORDS_APP)
    q = rt2.query_runtimes["q"]
    device_route_query_step(q, make_mesh(n_dev), rows_per_shard=rows_per_shard)
    seen = _spy_step_calls(q)
    _feed_words(rt2, hot)
    m2.shutdown()
    assert (len(seen) > 15) == bool(hot)       # split, or one step a batch
    assert len(c1.rows) > 240                  # current and expired rows
    assert {r[2] for r in c1.rows} == set(_NASTY.tolist())
    assert [_bits(r) for r in c2.rows] == [_bits(r) for r in c1.rows]


def test_ingress_buckets_are_32_bit_scatters():
    """The routed program as lowered: under ``siddhi.route`` no bucket
    scatter has a 64-bit integer operand (two planes on the chip, no
    sorted path: PERF.md section 5); a ``long`` column, the timestamps
    and the row index go as two ``ui32`` words each. The exception,
    written down: a ``double`` cannot be split there and is ONE
    ``f64`` scatter."""
    m, rt, _c = _build(_WORDS_APP)
    q = rt.query_runtimes["q"]
    device_route_query_step(q, make_mesh(4), rows_per_shard=64)
    seen = _spy_step_calls(q)
    _feed_words(rt, 0)
    step, (state, cols, now) = seen[-1]
    text = step._routed_raw.lower(
        state, cols, q._route_layout.device_luts(), now).as_text(
            debug_info=True)
    m.shutdown()
    buckets = [o[3] for o in _scope_ops(text, "siddhi.route")
               if o[0] == "scatter"]
    int64s = [k for k, v in cols.items() if v.dtype == np.int64]
    assert sorted(int64s) == ["__ts__", "big", "v"]
    assert buckets.count("tensor<64xui32>") == 2 * (len(int64s) + 1)
    assert buckets.count("tensor<64xf64>") == 1
    assert not [b for b in buckets if "i64" in b], buckets
    assert len(buckets) == len(cols) + 1 + len(int64s) + 1, buckets


def test_oversized_batches_split_not_die():
    """Key skew past the per-pair exchange quota splits the batch
    host-side (prepare_routed_batches) instead of overflowing — output
    stays bit-identical."""
    app = """
        define stream S (k string, v long);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(4) select k, sum(v) as s insert into Out;
        end;
    """
    def feed(rt):
        h = rt.get_input_handler("S")
        for i in range(200):           # 80% of rows on one key/shard
            h.send([f"K{0 if i % 5 else i % 7}", i])

    m1, rt1, c1 = _build(app)
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app)
    device_route_query_step(rt2.query_runtimes["q"], make_mesh(4),
                            rows_per_shard=8)   # quota 2 rows per pair
    feed(rt2)
    m2.shutdown()
    assert c2.rows == c1.rows


def test_exchange_overflow_attribution():
    """A direct step call that bypasses the host precheck trips the
    device-side overflow flag; the meta check surfaces it as
    FatalQueryError naming rows_per_shard (the overflow_knob_msg
    convention), and the per-shard routed-row counts ride the meta."""
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY

    app = """
        define stream S (k string, v long);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(4) select k, sum(v) as s insert into Out;
        end;
    """
    m, rt, _c = _build(app)
    q = rt.query_runtimes["q"]
    device_route_query_step(q, make_mesh(4), rows_per_shard=8)
    h = rt.get_input_handler("S")
    for i in range(20):
        h.send([f"K{i % 6}", i])
    B = 32
    pk = np.zeros(B, np.int32)   # every row on one shard: pair count 8 > 2
    cols = {TS_KEY: np.arange(B, dtype=np.int64),
            TYPE_KEY: np.zeros(B, np.int8), VALID_KEY: np.ones(B, bool),
            "k": pk.astype(np.int64), "k?": np.zeros(B, bool),
            "v": np.arange(B, dtype=np.int64), "v?": np.zeros(B, bool),
            GK_KEY: pk, PK_KEY: pk}
    _st, out = q._step(q._state, cols, np.int64(99))
    meta = np.asarray(out["__meta__"])
    # layout = [ov, notify, count] + the runtime's declared instrument
    # spec (route_overflow, rows_0..3, residual, win_fill, groups —
    # observability/instruments.py); route overflow stays at lane 3
    spec = q.instrument_slots()
    assert [s.name for s in spec][:2] == ["route_overflow", "shard_rows"]
    assert meta.shape[0] == 3 + sum(s.width for s in spec)
    assert int(meta[3]) > 0                # route overflow flag
    with pytest.raises(FatalQueryError, match="rows_per_shard"):
        q.decode_meta_suffix(meta)
    m.shutdown()


def test_snapshot_cross_restore_between_layouts():
    """A revision persisted by a 2-shard routed runtime restores into
    4- and 8-shard routed runtimes AND into an unsharded one, and every
    continuation matches the continuous unsharded reference exactly —
    snapshots store canonical (unsharded) layout."""
    ref = _run_unsharded(DISTINCT_GK_APP, 0, 500)

    store = InMemoryPersistenceStore()
    m1, rt1, c1 = _build(DISTINCT_GK_APP)
    m1.set_persistence_store(store)
    device_route_query_step(rt1.query_runtimes["q"], make_mesh(2),
                            rows_per_shard=128)
    _feed(rt1, 0, 250)
    rt1.persist()
    m1.shutdown()
    head = len(c1.rows)

    for n_dev in (4, 8, None):
        m2, rt2, c2 = _build(DISTINCT_GK_APP)
        m2.set_persistence_store(store)
        if n_dev is not None:
            device_route_query_step(rt2.query_runtimes["q"], make_mesh(n_dev),
                                    rows_per_shard=128)
        rt2.restore_last_revision()
        _feed(rt2, 250, 500)
        m2.shutdown()
        assert c2.rows == ref[head:], f"restore into {n_dev or 'unsharded'}"


def test_grouped_no_window_routes_by_group_key():
    """Non-partitioned grouped aggregation (no window): rows route by the
    group key itself; no partition-key column exists at all."""
    app = """
        define stream S (k string, v long);
        @info(name = 'q')
        from S select k, sum(v) as s, count() as c group by k insert into Out;
    """
    def feed(rt):
        rng = np.random.default_rng(3)
        h = rt.get_input_handler("S")
        for i in range(300):
            h.send([f"G{int(rng.integers(0, 40))}", i])

    m1, rt1, c1 = _build(app)
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app)
    device_route_query_step(rt2.query_runtimes["q"], make_mesh(8),
                            rows_per_shard=256)
    feed(rt2)
    m2.shutdown()
    assert len(c1.rows) == 300
    assert c2.rows == c1.rows


def test_ineligible_runtimes_raise_cleanly():
    from siddhi_tpu.ops.expressions import CompileError

    app = """
        define stream S (k string, v double);
        @info(name = 'q')
        from S#window.length(4) select k, sum(v) as s insert into Out;
    """
    m, rt, _c = _build(app)
    with pytest.raises(CompileError, match="device routing"):
        # global (unpartitioned) window: ring semantics need every row
        device_route_query_step(rt.query_runtimes["q"], make_mesh(2),
                                rows_per_shard=64)
    m.shutdown()


def test_one_exchange_and_any_other_value_is_refused():
    """``exchange`` is what the configuration files still pass: ``None``
    and ``"all_to_all"`` install the same program, and any other value is
    refused by name, on every platform, before anything is installed."""
    from siddhi_tpu.ops.expressions import CompileError

    m, rt, _c = _build(DISTINCT_GK_APP)
    q = rt.query_runtimes["q"]
    with pytest.raises(CompileError, match="exchange = 'pallas_ring'"):
        device_route_query_step(q, make_mesh(2), rows_per_shard=64,
                                exchange="pallas_ring")
    assert q._route_layout is None
    m.shutdown()

    def routed_program(exchange):
        m, rt, _c = _build(DISTINCT_GK_APP)
        q = rt.query_runtimes["q"]
        device_route_query_step(q, make_mesh(2), rows_per_shard=64,
                                exchange=exchange)
        seen = _spy_step_calls(q)
        _feed(rt, 0, 8)
        step, (state, cols, now) = seen[-1]
        text = step._routed_raw.lower(
            state, cols, q._route_layout.device_luts(), now).as_text()
        m.shutdown()
        return text

    named = routed_program("all_to_all")
    assert "all_to_all" in named
    assert routed_program(None) == named


def test_purged_groups_do_not_leak_into_new_ones():
    """Regression (round-6 review): after reset_partition_keys prunes the
    keyer map, a LUT rebuild (re-install / growth / restore) compacts
    local gk ids — the freed slots are what NEW groups allocate next, and
    the relayout must NOT pour the purged groups' stale aggregate rows
    into them."""
    app = """
        define stream S (k string, g string, v long);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S select k, g, sum(v) as s group by g insert into Out;
        end;
    """
    def feed_phase1(rt):
        h = rt.get_input_handler("S")
        for i in range(24):
            h.send([f"K{i % 12}", f"G{i % 12}", 7])

    def feed_phase2(rt):
        h = rt.get_input_handler("S")
        for i in range(8):
            h.send([f"KN{i}", f"GN{i}", 100])

    def run(routed):
        m = SiddhiManager()
        rt = m.create_siddhi_app_runtime(app)
        c = Collector()
        rt.add_callback("Out", c)
        q = rt.query_runtimes["q"]
        if routed:
            device_route_query_step(q, make_mesh(2), rows_per_shard=64)
        feed_phase1(rt)
        # purge a few partition keys, then force a re-layout (the
        # re-install path exercises rebuild_gk + _canonical_to_routed)
        q.reset_partition_keys([0, 1])
        if routed:
            device_route_query_step(q, make_mesh(2), rows_per_shard=64)
        feed_phase2(rt)
        m.shutdown()
        return c.rows

    ref = run(False)
    got = run(True)
    # fresh groups must start from init (sum == 100), not inherit a
    # purged group's leftovers
    assert [r for r in got if r[0].startswith("KN")] == \
        [r for r in ref if r[0].startswith("KN")]
    assert got == ref


def test_gk_equals_pk_reinstall_and_cross_restore():
    """Regression (round-6 review follow-up): a partitioned query WITHOUT
    a distinct group-by (gk == pk, no LUT) must survive the relayout
    paths too — re-install onto a larger mesh mid-run, and snapshot
    cross-restore — translating its window-buffered key ids by the
    round-robin formula."""
    app = """
        @app:name('gkpk')
        define stream S (k string, v double);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(4) select k, sum(v) as s insert into Out;
        end;
    """
    def feed(rt, lo, hi):
        h = rt.get_input_handler("S")
        for i in range(lo, hi):
            h.send([f"P{i % 24}", float(i % 9)])

    m1, rt1, c1 = _build(app)
    feed(rt1, 0, 300)
    m1.shutdown()

    store = InMemoryPersistenceStore()
    m2, rt2, c2 = _build(app)
    m2.set_persistence_store(store)
    q = rt2.query_runtimes["q"]
    device_route_query_step(q, make_mesh(2), rows_per_shard=64)
    feed(rt2, 0, 100)
    device_route_query_step(q, make_mesh(8), rows_per_shard=64)  # re-install
    feed(rt2, 100, 200)
    rt2.persist()
    m2.shutdown()
    assert c2.rows == c1.rows[:len(c2.rows)]

    m3, rt3, c3 = _build(app)
    m3.set_persistence_store(store)
    device_route_query_step(rt3.query_runtimes["q"], make_mesh(4),
                            rows_per_shard=64)
    rt3.restore_last_revision()
    feed(rt3, 200, 300)
    m3.shutdown()
    assert c3.rows == c1.rows[len(c2.rows):]


def test_capacity_growth_relayouts_live_state():
    """Key dictionaries outgrowing n * localK mid-run force a routed
    relayout (canonical round trip) without output divergence."""
    app = """
        define stream S (k string, g string, v long);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(4)
          select k, g, sum(v) as s group by g insert into Out;
        end;
    """
    def feed(rt):
        h = rt.get_input_handler("S")
        for i in range(600):           # 60 pks x composite groups >> 16*n
            h.send([f"K{i % 60}", f"G{i % 7}", i])

    m1, rt1, c1 = _build(app)
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app)
    q = rt2.query_runtimes["q"]
    device_route_query_step(q, make_mesh(4), rows_per_shard=256)
    k0 = q.selector_plan.num_keys
    feed(rt2)
    m2.shutdown()
    assert q.selector_plan.num_keys > k0    # growth actually happened
    assert c2.rows == c1.rows
