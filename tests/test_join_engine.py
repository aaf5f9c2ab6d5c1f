"""Device-resident join engine (siddhi_tpu/core/join/).

Eligible stream-stream window joins attach the engine and become
pipeline-eligible (entries ride the CompletionPump at depth >= 2 with
per-side notify attribution), join checkpoint/restore is exactly-once
with a NON-empty pipeline, snapshots cross-restore between the engine
and the legacy ``[W]`` ring at any pipeline depth, join overflow errors
name the exact config knob, join sides fuse into fan-out groups, and
partitioned keyed joins run mesh-sharded bit-identically.

Direct ``process_side_batch`` calls are the deterministic way to park
join batches in the pipeline: junction sends flush the pump before
returning (the synchronous-semantics contract), so a test that needs
entries IN FLIGHT feeds the runtime below the junction.
"""

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.event import HostBatch
from siddhi_tpu.core.stream.junction import FatalQueryError
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


APP = """
@app:name('japp')
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(64) join R#window.length(64)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into Out;
"""


def _manager(depth=2, mode="device", extra=None):
    m = SiddhiManager()
    cfg = {
        "siddhi_tpu.pipeline_depth": str(depth),
        "siddhi_tpu.join_engine": mode,
    }
    cfg.update(extra or {})
    m.set_config_manager(InMemoryConfigManager(cfg))
    return m


def _build(depth=2, mode="device", extra=None, store=None, app=APP):
    m = _manager(depth, mode, extra)
    if store is not None:
        m.set_persistence_store(store)
    rt = m.create_siddhi_app_runtime(app)
    c = Collector()
    rt.add_callback("Out", c)
    rt.start()
    return m, rt, c


def _side_batch(rt, stream, syms, vals, ts0=0):
    defn = rt.junctions[stream].definition
    col = "lv" if stream == "L" else "rv"
    return HostBatch.from_columns(
        {"sym": np.array(syms, dtype=object),
         col: np.asarray(vals, np.int64)},
        defn, rt.app_context.string_dictionary,
        timestamps=np.arange(ts0, ts0 + len(vals), dtype=np.int64))


def _feed(rt, lo, hi, seed=5):
    rng = np.random.default_rng(seed)
    picks, syms, vals = (rng.random(1000), rng.integers(0, 5, 1000),
                         rng.integers(0, 99, 1000))
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    for i in range(lo, hi):
        (hl if picks[i] < .5 else hr).send([f"S{syms[i]}", int(vals[i])])


# ------------------------------------------------------------ eligibility

def test_eligible_join_attaches_engine_and_is_pipeline_ok():
    m, rt, _c = _build()
    q = rt.query_runtimes["jq"]
    assert q.engine is not None and q.engine_reason is None
    assert q.pipeline_reason is None and q._pipeline_ok
    # the equality conjunct engaged the keyed probe on both sides
    assert all(q.engine.keyed.values())
    assert set(q.engine.rows) == {"left", "right"}
    m.shutdown()


def test_legacy_mode_keeps_joins_synchronous():
    m, rt, _c = _build(mode="legacy")
    q = rt.query_runtimes["jq"]
    assert q.engine is None
    assert "legacy" in (q.engine_reason or "")
    assert not q._pipeline_ok
    m.shutdown()


def test_ineligible_shapes_keep_legacy_with_reason():
    app = """
define stream L (sym string, lv long);
define table T (sym string, tv long);
@info(name='tj') from L join T on L.sym == T.sym
  select L.sym as sym, T.tv as tv insert into Out;
"""
    m = _manager()
    rt = m.create_siddhi_app_runtime(app)
    q = rt.query_runtimes["tj"]
    assert q.engine is None
    assert "shared-store" in q.engine_reason
    assert not q._pipeline_ok and "store" in q.pipeline_reason
    m.shutdown()


def test_float_key_keeps_broadcast_probe_but_still_pipelines():
    app = """
define stream L (k double, lv long);
define stream R (k double, rv long);
@info(name='fj') from L#window.length(64) join R#window.length(64)
  on L.k == R.k select L.lv as lv, R.rv as rv insert into Out;
"""
    m = _manager()
    rt = m.create_siddhi_app_runtime(app)
    q = rt.query_runtimes["fj"]
    # float equality must not key the probe (-0.0 == 0.0, NaN), but the
    # fused in-state step still attaches and pipelines
    assert q.engine is not None and not any(q.engine.keyed.values())
    assert q._pipeline_ok
    m.shutdown()


# ------------------------------------------------------- pump + sequence

def test_join_batches_ride_pump_at_depth2_and_drain_in_order():
    m, rt, c = _build(depth=4)
    q = rt.query_runtimes["jq"]
    pump = rt.app_context.completion_pump
    q.process_side_batch("right", _side_batch(rt, "R", ["A"], [100]))
    q.process_side_batch("left", _side_batch(rt, "L", ["A"], [1], ts0=1))
    q.process_side_batch("left", _side_batch(rt, "L", ["A"], [2], ts0=2))
    assert pump.inflight(q) == 3 and c.rows == []
    pump.flush_owner(q)
    # cross-stream dispatch order: right insert emitted nothing, the two
    # left probes emitted in order; drain verified the explicit sequence
    assert c.rows == [("A", 1, 100), ("A", 2, 100)]
    assert q._drain_seq == 3
    tel = rt.app_context.telemetry.snapshot()
    assert tel["counters"].get("join.seq_breaks", 0) == 0
    m.shutdown()


def test_checkpoint_restore_with_nonempty_pipeline_exactly_once():
    store = InMemoryPersistenceStore()
    m, rt, c = _build(depth=4, store=store)
    q = rt.query_runtimes["jq"]
    pump = rt.app_context.completion_pump
    q.process_side_batch("right", _side_batch(rt, "R", ["A"], [100]))
    q.process_side_batch("left", _side_batch(rt, "L", ["A"], [1], ts0=1))
    assert pump.inflight(q) == 2 and c.rows == []
    rev = rt.persist()
    # the in-flight batches emitted exactly once, inside the barrier
    assert c.rows == [("A", 1, 100)]
    assert pump.inflight(q) == 0
    # post-checkpoint in-flight work is discarded by the rollback
    q.process_side_batch("left", _side_batch(rt, "L", ["A"], [7], ts0=2))
    assert pump.inflight(q) == 1
    rt.restore_revision(rev)
    assert pump.inflight(q) == 0
    assert c.rows == [("A", 1, 100)]      # no loss, no double emission
    # restored build state: both windows hold their pre-checkpoint rows
    rt.get_input_handler("L").send(["A", 9])
    assert c.rows[-1] == ("A", 9, 100)
    m.shutdown()


# ------------------------------------------------------ snapshot layouts

@pytest.mark.parametrize("dst_mode,dst_depth", [
    ("device", 1), ("device", 4), ("device", 8), ("legacy", 8)])
def test_cross_restore_partitioned_and_legacy_layouts(dst_mode, dst_depth):
    """A revision captured under the engine (depth 2) restores into the
    engine at pipeline depths 1, 4 and 8 AND into the legacy path — and
    the continuation is bit-identical to an uninterrupted run (snapshots
    store only the canonical [W] ring layout; the host row mirrors
    rebuild at restore)."""
    m, rt, c = _build()
    _feed(rt, 0, 120)
    ref = list(c.rows)
    m.shutdown()

    store = InMemoryPersistenceStore()
    m1, rt1, c1 = _build(store=store)
    _feed(rt1, 0, 60)
    rev = rt1.persist()
    head = list(c1.rows)
    m1.shutdown()

    m2, rt2, c2 = _build(depth=dst_depth, mode=dst_mode, store=store)
    rt2.restore_revision(rev)
    _feed(rt2, 60, 120)
    m2.shutdown()
    assert head + c2.rows == ref


def test_legacy_snapshot_restores_into_engine():
    store = InMemoryPersistenceStore()
    m, rt, c = _build(mode="legacy", store=store)
    _feed(rt, 0, 60)
    rev = rt.persist()
    head = list(c.rows)
    m.shutdown()

    m2, rt2, c2 = _build(mode="device", store=store)
    _feed_ref_m, _rt_ref, c_ref = _build()
    _feed(_rt_ref, 0, 120)
    _feed_ref_m.shutdown()
    rt2.restore_revision(rev)
    _feed(rt2, 60, 120)
    m2.shutdown()
    assert head + c2.rows == c_ref.rows


# ------------------------------------------------------- overflow knobs

def test_adaptive_growth_absorbs_skew_bit_identically():
    """A hot key that holds most of both windows grows the keyed probe's
    output width pre-dispatch (a width its pairs need) and the output
    stays bit-identical to the legacy path."""
    skew_app = APP.replace("length(64)", "length(32)")

    def run(mode):
        m, rt, c = _build(app=skew_app, mode=mode)
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        rng = np.random.default_rng(17)
        for i in range(120):                     # ~70% one hot key
            sym = "HOT" if rng.random() < .7 else f"S{rng.integers(0, 4)}"
            (hl if rng.random() < .5 else hr).send([sym, int(i)])
        # a batch of 64 hot rows meets ~20 hot rows of the other side
        hl.send_columns(
            {"sym": np.array(["HOT"] * 64, dtype=object),
             "lv": np.arange(64, dtype=np.int64)})
        rt.app_context.completion_pump.flush()
        if mode == "device":
            q = rt.query_runtimes["jq"]
            assert q.engine.hot_key_rows("right") > 16
            assert q.engine.width["left"] > 1024, q.engine.width
        m.shutdown()
        return c.rows

    assert run("device") == run("legacy")


def test_window_capacity_overflow_names_capacity_knob():
    app = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.time(10 sec) join R#window.length(8)
  on L.sym == R.sym
  select L.sym as sym, R.rv as rv insert into Out;
"""
    rows = {"sym": np.array([f"S{i}" for i in range(40)], dtype=object),
            "lv": np.arange(40, dtype=np.int64)}
    # the legacy path's ring is the knob's size: the 40 rows (wall-clock
    # timestamps, all live inside the 10 s window) overflow its 16 slots
    m, rt, _c = _build(extra={"siddhi_tpu.window_capacity": "16"}, app=app,
                       mode="legacy")
    h = rt.get_input_handler("L")
    with pytest.raises(FatalQueryError, match="window_capacity"):
        h.send_columns(rows)
        rt.app_context.completion_pump.flush()
        h.send(["A", 99])
    m.shutdown()
    # the device engine's time ring grows with its content instead, to
    # the power of two that holds it and at least eight times its size,
    # and names no knob
    m, rt, c = _build(extra={"siddhi_tpu.window_capacity": "16"}, app=app)
    q = rt.query_runtimes["jq"]
    assert q.engine is not None and q.engine.plans["left"].grows
    rt.get_input_handler("L").send_columns(rows)
    rt.get_input_handler("R").send(["S3", 7])
    rt.app_context.completion_pump.flush()
    assert q.sides["left"].window_stage.capacity == 128
    assert c.rows == [("S3", 7)]
    assert "window_capacity" not in q.overflow_knob_msg(32)
    m.shutdown()


def test_overflow_knob_msg_decodes_bitmask():
    m, rt, _c = _build()
    q = rt.query_runtimes["jq"]
    assert "window_capacity" in q.overflow_knob_msg(1)
    assert "index_probe_width" in q.overflow_knob_msg(2)
    assert "distinct_values_capacity" in q.overflow_knob_msg(8)
    # the engine sizes these two itself: a bug, and no knob to name
    for code in (16, 32):
        msg = q.overflow_knob_msg(code)
        assert "engine bug" in msg and "raise" not in msg, msg
    both = q.overflow_knob_msg(9)
    assert "window_capacity" in both and "distinct_values_capacity" in both
    m.shutdown()


# ------------------------------------------------------------- fan-out

FANOUT_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='f0') from L[lv > 5] select sym, lv insert into F0;
@info(name='jq') from L#window.length(16) join R#window.length(16)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into Out;
@info(name='f1') from L select sym, lv * 2 as dbl insert into F1;
"""


def _run_fanout(fused: bool):
    m = _manager(extra={"siddhi_tpu.fuse_fanout": "1" if fused else "0"})
    rt = m.create_siddhi_app_runtime(FANOUT_APP)
    outs = {s: Collector() for s in ("F0", "Out", "F1")}
    for s, c in outs.items():
        rt.add_callback(s, c)
    rt.start()
    if fused:
        (group,) = rt.fused_fanout_groups
        assert [mm.name for mm in group.members] == ["f0", "jq.left", "f1"]
    rng = np.random.default_rng(3)
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    for _ in range(80):
        s = f"S{rng.integers(0, 4)}"
        ((hl, "lv") if rng.random() < 0.6 else (hr, "rv"))[0].send(
            [s, int(rng.integers(0, 20))])
    m.shutdown()
    return {s: c.rows for s, c in outs.items()}


def test_join_side_fuses_on_shared_junction_bit_identical():
    ref = _run_fanout(False)
    got = _run_fanout(True)
    assert got == ref
    assert ref["Out"]


def test_fused_join_side_overflow_names_partition_knob():
    """The fused drain decodes the join side's overflow BITMASK: a
    selector value-table overflow (distinctCount) inside a fan-out group
    must name distinct_values_capacity, not default to window
    capacity."""
    app = FANOUT_APP.replace(
        "select L.sym as sym, L.lv as lv, R.rv as rv insert into Out;",
        "select distinctCount(L.lv) as d insert into Out;")
    m = _manager(extra={"siddhi_tpu.fuse_fanout": "1"})
    rt = m.create_siddhi_app_runtime(app)
    rt.start()
    assert rt.fused_fanout_groups
    q = rt.query_runtimes["jq"]
    assert q.engine is not None
    for spec in q.selector_plan.specs:
        spec.distinct_capacity = 4   # shrink the value table
    rt.get_input_handler("R").send(["A", 1])
    h = rt.get_input_handler("L")
    with pytest.raises(FatalQueryError, match="distinct_values_capacity"):
        # 10 distinct values of one key meet the right row: > 4 slots
        h.send_columns(
            {"sym": np.array(["A"] * 10, dtype=object),
             "lv": np.arange(10, dtype=np.int64)},
            timestamps=np.arange(10, dtype=np.int64))
        rt.app_context.completion_pump.flush()
        h.send(["A", 99])
    m.shutdown()


def test_self_join_sides_do_not_fuse():
    app = """
define stream L (sym string, lv long);
@info(name='sj') from L#window.length(8) as a join L#window.length(8) as b
  on a.sym == b.sym
  select a.sym as sym, b.lv as lv insert into Out;
"""
    m = _manager()
    rt = m.create_siddhi_app_runtime(app)
    # both proxies would share one state pytree in one fused step
    assert not rt.fused_fanout_groups
    m.shutdown()


# -------------------------------------------------------- mesh-sharded

PART_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
partition with (sym of L, sym of R)
begin
  @info(name='pj') from L#window.length(8) join R#window.length(8)
    on L.lv > R.rv
    select L.sym as sym, L.lv as lv, R.rv as rv insert into Out;
end;
"""


def _feed_part(rt, lo, hi, n_sym=9, seed=11):
    rng = np.random.default_rng(seed)
    picks, syms, vals = (rng.random(1000), rng.integers(0, n_sym, 1000),
                         rng.integers(0, 30, 1000))
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    for i in range(lo, hi):
        (hl if picks[i] < .5 else hr).send([f"S{syms[i]}", int(vals[i])])


@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_routed_partitioned_join_bit_identical(n_dev):
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m, rt, c = _build(app=PART_APP)
    _feed_part(rt, 0, 200)
    ref = list(c.rows)
    m.shutdown()
    assert ref

    m2, rt2, c2 = _build(app=PART_APP)
    q = rt2.query_runtimes["pj"]
    device_route_query_step(q, make_mesh(n_dev), rows_per_shard=256)
    assert q._route_layout.n == n_dev
    _feed_part(rt2, 0, 200)
    m2.shutdown()
    assert c2.rows == ref


def test_routed_join_cross_restores_to_unsharded():
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m, rt, c = _build(app=PART_APP)
    _feed_part(rt, 0, 160)
    ref = list(c.rows)
    m.shutdown()

    store = InMemoryPersistenceStore()
    m1, rt1, c1 = _build(app=PART_APP, store=store)
    q = rt1.query_runtimes["pj"]
    device_route_query_step(q, make_mesh(2), rows_per_shard=256)
    _feed_part(rt1, 0, 80)
    rev = rt1.persist()
    head = list(c1.rows)
    m1.shutdown()

    m2, rt2, c2 = _build(app=PART_APP, store=store)
    rt2.restore_revision(rev)
    _feed_part(rt2, 80, 160)
    m2.shutdown()
    assert head + c2.rows == ref


def test_route_ineligibility_reasons_for_joins():
    from siddhi_tpu.parallel.mesh import route_ineligibility

    m, rt, _c = _build()      # non-partitioned engine join
    assert "non-partitioned" in route_ineligibility(
        rt.query_runtimes["jq"])
    m.shutdown()
    m2, rt2, _c2 = _build(app=PART_APP)
    assert route_ineligibility(rt2.query_runtimes["pj"]) is None
    m2.shutdown()


# ------------------------------------------------------------- metrics

def test_join_metrics_families_on_prometheus_surface():
    from siddhi_tpu.observability.export import prometheus_text

    m, rt, _c = _build()
    rt.get_input_handler("L").send(["A", 1])
    rt.get_input_handler("R").send(["A", 2])
    rt.get_input_handler("R").send(["A", 3])
    text = prometheus_text(m)
    assert "siddhi_join_window_rows{" in text
    assert "siddhi_join_hot_key_rows{" in text
    assert "siddhi_join_probe_ms{" in text
    assert "siddhi_join_insert_ms{" in text
    # the live rows of each side, and of its hottest key, from the host
    # mirrors
    import re

    rows = {}
    for line in text.splitlines():
        mm = re.match(r'siddhi_join_(window|hot_key)_rows\{.*side="(\w+)"'
                      r'.*\} (\S+)', line)
        if mm:
            rows[(mm.group(1), mm.group(2))] = float(mm.group(3))
    assert rows == {("window", "left"): 1, ("window", "right"): 2,
                    ("hot_key", "left"): 1, ("hot_key", "right"): 2}
    m.shutdown()
