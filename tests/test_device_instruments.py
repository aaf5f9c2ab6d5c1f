"""Device telemetry plane (observability/instruments.py): instrument
slots ride the meta vector — zero extra pulls.

The load-bearing acceptance set: per-batch device truth (window ring
fill, join matches, NFA runs, routed-row skew) lands in
``device.<query>.<slot>`` telemetry off the meta pull that already
happens; a /metrics scrape performs ZERO device pulls
(transfer-guard-verified, including the join window gauges, which read
the engine's host mirrors); with the knob off the meta layouts are
bit-for-bit the pre-round-9 ones; and ``journey.critical_path_report()``
names the saturated device structure for a PLANTED bottleneck
(near-full keyed window)."""

import jax
import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.observability import export, instruments, journey


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


@pytest.fixture(autouse=True)
def _off_after():
    yield
    journey.disable(force=True)
    instruments.disable(force=True)


def _manager(extra=None):
    m = SiddhiManager()
    cfg = {"siddhi_tpu.pipeline_depth": "2"}
    cfg.update(extra or {})
    m.set_config_manager(InMemoryConfigManager(cfg))
    return m


JOIN_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(64) join R#window.length(64)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""


def _feed_join(rt, n=24, keys=5):
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    for i in range(n):
        hl.send([f"S{i % keys}", i])
        hr.send([f"S{i % keys}", 100 + i])


# --------------------------------------------------- slots ride the meta


def test_join_matches_instrument_feeds_counter_and_histogram():
    """The pairs each join step matched ride the meta: the drained lane,
    the ``device.<query>.join_matches`` histogram and the
    ``join.<query>.matches`` counter agree with the rows emitted."""
    m = _manager()
    rt = m.create_siddhi_app_runtime(JOIN_APP)
    c = Collector()
    rt.add_callback("JOut", c)
    _feed_join(rt)
    q = rt.query_runtimes["jq"]
    assert "join_matches" in q._instr_last
    snap = rt.app_context.telemetry.snapshot()
    assert snap["counters"]["join.jq.matches"] == len(c.rows) > 0
    assert "device.jq.join_matches" in snap.get("histograms", {})
    m.shutdown()


def test_scrape_zero_device_pulls_under_transfer_guard():
    """A full /metrics scrape with live join + instrument gauges makes
    NO device pull: it completes under jax's transfer guard and the
    guarded families read real numbers, not the NaN a guarded gauge
    closure would produce."""
    m = _manager()
    rt = m.create_siddhi_app_runtime(JOIN_APP)
    rt.add_callback("JOut", Collector())
    _feed_join(rt)
    with jax.transfer_guard("disallow"):
        text = export.prometheus_text(m)
    assert "siddhi_join_window_rows" in text
    assert "siddhi_device_instrument" in text
    values = []
    for line in text.splitlines():
        if line.startswith(("siddhi_join_window_rows",
                            "siddhi_device_instrument{")):
            assert not line.endswith("NaN"), f"guarded gauge pulled: {line}"
            values.append(float(line.rsplit(" ", 1)[1]))
    assert values and sum(values) > 0
    m.shutdown()


def test_occupancy_host_mirror_fallback_with_knob_off():
    """Instruments off: the join window gauges answer from the host row
    mirrors (zero device pulls; exact for length rings)."""
    m = _manager({"siddhi_tpu.profile_device_instruments": "false"})
    rt = m.create_siddhi_app_runtime(JOIN_APP)
    rt.add_callback("JOut", Collector())
    _feed_join(rt)
    q = rt.query_runtimes["jq"]
    assert not q._instr_last        # nothing drained
    with jax.transfer_guard("disallow"):
        rows = q.engine.window_rows("left")
        hot = q.engine.hot_key_rows("left")
    assert rows == 24               # every inserted row is live (W=64)
    assert hot == 5                 # S0..S3 hold 5 rows each, S4 four
    m.shutdown()


def test_knob_off_meta_layouts_bit_for_bit():
    """profile_device_instruments: false reproduces the pre-round-9
    layouts exactly — [3] plain, [4] engine join (prefix + seq)."""
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY

    m = _manager({"siddhi_tpu.profile_device_instruments": "false"})
    rt = m.create_siddhi_app_runtime(JOIN_APP + """
@info(name='pq') from L#window.length(8) select sym, lv insert into POut;
""")
    jq, pq = rt.query_runtimes["jq"], rt.query_runtimes["pq"]
    assert pq.instrument_slots() == []
    assert [s.name for s in jq.instrument_slots()] == ["seq"]
    B = 4
    cols = {TS_KEY: np.arange(B, dtype=np.int64),
            TYPE_KEY: np.zeros(B, np.int8), VALID_KEY: np.ones(B, bool),
            "sym": np.zeros(B, np.int64), "sym?": np.zeros(B, bool),
            "lv": np.arange(B, dtype=np.int64), "lv?": np.zeros(B, bool),
            GK_KEY: np.zeros(B, np.int32)}
    _st, out = jax.jit(pq.build_step_fn())(pq._init_state(), dict(cols),
                                           np.int64(0))
    assert np.asarray(out["__meta__"]).shape == (3,)
    import jax.numpy as jnp

    _st, out = jax.jit(jq.build_side_step_fn("left"))(
        jq._init_state(), {}, jnp.zeros((1,), bool), dict(cols),
        np.int64(0))
    assert np.asarray(out["__meta__"]).shape == (4,)
    m.shutdown()


def test_refcounted_process_collector():
    """The knob holds one refcount on the process collector for the
    app's lifetime, like profile_journeys."""
    m = _manager()
    rt = m.create_siddhi_app_runtime(
        "define stream S (sym string, v long);\n"
        "@info(name='q') from S select sym, v insert into Out;")
    rt.start()
    assert instruments.enabled()
    m.shutdown()
    assert not instruments.enabled()


def test_fused_members_decode_their_own_rows():
    """A fused fan-out group stacks per-member suffixes (zero-padded);
    each member's drain decodes its own spec."""
    m = _manager()
    rt = m.create_siddhi_app_runtime("""
define stream S (sym string, v long);
@info(name='g1') from S#window.length(8) select sym, v insert into O1;
@info(name='g2') from S select sym, v insert into O2;
""")
    c1, c2 = Collector(), Collector()
    rt.add_callback("O1", c1)
    rt.add_callback("O2", c2)
    h = rt.get_input_handler("S")
    for i in range(20):
        h.send([f"K{i % 3}", i])
    assert rt.fused_fanout_groups, "shape did not fuse"
    g1 = rt.query_runtimes["g1"]
    assert g1._instr_last["win_fill"].tolist() == [8]
    assert len(c1.rows) and len(c2.rows) == 20
    m.shutdown()


# ------------------------------------------- planted saturated structures


def test_report_names_near_full_keyed_window():
    """A keyed length window fed past W rows per key reports win_fill
    == W — the report names the window ring at ratio 1.0."""
    m = _manager()
    rt = m.create_siddhi_app_runtime("""
define stream S (k string, v double);
partition with (k of S)
begin
  @info(name='kq')
  from S#window.length(8) select k, v, sum(v) as s insert into Out;
end;
""")
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    h.send(["A", 0.0])            # warm
    journey.enable()
    try:
        for i in range(20):
            h.send(["A", float(i)])
        rep = journey.critical_path_report(m)
        q = rep["apps"][rt.name]["queries"]["kq"]
        st = q.get("device_structure")
        assert st is not None, q
        # log-bucket histogram p99 carries ~3.5% relative error
        assert st["slot"] == "win_fill" and st["ratio"] >= 0.95, st
        assert "window ring fill" in st["text"]
    finally:
        journey.disable(force=True)
    m.shutdown()


def test_device_bottleneck_verdict_carries_structure():
    """When the device stage IS the bottleneck, the verdict line names
    the saturated structure (unit-level: synthetic stage histograms +
    instrument signals through _query_report)."""
    dev = {"win_fill": {"snap": {"p99": 15.5, "count": 10, "sum": 150.0},
                        "capacity": 16.0}}
    stages = {
        "pack": {"service": {"sum": 5.0, "count": 10, "p99": 0.6}},
        "device": {"service": {"sum": 400.0, "count": 10, "p99": 45.0}},
        "emit": {"service": {"sum": 3.0, "count": 10, "p99": 0.4}},
    }
    rep = journey._query_report("app", "jq", stages, device_slots=dev)
    assert rep["bottleneck"]["stage"] == "device"
    assert "window ring fill" in rep["bottleneck"]["structure"]
    assert "0.97 of window capacity" in rep["bottleneck"]["structure"]
    assert rep["device_structure"]["ratio"] == pytest.approx(15.5 / 16.0,
                                                             abs=1e-3)


# ----------------------------------------------- routed + NFA instruments


def test_routed_instruments_aggregate_across_shards():
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m = _manager()
    rt = m.create_siddhi_app_runtime("""
define stream S (k string, v double);
partition with (k of S)
begin
  @info(name='rq')
  from S#window.length(4) select k, v, sum(v) as s insert into Out;
end;
""")
    rt.add_callback("Out", Collector())
    q = rt.query_runtimes["rq"]
    device_route_query_step(q, make_mesh(4), rows_per_shard=64)
    h = rt.get_input_handler("S")
    for i in range(80):
        h.send([f"P{i % 8}", float(i)])
    last = q._instr_last
    assert last["shard_rows"].shape == (4,)
    assert last["win_fill"].tolist() == [4]     # hottest key's ring full
    assert int(last["route_residual"][0]) <= 64
    assert int(last["groups"][0]) >= 1
    caps = q._instr_caps
    assert caps["win_fill"] == 4.0
    m.shutdown()


def test_nfa_runs_instrument():
    m = _manager()
    rt = m.create_siddhi_app_runtime("""
define stream A (sym string, p double);
@info(name='nq') from every e1=A[p > 10] -> e2=A[p > e1.p]
  select e1.sym as s1, e2.sym as s2 insert into Out;
""")
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("A")
    for i in range(10):
        h.send([f"N{i}", 11.0 + i])
    q = rt.query_runtimes["nq"]
    assert "nfa_runs" in q._instr_last
    assert int(q._instr_last["nfa_runs"][0]) >= 1
    assert q._instr_caps["nfa_runs"] > 0
    m.shutdown()
