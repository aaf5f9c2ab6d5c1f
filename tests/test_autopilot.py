"""Autopilot (siddhi_tpu/autopilot/): closed-loop controller contracts.

The load-bearing claims, each tested here:

- default OFF is bit-identical and registers nothing;
- dry_run logs full decisions WITHOUT actuating;
- hysteresis: cooldown blocks repeat moves, oscillation damping blocks
  direction reversals, compile-storm backoff freezes every knob;
- LIVE actuation safety — depth / ingest-pool / fusion / shard knobs
  flipped at batch boundaries under live ingest stay bit-identical, and
  a persist/restore straddling a reshard actuation is exactly-once;
- the decision log, ``siddhi_autopilot_*`` metric families and
  ``GET /autopilot`` agree about what happened.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.autopilot import ACTUATORS, AutopilotController
from siddhi_tpu.autopilot.actuators import DOWN, UP
from siddhi_tpu.autopilot.policy import Policy, RULES
from siddhi_tpu.autopilot.signals import SignalSnapshot, collect
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


MULTI_APP = """
@app:name('apapp')
define stream S (sym string, v long);
@info(name='q1') from S select sym, v * 2 as d insert into Out;
@info(name='q2') from S select sym, v + 7 as p insert into Out2;
@info(name='q3') from S select sym, sum(v) as s group by sym insert into Out3;
"""


def _build(app=MULTI_APP, extra=None):
    m = SiddhiManager()
    cfg = {"siddhi_tpu.ingest_split": "8"}
    cfg.update(extra or {})
    m.set_config_manager(InMemoryConfigManager(cfg))
    rt = m.create_siddhi_app_runtime(app)
    sinks = {}
    for s in ("Out", "Out2", "Out3"):
        sinks[s] = Collector()
        rt.add_callback(s, sinks[s])
    rt.start()
    return m, rt, sinks


def _chunks(n_chunks=10, rows=24, seed=11):
    rng = np.random.default_rng(seed)
    out = []
    t = 0
    for _ in range(n_chunks):
        syms = rng.integers(0, 6, rows)
        vals = rng.integers(0, 100, rows)
        out.append((
            {"sym": np.array([f"S{s}" for s in syms], dtype=object),
             "v": vals.astype(np.int64)},
            np.arange(t, t + rows, dtype=np.int64)))
        t += rows
    return out


def _rows(sinks):
    return {s: list(c.rows) for s, c in sinks.items()}


# -------------------------------------------------------- default off


def test_default_off_registers_nothing_and_is_bit_identical():
    ctl = AutopilotController.instance()

    def run(extra):
        m, rt, sinks = _build(extra=extra)
        assert rt.name not in ctl.report()["apps"]
        for data, ts in _chunks():
            rt.get_input_handler("S").send_columns(data, timestamps=ts)
        out = _rows(sinks)
        m.shutdown()
        return out

    assert run(None) == run({"siddhi_tpu.autopilot": "off"})


def test_autopilot_knob_parses_and_registers_on_start():
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build(extra={
        "siddhi_tpu.autopilot": "dry_run",
        "siddhi_tpu.autopilot_interval_s": "30",
        "siddhi_tpu.autopilot_cooldown_s": "1.5",
    })
    try:
        assert rt.app_context.autopilot == "dry_run"
        rep = ctl.report(rt.name)["apps"][rt.name]
        assert rep["mode"] == "dry_run"
        assert rep["interval_s"] == 30.0
        assert rep["cooldown_s"] == 1.5
    finally:
        m.shutdown()
    # shutdown unregistered it
    assert rt.name not in ctl.report()["apps"]


def test_bad_autopilot_mode_rejected():
    from siddhi_tpu.core.util.knobs import KNOBS

    with pytest.raises(Exception, match="autopilot"):
        KNOBS["autopilot"].parse("sideways")
    m, rt, _sinks = _build()
    try:
        with pytest.raises(ValueError):
            rt.enable_autopilot(mode="off")
    finally:
        m.shutdown()


# ---------------------------------------------- dry_run vs on, per tick


def _device_bound_collect(rt):
    """Real signals with a synthetic device-bound bottleneck planted —
    deterministic rule trigger without having to manufacture load."""
    sig = collect(rt)
    sig.bottlenecks = {"q1": {"stage": "device", "kind": "service",
                              "utilization": 0.9}}
    sig.jit_compiles = 0
    return sig


def test_dry_run_logs_decisions_without_actuating(monkeypatch):
    from siddhi_tpu.autopilot import signals as sigmod

    monkeypatch.setattr(sigmod, "collect", _device_bound_collect)
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build()
    try:
        rt.enable_autopilot(mode="dry_run", interval_s=3600, cooldown_s=0.1)
        depth0 = rt.app_context.pipeline_depth
        entries = ctl.tick(rt.name, now=1000.0)
        dec = [e for e in entries if e["actuator"] == "pipeline_depth"]
        assert dec and dec[0]["applied"] is False
        assert dec[0]["mode"] == "dry_run"
        assert dec[0]["direction"] == "up"
        assert dec[0]["reason"] == "device_bound"
        assert rt.app_context.pipeline_depth == depth0   # untouched
        # the decision rode the log and the counter
        log = ctl.report(rt.name)["apps"][rt.name]["decisions"]
        assert any(e["actuator"] == "pipeline_depth" for e in log)
        counters = rt.app_context.telemetry.snapshot()["counters"]
        assert counters[
            "autopilot.decisions.pipeline_depth.up.device_bound"] >= 1
    finally:
        m.shutdown()


def test_on_mode_actuates_and_cooldown_blocks_repeat(monkeypatch):
    from siddhi_tpu.autopilot import signals as sigmod

    monkeypatch.setattr(sigmod, "collect", _device_bound_collect)
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build()
    try:
        rt.enable_autopilot(mode="on", interval_s=3600, cooldown_s=5.0)
        depth0 = rt.app_context.pipeline_depth
        entries = ctl.tick(rt.name, now=1000.0)
        dec = [e for e in entries if e["actuator"] == "pipeline_depth"]
        assert dec and dec[0]["applied"] is True
        assert dec[0]["old"] == depth0 and dec[0]["new"] == depth0 + 1
        assert rt.app_context.pipeline_depth == depth0 + 1
        # inside the cooldown window the same rule is logged, blocked
        entries = ctl.tick(rt.name, now=1001.0)
        dec = [e for e in entries if e["actuator"] == "pipeline_depth"]
        assert dec and dec[0]["applied"] is False
        assert dec[0]["blocked"] == "cooldown"
        assert rt.app_context.pipeline_depth == depth0 + 1
        # past the cooldown it moves again
        ctl.tick(rt.name, now=1006.0)
        assert rt.app_context.pipeline_depth == depth0 + 2
    finally:
        m.shutdown()


def test_compile_storm_freezes_actuation(monkeypatch):
    from siddhi_tpu.autopilot import signals as sigmod

    compiles = {"n": 0}

    def storm_collect(rt):
        sig = _device_bound_collect(rt)
        sig.jit_compiles = compiles["n"]
        return sig

    monkeypatch.setattr(sigmod, "collect", storm_collect)
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build()
    try:
        rt.enable_autopilot(mode="on", interval_s=3600, cooldown_s=0.1)
        depth0 = rt.app_context.pipeline_depth
        ctl.tick(rt.name, now=1000.0)      # baseline compile count
        compiles["n"] = 5                  # storm: count climbing
        assert ctl.tick(rt.name, now=1001.0) == []
        compiles["n"] = 9
        assert ctl.tick(rt.name, now=1002.0) == []
        rep = ctl.report(rt.name)["apps"][rt.name]
        assert rep["freezes"] >= 2 and rep["frozen"] is True
        # count stops climbing -> actuation resumes next tick
        entries = ctl.tick(rt.name, now=1003.0)
        assert any(e["applied"] for e in entries)
        assert rt.app_context.pipeline_depth > depth0
        counters = rt.app_context.telemetry.snapshot()["counters"]
        assert counters["autopilot.freezes"] >= 2
    finally:
        m.shutdown()


def test_oscillation_damping_suppresses_reversal():
    pol = Policy(cooldown_s=5.0)
    up_sig = SignalSnapshot(
        app="a", bottlenecks={"q": {"stage": "device", "utilization": 0.9}},
        pipeline_depth=2)
    down_sig = SignalSnapshot(
        app="a", bottlenecks={"q": {"stage": "device", "utilization": 0.05}},
        pipeline_depth=4)
    v = [x for x in pol.decide(up_sig, 100.0)
         if x["rule"].actuator == "pipeline_depth"]
    assert v and v[0]["blocked"] is None
    pol.applied("pipeline_depth", UP, 100.0)
    # a reversal within 2x cooldown is damped, not applied
    v = [x for x in pol.decide(down_sig, 107.0)
         if x["rule"].actuator == "pipeline_depth"]
    assert v and v[0]["blocked"] == "damped"
    # past the damping horizon the reversal is free to run
    v = [x for x in pol.decide(down_sig, 111.0)
         if x["rule"].actuator == "pipeline_depth"]
    assert v and v[0]["blocked"] is None


def test_every_actuator_reachable_and_bounded():
    reached = {r.actuator for r in RULES}
    assert reached == set(ACTUATORS)
    for a in ACTUATORS.values():
        assert a.lo <= a.hi
        assert a.apply is not None


# ------------------------------------------- live re-actuation safety


def test_live_actuations_at_batch_boundaries_bit_identical():
    """Depth / ingest-pool / fusion knobs flipped between live batches
    (seeded schedule) leave every output stream bit-identical to an
    untouched run of the same feed."""
    feed = _chunks(n_chunks=12, rows=24)

    def run(actuate):
        m, rt, sinks = _build()
        schedule = {
            2: ("pipeline_depth", UP),
            4: ("ingest_pool", UP),
            5: ("fuse_fanout", DOWN),
            7: ("pipeline_depth", DOWN),
            8: ("fuse_fanout", UP),
            9: ("ingest_pool", UP),
            10: ("ingest_pool", DOWN),
        }
        h = rt.get_input_handler("S")
        for i, (data, ts) in enumerate(feed):
            h.send_columns({k: v.copy() for k, v in data.items()},
                           timestamps=ts.copy())
            if actuate and i in schedule:
                name, direction = schedule[i]
                ACTUATORS[name].apply(rt, direction)
        out = _rows(sinks)
        m.shutdown()
        return out

    assert run(True) == run(False)


def test_controller_on_under_live_ingest_bit_identical():
    """The real loop: controller ON with an aggressive cadence, manual
    ticks between every chunk — whatever it decides to actuate, outputs
    match the autopilot-off run exactly."""
    feed = _chunks(n_chunks=10, rows=24, seed=23)
    ctl = AutopilotController.instance()

    def run(autopilot):
        extra = {"siddhi_tpu.autopilot": "on",
                 "siddhi_tpu.autopilot_interval_s": "3600",
                 "siddhi_tpu.autopilot_cooldown_s": "0.0"} if autopilot \
            else None
        m, rt, sinks = _build(extra=extra)
        h = rt.get_input_handler("S")
        for data, ts in feed:
            h.send_columns({k: v.copy() for k, v in data.items()},
                           timestamps=ts.copy())
            if autopilot:
                ctl.tick(rt.name)
        out = _rows(sinks)
        m.shutdown()
        return out

    assert run(True) == run(False)


ROUTED_APP = """
@app:name('aproute')
define stream S (sym string, side string, price double, volume long);
partition with (sym of S)
begin
  @info(name = 'q')
  from S#window.length(8)
  select sym, side, avg(price) as ap, sum(volume) as tv
  group by side
  insert into Out;
end;
"""


def _route_feed(rt, lo, hi):
    rng = np.random.default_rng(42)
    syms = rng.integers(0, 13, 1000)
    sides = rng.integers(0, 5, 1000)
    h = rt.get_input_handler("S")
    for i in range(lo, hi):
        h.send([f"SYM{syms[i]}", f"SIDE{sides[i]}",
                float(i % 17) + 0.25, int(i)])


def _build_routed(store=None, shards=None):
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m = SiddhiManager()
    if store is not None:
        m.set_persistence_store(store)
    rt = m.create_siddhi_app_runtime(ROUTED_APP)
    c = Collector()
    rt.add_callback("Out", c)
    if shards:
        device_route_query_step(rt.query_runtimes["q"], make_mesh(shards),
                                rows_per_shard=128)
    return m, rt, c


def test_route_shards_actuation_and_straddling_restore_exactly_once():
    """A reshard actuation mid-feed is bit-identical, and a persist
    taken AFTER the actuation restores exactly-once into a
    differently-sharded continuation (the canonical-snapshot
    contract straddles the actuation)."""
    m0, rt0, c0 = _build_routed()
    _route_feed(rt0, 0, 400)
    m0.shutdown()
    ref = list(c0.rows)

    store = InMemoryPersistenceStore()
    m1, rt1, c1 = _build_routed(store=store, shards=2)
    _route_feed(rt1, 0, 100)
    changed = ACTUATORS["route_shards"].apply(rt1, UP)
    assert changed == (2, 4)
    assert rt1.query_runtimes["q"]._route_layout.n == 4
    _route_feed(rt1, 100, 200)
    rt1.persist()
    m1.shutdown()
    head = len(c1.rows)
    assert c1.rows == ref[:head]

    m2, rt2, c2 = _build_routed(store=store, shards=2)
    rt2.restore_last_revision()
    _route_feed(rt2, 200, 300)
    # and actuate DOWN in the restored world too
    changed = ACTUATORS["route_shards"].apply(rt2, DOWN)
    # restored install re-lands at its configured 2 shards: nothing to halve
    assert changed is None or changed[1] >= 2
    _route_feed(rt2, 300, 400)
    m2.shutdown()
    assert c2.rows == ref[head:]


# --------------------------------------------------- export + REST


def test_autopilot_metric_families_render(monkeypatch):
    from siddhi_tpu.autopilot import signals as sigmod
    from siddhi_tpu.observability import export

    monkeypatch.setattr(sigmod, "collect", _device_bound_collect)
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build()
    try:
        rt.enable_autopilot(mode="on", interval_s=3600, cooldown_s=0.1)
        ctl.tick(rt.name, now=1000.0)
        text = export.prometheus_text(m)
        assert ('siddhi_autopilot_mode{app="apapp"} 2') in text
        assert "siddhi_autopilot_ticks_total" in text
        assert ('siddhi_autopilot_decisions_total{app="apapp",'
                'knob="pipeline_depth",direction="up",'
                'reason="device_bound"}') in text
        # dotted autopilot.* names never leak as generic families
        assert 'name="autopilot' not in text
    finally:
        m.shutdown()
    # the gauge dies with the registration (remove_gauge paired)
    assert "autopilot.mode" not in \
        rt.app_context.telemetry.snapshot()["gauges"]


def _http_get(url):
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_rest_autopilot_endpoint():
    from siddhi_tpu.service import SiddhiRestService

    m, rt, _sinks = _build()
    svc = SiddhiRestService(m).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        # deployed but not under autopilot control -> 404
        st, body = _http_get(f"{base}/autopilot/{rt.name}")
        assert st == 404 and "autopilot" in body["error"]
        st, body = _http_get(f"{base}/autopilot/nosuchapp")
        assert st == 404
        rt.enable_autopilot(mode="dry_run", interval_s=3600)
        st, body = _http_get(f"{base}/autopilot")
        assert st == 200
        assert set(body["actuators"]) == set(ACTUATORS)
        assert body["decision_log_capacity"] == 256
        st, body = _http_get(f"{base}/autopilot/{rt.name}")
        assert st == 200
        assert body["apps"][rt.name]["mode"] == "dry_run"
    finally:
        svc.stop()
        m.shutdown()


def test_decision_log_is_bounded(monkeypatch):
    from siddhi_tpu.autopilot import controller as ctlmod
    from siddhi_tpu.autopilot import signals as sigmod

    monkeypatch.setattr(sigmod, "collect", _device_bound_collect)
    ctl = AutopilotController.instance()
    m, rt, _sinks = _build()
    try:
        rt.enable_autopilot(mode="dry_run", interval_s=3600, cooldown_s=0.0)
        for i in range(ctlmod.DECISION_LOG_CAPACITY + 40):
            ctl.tick(rt.name, now=1000.0 + i)
        log = ctl.report(rt.name)["apps"][rt.name]["decisions"]
        assert len(log) == ctlmod.DECISION_LOG_CAPACITY
        # oldest entries fell off; seq numbers stay monotonic
        seqs = [e["seq"] for e in log]
        assert seqs == sorted(seqs) and seqs[0] > 1
    finally:
        m.shutdown()
