"""Critical-path profiler (observability/journey.py + costmodel.py).

The load-bearing acceptance set: a PLANTED bottleneck (FaultInjector
delay in pack, and in an @Async queue) must be the stage the
critical-path report names, at pipeline depth 1 AND depth 4 — and
overlapped stages must be attributed by max, not sum (a slow host must
not make the device look busy for the full wall). Plus: the compiled-
program registry's fingerprint-duplicate clusters vs the fan-out
``unique_programs`` gauge on a 4-identical-query app, the new REST
endpoints, Prometheus label-value escaping under hostile names, and
scrape hygiene (no app barrier, wedged worker can't stall a scrape).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.observability import costmodel, export, journey
from siddhi_tpu.resilience import FaultInjector


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


APP = """
define stream S (sym string, v long);
@info(name='pq')
from S#window.length(8)
  select sym, sum(v) as total group by sym
  insert into Out;
"""

ASYNC_APP = """
@Async(buffer.size='1024')
define stream S (sym string, v long);
@info(name='pq')
from S#window.length(8)
  select sym, sum(v) as total group by sym
  insert into Out;
"""


@pytest.fixture(autouse=True)
def _journey_off():
    yield
    journey.disable(force=True)
    journey.clear_delays()
    costmodel.disable(force=True)


def _manager(depth, extra=None):
    m = SiddhiManager()
    cfg = {"siddhi_tpu.pipeline_depth": str(depth)}
    cfg.update(extra or {})
    m.set_config_manager(InMemoryConfigManager(cfg))
    return m


def _warm(handler, n=3):
    """Sends BEFORE journeys are enabled: jit compiles land outside the
    measured window (a one-off 500 ms compile would otherwise drown a
    20 ms planted delay in the dispatch mean)."""
    for i in range(n):
        handler.send(["A", i])


def _bottleneck(m, rt, query="pq"):
    rep = journey.critical_path_report(m)
    q = rep["apps"][rt.name]["queries"][query]
    assert q["bottleneck"] is not None, q
    return q


# -------------------------------------------------- planted bottlenecks


@pytest.mark.parametrize("depth", [1, 4])
def test_pack_bottleneck_named(depth):
    """FaultInjector.delay_stage('pack'): the report must name pack —
    at depth 1 (synchronous) and depth 4 (pipelined submit path)."""
    m = _manager(depth)
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    _warm(h)
    journey.enable()
    rt.app_context.telemetry.reset()
    inj = FaultInjector()
    inj.delay_stage("pack", 0.02)
    try:
        for i in range(8):
            h.send(["A", i])
    finally:
        inj.clear()
    q = _bottleneck(m, rt)
    assert q["bottleneck"]["stage"] == "pack", q["bottleneck"]
    assert q["stages"]["pack"]["mean_service_ms"] >= 15.0
    m.shutdown()


@pytest.mark.parametrize("depth", [1, 4])
def test_async_queue_bottleneck_named(depth):
    """A persistently delayed @Async worker makes the queue the place
    where the batch's latency goes: the report must attribute it to
    QUEUEING at the queue stage, not to any measured service."""
    m = _manager(depth)
    rt = m.create_siddhi_app_runtime(ASYNC_APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    _warm(h)
    # warm the pad-16 batch shape too: the delayed worker coalesces the
    # measured sends into one unit, and a cold jit shape would charge a
    # one-off compile to the dispatch stage
    from siddhi_tpu.core.event import Event

    h.send([Event(timestamp=-1, data=["A", i]) for i in range(12)])
    time.sleep(0.3)         # async warmup batches fully drained
    journey.enable()
    rt.app_context.telemetry.reset()
    j = rt.junctions["S"]
    inj = FaultInjector()
    inj.delay_worker(j, 0.03, persistent=True)
    try:
        for i in range(12):
            h.send(["B", i])
            time.sleep(0.01)   # several worker iterations observe a wait
        # the worker may deliver the backlog as ONE coalesced unit (its
        # queue wait carries the first chunk's full residence) or as
        # several — either way at least one delivery with a recorded
        # queue wait must land and the queue must drain
        deadline = time.time() + 20
        while True:
            snap = rt.app_context.telemetry.snapshot().get("histograms", {})
            got = snap.get("stage.pq.queue.queue_ms", {}).get("count", 0)
            if got >= 1 and j._queue.qsize() == 0:
                break
            assert time.time() < deadline, \
                f"queue never drained ({got} deliveries observed)"
            time.sleep(0.05)
    finally:
        inj.clear()
    q = _bottleneck(m, rt)
    assert q["bottleneck"]["stage"] == "queue", q["bottleneck"]
    assert q["bottleneck"]["kind"] == "queueing"
    # the planted delay sits OUTSIDE every measured service window
    assert q["stages"]["queue"]["mean_queue_ms"] > 2 * max(
        q["stages"][s]["mean_service_ms"]
        for s in ("pack", "dispatch", "device"))
    m.shutdown()


def test_overlap_attributed_by_max_not_sum():
    """Depth 4, host-bound pipeline: outputs are READY at drain, so the
    ride must count as device slack (queue), NOT device service — the
    per-stage busy times must not each claim the wall."""
    m = _manager(4)
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    _warm(h)
    journey.enable()
    rt.app_context.telemetry.reset()
    inj = FaultInjector()
    inj.delay_stage("pack", 0.02)
    try:
        for i in range(10):
            h.send(["A", i])
    finally:
        inj.clear()
    q = _bottleneck(m, rt)
    pack_busy = q["stages"]["pack"]["busy_ms"]
    dev_busy = q["stages"]["device"]["busy_ms"]
    assert q["bottleneck"]["stage"] == "pack"
    # max-not-sum: the device's attributed service is a small fraction
    # of the host bottleneck's busy time, and the total attributed busy
    # stays in the same ballpark as the wall (no double counting)
    assert dev_busy < 0.5 * pack_busy, (dev_busy, pack_busy)
    total_busy = sum(s["busy_ms"] for s in q["stages"].values())
    assert total_busy < 2.0 * q["wall_ms"], (total_busy, q["wall_ms"])
    m.shutdown()


CHAIN_APP = """
@Async(buffer.size='256')
define stream S (sym string, v long);
define stream Mid (sym string, v long);
@info(name='up')
from S select sym, v insert into Mid;
@info(name='down')
from Mid select sym, v insert into Out;
"""


def test_sync_cascade_does_not_inherit_queue_wait():
    """A downstream query fed SYNCHRONOUSLY by an upstream emit (inside
    the @Async worker's delivery) must not be charged the upstream
    queue's residence — the delivery scope masks the thread-local for
    nested deliveries."""
    m = _manager(1)
    rt = m.create_siddhi_app_runtime(CHAIN_APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    _warm(h)
    time.sleep(0.3)
    journey.enable()
    rt.app_context.telemetry.reset()
    for i in range(6):
        h.send(["A", i])
        time.sleep(0.01)
    deadline = time.time() + 10
    while True:
        hists = rt.app_context.telemetry.snapshot().get("histograms", {})
        if hists.get("stage.down.dispatch.service_ms", {}).get("count", 0):
            break
        assert time.time() < deadline, "downstream query never ran"
        time.sleep(0.05)
    # the upstream query saw the @Async queue; the downstream one is a
    # sync cascade and must record NO queue residence
    assert hists.get("stage.up.queue.queue_ms", {}).get("count", 0) > 0
    assert "stage.down.queue.queue_ms" not in hists
    m.shutdown()


def test_journey_off_leaves_no_trace():
    """Default config: no Journey objects ride the batches, no stage
    histograms appear, the ring gains no record and the span primitive
    hands out its shared no-op (so no profiler annotation is entered) —
    the off path is one flag check."""
    from siddhi_tpu.core.event import launch_step
    from siddhi_tpu.observability import tracing

    m = _manager(2)
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    ring_before = journey.ring()
    assert not tracing.spans_on()
    assert tracing.span("pack") is tracing.span("query.step", query="pq")
    assert journey.pack_span() is tracing.NOOP is tracing.span("emit")
    # the two sub-stages of dispatch: the key stage never sizes the
    # dictionary, the launch is the step's call with nothing around it
    assert journey.keying(None, "pq", 8, None) is tracing.NOOP
    called = []
    assert launch_step(lambda st, *a: called.append((st, a)) or "out",
                       "state", 1, query="pq", jr=None) == "out"
    assert called == [("state", (1,))]
    for i in range(3):
        h.send(["A", i])
    hists = rt.app_context.telemetry.snapshot().get("histograms", {})
    assert not any(k.startswith("stage.") for k in hists)
    assert journey.critical_path_report(m)["apps"][rt.name]["queries"] == {}
    assert journey.ring() == ring_before
    m.shutdown()


def test_profile_knobs_enable_collectors():
    """siddhi_tpu.profile_journeys / profile_costs ride the typed knob
    registry and flip the process collectors for the app's lifetime."""
    m = _manager(2, {"siddhi_tpu.profile_journeys": "true",
                     "siddhi_tpu.profile_costs": "on"})
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("Out", Collector())
    rt.start()
    assert journey.enabled() and costmodel.enabled()
    h = rt.get_input_handler("S")
    h.send(["A", 1])
    assert any(p.key == "query.pq.step"
               for p in costmodel.registry().programs())
    hists = rt.app_context.telemetry.snapshot().get("histograms", {})
    assert any(k.startswith("stage.pq.") for k in hists)
    m.shutdown()
    assert not journey.enabled()


# ---------------------------- routed + device-join coverage (ISSUE 12)


ROUTED_APP = """
define stream S (k string, v double);
partition with (k of S)
begin
  @info(name='rq')
  from S#window.length(4) select k, v, sum(v) as s insert into Out;
end;
"""

JOIN_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(32) join R#window.length(32)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""


@pytest.mark.parametrize("n_dev", [2, 4])
def test_routed_query_stage_attribution(n_dev):
    """A device-routed query at pipeline depth 4 produces correct stage
    attribution: every core stage populated, and its EXTENDED meta
    prefix (route slots + inner instrument lanes) rides the
    CompletionPump with output bit-identical to the unrouted run."""
    from siddhi_tpu.parallel.mesh import device_route_query_step, make_mesh

    m0 = _manager(4)
    rt0 = m0.create_siddhi_app_runtime(ROUTED_APP)
    ref = Collector()
    rt0.add_callback("Out", ref)
    h0 = rt0.get_input_handler("S")
    for i in range(160):
        h0.send([f"P{i % 16}", float(i)])
    m0.shutdown()
    # journey window: warm first (compiles outside the measurement)
    m = _manager(4)
    rt = m.create_siddhi_app_runtime(ROUTED_APP)
    c = Collector()
    rt.add_callback("Out", c)
    q = rt.query_runtimes["rq"]
    device_route_query_step(q, make_mesh(n_dev), rows_per_shard=256)
    h = rt.get_input_handler("S")
    for i in range(32):
        h.send([f"P{i % 16}", float(i)])
    journey.enable()
    rt.app_context.telemetry.reset()
    for i in range(32, 160):
        h.send([f"P{i % 16}", float(i)])
    qrep = _bottleneck(m, rt, query="rq")
    for stage in ("pack", "dispatch", "device", "emit"):
        assert qrep["stages"].get(stage, {}).get("batches", 0) > 0, \
            (stage, qrep["stages"].keys())
    # pump-compat: the routed run's full output equals the unrouted one
    assert c.rows == ref.rows
    # extended prefix decoded: shard-rows instrument drained per batch
    assert q._instr_last["shard_rows"].shape == (n_dev,)
    m.shutdown()


@pytest.mark.parametrize("n_parts", [2, 4])
def test_device_join_stage_attribution(n_parts):
    """Device-join batches (engine meta carries seq + partition fills)
    get stage attribution at depth 4, stay pump-compatible (no seq
    breaks), and both sides' journeys land under the join query."""
    m = _manager(4, {"siddhi_tpu.join_partitions": str(n_parts),
                     "siddhi_tpu.join_partition_slack": "8"})
    rt = m.create_siddhi_app_runtime(JOIN_APP)
    c = Collector()
    rt.add_callback("JOut", c)
    q = rt.query_runtimes["jq"]
    assert q.engine is not None, q.engine_reason
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    hl.send(["S0", 0])
    hr.send(["S0", 100])   # warm both side steps
    journey.enable()
    rt.app_context.telemetry.reset()
    for i in range(24):
        hl.send([f"S{i % 3}", i])
        hr.send([f"S{i % 3}", 100 + i])
    qrep = _bottleneck(m, rt, query="jq")
    for stage in ("pack", "dispatch", "device", "emit"):
        assert qrep["stages"].get(stage, {}).get("batches", 0) > 0, \
            (stage, qrep["stages"].keys())
    assert len(c.rows) > 0
    # cross-stream order held through the pump: seq verified at drain
    counters = rt.app_context.telemetry.snapshot()["counters"]
    assert counters.get("join.seq_breaks", 0) == 0
    m.shutdown()


# ------------------------------------------- program registry vs fan-out


FOUR_Q = """
define stream S (sym string, v long);
@info(name='q1') from S#window.length(8) select sym, sum(v) as t group by sym insert into O1;
@info(name='q2') from S#window.length(8) select sym, sum(v) as t group by sym insert into O2;
@info(name='q3') from S#window.length(8) select sym, sum(v) as t group by sym insert into O3;
@info(name='q4') from S#window.length(8) select sym, sum(v) as t group by sym insert into O4;
"""


def test_programs_duplicate_clusters_agree_with_fanout_gauge():
    """Acceptance: on a 4-identical-query app the registry's duplicate-
    fingerprint clusters tell the same story as the fan-out dedup's
    ``unique_programs`` gauge — 4 compiled programs, ONE distinct
    computation."""
    # fusion ON (default): the fan-out dedup clusters the 4 members
    m1 = _manager(2)
    rt1 = m1.create_siddhi_app_runtime(FOUR_Q)
    rt1.get_input_handler("S").send(["A", 1])
    gauges = rt1.app_context.telemetry.read_gauges()
    unique = int(gauges["fanout.S.unique_programs"])
    assert unique == 1
    m1.shutdown()

    # fusion OFF + cost capture: 4 separate programs, equal fingerprints
    costmodel.registry().reset()
    costmodel.enable()
    m2 = _manager(2, {"siddhi_tpu.fuse_fanout": "false"})
    rt2 = m2.create_siddhi_app_runtime(FOUR_Q)
    rt2.get_input_handler("S").send(["A", 1])
    snap = costmodel.registry().snapshot()
    step_keys = [p["key"] for p in snap["programs"]
                 if p["key"].startswith("query.q")]
    assert len(step_keys) == 4
    step_clusters = [c for c in snap["clusters"]
                     if any(k.startswith("query.q") for k in c["keys"])]
    # every per-query step lands in ONE duplicate cluster — exactly the
    # unique_programs count the fused path reports
    assert len(step_clusters) == unique == 1
    assert step_clusters[0]["size"] == 4
    assert step_clusters[0]["duplicates"] == 3
    m2.shutdown()


def test_cost_capture_records_analysis_fields():
    costmodel.registry().reset()
    costmodel.enable()
    m = _manager(2)
    rt = m.create_siddhi_app_runtime(APP)
    rt.get_input_handler("S").send(["A", 1])
    recs = {p.key: p for p in costmodel.registry().programs()}
    rec = recs["query.pq.step"]
    assert rec.error is None
    assert rec.flops > 0
    assert rec.bytes_accessed > 0
    assert rec.arg_bytes > 0
    assert len(rec.fingerprint) == 16
    # bit-identity sanity: capture ran BEFORE the first (donating) call
    out = Collector()
    rt.add_callback("Out", out)
    rt.get_input_handler("S").send(["A", 2])
    assert out.rows == [("A", 3)]
    m.shutdown()


# ------------------------------------------------------------------ REST


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def _post(url, body=None):
    req = urllib.request.Request(
        url, data=json.dumps(body or {}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def test_rest_profile_endpoints(tmp_path):
    from siddhi_tpu.service import SiddhiRestService

    costmodel.registry().reset()
    m = _manager(2)
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("Out", Collector())
    svc = SiddhiRestService(m, trace_base=str(tmp_path)).start()
    base = f"http://127.0.0.1:{svc.port}"
    try:
        st, body = _post(f"{base}/profile/journeys/start")
        assert st == 200 and body["journeys"] is True
        st, body = _post(f"{base}/profile/costs/start")
        assert st == 200 and body["costs"] is True
        h = rt.get_input_handler("S")
        for i in range(4):
            h.send(["A", i])
        st, rep = _get(f"{base}/profile/critical_path/{rt.name}")
        assert st == 200
        q = rep["apps"][rt.name]["queries"]["pq"]
        assert set(q["stages"]) >= {"pack", "dispatch", "device", "emit"}
        assert q["bottleneck"]["stage"] in rep["stage_glossary"]
        st, progs = _get(f"{base}/programs")
        assert st == 200
        assert any(p["key"] == "query.pq.step" for p in progs["programs"])
        assert progs["unique_fingerprints"] >= 1
        st, body = _post(f"{base}/profile/journeys/stop")
        assert st == 200 and body["journeys"] is False
        _post(f"{base}/profile/costs/stop")
        # device profiler: path confinement mirrors /trace
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/profile/device/start", {"dir": "../escape"})
        assert e.value.code == 400
        st, body = _post(f"{base}/profile/device/start", {"dir": "prof1"})
        assert st == 200 and body["device_profile"].startswith(str(tmp_path))
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/profile/device/start", {"dir": "prof2"})
        assert e.value.code == 409
        st, body = _post(f"{base}/profile/device/stop")
        assert st == 200 and body["device_profile"] is None
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/profile/device/stop")
        assert e.value.code == 409
    finally:
        svc.stop()
        m.shutdown()


# ------------------------------------------- exposition escaping (sat 1)


def _assert_valid_exposition(text):
    """Every sample line must match the text-format grammar: label
    values with backslash/quote/newline ESCAPED (a raw one breaks the
    line structure or the value quoting)."""
    import re

    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{([a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\\n])*",?)*\})?'
        r' (NaN|[-+0-9.e]+)$')
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert sample.match(line), f"malformed exposition line: {line!r}"


def test_prometheus_escaping_hostile_label_values():
    """Regression (satellite): backslash, double-quote and newline in
    label VALUES — stream/app/query names are user-controlled SiddhiQL
    identifiers and counter names are free-form — must be escaped per
    the exposition spec."""
    m = _manager(2)
    rt = m.create_siddhi_app_runtime(APP)
    hostile = 'ev"il\\str\neam'
    tel = rt.app_context.telemetry
    tel.gauge(f"junction.{hostile}.queue_depth", lambda: 7)
    tel.count(f"junction.{hostile}.backpressure_stalls", 3)
    tel.count(f'overload.{hostile}.events', 2)
    text = export.prometheus_text(m)
    _assert_valid_exposition(text)
    assert 'ev\\"il\\\\str\\neam' in text
    assert "\neam" not in text.replace("\\neam", "")  # no raw newline leak
    # JSON snapshot keeps the raw name (JSON handles its own escaping)
    snap = export.json_snapshot(m)
    tele = snap["apps"][rt.name]["telemetry"]
    assert tele["gauges"][f"junction.{hostile}.queue_depth"] == 7
    m.shutdown()


# ------------------------------------------------- scrape hygiene (sat 2)


def test_scrape_self_histogram_and_no_barrier():
    """A scrape must never take the app barrier OR the device: it
    completes while the barrier is HELD and an @Async worker is WEDGED,
    performs ZERO device pulls (the SIDDHI_TPU_SANITIZE transfer guard
    — asserted here with jax's transfer_guard directly, the same
    mechanism the sanitizer arms), and times itself into
    siddhi_scrape_ms (visible on the following scrape)."""
    import jax

    m = _manager(2)
    rt = m.create_siddhi_app_runtime(ASYNC_APP)
    rt.add_callback("Out", Collector())
    h = rt.get_input_handler("S")
    h.send(["A", 1])
    inj = FaultInjector()
    j = rt.junctions["S"]
    inj.wedge_worker(j)
    h.send(["A", 2])        # worker picks it up and wedges
    deadline = time.time() + 10
    while not inj._wedged.is_set():
        assert time.time() < deadline, "worker never wedged"
        time.sleep(0.01)
    result = {}

    def scrape():
        # device-instrument + pipeline + junction gauges all answer
        # host-side: a gauge pulling device state here would raise
        # under the guard and surface as NaN in its family
        with jax.transfer_guard("disallow"):
            result["text"] = export.prometheus_text(m)

    with rt._barrier:       # a checkpoint/ingest holding the barrier
        t = threading.Thread(target=scrape, daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive(), "scrape blocked on the app barrier"
    assert "siddhi_junction_queue_depth" in result["text"]
    for line in result["text"].splitlines():
        if line.startswith(("siddhi_device_instrument",
                            "siddhi_join_partition_rows")):
            assert not line.endswith("NaN"), \
                f"scrape gauge pulled device state: {line}"
    inj.release()
    inj.clear()
    # self-timing: the first scrape's duration shows on the second
    text2 = export.prometheus_text(m)
    assert "siddhi_scrape_ms" in text2
    assert 'siddhi_scrape_ms_count' in text2
    m.shutdown()
