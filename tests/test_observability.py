"""Observability subsystem tests: span nesting/thread-safety, histogram
percentile correctness vs numpy, @Async queue-depth gauges under a soak,
Prometheus exposition over REST, Chrome-trace structural validity, and
the bounded cluster-pull gauge."""

import json
import re
import threading
import time
import urllib.request
from collections import defaultdict

import numpy as np
import pytest

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.observability.histogram import Histogram
from siddhi_tpu.observability.tracing import TRACER, Tracer, span
from siddhi_tpu.observability.telemetry import global_registry


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.events = []

    def receive(self, events):
        self.events.extend(events)


# ------------------------------------------------------------------ spans


def _complete_events(trace):
    evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    for e in evs:
        for key in ("name", "pid", "tid", "ts", "dur"):
            assert key in e, f"chrome event missing '{key}': {e}"
        assert e["dur"] > 0
    return evs


def _assert_properly_nested(events):
    """Per tid, every pair of spans is either disjoint or contained —
    the Trace Event Format contract for complete ('X') events."""
    by_tid = defaultdict(list)
    for e in events:
        by_tid[e["tid"]].append(e)
    eps = 0.01   # ts/dur are rounded to 3 decimals of a microsecond
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            while stack and e["ts"] >= stack[-1]["ts"] + stack[-1]["dur"] - eps:
                stack.pop()
            if stack:
                assert (e["ts"] + e["dur"]
                        <= stack[-1]["ts"] + stack[-1]["dur"] + eps), \
                    f"span {e} escapes its parent {stack[-1]}"
            stack.append(e)


def test_span_nesting_structure():
    t = Tracer(capacity=1024)
    t.start()
    with t.span("outer", kind="test"):
        with t.span("mid"):
            with t.span("inner"):
                time.sleep(0.001)
        with t.span("mid2"):
            time.sleep(0.001)
    trace = t.stop()
    evs = _complete_events(trace)
    assert {e["name"] for e in evs} == {"outer", "mid", "inner", "mid2"}
    _assert_properly_nested(evs)
    outer = next(e for e in evs if e["name"] == "outer")
    inner = next(e for e in evs if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.01
    assert outer["args"] == {"kind": "test"}


def test_span_thread_safety():
    t = Tracer(capacity=100_000)
    t.start()
    n_threads, n_iters = 8, 200
    barrier = threading.Barrier(n_threads)   # all alive at once, so
    #                                          thread idents stay distinct

    def work():
        barrier.wait()
        for i in range(n_iters):
            with t.span("outer", i=i):
                with t.span("mid"):
                    with t.span("inner"):
                        pass

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    trace = t.stop()
    evs = _complete_events(trace)
    assert len(evs) == n_threads * n_iters * 3
    assert len({e["tid"] for e in evs}) == n_threads
    _assert_properly_nested(evs)


def test_span_ring_buffer_bound_and_disabled_noop():
    t = Tracer(capacity=16)
    t.start()
    for i in range(100):
        with t.span("s", i=i):
            pass
    assert len(t) == 16
    trace = t.stop()
    assert trace["otherData"]["dropped_spans"] == 84
    # newest survive the ring
    kept = [e["args"]["i"] for e in trace["traceEvents"]
            if e.get("ph") == "X"]
    assert sorted(kept) == list(range(84, 100))
    # disabled: the global helper returns the shared no-op
    assert not TRACER.enabled
    TRACER.clear()      # whatever an earlier file of this worker left
    cm = span("ignored", x=1)
    with cm:
        pass
    assert len(TRACER) == 0


# -------------------------------------------------------------- histogram


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    for sample in (
        rng.lognormal(mean=1.0, sigma=1.5, size=20_000),     # heavy tail
        rng.uniform(0.01, 50.0, size=10_000),                # flat
        np.abs(rng.normal(5.0, 2.0, size=10_000)) + 0.05,    # bell
    ):
        h = Histogram()
        for v in sample:
            h.record(float(v))
        for q in (0.50, 0.95, 0.99):
            got = h.quantile(q)
            want = float(np.quantile(sample, q))
            assert got == pytest.approx(want, rel=0.08), \
                f"q={q}: hist {got} vs numpy {want}"
    assert h.count == 10_000
    assert h.mean == pytest.approx(float(sample.mean()), rel=1e-6)


def test_histogram_edges_and_reset():
    h = Histogram()
    assert h.quantile(0.5) == 0.0
    h.record(3.25)
    assert h.quantile(0.5) == pytest.approx(3.25, rel=0.08)
    assert h.quantile(0.0) == 3.25 and h.quantile(1.0) == 3.25
    h.record(-1.0)           # negative: clock-skew artifact, ignored
    h.record(float("nan"))   # ignored
    assert h.count == 1
    h.record(1e9)            # beyond the top bucket: clamped, counted
    assert h.count == 2 and h.max_seen == 1e9
    h.reset()
    assert h.count == 0 and h.quantile(0.99) == 0.0


def test_latency_tracker_has_percentiles():
    from siddhi_tpu.core.util.statistics import LatencyTracker

    t = LatencyTracker("q")
    for v in [1.0] * 90 + [100.0] * 10:
        t.record(v)
    assert t.p50_ms == pytest.approx(1.0, rel=0.1)
    assert t.p99_ms == pytest.approx(100.0, rel=0.1)
    assert t.avg_ms == pytest.approx(10.9, rel=1e-6)
    t.reset()
    assert t.p99_ms == 0.0


# ------------------------------------------------- @Async telemetry gauges


def test_queue_depth_gauge_under_async_soak():
    from siddhi_tpu.resilience import FaultInjector

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:name('SoakApp')
        @Async(buffer.size='256', batch.size='16')
        define stream S (sym string, v long);
        from S select sym, v insert into Out;
    """)
    c = Collector()
    rt.add_callback("Out", c)
    rt.start()
    tel = rt.app_context.telemetry
    inj = FaultInjector()
    j = rt.junctions["S"]
    h = rt.get_input_handler("S")
    try:
        inj.wedge_worker(j)
        h.send(["a", 0])                    # wakes the worker into the wedge
        assert inj.wait_wedged(10.0)
        for i in range(50):                 # soak against a wedged worker
            h.send(["a", i])
        depth = tel.read_gauges()["junction.S.queue_depth"]
        assert depth >= 50                  # queued behind the wedge
    finally:
        inj.release()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        g = tel.read_gauges()
        if (g["junction.S.queue_depth"] == 0
                and g["junction.S.inflight_batches"] == 0
                and len(c.events) == 51):
            break
        time.sleep(0.02)
    g = tel.read_gauges()
    m.shutdown()
    assert g["junction.S.queue_depth"] == 0
    assert g["junction.S.inflight_batches"] == 0
    assert len(c.events) == 51              # nothing lost across the soak


def test_backpressure_stall_counter():
    from siddhi_tpu.resilience import FaultInjector

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        @app:name('StallApp')
        @Async(buffer.size='4', batch.size='4')
        define stream S (v long);
        from S select v insert into Out;
    """)
    rt.add_callback("Out", Collector())
    rt.start()
    inj = FaultInjector()
    j = rt.junctions["S"]
    h = rt.get_input_handler("S")
    inj.wedge_worker(j)
    h.send([0])
    assert inj.wait_wedged(10.0)

    def pump():
        for i in range(8):                  # overflows the 4-slot queue
            h.send([i])

    t = threading.Thread(target=pump)
    t.start()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if rt.app_context.telemetry.counters.get(
                "junction.S.backpressure_stalls", 0) > 0:
            break
        time.sleep(0.02)
    stalls = rt.app_context.telemetry.counters.get(
        "junction.S.backpressure_stalls", 0)
    inj.release()
    t.join(timeout=10)
    m.shutdown()
    assert stalls > 0


# ------------------------------------------------------------ REST surface


def _req(port, method, path, body=None, as_json=True, raw=False):
    url = f"http://127.0.0.1:{port}{path}"
    data = None
    headers = {}
    if body is not None:
        if as_json:
            data = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        else:
            data = body.encode()
            headers["Content-Type"] = "text/plain"
    req = urllib.request.Request(url, data=data, method=method,
                                 headers=headers)
    with urllib.request.urlopen(req) as r:
        payload = r.read()
        return payload.decode() if raw else json.loads(payload)


_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{[^}]*\})?'
    r' (?P<value>-?(?:[0-9.]+(?:[eE][+-]?[0-9]+)?|NaN))$')
_LABEL = re.compile(r'(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"')


def _parse_prometheus(text):
    """Minimal exposition-format parser: returns (types, samples) where
    samples is a list of (metric, labels dict, value). Raises on any
    malformed line — the 'parses' half of the acceptance criterion."""
    types = {}
    samples = []
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            fam, ftype = rest.rsplit(" ", 1)
            assert ftype in ("counter", "gauge", "summary", "histogram")
            types[fam] = ftype
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        labels = {lm.group("k"): lm.group("v")
                  for lm in _LABEL.finditer(m.group("labels") or "")}
        samples.append((m.group("name"), labels, m.group("value")))
    # every sample belongs to a TYPE-declared family (summaries add
    # _sum/_count suffixes to the family name)
    for name, _labels, _v in samples:
        fam = name
        for suf in ("_sum", "_count"):
            if name.endswith(suf) and name[: -len(suf)] in types:
                fam = name[: -len(suf)]
        assert fam in types or name in types, f"undeclared family: {name}"
    return types, samples


OBS_APP = """
@app:name('ObsApp')
@app:statistics(level='detail')
define stream S (sym string, price double);
@Async(buffer.size='64', batch.size='8')
define stream Mid (sym string, price double);
@info(name='q1') from S[price > 1.0] select sym, price insert into Mid;
@info(name='q2') from Mid select sym, price insert into Out;
"""


def test_rest_metrics_prometheus_exposition():
    from siddhi_tpu.core.util.persistence import InMemoryPersistenceStore
    from siddhi_tpu.service import SiddhiRestService

    m = SiddhiManager()
    m.set_persistence_store(InMemoryPersistenceStore())
    svc = SiddhiRestService(m).start()
    p = svc.port
    try:
        assert _req(p, "POST", "/apps", OBS_APP,
                    as_json=False) == {"app": "ObsApp"}
        rt = m.get_siddhi_app_runtime("ObsApp")
        rt.enable_wal(max_batches=16)
        _req(p, "POST", "/apps/ObsApp/events",
             {"stream": "S", "data": [["IBM", 5.5], ["X", 2.0]]})
        time.sleep(0.4)                       # let the @Async hop deliver
        _req(p, "POST", "/apps/ObsApp/persist")
        _req(p, "POST", "/apps/ObsApp/events",
             {"stream": "S", "data": [["Y", 3.0]]})
        time.sleep(0.3)
        _req(p, "POST", "/apps/ObsApp/restore", {})   # replays the WAL
        time.sleep(0.4)

        text = _req(p, "GET", "/metrics", raw=True)
        types, samples = _parse_prometheus(text)

        def named(metric):
            return [(lb, v) for name, lb, v in samples if name == metric]

        # per-query latency percentiles (q1 runs on the ingest thread)
        quantiles = {lb["quantile"] for lb, _v in named("siddhi_latency_ms")
                     if lb.get("name") == "q1"}
        assert {"0.5", "0.95", "0.99"} <= quantiles
        assert types["siddhi_latency_ms"] == "summary"
        # junction queue-depth gauge for the @Async stream
        assert any(lb.get("stream") == "Mid"
                   for lb, _v in named("siddhi_junction_queue_depth"))
        # jit-compile counters
        jit_keys = {lb["key"] for lb, v in named("siddhi_jit_compiles_total")
                    if lb.get("app") == "ObsApp" and float(v) > 0}
        assert any(k.startswith("query.q1") for k in jit_keys)
        # resilience.* counters, the replayed-WAL one genuinely non-zero
        res = {lb["name"]: float(v) for lb, v in named("siddhi_counter_total")
               if lb.get("app") == "ObsApp"
               and lb.get("name", "").startswith("resilience.")}
        assert set(res) >= {
            "resilience.worker_restarts", "resilience.wal_replayed_batches",
            "resilience.wal_dropped_batches", "resilience.sink_retries"}
        assert res["resilience.wal_replayed_batches"] >= 1
        # WAL gauges ride the generic gauge family
        assert any(lb.get("name") == "wal.batches"
                   for lb, _v in named("siddhi_gauge"))

        # single-app scope + JSON snapshot
        text_one = _req(p, "GET", "/metrics/ObsApp", raw=True)
        _parse_prometheus(text_one)
        js = _req(p, "GET", "/metrics/ObsApp?format=json")
        assert list(js["apps"]) == ["ObsApp"]
        tel = js["apps"]["ObsApp"]["telemetry"]
        assert "junction.Mid.queue_depth" in tel["gauges"]
        lat = js["apps"]["ObsApp"]["statistics"]["latency"]["q1"]
        assert {"p50_ms", "p95_ms", "p99_ms"} <= set(lat)
        # unknown app -> 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(p, "GET", "/metrics/NoSuchApp", raw=True)
        assert ei.value.code == 404
    finally:
        svc.stop()
        m.shutdown()


def test_rest_trace_start_stop_dumps_chrome_json(tmp_path):
    from siddhi_tpu.service import SiddhiRestService

    m = SiddhiManager()
    svc = SiddhiRestService(m, trace_base=str(tmp_path)).start()
    p = svc.port
    try:
        _req(p, "POST", "/apps",
             "@app:name('TrSpanApp') define stream S (v int); "
             "from S[v > 0] select v insert into O;", as_json=False)
        got = _req(p, "POST", "/trace/start", {})
        assert got["tracing"] is True
        # double start -> 409
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(p, "POST", "/trace/start", {})
        assert ei.value.code == 409
        for i in range(3):
            _req(p, "POST", "/apps/TrSpanApp/events",
                 {"stream": "S", "data": [[i + 1]]})
        got = _req(p, "POST", "/trace/stop", {"file": "soak/spans.json"})
        assert got["tracing"] is False and got["events"] > 0
        # the span file is a loadable Chrome trace, confined to trace_base
        assert got["file"].startswith(str(tmp_path))
        with open(got["file"], encoding="utf-8") as f:
            trace = json.load(f)
        evs = _complete_events(trace)
        names = {e["name"] for e in evs}
        assert "junction.dispatch" in names and "query.step" in names
        _assert_properly_nested(evs)
        # stop without start -> 409; escape -> 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(p, "POST", "/trace/stop", {})
        assert ei.value.code == 409
        _req(p, "POST", "/trace/start", {})
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(p, "POST", "/trace/stop", {"file": "../../etc/passwd"})
        assert ei.value.code == 400
        # "." resolves to the trace DIRECTORY itself: rejected, and the
        # rejection must NOT have stopped the running trace
        with pytest.raises(urllib.error.HTTPError) as ei:
            _req(p, "POST", "/trace/stop", {"file": "."})
        assert ei.value.code == 400
        assert TRACER.enabled
        _req(p, "POST", "/trace/stop", {})   # leave the tracer off
    finally:
        TRACER.enabled = False
        svc.stop()
        m.shutdown()


def test_wal_gauges_register_at_attach_not_only_create():
    """A WAL attached to a rebuilt runtime's context (the PeerRecovery
    path assigns ``app_context.ingest_wal`` directly) must still get its
    /metrics gauges — registration follows the ATTACH, not the create."""
    from siddhi_tpu.resilience.replay import IngestWAL, register_wal_gauges

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(
        "@app:name('WalGaugeApp') define stream S (v long); "
        "from S select v insert into Out;")
    survivor_wal = IngestWAL(max_batches=8)
    rt.app_context.ingest_wal = survivor_wal      # recovery-style attach
    register_wal_gauges(rt.app_context)
    rt.get_input_handler("S").send([1])
    g = rt.app_context.telemetry.read_gauges()
    assert g["wal.batches"] == 1 and g["wal.pending_events"] == 1
    register_wal_gauges(rt.app_context)           # idempotent
    assert rt.app_context.telemetry.read_gauges()["wal.batches"] == 1
    m.shutdown()


# ------------------------------------------------- bounded cluster pulls


def test_guarded_pull_outstanding_gauge_and_cap(monkeypatch):
    from siddhi_tpu.parallel import distributed as d

    release = threading.Event()

    class Blocker:
        def __array__(self, *a, **kw):
            release.wait(20)
            return np.zeros(1)

    base = d.outstanding_pulls()
    try:
        with pytest.raises(d.ClusterPeerError, match="terminal"):
            d.guarded_pull(Blocker(), 0.05, what="test pull")
        # the abandoned native wait is tracked as outstanding...
        assert d.outstanding_pulls() == base + 1
        # ...and exported as a process-global gauge
        g = global_registry().read_gauges()
        assert g["cluster.outstanding_pulls"] == base + 1
        # at the cap, new pulls fail fast instead of stacking threads
        monkeypatch.setattr(d, "_MAX_OUTSTANDING_PULLS", base + 1)
        with pytest.raises(d.ClusterPeerError, match="already outstanding"):
            d.guarded_pull(np.zeros(1), 5.0, what="capped pull")
    finally:
        release.set()
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and d.outstanding_pulls() > base:
        time.sleep(0.02)
    assert d.outstanding_pulls() == base   # leaked thread drained
