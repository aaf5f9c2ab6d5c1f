"""Benchmark sections: the BASELINE.json north-star shapes and the
subsystem curves, one section per call.

    python bench.py --section <name> [--section <name> ...]

Every section runs in THIS process, on the device JAX finds (a chip
belongs to one process: nothing here spawns a child that needs it), and
prints one JSON line: its result plus the device it ran on
(``jax.devices()[0].platform``, ``device_kind``, device count). A section
that raises makes the exit code non-zero; nothing falls back to another
backend, nothing is relabelled, and no record is written into the
checkout. The cell table and runner that turn these sections into a
benchmark (ROADMAP S1/D1) are a later PR; ``chip_smoke.py`` is the
correctness check on the chip.

Sections (``--list`` prints them): ``device`` is the pre-staged jitted
step of the 10k-key length(1000) -> avg/sum group-by (BASELINE.json
config #2/#3 family); ``e2e`` the same query through the real ingest
path with genuine string ingest and with pre-encoded ids; ``nfa`` config
#4's per-batch latency and throughput; the rest are the curves the
subsystem PRs recorded. ``vs_baseline`` refers to the single-threaded
event-at-a-time native stand-in (tools/baseline_cpp/baseline.cpp — no
JVM exists in this image). The cluster-fabric and fleet soaks are CPU
tools of their own: ``tools/cluster_soak.py``, ``tools/fleet_soak.py``.
Methodology mirrors the reference's
SimpleFilterSingleQueryPerformance.java:44-56 (pump events, count
outputs, divide by elapsed).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# Measured on this host: tools/baseline_cpp/baseline.cpp, g++ -O2, 20M
# events (single-threaded event-at-a-time engine with the reference's
# per-event cost structure). See BASELINE.md.
MEASURED_BASELINE_EPS = 8.5e6

NUM_KEYS = 10_000
WINDOW = 1_000
BATCH = int(os.environ.get("BENCH_BATCH", 65_536))
MEASURE_SECONDS = float(os.environ.get("BENCH_SECONDS", 4.0))

_APP = """
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.length({W})
select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
group by symbol
insert into OutStream;
""".format(W=WINDOW)


def bench_device():
    """Device-path throughput: pre-staged columnar batches through the
    fused query step (the selector/keyer warmed to full key capacity)."""
    import jax

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY

    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(_APP)
    rt.start()
    q = rt.query_runtimes["bench"]
    q.selector_plan.num_keys = 16_384  # >= NUM_KEYS, pow2: no growth re-jits

    rng = np.random.default_rng(0)

    def make_batch(i):
        sym = rng.integers(0, NUM_KEYS, BATCH, dtype=np.int64)
        return {
            TS_KEY: np.arange(i * BATCH, (i + 1) * BATCH, dtype=np.int64),
            TYPE_KEY: np.zeros(BATCH, np.int8),
            VALID_KEY: np.ones(BATCH, bool),
            "symbol": sym,
            "symbol?": np.zeros(BATCH, bool),
            "price": (rng.random(BATCH) * 100.0).astype(np.float32),
            "price?": np.zeros(BATCH, bool),
            "volume": rng.integers(1, 1000, BATCH, dtype=np.int64),
            "volume?": np.zeros(BATCH, bool),
            GK_KEY: sym.astype(np.int32),
        }

    state = q._init_state()
    step = jax.jit(q.build_step_fn(), donate_argnums=0)
    now = np.int64(0)
    batches = [jax.device_put(make_batch(i)) for i in range(4)]

    for i in range(3):
        state, out = step(state, batches[i % len(batches)], now)
    jax.block_until_ready(state)

    t0 = time.perf_counter()
    n_events = 0
    i = 0
    while True:
        state, out = step(state, batches[i % len(batches)], now)
        n_events += BATCH
        i += 1
        if i % 20 == 0:
            jax.block_until_ready(state)
            if time.perf_counter() - t0 >= MEASURE_SECONDS:
                break
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0
    manager.shutdown()
    return n_events / dt


def _make_e2e_runtime(pipeline_depth: int = 8):
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    manager = SiddhiManager()
    # dispatch-pipeline depth (core/query/completion.py; replaces the
    # deprecated defer_meta hold-N queue): synchronous sends flush per
    # batch, so depth only engages through @Async producers — kept here
    # for parity with bench_pipeline_curve's async shape
    manager.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.pipeline_depth": str(pipeline_depth)}))
    rt = manager.create_siddhi_app_runtime(_APP)

    class Counter(StreamCallback):
        n = 0

        def receive_batch(self, batch, junction):
            Counter.n += batch.size

        def receive(self, events):
            Counter.n += len(events)

    Counter.n = 0
    rt.add_callback("OutStream", Counter())
    rt.query_runtimes["bench"].selector_plan.num_keys = 16_384
    return manager, rt, Counter


def bench_e2e():
    """End-to-end: InputHandler.send_columns -> junction -> query ->
    StreamCallback (columnar), mirroring the reference harness methodology
    (SimpleFilterSingleQueryPerformance.java:44-56: pump, count outputs,
    events/sec). Two measured windows in one session: genuine STRING
    ingest (the dictionary encodes every batch — the cost the reference
    pays per event) and pre-encoded int ids (isolating that cost)."""
    manager, rt, Counter = _make_e2e_runtime()
    h = rt.get_input_handler("StockStream")

    rng = np.random.default_rng(1)
    B = BATCH
    sym_strings = np.array([f"S{i}" for i in range(NUM_KEYS)], dtype=object)

    def make_cols(i, strings: bool):
        ids = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
        return {
            "symbol": sym_strings[ids] if strings else ids,
            "price": (rng.random(B) * 100.0).astype(np.float32),
            "volume": rng.integers(1, 1000, B, dtype=np.int64),
        }, np.arange(i * B, (i + 1) * B, dtype=np.int64)

    # warm at the MEASURED batch shape (pow2 padding would otherwise
    # compile a second shape): one B-row batch covering every key — string
    # ingest, so the dictionary also reaches its full size up front
    warm_sym = sym_strings[np.arange(B, dtype=np.int64) % NUM_KEYS]
    h.send_columns({"symbol": warm_sym,
                    "price": np.ones(B, np.float32),
                    "volume": np.ones(B, np.int64)},
                   timestamps=np.zeros(B, np.int64))

    def measure(strings: bool, seconds: float) -> float:
        pre = [make_cols(i + 1, strings) for i in range(4)]
        h.send_columns(pre[0][0], timestamps=pre[0][1])   # settle the shape
        t0 = time.perf_counter()
        n = 0
        i = 0
        while time.perf_counter() - t0 < seconds:
            cols, ts = pre[i % len(pre)]
            h.send_columns(cols, timestamps=ts)
            n += B
            i += 1
        return n / (time.perf_counter() - t0)

    eps_str = measure(strings=True, seconds=MEASURE_SECONDS)
    eps_pre = measure(strings=False, seconds=MEASURE_SECONDS)
    manager.shutdown()
    assert Counter.n > 0
    return eps_str, eps_pre


def bench_e2e_curve():
    """Operating-point curve: e2e throughput AND per-batch p99 at
    several (batch size, pipeline_depth) points — the trade-off surface
    the junction's adaptive batcher navigates (junction.py adaptive
    cap)."""
    rng = np.random.default_rng(7)
    sym_strings = np.array([f"S{i}" for i in range(NUM_KEYS)], dtype=object)
    points = []
    for B, depth in ((16_384, 1), (16_384, 8), (65_536, 1), (65_536, 8)):
        manager, rt, Counter = _make_e2e_runtime(pipeline_depth=depth)
        h = rt.get_input_handler("StockStream")
        warm_sym = sym_strings[np.arange(B, dtype=np.int64) % NUM_KEYS]
        h.send_columns({"symbol": warm_sym,
                        "price": np.ones(B, np.float32),
                        "volume": np.ones(B, np.int64)},
                       timestamps=np.zeros(B, np.int64))
        pre = []
        for i in range(4):
            ids = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
            pre.append(({
                "symbol": sym_strings[ids],
                "price": (rng.random(B) * 100.0).astype(np.float32),
                "volume": rng.integers(1, 1000, B, dtype=np.int64),
            }, np.arange(i * B, (i + 1) * B, dtype=np.int64)))
        h.send_columns(pre[0][0], timestamps=pre[0][1])
        lat = []
        n = 0
        i = 0
        t_end = time.perf_counter() + MEASURE_SECONDS / 2
        while time.perf_counter() < t_end:
            cols, ts = pre[i % 4]
            t0 = time.perf_counter()
            h.send_columns(cols, timestamps=ts)
            lat.append((time.perf_counter() - t0) * 1000.0)
            n += B
            i += 1
        manager.shutdown()
        assert Counter.n > 0
        lat = np.sort(np.asarray(lat))
        points.append({
            "batch": B, "pipeline_depth": depth,
            "eps": round(n / float(np.sum(lat) / 1000.0), 1),
            "p99_ms": round(float(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 3),
        })
    return points


def bench_pipeline_curve():
    """Dispatch-pipeline depth curve (ISSUE 5): the bench shape behind an
    @Async junction — the producer shape where the CompletionPump
    actually pipelines (the worker delivers back-to-back, so up to D
    device batches ride in flight while the next batch packs; sync sends
    flush per batch by design). D=1 is the old synchronous
    pull-per-batch engine. Records input events/sec send->fully-drained
    and the pump's metas-per-pull batching ratio per depth.

    The expected win is ``max(pack, step+pull)`` vs ``pack + step +
    pull`` (on a co-located chip: not measured); on a single-core CPU
    sandbox there is nothing to overlap with, so the acceptance bar
    there is no-regression (depth-2 >= 0.95x depth-1)."""
    from siddhi_tpu.core.stream.junction import _NOTHING

    B = int(os.environ.get("BENCH_PIPELINE_BATCH", 8192))
    app = """
@Async(buffer.size='64')
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.length({W})
select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
group by symbol
insert into OutStream;
""".format(W=WINDOW)
    rng = np.random.default_rng(23)
    sym_strings = np.array([f"S{i}" for i in range(NUM_KEYS)], dtype=object)

    def run_one(depth: int):
        from siddhi_tpu import SiddhiManager, StreamCallback
        from siddhi_tpu.core.util.config import InMemoryConfigManager

        manager = SiddhiManager()
        manager.set_config_manager(InMemoryConfigManager(
            {"siddhi_tpu.pipeline_depth": str(depth)}))
        rt = manager.create_siddhi_app_runtime(app)

        class Counter(StreamCallback):
            n = 0

            def receive_batch(self, batch, junction):
                Counter.n += batch.size

            def receive(self, events):
                Counter.n += len(events)

        rt.add_callback("OutStream", Counter())
        rt.query_runtimes["bench"].selector_plan.num_keys = 16_384
        rt.start()
        h = rt.get_input_handler("StockStream")
        j = rt.junctions["StockStream"]
        pump = rt.app_context.completion_pump

        def drained() -> bool:
            return (j._queue.empty() and j._inflight is _NOTHING
                    and not pump.has_pending)

        pre = []
        for i in range(4):
            ids = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
            pre.append(({
                "symbol": sym_strings[ids],
                "price": (rng.random(B) * 100.0).astype(np.float32),
                "volume": rng.integers(1, 1000, B, dtype=np.int64),
            }, np.arange(i * B, (i + 1) * B, dtype=np.int64)))
        warm_sym = sym_strings[np.arange(B, dtype=np.int64) % NUM_KEYS]
        h.send_columns({"symbol": warm_sym,
                        "price": np.ones(B, np.float32),
                        "volume": np.ones(B, np.int64)},
                       timestamps=np.zeros(B, np.int64))
        h.send_columns(pre[0][0], timestamps=pre[0][1])
        deadline = time.perf_counter() + 30.0
        while not drained() and time.perf_counter() < deadline:
            time.sleep(0.002)

        t0 = time.perf_counter()
        n = 0
        i = 0
        t_end = t0 + MEASURE_SECONDS / 2
        while time.perf_counter() < t_end:
            cols, ts = pre[i % 4]
            h.send_columns(cols, timestamps=ts)   # blocks only on full queue
            n += B
            i += 1
        deadline = time.perf_counter() + 60.0
        while not drained() and time.perf_counter() < deadline:
            time.sleep(0.002)
        dt = time.perf_counter() - t0
        tel = rt.app_context.telemetry.snapshot()
        metas = tel["counters"].get("pipeline.metas", 0)
        pulls = tel["counters"].get("pipeline.pulls", 0)
        stalls = tel["counters"].get("pipeline.stalls", 0)
        manager.shutdown()
        assert Counter.n > 0
        return {
            "depth": depth, "eps": round(n / dt, 1),
            "metas_per_pull": round(metas / pulls, 2) if pulls else None,
            "stalls": stalls,
        }

    return [run_one(d) for d in (1, 2, 4, 8)]


def bench_serving():
    """Serving-tier shard curve (ISSUE 6): ingest eps and on-demand store
    query p50/p99 under MIXED load, for 1/2/4/8 aggregation shards. An
    ingest thread pumps columnar batches into a grouped multi-granularity
    aggregation the whole time while two query threads fire canned
    `within ... per ...` reads (in-process `rt.query` — the REST hop is
    measured by tools/serve_soak.py). Sharded reads scatter per-shard
    epoch-pinned partials and ordered-merge them without the app barrier,
    so the signal is (a) ingest eps holding steady under the query storm
    and (b) query latency vs shard count."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.util.config import InMemoryConfigManager
    from siddhi_tpu.observability.histogram import Histogram

    app = """
@app:name('BenchServe')
define stream TradeStream (symbol string, price double, ts long);
define aggregation TradeAgg
from TradeStream
select symbol, sum(price) as total, count() as n
group by symbol
aggregate by ts every sec ... day;
"""
    KEYS, B, TS_RANGE = 50, 512, 600_000
    measure_s = float(os.environ.get("BENCH_SERVING_SECONDS", 8.0))
    rng = np.random.default_rng(11)
    syms = np.array([f"S{i}" for i in range(KEYS)], dtype=object)
    queries = [
        f"from TradeAgg within {lo}L, {lo + 300_000}L per '{p}' "
        f"select AGG_TIMESTAMP, symbol, total, n"
        for p in ("seconds", "minutes", "hours")
        for lo in (0, 150_000, 300_000)
    ]

    def run_one(shards: int):
        import threading

        manager = SiddhiManager()
        manager.set_config_manager(InMemoryConfigManager(
            {"siddhi_tpu.agg_shards": str(shards)}))
        rt = manager.create_siddhi_app_runtime(app)
        h = rt.get_input_handler("TradeStream")
        pre = []
        for i in range(4):
            ids = rng.integers(0, KEYS, B)
            pre.append({
                "symbol": syms[ids],
                "price": (rng.random(B) * 100.0).astype(np.float64),
                "ts": rng.integers(0, TS_RANGE, B, dtype=np.int64)})
        h.send_columns(pre[0], timestamps=np.arange(B, dtype=np.int64))
        for q in queries:    # warm the on-demand plans + jit shapes
            rt.query(q)

        stop = threading.Event()
        sent = {"n": 0}

        def ingest():
            i = 0
            while not stop.is_set():
                h.send_columns(pre[i % 4],
                               timestamps=np.arange(B, dtype=np.int64))
                sent["n"] += B
                i += 1

        hist = Histogram()
        qcount = {"n": 0}

        def querier(ci):
            qrng = np.random.default_rng(100 + ci)
            while not stop.is_set():
                q = queries[int(qrng.integers(0, len(queries)))]
                t0 = time.perf_counter()
                rt.query(q)
                hist.record((time.perf_counter() - t0) * 1000.0)
                qcount["n"] += 1

        threads = [threading.Thread(target=ingest, daemon=True)] + [
            threading.Thread(target=querier, args=(i,), daemon=True)
            for i in range(2)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(measure_s)
        stop.set()
        for t in threads:
            t.join(30)
        dt = time.perf_counter() - t0
        manager.shutdown()
        return {
            "shards": shards,
            "ingest_eps": round(sent["n"] / dt, 1),
            "queries": qcount["n"],
            "query_qps": round(qcount["n"] / dt, 1),
            "query_p50_ms": round(hist.quantile(0.50), 2),
            "query_p99_ms": round(hist.quantile(0.99), 2),
        }

    return [run_one(s) for s in (1, 2, 4, 8)]


def bench_host_pipeline():
    """Host-pipeline throughput with the device step STUBBED: the full
    ingest pump — string columns -> dictionary encode (native strdict.cpp)
    -> HostBatch -> junction -> group keyer -> step dispatch/defer/flush
    bookkeeping -> emit — with the jitted device function replaced by a
    host no-op. Isolates Python/host cost from device compute: on a live
    TPU the e2e ceiling is min(host_pipeline, device, encode-overlap).
    Reference counterpart: the whole JVM engine IS this pipeline
    (StreamJunction.java:156-165 -> ProcessStreamReceiver.java:74-184),
    measured at ~8.5M eps by tools/baseline_cpp.

    Also measures ingest_csv_eps: the same pump fed by the NATIVE CSV
    loader (csv_loader.cpp) parsing raw transport bytes, the analog of the
    reference's source->mapper->event path."""
    from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY

    manager, rt, Counter = _make_e2e_runtime()
    h = rt.get_input_handler("StockStream")
    q = rt.query_runtimes["bench"]

    rng = np.random.default_rng(3)
    B = BATCH
    sym_strings = np.array([f"S{i}" for i in range(NUM_KEYS)], dtype=object)

    def make_cols(i):
        ids = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
        return {
            "symbol": sym_strings[ids],
            "price": (rng.random(B) * 100.0).astype(np.float32),
            "volume": rng.integers(1, 1000, B, dtype=np.int64),
        }, np.arange(i * B, (i + 1) * B, dtype=np.int64)

    warm_sym = sym_strings[np.arange(B, dtype=np.int64) % NUM_KEYS]
    h.send_columns({"symbol": warm_sym,
                    "price": np.ones(B, np.float32),
                    "volume": np.ones(B, np.int64)},
                   timestamps=np.zeros(B, np.int64))
    pre = [make_cols(i + 1) for i in range(4)]
    h.send_columns(pre[0][0], timestamps=pre[0][1])

    # stub the device step: state passes through untouched, the output is
    # an empty (all-invalid) packed batch whose __meta__ says
    # overflow=0/notify=-1/size=0 — every HOST stage still runs for real
    empty_meta = np.array([0, -1, 0], np.int64)

    def stub_step(state, cols, now):
        return state, {
            VALID_KEY: np.zeros(1, bool),
            TS_KEY: np.zeros(1, np.int64),
            TYPE_KEY: np.zeros(1, np.int8),
            "__meta__": empty_meta,
        }

    q._step = stub_step

    t0 = time.perf_counter()
    n = 0
    i = 0
    while time.perf_counter() - t0 < MEASURE_SECONDS:
        cols, ts = pre[i % len(pre)]
        h.send_columns(cols, timestamps=ts)
        n += B
        i += 1
    eps_pipeline = n / (time.perf_counter() - t0)

    # ---- native CSV ingest -> the same stubbed pump
    from siddhi_tpu.native import CsvLoader

    loader = CsvLoader(rt.stream_definitions["StockStream"],
                       rt.app_context.string_dictionary)
    lines = []
    ids = rng.integers(0, NUM_KEYS, B)
    prices = rng.random(B) * 100.0
    vols = rng.integers(1, 1000, B)
    for j in range(B):
        lines.append(f"S{ids[j]},{prices[j]:.4f},{vols[j]}")
    payload = ("\n".join(lines) + "\n").encode()
    cols0, nrows = loader.parse(payload)
    h.send_columns(cols0, timestamps=np.arange(nrows, dtype=np.int64))

    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < MEASURE_SECONDS:
        cols_j, nrows = loader.parse(payload)
        h.send_columns(cols_j, timestamps=np.arange(nrows, dtype=np.int64))
        n += nrows
    eps_csv = n / (time.perf_counter() - t0)
    manager.shutdown()
    return eps_pipeline, eps_csv


_PARTITIONED_APP = """
define stream StockStream (symbol string, price float, volume long);
partition with (symbol of StockStream)
begin
  @info(name = 'bench')
  from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
  insert into OutStream;
end;
""".format(W=WINDOW)


def bench_mesh_scaling():
    """Strong scaling of the partitioned flagship (per-key length(1000)
    window -> avg/sum over 10k keys) under round-6 DEVICE-side
    repartitioning (``device_route_query_step``): the unrouted batch
    enters the jitted step B-sharded, owners are computed on device, rows
    exchange shard-to-shard with a dense all_to_all inside the shard_map
    body, and emitted rows re-merge into unsharded order on the way out —
    the round-5 host router is gone from the loop entirely. Three
    numbers per run: the UNROUTED single-shard jit (the bar the 1-dev
    routed point must hold 0.9x of), the legacy host-routed 1-dev point
    (the before), and the device-routed curve over 1/2/4/8 devices, as
    many as this host has. On a virtual CPU mesh shards share one host's
    cores, so the curve there bounds overhead rather than demonstrating
    speedup."""
    import warnings

    import jax

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
    from siddhi_tpu.parallel.mesh import (
        device_route_query_step, make_mesh, route_batch_to_shards,
        shard_keyed_query_step)

    rng = np.random.default_rng(5)
    B = BATCH

    def make_batch(i):
        sym = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
        return {
            TS_KEY: np.arange(i * B, (i + 1) * B, dtype=np.int64),
            TYPE_KEY: np.zeros(B, np.int8),
            VALID_KEY: np.ones(B, bool),
            "symbol": sym,
            "symbol?": np.zeros(B, bool),
            "price": (rng.random(B) * 100.0).astype(np.float32),
            "price?": np.zeros(B, bool),
            "volume": rng.integers(1, 1000, B, dtype=np.int64),
            "volume?": np.zeros(B, bool),
            GK_KEY: sym.astype(np.int32),
            PK_KEY: sym.astype(np.int32),
        }

    def _pow2(n):
        k = 16
        while k < n:
            k *= 2
        return k

    batches = [make_batch(i) for i in range(4)]

    def timed_loop(fn):
        for i in range(3):
            st = fn(i)
        jax.block_until_ready(st)
        t0 = time.perf_counter()
        n = i = 0
        while True:
            st = fn(i)
            n += B
            i += 1
            if i % 10 == 0:
                jax.block_until_ready(st)
                if time.perf_counter() - t0 >= MEASURE_SECONDS / 2:
                    break
        jax.block_until_ready(st)
        return n / (time.perf_counter() - t0)

    def fresh_runtime(num_keys):
        manager = SiddhiManager()
        rt = manager.create_siddhi_app_runtime(_PARTITIONED_APP)
        rt.start()
        q = rt.query_runtimes["bench"]
        q.selector_plan.num_keys = num_keys
        q._win_keys = num_keys
        return manager, q

    result = {}

    # --- unrouted single-shard baseline: the plain jitted step
    manager, q = fresh_runtime(16_384)
    step = jax.jit(q.build_step_fn(), donate_argnums=0)
    holder = {"st": q._init_state()}

    def run_plain(i):
        holder["st"], _out = step(holder["st"], batches[i % 4], np.int64(0))
        return holder["st"]

    result["unrouted_1dev"] = timed_loop(run_plain)
    manager.shutdown()

    # --- legacy host router at 1 dev (the round-5 "before" point)
    manager, q = fresh_runtime(16_384)
    hstep, hstate = shard_keyed_query_step(q, make_mesh(1), rows_per_shard=B)
    hold = {"st": hstate}

    def run_host(i):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            rb = route_batch_to_shards(batches[i % 4], 1, B)
        hold["st"], _out = hstep(hold["st"], rb, np.int64(0))
        return hold["st"]

    result["host_routed_1dev"] = timed_loop(run_host)
    manager.shutdown()

    # --- device-routed curve: routing inside the jitted step
    device_routed = {}
    for n_dev in (n for n in (1, 2, 4, 8) if n <= len(jax.devices())):
        manager, q = fresh_runtime(16_384)
        rows_per_shard = B if n_dev == 1 else int(B / n_dev * 1.25)
        step3, state = device_route_query_step(
            q, make_mesh(n_dev), rows_per_shard=rows_per_shard)
        hold = {"st": state}

        def run_dev(i):
            hold["st"], out = step3(hold["st"], batches[i % 4], np.int64(0))
            return hold["st"]

        device_routed[str(n_dev)] = timed_loop(run_dev)
        # balanced random keys must never trip the exchange quota here
        _st, out = step3(hold["st"], batches[0], np.int64(0))
        assert int(np.asarray(out["__meta__"])[3]) == 0, "exchange overflow"
        manager.shutdown()
    result["device_routed"] = device_routed
    result["routed_vs_unrouted_1dev"] = round(
        device_routed["1"] / result["unrouted_1dev"], 3)
    return result


def bench_nfa_p99():
    """Config #4: `every e1=A -> e2=B[e2.v > e1.v] within 5 sec` over 10k
    partition keys, through the loop-free two-step NFA kernel
    (ops/nfa.py `_apply_stream_fast`, round 5). Two operating points from
    one session: p99 per-batch latency at the LATENCY batch size (1024
    rows — the adaptive batcher's low-delay end), and aggregate events/sec
    at the THROUGHPUT batch size (4096 — amortizes per-step dispatch;
    the junction's adaptive cap picks this trade-off live)."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    app = """
    @app:playback
    define stream AStream (k string, v double);
    define stream BStream (k string, v double);
    partition with (k of AStream, k of BStream)
    begin
      @info(name = 'nfa')
      from every e1=AStream -> e2=BStream[e2.v > e1.v] within 5 sec
      select e1.v as v1, e2.v as v2
      insert into MatchStream;
    end;
    """
    manager = SiddhiManager()
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    # config #4 holds at most a couple of pending matches per key: 8 slots
    # (vs the 32 default) quarters the [K, S] state and the emission pull;
    # pipeline_depth=2 lets the A-batch and B-batch dispatches ride the
    # pump back-to-back (completion.py; wait-free NFA plans are eligible)
    manager.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.nfa_slots": "8", "siddhi_tpu.pipeline_depth": "2"}))
    rt = manager.create_siddhi_app_runtime(app)

    class Counter(StreamCallback):
        n = 0

        def receive_batch(self, batch, junction):
            Counter.n += batch.size

        def receive(self, events):
            Counter.n += len(events)

    rt.add_callback("MatchStream", Counter())
    ha = rt.get_input_handler("AStream")
    hb = rt.get_input_handler("BStream")

    rng = np.random.default_rng(2)
    B_LAT = int(os.environ.get("BENCH_NFA_BATCH", 1024))
    B_THR = int(os.environ.get("BENCH_NFA_BATCH_THR", 4096))

    # pre-size the key space so key registration never grows capacity
    # mid-run (each pow2 growth would re-jit the [K, S] step), and warm
    # BOTH measured batch shapes — one compiled shape per (stream, B)
    q = rt.query_runtimes["nfa"]
    q._win_keys = 16_384
    q.selector_plan.num_keys = 16_384
    for B in {B_LAT, B_THR}:
        for c0 in range(0, NUM_KEYS, B):
            wk = np.array([f"K{i}" for i in range(c0, c0 + B)], dtype=object)
            wts = np.full(B, 1_000, np.int64)
            ha.send_columns({"k": wk, "v": np.zeros(B)}, timestamps=wts)
            hb.send_columns({"k": wk, "v": np.ones(B)}, timestamps=wts + 1)

    t_ms = 10_000

    def measure(B: int, seconds: float):
        nonlocal t_ms
        lat = []
        n = 0
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            keys = rng.integers(0, NUM_KEYS, B)
            ka = np.array([f"K{i}" for i in keys], dtype=object)
            va = rng.random(B) * 100.0
            ts = np.full(B, t_ms, np.int64)
            t0 = time.perf_counter()
            ha.send_columns({"k": ka, "v": va}, timestamps=ts)
            hb.send_columns({"k": ka, "v": va + 1.0}, timestamps=ts + 1)
            lat.append((time.perf_counter() - t0) * 1000.0 / 2)  # per batch
            n += 2 * B
            t_ms += 10
        lat = np.sort(np.asarray(lat))
        p99 = float(lat[min(len(lat) - 1, int(len(lat) * 0.99))])
        return p99, n / float(np.sum(lat) * 2 / 1000.0)

    p99, _ = measure(B_LAT, MEASURE_SECONDS / 2)       # latency point
    measure(B_THR, 1.0)                                # settle the new shape
    _, eps = measure(B_THR, MEASURE_SECONDS)           # throughput point
    manager.shutdown()
    assert Counter.n > 0
    return p99, eps


def bench_fanout():
    """Fan-out amortization curve (ISSUE 4): N identical bench-shape
    queries (10k-key length(1000) -> avg/sum group by symbol) subscribed
    to ONE stream, fused (one jitted dispatch + one combined __meta__
    pull per junction batch — core/query/fused_fanout.py) vs unfused
    (N dispatches + N pulls). Records, per (n_queries, mode):
    input events/sec, p99 per-batch send latency, and the measured
    dispatches per batch (from the telemetry counters, not assumed).
    The batch size (BENCH_FANOUT_BATCH, default 8192) sits at the
    dispatch-bound end of the e2e curve, where fan-out overhead is the
    cost being amortized."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    B = int(os.environ.get("BENCH_FANOUT_BATCH", 8192))
    rng = np.random.default_rng(11)
    sym_strings = np.array([f"S{i}" for i in range(NUM_KEYS)], dtype=object)
    q_tmpl = """
    @info(name = 'q{I}')
    from StockStream#window.length({W})
    select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
    group by symbol
    insert into Out{I};"""

    def run_one(n: int, fused: bool):
        app = ("define stream StockStream "
               "(symbol string, price float, volume long);\n")
        app += "\n".join(q_tmpl.format(I=i, W=WINDOW) for i in range(n))
        manager = SiddhiManager()
        manager.set_config_manager(InMemoryConfigManager(
            {"siddhi_tpu.fuse_fanout": "1" if fused else "0"}))
        rt = manager.create_siddhi_app_runtime(app)

        class Counter(StreamCallback):
            n_out = 0

            def receive_batch(self, batch, junction):
                Counter.n_out += batch.size

            def receive(self, events):
                Counter.n_out += len(events)

        for i in range(n):
            rt.add_callback(f"Out{i}", Counter())
            rt.query_runtimes[f"q{i}"].selector_plan.num_keys = 16_384
        h = rt.get_input_handler("StockStream")
        warm_sym = sym_strings[np.arange(B, dtype=np.int64) % NUM_KEYS]
        h.send_columns({"symbol": warm_sym,
                        "price": np.ones(B, np.float32),
                        "volume": np.ones(B, np.int64)},
                       timestamps=np.zeros(B, np.int64))
        pre = []
        for i in range(4):
            ids = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
            pre.append(({
                "symbol": sym_strings[ids],
                "price": (rng.random(B) * 100.0).astype(np.float32),
                "volume": rng.integers(1, 1000, B, dtype=np.int64),
            }, np.arange(i * B, (i + 1) * B, dtype=np.int64)))
        h.send_columns(pre[0][0], timestamps=pre[0][1])   # settle the shape
        h.send_columns(pre[1][0], timestamps=pre[1][1])
        tel = rt.app_context.telemetry
        base = tel.snapshot()
        # three windows per mode, best-window eps: a single-core sandbox
        # jitters +-15% across 2 s windows, and the N=1 ratio (where
        # fused == unfused code paths exactly) must not drown in it
        lat = []
        n_batches = 0
        best_eps = 0.0
        i = 0
        for _w in range(3):
            w_lat = []
            t_end = time.perf_counter() + MEASURE_SECONDS / 3
            while time.perf_counter() < t_end:
                cols, ts = pre[i % 4]
                t0 = time.perf_counter()
                h.send_columns(cols, timestamps=ts)
                w_lat.append((time.perf_counter() - t0) * 1000.0)
                i += 1
            best_eps = max(best_eps,
                           len(w_lat) * B / float(np.sum(w_lat) / 1000.0))
            lat.extend(w_lat)
            n_batches += len(w_lat)
        snap = tel.snapshot()
        if fused and n > 1:
            dispatches = (snap["counters"]["fanout.StockStream.dispatches"]
                          - base["counters"]["fanout.StockStream.dispatches"])
        else:
            dispatches = 0
            for qi in range(n):
                rec = snap["jit"].get(f"query.q{qi}.step",
                                      {"compiles": 0, "hits": 0})
                rec0 = base["jit"].get(f"query.q{qi}.step",
                                       {"compiles": 0, "hits": 0})
                dispatches += (rec["compiles"] + rec["hits"]
                               - rec0["compiles"] - rec0["hits"])
        manager.shutdown()
        assert Counter.n_out > 0
        lat = np.sort(np.asarray(lat))
        return {
            "eps": round(best_eps, 1),
            "p99_ms": round(float(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 3),
            "dispatches_per_batch": round(dispatches / max(1, n_batches), 2),
        }

    points = []
    for n in (1, 2, 4, 8):
        unfused = run_one(n, fused=False)
        fused = run_one(n, fused=True)
        points.append({
            "n_queries": n, "batch": B,
            "eps_unfused": unfused["eps"], "eps_fused": fused["eps"],
            "speedup": round(fused["eps"] / unfused["eps"], 3),
            "p99_unfused_ms": unfused["p99_ms"],
            "p99_fused_ms": fused["p99_ms"],
            "dispatches_per_batch_unfused": unfused["dispatches_per_batch"],
            "dispatches_per_batch_fused": fused["dispatches_per_batch"],
        })
        print(json.dumps({"partial": points[-1]}), flush=True)
    return points


def bench_join():
    """Device join engine curve (ISSUE 9): a stream-stream length-window
    join driven through the real ingest path under two mixes —
    **probe-heavy** (the build side is pre-filled to its window capacity
    and held; every measured batch triggers probes against it) and
    **insert-heavy** (batches alternate sides under a selective ``on``
    condition, so window insert + directory upkeep dominate) — across
    join partition counts P in {1, 2, 4, 8} and pipeline depth {1, 2},
    plus the legacy synchronous probe path at depth 1 as the acceptance
    reference (the engine must hold >= 0.9x legacy at depth 1)."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    B = int(os.environ.get("BENCH_JOIN_BATCH", 2048))
    W = int(os.environ.get("BENCH_JOIN_WINDOW", 2048))
    K = 512                       # join key cardinality
    rng = np.random.default_rng(23)
    sym_strings = np.array([f"S{i}" for i in range(K)], dtype=object)
    app = f"""
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length({W}) join R#window.length({W})
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""

    def batch(i, side):
        ids = rng.integers(0, K, B, dtype=np.int64)
        return ({"sym": sym_strings[ids],
                 ("lv" if side == "L" else "rv"):
                     rng.integers(0, 1000, B, dtype=np.int64)},
                np.arange(i * B, (i + 1) * B, dtype=np.int64))

    def run_one(mode: str, P: int, depth: int, mix: str) -> float:
        manager = SiddhiManager()
        manager.set_config_manager(InMemoryConfigManager({
            "siddhi_tpu.join_engine": mode,
            "siddhi_tpu.join_partitions": str(P),
            "siddhi_tpu.pipeline_depth": str(depth),
            "siddhi_tpu.window_capacity": str(W),
        }))
        rt = manager.create_siddhi_app_runtime(app)

        class Counter(StreamCallback):
            n_out = 0

            def receive_batch(self, b, junction):
                Counter.n_out += b.size

            def receive(self, events):
                Counter.n_out += len(events)

        rt.add_callback("JOut", Counter())
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        if mix == "probe":
            # fill the build side to capacity once; measured batches all
            # probe (the PanJoin case: the partition directory cuts the
            # [B, W] condition surface ~P-fold)
            cols, ts = batch(0, "R")
            for j in range(W // B):
                hr.send_columns(cols, timestamps=ts)
        # warm both side steps' compiles out of the measure window
        for j in range(2):
            hl.send_columns(*batch(1 + j, "L"))
            hr.send_columns(*batch(3 + j, "R"))
        pre = [(side, batch(5 + j, side)) for j, side in enumerate(
            ["L"] * 8 if mix == "probe" else ["L", "R"] * 4)]
        n, i = 0, 0
        t0 = time.perf_counter()
        t_end = t0 + MEASURE_SECONDS / 2
        while time.perf_counter() < t_end:
            side, (cols, ts) = pre[i % len(pre)]
            (hl if side == "L" else hr).send_columns(cols, timestamps=ts)
            n += B
            i += 1
        eps = n / (time.perf_counter() - t0)
        manager.shutdown()
        assert Counter.n_out > 0
        return eps

    points = []
    for mix in ("probe", "insert"):
        ref = run_one("legacy", 1, 1, mix)
        rec = {"mix": mix, "batch": B, "window": W,
               "eps_legacy_d1": round(ref, 1), "device": []}
        for P in (1, 2, 4, 8):
            for depth in (1, 2):
                eps = run_one("device", P, depth, mix)
                rec["device"].append({
                    "P": P, "depth": depth, "eps": round(eps, 1),
                    "vs_legacy_d1": round(eps / ref, 3)})
                print(json.dumps({"partial": {"mix": mix, "P": P,
                                              "depth": depth,
                                              "eps": round(eps, 1)}}),
                      flush=True)
        points.append(rec)
    return points


def bench_ingest():
    """Multicore ingest front door curve (ISSUE 13): pack-path
    throughput over identical data for (a) the per-event
    ``HostBatch.from_events`` path, (b) the raw string-column
    ``from_columns`` path (dictionary encodes every batch), (c) the
    zero-copy wire path (``decode_frame`` LUT gather ->
    ``from_columns`` on pre-encoded ids — the POST /ingest/{stream}
    server cost), and (d) the parallel pack-pool curve over pool sizes
    {0, 2, 4} with a bit-identity assertion per point. The record
    carries ``host_cores`` explicitly: on a single-core sandbox the
    pool points bound coordination overhead, they cannot demonstrate
    the multicore speedup (the wire path's per-event-Python
    elimination is core-count-independent)."""
    from types import SimpleNamespace

    from siddhi_tpu.core.event import Event, HostBatch, StringDictionary
    from siddhi_tpu.core.stream.input.pack_pool import IngestPackPool
    from siddhi_tpu.core.stream.input.wire import (
        DecoderRegistry, WireEncoder, decode_frame)
    from siddhi_tpu.observability.telemetry import TelemetryRegistry
    from siddhi_tpu.query_api.definitions import (
        Attribute, AttrType, StreamDefinition)

    definition = StreamDefinition("StockStream", attributes=[
        Attribute("symbol", AttrType.STRING),
        Attribute("price", AttrType.FLOAT),
        Attribute("volume", AttrType.LONG)])
    B = BATCH
    rng = np.random.default_rng(17)
    ids = rng.integers(0, NUM_KEYS, B)
    syms = np.array([f"S{i}" for i in ids], dtype=object)
    price = (rng.random(B) * 100.0).astype(np.float32)
    volume = rng.integers(1, 1000, B, dtype=np.int64)
    ts = np.arange(B, dtype=np.int64)
    cols = {"symbol": syms, "price": price, "volume": volume}
    events = [Event(timestamp=int(t), data=[s, float(p), int(v)])
              for t, s, p, v in zip(ts, syms, price, volume)]

    def measure(fn, seconds=MEASURE_SECONDS / 2):
        fn()
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            fn()
            n += B
        return n / (time.perf_counter() - t0)

    d1 = StringDictionary()
    eps_events = measure(
        lambda: HostBatch.from_events(events, definition, d1))

    d2 = StringDictionary()
    eps_cols = measure(
        lambda: HostBatch.from_columns(cols, definition, d2,
                                       timestamps=ts))

    enc = WireEncoder()
    first = enc.encode(cols, timestamps=ts)
    frame = enc.encode(cols, timestamps=ts)     # steady state: no delta
    d3 = StringDictionary()
    reg = DecoderRegistry()
    decode_frame(first, definition, d3, reg)

    def wire_once():
        data, wts = decode_frame(frame, definition, d3, reg)
        HostBatch.from_columns(data, definition, d3, timestamps=wts)

    eps_wire = measure(wire_once)

    # --- parallel pack-pool curve, bit-identity asserted per point
    ref_d = StringDictionary()
    ref = HostBatch.from_events(events, definition, ref_d)
    pool_curve = []
    for workers in (0, 2, 4):
        if workers == 0:
            pool_curve.append({"pool": 0, "eps": round(eps_events, 1)})
            continue
        ctx = SimpleNamespace(name=f"bench-pool{workers}",
                              telemetry=TelemetryRegistry())
        pool = IngestPackPool(ctx, workers=workers, split_rows=8192)
        dp = StringDictionary()
        got = HostBatch.from_events(events, definition, dp, pool=pool)
        assert all(np.array_equal(got.cols[k], ref.cols[k])
                   for k in ref.cols), "pool pack diverged from inline"
        assert dp._to_str == ref_d._to_str, "dictionary order diverged"
        eps = measure(lambda: HostBatch.from_events(
            events, definition, dp, pool=pool))
        pool.shutdown()
        pool_curve.append({"pool": workers, "eps": round(eps, 1),
                           "vs_inline": round(eps / eps_events, 3)})

    return {
        "host_cores": os.cpu_count(),
        "batch": B,
        "frame_bytes": len(frame),
        "from_events_eps": round(eps_events, 1),
        "from_columns_str_eps": round(eps_cols, 1),
        "wire_eps": round(eps_wire, 1),
        "wire_vs_events": round(eps_wire / eps_events, 2),
        "pool_curve": pool_curve,
        "pool_identical": True,
    }


def bench_autopilot():
    """Closed-loop controller soak (ISSUE 16): one bursty "diurnal"
    feed — alternating quiet phases (idle gap before each batch) and
    burst phases (back-to-back) over an IDENTICAL chunk sequence —
    through the headline grouped-agg app under three configurations:
    the worst static operating point (depth 1, no ingest pool), the
    best static point (depth 4, pool 2), and autopilot ON starting
    from the worst point at an aggressive cadence. Records per-config
    events/sec + per-batch p99 + the controller's tick/freeze/decision
    counts, and asserts the autopilot run's output rows are
    bit-identical to both static runs — live actuation must never
    change semantics."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.autopilot import AutopilotController
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    B = 8_192
    N_KEYS = 1_024
    N_BATCH = 24
    rng = np.random.default_rng(23)
    sym_strings = np.array([f"S{i}" for i in range(N_KEYS)], dtype=object)
    chunks = []
    for i in range(N_BATCH):
        ids = rng.integers(0, N_KEYS, B, dtype=np.int64)
        chunks.append((
            {"symbol": sym_strings[ids],
             "price": (rng.random(B) * 100.0).astype(np.float32),
             "volume": rng.integers(1, 1000, B, dtype=np.int64)},
            np.arange(i * B, (i + 1) * B, dtype=np.int64)))
    # diurnal schedule: quiet-phase batches idle 20 ms before sending
    # (trough), burst-phase batches go back-to-back (peak); the SAME
    # batches in the SAME order for every configuration
    quiet = {i for i in range(N_BATCH) if (i // 4) % 2 == 0}

    def run(knobs, autopilot=False):
        manager = SiddhiManager()
        cfg = {"siddhi_tpu.ingest_split": "8"}
        cfg.update(knobs)
        if autopilot:
            cfg.update({"siddhi_tpu.autopilot": "on",
                        "siddhi_tpu.autopilot_interval_s": "0.05",
                        "siddhi_tpu.autopilot_cooldown_s": "0.1"})
        manager.set_config_manager(InMemoryConfigManager(cfg))
        rt = manager.create_siddhi_app_runtime(_APP)
        rows = []

        class Sink(StreamCallback):
            def receive(self, events):
                rows.extend(tuple(e.data) for e in events)

        rt.add_callback("OutStream", Sink())
        rt.start()
        rt.query_runtimes["bench"].selector_plan.num_keys = 2_048
        h = rt.get_input_handler("StockStream")
        # warm OUTSIDE the timed window: a full-key batch at the
        # measured shape settles the compiles every config would hit
        warm_ids = np.arange(B, dtype=np.int64) % N_KEYS
        h.send_columns({"symbol": sym_strings[warm_ids],
                        "price": np.ones(B, np.float32),
                        "volume": np.ones(B, np.int64)},
                       timestamps=np.zeros(B, np.int64))
        warm_rows = len(rows)
        ctl = AutopilotController.instance()
        lat = []
        t0 = time.perf_counter()
        for i, (cols, ts) in enumerate(chunks):
            if i in quiet:
                time.sleep(0.02)
            tb = time.perf_counter()
            h.send_columns(cols, timestamps=ts)
            lat.append(time.perf_counter() - tb)
            if autopilot and i % 4 == 3:
                # deterministic cadence on top of the interval thread —
                # the same manual-tick drive the soak and tests use
                ctl.tick(rt.name)
        elapsed = time.perf_counter() - t0
        ticks = freezes = applied = logged = 0
        if autopilot:
            rep = ctl.report(rt.name)["apps"][rt.name]
            ticks, freezes = rep["ticks"], rep["freezes"]
            logged = len(rep["decisions"])
            applied = sum(1 for d in rep["decisions"] if d.get("applied"))
        out_rows = rows[warm_rows:]
        manager.shutdown()
        return {
            "eps": round(N_BATCH * B / elapsed, 1),
            "p99_ms": round(float(np.percentile(
                np.array(lat) * 1e3, 99)), 3),
            "ticks": ticks,
            "freezes": freezes,
            "decisions_logged": logged,
            "decisions_applied": applied,
        }, out_rows

    worst, ref = run({"siddhi_tpu.pipeline_depth": "1"})
    best, ref_best = run({"siddhi_tpu.pipeline_depth": "4",
                          "siddhi_tpu.ingest_pool": "2"})
    ap, ap_rows = run({"siddhi_tpu.pipeline_depth": "1"}, autopilot=True)
    assert ref_best == ref, "static configs diverged"
    assert ap_rows == ref, "autopilot run diverged from static baseline"
    return {
        "batch": B,
        "batches": N_BATCH,
        "keys": N_KEYS,
        "static_worst": worst,
        "static_best": best,
        "autopilot": ap,
        "autopilot_vs_worst": round(ap["eps"] / worst["eps"], 3),
        "autopilot_vs_best": round(ap["eps"] / best["eps"], 3),
        "identical": True,
    }


# ------------------------------------------------------------ dispatcher


def _device_eps():
    eps = bench_device()
    return {"eps": eps, "vs_baseline": round(eps / MEASURED_BASELINE_EPS, 3)}


def _e2e():
    eps_str, eps_pre = bench_e2e()
    return {"eps_str": eps_str, "eps_pre": eps_pre}


def _host_pipeline():
    eps_pipeline, eps_csv = bench_host_pipeline()
    return {"eps_pipeline": eps_pipeline, "eps_csv": eps_csv}


def _nfa():
    p99, eps = bench_nfa_p99()
    return {"p99_ms": p99, "eps": eps}


SECTIONS = {
    "device": _device_eps,
    "e2e": _e2e,
    "host_pipeline": _host_pipeline,
    "nfa": _nfa,
    "mesh": lambda: {"mesh": bench_mesh_scaling()},
    "e2e_curve": lambda: {"points": bench_e2e_curve()},
    "fanout": lambda: {"points": bench_fanout()},
    "pipeline": lambda: {"points": bench_pipeline_curve()},
    "join": lambda: {"points": bench_join()},
    "ingest": lambda: {"ingest": bench_ingest()},
    "serving": lambda: {"points": bench_serving()},
    "autopilot": lambda: {"autopilot": bench_autopilot()},
}


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--section", action="append", choices=sorted(SECTIONS),
                    help="section to run (repeatable)")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)
    if args.list or not args.section:
        print("\n".join(sorted(SECTIONS)))
        return 0 if args.list else 2

    import jax

    from siddhi_tpu.core.util.compile_cache import place_compile_cache

    place_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    for name in args.section:
        t0 = time.perf_counter()
        result = SECTIONS[name]()       # a failing section raises: rc != 0
        print(json.dumps({
            "section": name, "device": device, "host_cores": os.cpu_count(),
            "batch": BATCH, "measure_seconds": MEASURE_SECONDS,
            "seconds": round(time.perf_counter() - t0, 1), **result}),
            flush=True)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
