"""Serve soak: thousands of concurrent on-demand store queries against
live ingest — the "millions of users refreshing dashboards" workload
(ROADMAP item 3), plus a kill-one-shard restore mid-soak.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Drives one mesh-sharded aggregation app through the REST surface:

- an ingest thread pumps columnar batches into the aggregation the whole
  time (every event counted, so the final exactness check is absolute);
- N client threads fire on-demand `within ... per ...` queries as fast
  as the admission tier lets them (2xx answers and 503 sheds both
  counted; latency recorded client-side per granularity);
- mid-soak, one aggregation shard is killed and rebuilt from its last
  checkpoint blob + per-shard WAL suffix while the clients keep firing;
- at the end ingest quiesces and the stitched rollup is compared against
  an exact host-side recount of every sent event: **zero lost, zero
  duplicated rows** or the script exits non-zero.

    JAX_PLATFORMS=cpu python tools/serve_soak.py \
        [--clients 64] [--queries 2000] [--shards 4] [--seconds 20]

Prints one JSON line with sustained ingest eps, query throughput and
p50/p95/p99 — the PERF.md artifact.
"""

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from siddhi_tpu import SiddhiManager
from siddhi_tpu.core.util.config import InMemoryConfigManager
from siddhi_tpu.observability.histogram import Histogram
from siddhi_tpu.service import SiddhiRestService

APP = """
@app:name('SoakApp')
@app:statistics('true')
define stream TradeStream (symbol string, price double, ts long);
define aggregation TradeAgg
from TradeStream
select symbol, sum(price) as total, count() as n
group by symbol
aggregate by ts every sec ... day;
"""

PERS = ("seconds", "minutes", "hours")


def _req(port, method, path, body=None, text=False, timeout=30):
    data = None
    headers = {}
    if body is not None:
        data = body.encode() if text else json.dumps(body).encode()
        headers["Content-Type"] = "text/plain" if text else "application/json"
    r = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=data,
                               method=method, headers=headers)
    with urllib.request.urlopen(r, timeout=timeout) as resp:
        return json.loads(resp.read())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="minimum soak wall time (ingest keeps running "
                         "until the query budget drains)")
    ap.add_argument("--keys", type=int, default=50)
    ap.add_argument("--ts-range", type=int, default=600_000,
                    help="event-time spread in ms (sets the rollup cube "
                         "size: ts_range/1000 second-buckets per key)")
    args = ap.parse_args()

    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.agg_shards": str(args.shards)}))
    svc = SiddhiRestService(m, query_workers=8, query_queue_cap=256).start()
    port = svc.port
    _req(port, "POST", "/apps", APP, text=True)
    rt = m.get_siddhi_app_runtime("SoakApp")
    agg = rt.aggregations["TradeAgg"]
    h = rt.get_input_handler("TradeStream")

    # ---- ingest side: in-process bulk sends (the REST event endpoint
    # would measure JSON parsing, not the serving tier), exact recount
    stop_ingest = threading.Event()
    sent = {"events": 0}
    truth_total = np.zeros(args.keys)
    truth_n = np.zeros(args.keys, np.int64)
    sym_names = [f"S{k}" for k in range(args.keys)]
    sym_pool = np.array(sym_names, dtype=object)

    def ingest():
        rng = np.random.default_rng(0)
        B = 512
        while not stop_ingest.is_set():
            ids = rng.integers(0, args.keys, B)
            prices = np.round(rng.random(B) * 100.0, 6)
            ts = rng.integers(0, args.ts_range, B, dtype=np.int64)
            h.send_columns({"symbol": sym_pool[ids], "price": prices,
                            "ts": ts},
                           timestamps=np.arange(B, dtype=np.int64))
            np.add.at(truth_total, ids, prices)
            np.add.at(truth_n, ids, 1)
            sent["events"] += B

    # ---- query side
    hists = {p: Histogram() for p in PERS}
    codes = Counter()
    budget = threading.Semaphore(args.queries)
    done = threading.Event()

    def client(ci):
        rng = np.random.default_rng(1000 + ci)
        while budget.acquire(blocking=False):
            p = PERS[int(rng.integers(0, len(PERS)))]
            # a dashboard-like set of canned windows: query texts repeat,
            # so the on-demand runtime cache and the per-shape jit cache
            # both engage (a fresh text per call would measure compiles)
            w = args.ts_range // 4
            lo = int(rng.integers(0, 4)) * w
            q = (f"from TradeAgg within {lo}L, {lo + 2 * w}L per "
                 f"'{p}' select AGG_TIMESTAMP, symbol, total, n")
            t0 = time.perf_counter()
            try:
                _req(port, "POST", "/query",
                     {"app": "SoakApp", "query": q}, timeout=120)
                codes[200] += 1
                hists[p].record((time.perf_counter() - t0) * 1000.0)
            except urllib.error.HTTPError as e:
                codes[e.code] += 1
            except Exception:  # noqa: BLE001 — socket teardown at drain
                codes["err"] += 1
        done.set()

    t_start = time.perf_counter()
    ti = threading.Thread(target=ingest, daemon=True)
    ti.start()
    time.sleep(0.5)                       # some state before the storm
    blobs = agg.checkpoint_shards()       # rebuild base for the kill
    clients = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(args.clients)]
    for c in clients:
        c.start()

    # ---- kill one shard mid-soak, rebuild from blob + WAL suffix
    time.sleep(1.0)
    victim = args.shards - 1
    agg.kill_shard(victim)
    replayed = agg.rebuild_shard(victim, blobs[victim])
    print(f"[serve_soak] shard {victim} killed + rebuilt "
          f"(replayed {replayed} WAL records) under load",
          file=sys.stderr, flush=True)

    for c in clients:
        c.join()
    # keep ingest running for the minimum soak time
    while time.perf_counter() - t_start < args.seconds:
        time.sleep(0.1)
    stop_ingest.set()
    ti.join()
    elapsed = time.perf_counter() - t_start

    # ---- exactness: stitched rollup vs host recount, zero loss/dup
    rows = _req(port, "POST", "/query",
                {"app": "SoakApp",
                 "query": f"from TradeAgg within 0L, "
                          f"{args.ts_range + 86_400_000}L per 'days' "
                          f"select symbol, sum(total) as t, sum(n) as c "
                          f"group by symbol"})["rows"]
    got_total = {r[0]: r[1] for r in rows}
    got_n = {r[0]: r[2] for r in rows}
    assert set(got_n) == {s for s, c in zip(sym_names, truth_n) if c}, \
        (len(got_n), int((truth_n > 0).sum()))
    lost = dup = 0
    for s, c in zip(sym_names, truth_n):
        g = got_n.get(s, 0)
        if g < c:
            lost += int(c - g)
        elif g > c:
            dup += int(g - c)
    assert lost == 0 and dup == 0, f"lost={lost} dup={dup}"
    for s, t in zip(sym_names, truth_total):
        if s in got_total:
            assert abs(got_total[s] - t) < 1e-6 * max(1.0, abs(t)), \
                (s, got_total[s], t)

    met = _req(port, "GET", "/metrics?format=json")
    result = {
        "tool": "serve_soak",
        "backend": "cpu-fallback",
        "shards": args.shards,
        "clients": args.clients,
        "elapsed_s": round(elapsed, 1),
        "ingest_events": sent["events"],
        "ingest_eps": round(sent["events"] / elapsed, 1),
        "queries_ok": codes[200],
        "queries_shed_503": codes[503],
        "query_errors": codes.get("err", 0) + sum(
            v for k, v in codes.items() if k not in (200, 503, "err")),
        "query_qps": round(codes[200] / elapsed, 1),
        "query_ms": {p: {k: round(v, 2)
                         for k, v in hists[p].percentiles().items()}
                     for p in PERS if hists[p].count},
        "shard_rebuilds": met["apps"]["SoakApp"]["statistics"][
            "counters"].get("resilience.shard_rebuilds", 0),
        "rollup_rows_lost": lost,
        "rollup_rows_duplicated": dup,
    }
    assert result["query_errors"] == 0, result
    assert result["shard_rebuilds"] >= 1
    print(json.dumps(result))
    svc.stop()
    m.shutdown()


if __name__ == "__main__":
    main()
