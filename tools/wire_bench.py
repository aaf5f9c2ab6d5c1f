"""Wire-format ingest bench: the client-side encoder + the front door.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Two modes, both runnable from a clean shell on the CPU backend:

    JAX_PLATFORMS=cpu python tools/wire_bench.py          # pack paths
    JAX_PLATFORMS=cpu python tools/wire_bench.py rest     # + REST e2e

``pack`` measures the three ingest pack paths over identical data —
the per-event Event-object path (``HostBatch.from_events``), the raw
string-column path (``from_columns`` + dictionary encode), and the
zero-copy wire path (client ``WireEncoder.encode`` -> ``decode_frame``
-> ``from_columns`` on pre-encoded ids) — plus the client encode cost
alone. ``rest`` additionally drives frames through a live
``POST /ingest/{stream}`` endpoint from concurrent client threads.

Prints ONE JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

B = int(os.environ.get("WIRE_BENCH_BATCH", 65_536))
KEYS = int(os.environ.get("WIRE_BENCH_KEYS", 10_000))
SECONDS = float(os.environ.get("WIRE_BENCH_SECONDS", 2.0))

APP = """
@app:name('WireBench')
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.length(1000)
select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
group by symbol
insert into OutStream;
"""


def _measure(fn, seconds: float = SECONDS) -> float:
    """events/sec of fn() (one call = one B-row batch), warmed once."""
    fn()
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        fn()
        n += B
    return n / (time.perf_counter() - t0)


def bench_pack() -> dict:
    from siddhi_tpu.core.event import Event, HostBatch, StringDictionary
    from siddhi_tpu.core.stream.input.wire import (
        DecoderRegistry, WireEncoder, decode_frame)
    from siddhi_tpu.query_api.definitions import (
        Attribute, AttrType, StreamDefinition)

    definition = StreamDefinition("StockStream", attributes=[
        Attribute("symbol", AttrType.STRING),
        Attribute("price", AttrType.FLOAT),
        Attribute("volume", AttrType.LONG)])
    rng = np.random.default_rng(0)
    ids = rng.integers(0, KEYS, B)
    syms = np.array([f"S{i}" for i in ids], dtype=object)
    price = (rng.random(B) * 100.0).astype(np.float32)
    volume = rng.integers(1, 1000, B, dtype=np.int64)
    ts = np.arange(B, dtype=np.int64)

    # --- per-event path: the pre-round-10 single front door
    events = [Event(timestamp=int(t), data=[s, float(p), int(v)])
              for t, s, p, v in zip(ts, syms, price, volume)]
    d1 = StringDictionary()
    eps_events = _measure(
        lambda: HostBatch.from_events(events, definition, d1))

    # --- raw string columns (dictionary encodes every batch)
    d2 = StringDictionary()
    cols = {"symbol": syms, "price": price, "volume": volume}
    eps_cols = _measure(
        lambda: HostBatch.from_columns(cols, definition, d2,
                                       timestamps=ts))

    # --- wire path: encode once client-side, measure the SERVER cost
    # (decode_frame LUT gather + from_columns on pre-encoded ids) — the
    # per-frame work the front door pays per device push
    enc = WireEncoder()
    first = enc.encode(cols, timestamps=ts)     # full dict delta rides here
    frame = enc.encode(cols, timestamps=ts)     # steady state: no delta
    d3 = StringDictionary()
    reg = DecoderRegistry()
    decode_frame(first, definition, d3, reg)    # bootstrap the LUT

    def wire_once():
        data, wts = decode_frame(frame, definition, d3, reg)
        HostBatch.from_columns(data, definition, d3, timestamps=wts)

    eps_wire = _measure(wire_once)

    # --- client encode cost alone (steady state, no delta)
    eps_encode = _measure(lambda: enc.encode(cols, timestamps=ts))

    return {
        "batch": B,
        "frame_bytes": len(frame),
        "from_events_eps": round(eps_events, 1),
        "from_columns_str_eps": round(eps_cols, 1),
        "wire_eps": round(eps_wire, 1),
        "client_encode_eps": round(eps_encode, 1),
        "wire_vs_events": round(eps_wire / eps_events, 2),
    }


def bench_rest(threads: int = 4) -> dict:
    import http.client
    import threading

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.stream.input.wire import WireEncoder
    from siddhi_tpu.service.rest import SiddhiRestService

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP)

    class Counter(StreamCallback):
        n = 0

        def receive_batch(self, batch, junction):
            Counter.n += batch.size

        def receive(self, events):
            Counter.n += len(events)

    rt.add_callback("OutStream", Counter())
    rt.query_runtimes["bench"].selector_plan.num_keys = 16_384
    rt.start()
    svc = SiddhiRestService(m).start()
    rng = np.random.default_rng(1)
    rb = max(1024, B // 8)
    syms = np.array([f"S{i}" for i in rng.integers(0, KEYS, rb)],
                    dtype=object)
    stop = time.perf_counter() + SECONDS
    sent = [0] * threads

    def client(ci):
        enc = WireEncoder()
        conn = http.client.HTTPConnection("127.0.0.1", svc.port)
        cols = {"symbol": syms,
                "price": (rng.random(rb) * 100.0).astype(np.float32),
                "volume": rng.integers(1, 1000, rb, dtype=np.int64)}
        i = 0
        while time.perf_counter() < stop:
            # monotone per-client stamps; streams are shared so no
            # @app:enforceOrder here — the REST hop is what's measured
            frame = enc.encode(cols, timestamps=np.arange(
                i * rb, (i + 1) * rb, dtype=np.int64))
            conn.request("POST", "/ingest/StockStream", body=frame)
            r = conn.getresponse()
            body = r.read()
            if r.status == 200:
                sent[ci] += rb
            elif r.status != 503:
                raise RuntimeError(f"ingest failed {r.status}: {body!r}")
            i += 1
        conn.close()

    t0 = time.perf_counter()
    ths = [threading.Thread(target=client, args=(i,)) for i in range(threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    dt = time.perf_counter() - t0
    svc.stop()
    m.shutdown()
    assert Counter.n > 0
    return {
        "rest_clients": threads,
        "rest_frame_rows": rb,
        "rest_ingest_eps": round(sum(sent) / dt, 1),
    }


def main() -> int:
    result = {"host_cores": os.cpu_count(), **bench_pack()}
    if "rest" in sys.argv[1:]:
        result.update(bench_rest())
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
