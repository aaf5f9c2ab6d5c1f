"""Quick ingest front-door check: three ingest paths, one exact answer.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Drives the SAME event sequence through an ``@app:enforceOrder`` windowed
group-by app three ways and asserts bit-identical outputs in identical
order:

1. the per-event path — ``InputHandler.send`` with Event objects,
   inline single-thread pack (``ingest_pool`` 0, today's default);
2. the zero-copy wire path — client ``WireEncoder`` frames (dictionary
   delta growing every batch) decoded by ``decode_frame`` and landed via
   ``send_columns`` with pre-encoded server ids;
3. the parallel-pack path — the same Event sends with
   ``siddhi_tpu.ingest_pool: 2``, so the encode runs as
   sequence-numbered sub-batches with an ordered merge.

Also asserts the string dictionary's id-assignment ORDER matches
between inline and pooled packs (snapshots and rank tables observe it).
Runnable from a clean shell, ~5 s on the CPU backend:

    JAX_PLATFORMS=cpu python tools/quick_ingest_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.event import Event  # noqa: E402
from siddhi_tpu.core.stream.input.wire import (  # noqa: E402
    DecoderRegistry, WireEncoder, decode_frame)
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402

APP = """
@app:enforceOrder
define stream S (sym string, v double, n long);
@info(name='q') from S#window.length(64)
  select sym, sum(v) as sv, count() as c group by sym
  insert into Out;
"""

N_BATCHES, B = 6, 640
rng = np.random.default_rng(7)
BATCHES = []
ts = 0
for b in range(N_BATCHES):
    # key space grows per batch: the wire path's dictionary delta is
    # non-empty on every frame, and pooled packs keep inserting NEW
    # strings mid-stream (the id-order-sensitive case)
    keys = rng.integers(0, 20 + 15 * b, B)
    syms = [f"K{k}" for k in keys]
    syms[3] = None                      # null string rides every path
    vs = np.round(rng.random(B) * 100.0, 6)
    ns = rng.integers(0, 1000, B)
    tss = np.arange(ts, ts + B, dtype=np.int64)
    ts += B
    BATCHES.append((syms, vs, ns, tss))


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def make_rt(pool: int):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.ingest_pool": str(pool),
         "siddhi_tpu.ingest_split": "128"}))
    rt = m.create_siddhi_app_runtime(APP)
    c = Collector()
    rt.add_callback("Out", c)
    rt.start()
    return m, rt, c


def run_events(pool: int):
    m, rt, c = make_rt(pool)
    h = rt.get_input_handler("S")
    for syms, vs, ns, tss in BATCHES:
        h.send([Event(timestamp=int(t), data=[s, float(v), int(n)])
                for t, s, v, n in zip(tss, syms, vs, ns)])
    strings = list(rt.app_context.string_dictionary._to_str)
    m.shutdown()
    return c.rows, strings


def run_wire():
    m, rt, c = make_rt(0)
    h = rt.get_input_handler("S")
    enc = WireEncoder()
    reg = DecoderRegistry()
    definition = rt.junctions["S"].definition
    dictionary = rt.app_context.string_dictionary
    for syms, vs, ns, tss in BATCHES:
        frame = enc.encode(
            {"sym": np.array(syms, dtype=object), "v": vs, "n": ns},
            timestamps=tss)
        data, wts = decode_frame(frame, definition, dictionary, reg)
        h.send_columns(data, timestamps=wts)
    m.shutdown()
    return c.rows


events_rows, events_strings = run_events(pool=0)
wire_rows = run_wire()
pool_rows, pool_strings = run_events(pool=2)

assert len(events_rows) > 0, "no output rows"
assert events_rows == wire_rows, (
    f"wire path diverged: {len(events_rows)} vs {len(wire_rows)} rows; "
    f"first diff at "
    f"{next(i for i, (a, b) in enumerate(zip(events_rows, wire_rows)) if a != b)}")
assert events_rows == pool_rows, (
    f"parallel-pack path diverged: {len(events_rows)} vs "
    f"{len(pool_rows)} rows")
assert events_strings == pool_strings, \
    "pooled pack changed the dictionary id-assignment order"

print(f"quick_ingest_check PASS: {len(events_rows)} rows bit-identical "
      f"and identically ordered across event/wire/parallel-pack paths "
      f"({time.time() - t00:.1f}s)")
