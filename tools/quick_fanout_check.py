"""Quick fan-out fusion check: fused == unfused outputs on a 3-query app.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Runs the same event feed through a 3-query single-stream app twice —
once with fan-out fusion on (one jitted dispatch + one meta pull per
batch, asserted via telemetry) and once with the knob off — and
compares every output stream exactly. Runnable from a clean shell,
finishes well under 30 s on the CPU backend:

    JAX_PLATFORMS=cpu python tools/quick_fanout_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402

APP = """
define stream StockStream (symbol string, price float, volume long);
@info(name='q0') from StockStream[price > 20.0]
  select symbol, price insert into HighStream;
@info(name='q1') from StockStream#window.length(64)
  select symbol, sum(volume) as totalVolume group by symbol
  insert into VolumeStream;
@info(name='q2') from StockStream
  select symbol, price * 2.0 as doubled insert into DoubledStream;
"""

OUT_STREAMS = ("HighStream", "VolumeStream", "DoubledStream")


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def run(fused: bool):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.fuse_fanout": "1" if fused else "0"}))
    rt = m.create_siddhi_app_runtime(APP)
    outs = {s: Collector() for s in OUT_STREAMS}
    for s, c in outs.items():
        rt.add_callback(s, c)
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(0)
    n_batches, B = 5, 256
    for i in range(n_batches):
        ids = rng.integers(0, 40, B)
        h.send_columns(
            {"symbol": np.array([f"S{k}" for k in ids], dtype=object),
             "price": (rng.random(B) * 100.0).astype(np.float32),
             "volume": rng.integers(1, 100, B, dtype=np.int64)},
            timestamps=np.arange(i * B, (i + 1) * B, dtype=np.int64))
    tel = rt.app_context.telemetry.snapshot()
    if fused:
        assert [(g.stream_id, len(g.members))
                for g in rt.fused_fanout_groups] == [("StockStream", 3)], \
            "expected one fused group of 3"
        assert tel["counters"]["fanout.StockStream.dispatches"] == n_batches
        assert tel["counters"]["fanout.StockStream.meta_pulls"] == n_batches
        assert tel["jit"]["fanout.StockStream.step"]["compiles"] == 1
        assert not any(k.startswith("query.") for k in tel["jit"])
    else:
        assert rt.fused_fanout_groups == []
    rows = {s: c.rows for s, c in outs.items()}
    m.shutdown()
    return rows


fused_rows = run(True)
print(f"fused run done at {time.time() - t00:.1f}s", flush=True)
unfused_rows = run(False)
print(f"unfused run done at {time.time() - t00:.1f}s", flush=True)
for s in OUT_STREAMS:
    assert fused_rows[s] == unfused_rows[s], (
        f"{s}: fused != unfused "
        f"({len(fused_rows[s])} vs {len(unfused_rows[s])} rows)")
    print(f"  {s}: {len(fused_rows[s])} rows equal", flush=True)
print(f"PASS fused == unfused in {time.time() - t00:.1f}s", flush=True)
