"""Quick device-routing check: device-routed == unrouted, bit-identical.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Runs the same feed through a partitioned query with a DISTINCT group-by
key (the case the legacy host router rejected outright) twice — once
unsharded, once with on-device repartitioning over a 4-device virtual CPU
mesh (``parallel/mesh.device_route_query_step``) — and compares every
output row and its order exactly. Sits next to ``quick_fanout_check.py``
and ``pipeline_check.py`` in the quick-check set; finishes in ~5 s:

    JAX_PLATFORMS=cpu python tools/quick_route_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu.parallel.mesh import force_host_devices  # noqa: E402

force_host_devices(4)

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.parallel.mesh import (  # noqa: E402
    device_route_query_step, make_mesh)

APP = """
define stream StockStream (symbol string, side string, price float,
                           volume long);
partition with (symbol of StockStream)
begin
  @info(name = 'q')
  from StockStream#window.length(16)
  select symbol, side, avg(price) as avgPrice, sum(volume) as totalVolume
  group by side
  insert into OutStream;
end;
"""

N_DEV = 4


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def run(routed: bool):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP)
    c = Collector()
    rt.add_callback("OutStream", c)
    if routed:
        q = rt.query_runtimes["q"]
        device_route_query_step(q, make_mesh(N_DEV), rows_per_shard=512)
        assert q._route_layout.n == N_DEV
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(7)
    n_batches, B = 4, 256
    for i in range(n_batches):
        syms = rng.integers(0, 37, B)
        sides = rng.integers(0, 3, B)
        h.send_columns(
            {"symbol": np.array([f"S{k}" for k in syms], dtype=object),
             "side": np.array([("BUY", "SELL", "HOLD")[k] for k in sides],
                              dtype=object),
             "price": (rng.random(B) * 100.0).astype(np.float32),
             "volume": rng.integers(1, 100, B, dtype=np.int64)},
            timestamps=np.arange(i * B, (i + 1) * B, dtype=np.int64))
    rows = c.rows
    m.shutdown()
    return rows


unrouted = run(False)
print(f"unrouted run done at {time.time() - t00:.1f}s", flush=True)
routed = run(True)
print(f"device-routed run done at {time.time() - t00:.1f}s", flush=True)
assert len(unrouted) > 0, "no output rows"
assert routed == unrouted, (
    f"device-routed != unrouted ({len(routed)} vs {len(unrouted)} rows; "
    f"first diff: {next((p for p in zip(routed, unrouted) if p[0] != p[1]), None)})")
print(f"  {len(routed)} rows bit-identical (distinct GK, {N_DEV} shards)",
      flush=True)
print(f"PASS device-routed == unrouted in {time.time() - t00:.1f}s",
      flush=True)
