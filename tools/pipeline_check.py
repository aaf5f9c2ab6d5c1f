"""Quick dispatch-pipeline check: pipelined output == synchronous output.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Replays the bench shape (string ingest -> length-window group-by fan-out)
through an @Async junction — the producer shape where the CompletionPump
actually pipelines (the worker delivers back-to-back, so up to
``pipeline_depth`` device batches ride in flight while the next batch
packs) — at depth 1 (today's synchronous pull-per-batch) and depth 4,
with fan-out fusion both ON and OFF, and asserts every output stream is
**bit-identical and identically ordered** across all four runs.

Part of the quick-check set alongside ``quick_fanout_check.py``.
Runnable from a clean shell, finishes well under 60 s on CPU:

    JAX_PLATFORMS=cpu python tools/pipeline_check.py
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
@Async(buffer.size='1024')
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
N_BATCHES, B = 5, 256


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def run(depth: int, fused: bool):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager({
        "siddhi_tpu.pipeline_depth": str(depth),
        "siddhi_tpu.fuse_fanout": "1" if fused else "0",
    }))
    rt = m.create_siddhi_app_runtime(APP)
    outs = {s: Collector() for s in OUT_STREAMS}
    for s, c in outs.items():
        rt.add_callback(s, c)
    rt.start()
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(0)
    for i in range(N_BATCHES):
        ids = rng.integers(0, 40, B)
        h.send_columns(
            {"symbol": np.array([f"S{k}" for k in ids], dtype=object),
             "price": (rng.random(B) * 100.0).astype(np.float32),
             "volume": rng.integers(1, 100, B, dtype=np.int64)},
            timestamps=np.arange(i * B, (i + 1) * B, dtype=np.int64))
    m.shutdown()   # worker drains the queue + flushes the pipeline
    if depth > 1:
        tel = rt.app_context.telemetry.snapshot()
        metas = tel["counters"].get("pipeline.metas", 0)
        assert metas >= N_BATCHES, (
            f"pipeline never engaged at depth {depth} "
            f"(metas drained: {metas})")
    rows = {s: c.rows for s, c in outs.items()}
    for s in OUT_STREAMS:
        assert rows[s], f"{s}: produced no rows (depth={depth})"
    return rows


results = {}
for fused in (True, False):
    for depth in (1, 4):
        results[(fused, depth)] = run(depth, fused)
        print(f"run fused={fused} depth={depth} done at "
              f"{time.time() - t00:.1f}s", flush=True)

ref = results[(True, 1)]
for key, rows in results.items():
    for s in OUT_STREAMS:
        assert rows[s] == ref[s], (
            f"{s}: fused={key[0]} depth={key[1]} diverged from fused depth-1 "
            f"({len(rows[s])} vs {len(ref[s])} rows)")
for s in OUT_STREAMS:
    print(f"  {s}: {len(ref[s])} rows bit-identical across "
          f"fused x depth {{1,4}}", flush=True)
print(f"PASS pipelined == synchronous in {time.time() - t00:.1f}s",
      flush=True)
