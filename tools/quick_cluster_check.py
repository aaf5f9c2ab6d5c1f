"""Quick cluster-fabric check: 2 worker processes, one exact answer.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Drives the SAME columnar batch feed through (1) a plain in-process
runtime and (2) a 2-worker ``ClusterRuntime`` — router decode, crc32
key split into contiguous same-owner runs, relay re-encode on each
worker link, worker engines, and the ordered egress re-merge — and
asserts the merged output stream is BIT-IDENTICAL and identically
ordered. A checkpoint barrier runs mid-feed so the cut/trim protocol is
on the exercised path, and a second PINNED (un-partitioned) app rides
along to cover whole-app placement. Runnable from a clean shell:

    JAX_PLATFORMS=cpu python tools/quick_cluster_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.cluster import ClusterRuntime  # noqa: E402
from siddhi_tpu.cluster.protocol import py_value  # noqa: E402

SPLIT_APP = """
@app:name('fabSplit')
@app:playback
define stream S (k string, tag string, v double, n long);
partition with (k of S)
begin
  @info(name='q')
  from S#window.length(8)
  select k, sum(n) as sn, count() as c, max(v) as mv
  insert into Out;
end;
"""

PINNED_APP = """
@app:name('fabPinned')
@app:playback
define stream P (k string, v double);
@info(name='q')
from P[v > 25.0]
select k, v
insert into Out;
"""

N_BATCHES, B = 8, 64
rng = np.random.default_rng(11)
BATCHES = []
ts = 1_000
for b in range(N_BATCHES):
    keys = np.array([f"K{i}" for i in rng.integers(0, 10 + b, B)],
                    dtype=object)
    tags = np.array([None if i % 7 == 3 else f"t{i % 5}"
                     for i in range(B)], dtype=object)
    vs = np.round(rng.random(B) * 100.0, 6)
    ns = rng.integers(0, 1_000, B).astype(np.int64)
    tss = np.arange(ts, ts + B, dtype=np.int64)
    ts += B
    BATCHES.append((keys, tags, vs, ns, tss))


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend(
            (int(e.timestamp), tuple(py_value(v) for v in e.data))
            for e in events)


def baseline(app, stream):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app)
    c = Collector()
    rt.add_callback("Out", c)
    rt.start()
    h = rt.get_input_handler(stream)
    for keys, tags, vs, ns, tss in BATCHES:
        if stream == "S":
            h.send_columns({"k": keys, "tag": tags, "v": vs, "n": ns},
                           timestamps=tss)
        else:
            h.send_columns({"k": keys, "v": vs}, timestamps=tss)
    m.shutdown()
    return c.rows


def main() -> int:
    base_split = baseline(SPLIT_APP, "S")
    base_pinned = baseline(PINNED_APP, "P")
    t0 = time.time()
    cluster = ClusterRuntime(n_workers=2, heartbeat_s=0.2)
    try:
        cluster.wait_ready(60)
        t_up = time.time() - t0
        cluster.deploy(SPLIT_APP, partition_keys={"S": "k"},
                       sinks=["Out"])
        cluster.deploy(PINNED_APP, sinks=["Out"])
        for i, (keys, tags, vs, ns, tss) in enumerate(BATCHES):
            cluster.send_columns("fabSplit", "S",
                                 {"k": keys, "tag": tags, "v": vs,
                                  "n": ns},
                                 timestamps=tss)
            cluster.send_columns("fabPinned", "P",
                                 {"k": keys, "v": vs}, timestamps=tss)
            if i == N_BATCHES // 2:
                cluster.checkpoint()    # mid-feed barrier: cut + trim
        assert cluster.quiesce(120), "egress never quiesced"
        got_split = [(ts_, tuple(vals)) for ts_, vals in
                     cluster.egress.stream_rows("fabSplit", "Out")]
        got_pinned = [(ts_, tuple(vals)) for ts_, vals in
                      cluster.egress.stream_rows("fabPinned", "Out")]
    finally:
        cluster.shutdown()

    n_runs = cluster.egress.counters()["merged_runs"]
    assert got_split == base_split, (
        f"SPLIT mismatch: {len(got_split)} vs {len(base_split)} rows; "
        f"first diff at "
        f"{next((i for i, (a, b) in enumerate(zip(got_split, base_split)) if a != b), 'len')}")
    assert got_pinned == base_pinned, (
        f"PINNED mismatch: {len(got_pinned)} vs {len(base_pinned)} rows")
    assert len(base_split) == N_BATCHES * B, "split app must emit 1/row"
    print(f"quick_cluster_check OK: split={len(got_split)} rows "
          f"pinned={len(got_pinned)} rows over {n_runs} ordered runs, "
          f"workers up in {t_up:.1f}s, total {time.time() - t00:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
