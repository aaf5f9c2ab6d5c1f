"""Ingest-WAL overhead at the bench shape (ISSUE 1 acceptance: string
e2e throughput with the WAL enabled must stay >= 0.9x disabled).

Reuses bench.py's 10k-key length(1000) -> avg/sum e2e runtime and its
genuine string-ingest pump; the only delta between the two measured
windows is ``rt.enable_wal()``. The WAL cost per batch is one defensive
numpy copy of the raw columns — O(batch bytes), no device work — so the
ratio should sit near 1.0.

Run: ``python tools/wal_overhead.py`` (prints one JSON line). Knobs:
``BENCH_SECONDS`` (window per side), ``BENCH_BATCH``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _measure(with_wal: bool, seconds: float) -> float:
    import bench

    manager, rt, _counter = bench._make_e2e_runtime()
    if with_wal:
        # bounded like production: old batches fall off; the recording
        # cost is identical whether or not a checkpoint ever trims
        rt.enable_wal(max_batches=64)
    h = rt.get_input_handler("StockStream")
    rng = np.random.default_rng(11)
    B = bench.BATCH
    sym = np.array([f"S{i}" for i in range(bench.NUM_KEYS)], dtype=object)
    warm = sym[np.arange(B, dtype=np.int64) % bench.NUM_KEYS]
    h.send_columns({"symbol": warm,
                    "price": np.ones(B, np.float32),
                    "volume": np.ones(B, np.int64)},
                   timestamps=np.zeros(B, np.int64))
    pre = []
    for i in range(4):
        ids = rng.integers(0, bench.NUM_KEYS, B, dtype=np.int64)
        pre.append(({
            "symbol": sym[ids],
            "price": (rng.random(B) * 100.0).astype(np.float32),
            "volume": rng.integers(1, 1000, B, dtype=np.int64),
        }, np.arange(i * B, (i + 1) * B, dtype=np.int64)))
    h.send_columns(pre[0][0], timestamps=pre[0][1])
    t0 = time.perf_counter()
    n = i = 0
    while time.perf_counter() - t0 < seconds:
        cols, ts = pre[i % 4]
        h.send_columns(cols, timestamps=ts)
        n += B
        i += 1
    eps = n / (time.perf_counter() - t0)
    manager.shutdown()
    return eps


def main() -> int:
    import jax

    seconds = float(os.environ.get("BENCH_SECONDS", 4.0))
    # interleave off/on/off/on to cancel slow drift on shared hosts
    offs, ons = [], []
    for _ in range(2):
        offs.append(_measure(False, seconds))
        ons.append(_measure(True, seconds))
    eps_off = max(offs)
    eps_on = max(ons)
    out = {
        "backend": jax.devices()[0].platform,
        "batch": int(os.environ.get("BENCH_BATCH", 65_536)),
        "eps_wal_off": round(eps_off, 1),
        "eps_wal_on": round(eps_on, 1),
        "ratio": round(eps_on / eps_off, 3),
        "pass_0p9": eps_on >= 0.9 * eps_off,
    }
    print(json.dumps(out))
    return 0 if out["pass_0p9"] else 1


if __name__ == "__main__":
    sys.exit(main())
