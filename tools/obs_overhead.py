"""Observability overhead at the bench shape (ISSUE 2 acceptance: string
e2e throughput with FULL instrumentation enabled must stay >= 0.9x
instrumentation-off; ISSUE 11 extends the same bar to journey tracing).

Reuses bench.py's 10k-key length(1000) -> avg/sum e2e runtime and its
genuine string-ingest pump (same harness as tools/wal_overhead.py).
Four measured windows:

- ``off``     — no instrumentation at all (baseline; device instruments
  forced off via ``profile_device_instruments: false``);
- ``instruments`` — ONLY the device telemetry plane (ISSUE 12 bar):
  instrument slots computed inside the jitted step and appended to the
  meta the host pulls anyway, plus the per-drain decode (a couple of
  dict writes + O(1) histogram records);
- ``on``      — device instruments (production default) plus full
  classic instrumentation: ``@app:statistics`` DETAIL (per-batch
  latency histograms, memory/buffer probes), the structured span
  tracer (junction dispatch + query step spans per batch,
  ring-buffered), always-on telemetry (jit cache-hit counting);
- ``journey`` — everything above PLUS batch-journey critical-path
  tracing (``observability/journey.py``: a Journey object per batch,
  ~6 histogram records + a ring append at completion) and program-cost
  capture (one extra AOT compile per program at warmup, zero
  steady-state work).

Per batch the additions are a handful of device reductions,
perf_counter reads and O(1) histogram records against a multi-ms
device step, so every ratio should sit near 1.0; the acceptance bar is
>= 0.9x for each.

Run: ``python tools/obs_overhead.py`` (prints one JSON line). Knobs:
``BENCH_SECONDS`` (window per side), ``BENCH_BATCH``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _measure(mode: str, seconds: float) -> float:
    import bench
    from siddhi_tpu.observability import costmodel, journey
    from siddhi_tpu.observability.tracing import TRACER

    instrumented = mode in ("on", "journey")
    manager, rt, _counter = bench._make_e2e_runtime()
    if mode == "off":
        # true baseline: the device telemetry plane defaults ON — flip
        # the per-app knob before the first send (steps build lazily)
        rt.app_context.profile_device_instruments = False
    if instrumented:
        rt.set_statistics_level("detail")
        TRACER.start()          # default ring capacity; oldest spans drop
    if mode == "journey":
        journey.enable()
        costmodel.enable()
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
    spans = len(TRACER)
    if mode == "instruments":
        # the instruments window must actually have drained slot values
        q = rt.query_runtimes["bench"]
        assert q._instr_last, "instruments window decoded no slots"
        hists = rt.app_context.telemetry.snapshot().get("histograms", {})
        assert any(k.startswith("device.") for k in hists), \
            "instruments window fed no device.* histograms"
    if instrumented:
        TRACER.stop()
        # sanity: the instrumented window must actually have collected
        stats = rt.statistics()
        assert stats["level"] == "detail" and stats["latency"], \
            "instrumented run collected no latency"
        assert spans > 0, "instrumented run recorded no spans"
    if mode == "journey":
        # the journey window must have attributed stages and captured
        # at least the e2e step program
        rep = journey.critical_path_report(manager)
        queries = next(iter(rep["apps"].values()))["queries"]
        assert queries and all(q["bottleneck"] for q in queries.values()), \
            "journey window attributed nothing"
        assert costmodel.registry().programs(), "no programs captured"
        journey.disable()
        costmodel.disable()
    manager.shutdown()
    return eps


def main() -> int:
    import jax

    seconds = float(os.environ.get("BENCH_SECONDS", 4.0))
    # interleave the modes twice to cancel slow drift on shared hosts
    runs = {"off": [], "instruments": [], "on": [], "journey": []}
    for _ in range(2):
        for mode in runs:
            runs[mode].append(_measure(mode, seconds))
    eps_off = max(runs["off"])
    eps_instr = max(runs["instruments"])
    eps_on = max(runs["on"])
    eps_journey = max(runs["journey"])
    out = {
        "backend": jax.devices()[0].platform,
        "batch": int(os.environ.get("BENCH_BATCH", 65_536)),
        "eps_obs_off": round(eps_off, 1),
        "eps_instruments_on": round(eps_instr, 1),
        "eps_obs_on": round(eps_on, 1),
        "eps_journey_on": round(eps_journey, 1),
        "ratio_instruments": round(eps_instr / eps_off, 3),
        "ratio": round(eps_on / eps_off, 3),
        "ratio_journey": round(eps_journey / eps_off, 3),
        "pass_0p9": (eps_instr >= 0.9 * eps_off
                     and eps_on >= 0.9 * eps_off
                     and eps_journey >= 0.9 * eps_off),
    }
    print(json.dumps(out))
    return 0 if out["pass_0p9"] else 1


if __name__ == "__main__":
    sys.exit(main())
