"""Overload soak: N tenant apps, one flooded 10x — victims stay healthy.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

The multi-tenant acceptance scenario for the overload layer
(``siddhi_tpu/resilience/overload.py``):

- three apps ingest concurrently through @Async junctions, each
  registered with the process-global overload manager (fair scheduling
  engaged); the FLOODED app additionally carries a queue quota with
  ``shed_oldest``;
- phase 1 (baseline): every app at its steady rate — per-app end-to-end
  p99 recorded (send -> callback, measured per event via an embedded
  send timestamp);
- phase 2 (flood): app 0 is driven at ~10x its steady rate through
  ``FaultInjector.flood_stream`` (the shared deterministic injection
  path) while the victims keep their steady rate.

PASS iff:
- each victim's flooded p99 <= max(2 x its baseline p99, --floor-ms);
- the flooded app's accounting is EXACT against the host recount:
  events_in == emitted + shed (zero silent loss);
- victims' output rows are bit-identical to their baseline run;
- the process survives (no aborts, no FatalQueryError).

    JAX_PLATFORMS=cpu python tools/overload_soak.py
    JAX_PLATFORMS=cpu python tools/overload_soak.py --secs 15 --rate 4000
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np  # noqa: E402

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.resilience import FaultInjector  # noqa: E402

APP = """
@app:name('{name}')
@Async(buffer.size='512', batch.size='128')
define stream S (sym string, v long, ts long);
@info(name='q') from S[v >= 0] select sym, v, ts insert into Out;
"""


class LatencyCollector(StreamCallback):
    """Counts emitted events, records per-event end-to-end latency from
    the embedded send timestamp (us), and keeps the (sym, v) rows for
    bit-identity checks."""

    def __init__(self):
        super().__init__()
        self._lock = threading.Lock()
        self.lat_us = []
        self.rows = []
        self.count = 0

    def receive(self, events):
        now = time.perf_counter_ns() // 1000
        with self._lock:
            for e in events:
                self.count += 1
                self.rows.append((e.data[0], e.data[1]))
                self.lat_us.append(now - e.data[2])

    def reset(self):
        with self._lock:
            self.lat_us, self.rows, self.count = [], [], 0

    def p99_ms(self):
        with self._lock:
            lat = list(self.lat_us)
        return float(np.percentile(lat, 99)) / 1000.0 if lat else 0.0


def steady_producer(handler, rate_eps, secs, counter, batch=50):
    """Send ``rate_eps`` events/sec in fixed batches with embedded send
    timestamps; returns when ``secs`` elapsed. Deterministic payload:
    (sym K0..K7, v = running index)."""
    interval = batch / rate_eps
    t_end = time.perf_counter() + secs
    i = counter["i"]
    while time.perf_counter() < t_end:
        t0 = time.perf_counter()
        now_us = time.perf_counter_ns() // 1000
        handler.send_columns({
            "sym": [f"K{(i + k) % 8}" for k in range(batch)],
            "v": np.arange(i, i + batch, dtype=np.int64),
            "ts": np.full(batch, now_us, np.int64),
        })
        i += batch
        counter["i"] = i
        counter["sent"] = counter.get("sent", 0) + batch
        sleep = interval - (time.perf_counter() - t0)
        if sleep > 0:
            time.sleep(sleep)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--apps", type=int, default=3)
    ap.add_argument("--rate", type=int, default=2000,
                    help="steady events/sec per app")
    ap.add_argument("--secs", type=float, default=8.0,
                    help="seconds per phase")
    ap.add_argument("--flood-ratio", type=float, default=10.0)
    ap.add_argument("--floor-ms", type=float, default=50.0,
                    help="p99 bound floor (single-core CI sandboxes run "
                         "hot; the RATIO is the real assertion)")
    args = ap.parse_args()

    m = SiddhiManager()
    names = [f"tenant{k}" for k in range(args.apps)]
    rts, cols, ctls = [], [], []
    for k, name in enumerate(names):
        rt = m.create_siddhi_app_runtime(APP.format(name=name))
        c = LatencyCollector()
        rt.add_callback("Out", c)
        if k == 0:
            # the to-be-flooded tenant: bounded queue + shed_oldest —
            # freshest data wins, producers never wedge
            ctl = rt.enable_overload(queue_quota=32,
                                     shed_policy="shed_oldest",
                                     fair_weight=1.0)
        else:
            ctl = rt.enable_overload(fair_weight=1.0)
        rt.supervise()
        rt.start()
        rts.append(rt)
        cols.append(c)
        ctls.append(ctl)

    def run_phase(flood: bool):
        for c in cols:
            c.reset()
        for ctl in ctls:
            with ctl._lock:
                ctl.shed_events = 0
        counters = [{"i": 0} for _ in names]
        threads = [
            threading.Thread(
                target=steady_producer,
                args=(rt.get_input_handler("S"), args.rate, args.secs,
                      counters[k]),
                daemon=True, name=f"producer-{names[k]}")
            for k, rt in enumerate(rts)]
        stop_flood = threading.Event()
        flood_sent = {"n": 0}
        if flood:
            inj = FaultInjector()
            j0 = rts[0].junctions["S"]

            def flood_loop():
                # ~ (flood_ratio - 1) x steady on TOP of the steady
                # producer, through the shared injection path; events
                # carry the send timestamp like real traffic
                burst = 256
                per_sec = (args.flood_ratio - 1.0) * args.rate
                interval = burst / per_sec
                while not stop_flood.is_set():
                    t0 = time.perf_counter()
                    now_us = time.perf_counter_ns() // 1000
                    # chunk=16: the burst enters as MANY queue units, the
                    # shape that actually fills a bounded queue (one
                    # 256-event unit would never overrun a unit quota)
                    flood_sent["n"] += inj.flood_stream(
                        j0, ratio=1.0, base_events=burst, chunk=16,
                        make_data=lambda i, t=now_us:
                        [f"F{i % 8}", 1_000_000 + i, t])
                    sleep = interval - (time.perf_counter() - t0)
                    if sleep > 0:
                        time.sleep(sleep)

            ft = threading.Thread(target=flood_loop, daemon=True,
                                  name="flooder")
            ft.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop_flood.set()
        if flood:
            ft.join(timeout=30)
        # drain: every sent event must be emitted or shed
        deadline = time.time() + 30
        while time.time() < deadline:
            done = all(
                cols[k].count + (ctls[k].shed_events if k == 0 else 0)
                >= counters[k].get("sent", 0)
                + (flood_sent["n"] if k == 0 else 0)
                for k in range(len(names)))
            if done:
                break
            time.sleep(0.05)
        sent = [counters[k].get("sent", 0)
                + (flood_sent["n"] if k == 0 else 0)
                for k in range(len(names))]
        return sent

    print(f"[soak] {args.apps} apps, steady {args.rate} eps, "
          f"{args.secs}s/phase, flood x{args.flood_ratio}", flush=True)

    # warm-up: first batches pay jit compiles — they must not pollute the
    # baseline p99 the flood phase is bounded against
    warm = 50                      # the steady producer's batch shape
    for k, rt in enumerate(rts):
        h = rt.get_input_handler("S")
        now_us = time.perf_counter_ns() // 1000
        h.send_columns({"sym": [f"K{i % 8}" for i in range(warm)],
                        "v": np.arange(warm, dtype=np.int64),
                        "ts": np.full(warm, now_us, np.int64)})
    deadline = time.time() + 60
    while time.time() < deadline and any(c.count < warm for c in cols):
        time.sleep(0.05)
    assert all(c.count >= warm for c in cols), "warm-up never emitted"

    sent_base = run_phase(flood=False)
    base_p99 = [c.p99_ms() for c in cols]
    base_rows = [list(c.rows) for c in cols]
    base_counts = [c.count for c in cols]
    print(f"[soak] baseline: sent={sent_base} emitted={base_counts} "
          f"p99_ms={[round(p, 2) for p in base_p99]}", flush=True)
    for k in range(len(names)):
        assert base_counts[k] == sent_base[k], (
            f"baseline loss on {names[k]}: {base_counts[k]}/{sent_base[k]}")

    sent_flood = run_phase(flood=True)
    flood_p99 = [c.p99_ms() for c in cols]
    flood_counts = [c.count for c in cols]
    sheds = [ctl.shed_events for ctl in ctls]
    print(f"[soak] flooded:  sent={sent_flood} emitted={flood_counts} "
          f"shed={sheds} p99_ms={[round(p, 2) for p in flood_p99]}",
          flush=True)

    failures = []
    # exact shed accounting on the flooded app: zero silent loss
    if flood_counts[0] + sheds[0] != sent_flood[0]:
        failures.append(
            f"accounting: tenant0 in={sent_flood[0]} != emitted="
            f"{flood_counts[0]} + shed={sheds[0]}")
    # victims: zero loss, zero sheds, bit-identical rows, bounded p99
    for k in range(1, len(names)):
        if sheds[k] != 0 or flood_counts[k] != sent_flood[k]:
            failures.append(
                f"victim {names[k]} lost events: emitted="
                f"{flood_counts[k]}/{sent_flood[k]} shed={sheds[k]}")
        n = min(len(base_rows[k]), len(cols[k].rows))
        if cols[k].rows[:n] != base_rows[k][:n]:
            first = next((i for i in range(n)
                          if cols[k].rows[i] != base_rows[k][i]), None)
            failures.append(
                f"victim {names[k]} rows diverged from baseline at row "
                f"{first}")
        bound = max(2.0 * base_p99[k], args.floor_ms)
        if flood_p99[k] > bound:
            failures.append(
                f"victim {names[k]} p99 {flood_p99[k]:.2f}ms > bound "
                f"{bound:.2f}ms (baseline {base_p99[k]:.2f}ms)")
    if sheds[0] == 0:
        failures.append("flooded app shed nothing — flood did not "
                        "overrun the quota (raise --flood-ratio)")

    report = {
        "apps": len(names),
        "steady_eps": args.rate,
        "flood_ratio": args.flood_ratio,
        "baseline_p99_ms": [round(p, 3) for p in base_p99],
        "flooded_p99_ms": [round(p, 3) for p in flood_p99],
        "flooded_app": {"in": sent_flood[0], "emitted": flood_counts[0],
                        "shed": sheds[0]},
        "victims_ok": not failures,
    }
    m.shutdown()
    print(f"[soak] {json.dumps(report)}", flush=True)
    if failures:
        for f in failures:
            print(f"[soak] FAIL: {f}", flush=True)
        return 1
    print("[soak] PASS: victims bounded, accounting exact, process alive",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
