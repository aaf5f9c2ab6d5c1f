"""Autopilot soak: 3 bursty tenants, an induced pack bottleneck, and
the controller clearing it live — with zero output divergence.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Scripted closed-loop scenario (ISSUE 16 acceptance):

1. three tenant apps (projection / group-by sum / windowed avg) on one
   SiddhiManager, each with its own deterministic bursty feed;
2. mid-soak a ``FaultInjector().delay_stage("pack", ...)`` plants a
   service delay inside every HostBatch pack — the journey
   critical-path report must NAME the pack stage as the bottleneck;
3. the autopilot's decision log must record the ``pack_bound`` verdict
   AND the clearing actuation (``ingest_pool`` up — spreading pack
   across pool workers), applied, for at least one tenant;
4. the fault clears and the soak drains;
5. the ENTIRE scripted run re-executes with autopilot off on the SAME
   feeds: every tenant's output rows must match exactly (values and
   order) — live actuation must never change semantics.

    JAX_PLATFORMS=cpu python tools/autopilot_soak.py

Exit code 0 iff the bottleneck was named, the clearing actuation
applied, and no tenant diverged.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

WARM_CHUNKS = 4
BURST_CHUNKS = 16
DRAIN_CHUNKS = 6
ROWS = 256
PACK_DELAY_S = 0.04

TENANTS = {
    "soak_proj": """
@app:name('soak_proj')
define stream S (sym string, v long);
@info(name='q') from S select sym, v * 3 as x insert into Out;
""",
    "soak_agg": """
@app:name('soak_agg')
define stream S (sym string, v long);
@info(name='q') from S select sym, sum(v) as s group by sym insert into Out;
""",
    "soak_win": """
@app:name('soak_win')
define stream S (sym string, v long);
@info(name='q') from S#window.length(64)
select sym, avg(v) as a group by sym insert into Out;
""",
}


def make_feeds():
    """Per-tenant deterministic chunk sequences, identical across runs."""
    feeds = {}
    for ti, name in enumerate(TENANTS):
        rng = np.random.default_rng(100 + ti)
        chunks = []
        t = 0
        for _ in range(WARM_CHUNKS + BURST_CHUNKS + DRAIN_CHUNKS):
            syms = rng.integers(0, 12, ROWS)
            vals = rng.integers(0, 1000, ROWS)
            chunks.append((
                {"sym": np.array([f"K{s}" for s in syms], dtype=object),
                 "v": vals.astype(np.int64)},
                np.arange(t, t + ROWS, dtype=np.int64)))
            t += ROWS
        feeds[name] = chunks
    return feeds


def run_soak(feeds, autopilot: bool):
    """One scripted pass over every tenant's feed. Returns
    (rows per tenant, decision log per tenant)."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.autopilot import AutopilotController
    from siddhi_tpu.core.util.config import InMemoryConfigManager
    from siddhi_tpu.observability import journey
    from siddhi_tpu.resilience import FaultInjector

    cfg = {"siddhi_tpu.ingest_split": "64"}
    if autopilot:
        # huge interval: the thread never fires on its own — manual
        # ticks make the observe/decide points deterministic (the same
        # drive tests/test_autopilot.py uses)
        cfg.update({"siddhi_tpu.autopilot": "on",
                    "siddhi_tpu.autopilot_interval_s": "3600",
                    "siddhi_tpu.autopilot_cooldown_s": "0.05"})
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(cfg))

    class Sink(StreamCallback):
        def __init__(self):
            super().__init__()
            self.rows = []

        def receive(self, events):
            self.rows.extend(tuple(e.data) for e in events)

    rts, sinks = {}, {}
    for name, app in TENANTS.items():
        rt = m.create_siddhi_app_runtime(app)
        sinks[name] = Sink()
        rt.add_callback("Out", sinks[name])
        rt.start()
        rts[name] = rt

    ctl = AutopilotController.instance()

    def tick_all():
        if autopilot:
            for name in TENANTS:
                ctl.tick(name)

    # ---- phase 1: quiet warmup (compiles land here, outside the
    # measured bottleneck window)
    for name, rt in rts.items():
        h = rt.get_input_handler("S")
        for data, ts in feeds[name][:WARM_CHUNKS]:
            h.send_columns(data, timestamps=ts)
    tick_all()
    if autopilot:
        # restart every tenant's observed wall at the burst: warmup
        # compile seconds would otherwise dilute pack utilization below
        # the pack_bound threshold (journey.forget_app is the public
        # redeploy-reset for exactly this)
        for name in TENANTS:
            journey.forget_app(name)

    # ---- phase 2: concurrent bursts under an injected pack delay —
    # the pack stage becomes the critical path for every tenant
    inj = FaultInjector()
    inj.delay_stage("pack", PACK_DELAY_S)
    try:
        def burst(name):
            h = rts[name].get_input_handler("S")
            for data, ts in feeds[name][
                    WARM_CHUNKS:WARM_CHUNKS + BURST_CHUNKS]:
                h.send_columns(data, timestamps=ts)

        threads = [threading.Thread(target=burst, args=(n,), daemon=True)
                   for n in TENANTS]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # a compile-storm freeze on the first post-burst tick clears on
        # the next (count stopped climbing): tick a few times
        for _ in range(3):
            tick_all()
            time.sleep(0.06)    # past the cooldown between ticks
    finally:
        inj.clear()

    # ---- phase 3: fault cleared, drain the remaining feed
    for name, rt in rts.items():
        h = rt.get_input_handler("S")
        for data, ts in feeds[name][WARM_CHUNKS + BURST_CHUNKS:]:
            h.send_columns(data, timestamps=ts)
    tick_all()

    decisions = {}
    pools = {}
    if autopilot:
        rep = ctl.report()
        for name in TENANTS:
            decisions[name] = rep["apps"].get(name, {}).get("decisions", [])
            pool = getattr(rts[name].app_context, "ingest_pack_pool", None)
            pools[name] = int(pool.workers) if pool is not None else 0
    rows = {name: list(s.rows) for name, s in sinks.items()}
    m.shutdown()
    return rows, decisions, pools


def main() -> int:
    feeds = make_feeds()

    t0 = time.time()
    print("[soak] autopilot ON pass (3 tenants, injected pack fault)...",
          flush=True)
    rows_on, decisions, pools = run_soak(feeds, autopilot=True)
    print(f"[soak] ON pass done in {time.time() - t0:.1f}s", flush=True)

    ok = True
    named, applied = [], []
    for name, log in decisions.items():
        pb = [d for d in log if d["reason"] == "pack_bound"]
        if pb:
            named.append(name)
        if any(d["reason"] == "pack_bound" and d["knob"] == "ingest_pool"
               and d["direction"] == "up" and d.get("applied") for d in pb):
            applied.append(name)
        print(f"[soak] {name}: {len(log)} decisions "
              f"({len(pb)} pack_bound), pool workers now {pools[name]}",
              flush=True)
    if not named:
        print("[soak] FAIL: no tenant's decision log named the planted "
              "pack bottleneck (reason 'pack_bound')", flush=True)
        ok = False
    if not applied:
        print("[soak] FAIL: the clearing actuation (ingest_pool up, "
              "applied) never fired", flush=True)
        ok = False
    elif not all(pools[n] >= 1 for n in applied):
        print(f"[soak] FAIL: actuation logged but no live pool exists "
              f"({pools})", flush=True)
        ok = False
    else:
        print(f"[soak] bottleneck named by {named}, cleared by "
              f"ingest_pool-up on {applied}", flush=True)

    t1 = time.time()
    print("[soak] autopilot OFF reference pass (same feeds)...", flush=True)
    rows_off, _, _ = run_soak(feeds, autopilot=False)
    print(f"[soak] OFF pass done in {time.time() - t1:.1f}s", flush=True)

    for name in TENANTS:
        if rows_on[name] != rows_off[name]:
            a, b = rows_on[name], rows_off[name]
            bad = next((i for i in range(min(len(a), len(b)))
                        if a[i] != b[i]), min(len(a), len(b)))
            print(f"[soak] FAIL: {name} DIVERGED at row {bad} "
                  f"(on={len(a)} rows, off={len(b)} rows)", flush=True)
            ok = False
        else:
            print(f"[soak] {name}: {len(rows_on[name])} rows, "
                  f"bit-identical", flush=True)

    print(f"[soak] {'PASS' if ok else 'FAIL'} in {time.time() - t0:.1f}s",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
