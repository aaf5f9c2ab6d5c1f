"""Quick check: FULL critical-path profiling on == profiling off,
bit-identically, plus report/registry sanity — and (ISSUE 12) device
instruments on == off bit-identically across the routed / fused / join
/ NFA step shapes. ~40 s.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Part 1 runs the same deterministic input sequence through two fresh
runtimes of a 2-query app (the fused fan-out path — the default engine
shape):

- run A: profiling OFF (the tier-1 default);
- run B: journey tracing + program-cost capture + span tracer + DETAIL
  statistics all enabled.

Asserts the two output sequences are IDENTICAL (values and order — the
profiler never touches jitted step code, so there is nothing it may
change), that the critical-path report names a bottleneck with every
expected stage populated, and that the cost registry captured every
step program with consistent fingerprint-cluster arithmetic.

Part 2 runs each instrument-bearing step shape twice —
``profile_device_instruments`` on (default) vs off — and asserts query
outputs are bit-identical: the instrument lanes ride BEHIND the meta
prefix and touch nothing the selector emits.

Registered in ``tools/quick_all.py`` (name: ``obs``).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

APP = """
define stream S (sym string, v long);
@info(name='q_sum')
from S#window.length(32) select sym, sum(v) as total group by sym insert into OutA;
@info(name='q_avg')
from S#window.length(32) select sym, avg(v) as mean group by sym insert into OutB;
"""

BATCHES = 12
ROWS = 64


def _run(profiled: bool):
    import numpy as np

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.observability import costmodel, journey
    from siddhi_tpu.observability.tracing import TRACER

    rows = {"OutA": [], "OutB": []}

    class C(StreamCallback):
        def __init__(self, key):
            super().__init__()
            self.key = key

        def receive(self, events):
            rows[self.key].extend(tuple(e.data) for e in events)

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP)
    rt.add_callback("OutA", C("OutA"))
    rt.add_callback("OutB", C("OutB"))
    if profiled:
        journey.enable()
        costmodel.registry().reset()
        costmodel.enable()
        rt.set_statistics_level("detail")
        TRACER.start()
    h = rt.get_input_handler("S")
    rng = np.random.default_rng(7)
    sym = np.array([f"K{i}" for i in range(16)], dtype=object)
    for b in range(BATCHES):
        ids = rng.integers(0, 16, ROWS)
        h.send_columns(
            {"sym": sym[ids],
             "v": rng.integers(1, 100, ROWS).astype(np.int64)},
            timestamps=np.full(ROWS, b, np.int64))
    report = journey.critical_path_report(m) if profiled else None
    progs = costmodel.registry().snapshot() if profiled else None
    spans = len(TRACER) if profiled else 0
    if profiled:
        TRACER.stop()
        journey.disable()
        costmodel.disable()
    m.shutdown()
    return rows, report, progs, spans, rt.name


JOIN_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(64) join R#window.length(64)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""

NFA_APP = """
define stream A (sym string, p double);
@info(name='nq') from every e1=A[p > 10] -> e2=A[p > e1.p]
  select e1.sym as s1, e2.sym as s2 insert into NOut;
"""

ROUTED_APP = """
define stream S (k string, v double);
partition with (k of S)
begin
  @info(name='rq')
  from S#window.length(4) select k, v, sum(v) as s insert into ROut;
end;
"""


def _shape_run(instruments_on: bool, shape: str):
    """One deterministic run of one instrument-bearing step shape with
    the profile_device_instruments knob on/off; returns the output row
    sequence (values AND order)."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.config import InMemoryConfigManager

    rows = []

    class C(StreamCallback):
        def receive(self, events):
            rows.extend(tuple(e.data) for e in events)

    cfg = {"siddhi_tpu.profile_device_instruments":
           "true" if instruments_on else "false"}
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(cfg))
    if shape == "join":
        rt = m.create_siddhi_app_runtime(JOIN_APP)
        rt.add_callback("JOut", C())
        hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
        for i in range(40):
            hl.send([f"S{i % 5}", i])
            hr.send([f"S{i % 5}", 100 + i])
    elif shape == "nfa":
        rt = m.create_siddhi_app_runtime(NFA_APP)
        rt.add_callback("NOut", C())
        h = rt.get_input_handler("A")
        for i in range(24):
            h.send([f"N{i}", 11.0 + (i % 7)])
    elif shape == "routed":
        from siddhi_tpu.parallel.mesh import (device_route_query_step,
                                              make_mesh)

        rt = m.create_siddhi_app_runtime(ROUTED_APP)
        rt.add_callback("ROut", C())
        device_route_query_step(rt.query_runtimes["rq"], make_mesh(2),
                                rows_per_shard=256)
        h = rt.get_input_handler("S")
        for i in range(120):
            h.send([f"P{i % 16}", float(i)])
    else:   # fused fan-out (the default multi-query shape)
        rt = m.create_siddhi_app_runtime(APP)
        rt.add_callback("OutA", C())
        rt.add_callback("OutB", C())
        h = rt.get_input_handler("S")
        for i in range(60):
            h.send([f"K{i % 7}", i])
    if instruments_on:
        # the on-run must actually have drained instrument lanes
        q = next(iter(rt.query_runtimes.values()))
        assert q._instr_last, f"{shape}: no instrument lanes drained"
    m.shutdown()
    return rows


def main() -> int:
    # the routed shape needs a multi-device (virtual CPU) mesh — must
    # precede any jax backend touch
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(2)

    base_rows, _, _, _, _ = _run(profiled=False)
    prof_rows, report, progs, spans, app = _run(profiled=True)

    assert prof_rows == base_rows, (
        "profiling changed the outputs: "
        f"A {len(base_rows['OutA'])}/{len(prof_rows['OutA'])} rows, "
        f"B {len(base_rows['OutB'])}/{len(prof_rows['OutB'])} rows")
    assert base_rows["OutA"] and base_rows["OutB"], "no outputs produced"

    # report sanity: both queries profiled, every core stage populated,
    # a bottleneck named from the glossary
    queries = report["apps"][app]["queries"]
    for q in ("q_sum", "q_avg"):
        assert q in queries, f"query {q} missing from the report"
        stages = queries[q]["stages"]
        for stage in ("pack", "dispatch", "device", "emit"):
            assert stages.get(stage, {}).get("batches", 0) > 0, \
                f"{q}: stage '{stage}' recorded no batches"
        b = queries[q]["bottleneck"]
        assert b and b["stage"] in report["stage_glossary"], b
    assert spans > 0, "span tracer recorded nothing"

    # cost-registry sanity: the (fused) step program captured, analysis
    # fields populated, cluster arithmetic consistent
    assert progs["programs"], "cost registry captured no programs"
    assert sum(c["size"] for c in progs["clusters"]) == len(
        progs["programs"])
    assert progs["unique_fingerprints"] == len(progs["clusters"])
    step = [p for p in progs["programs"] if p["key"].endswith(".step")]
    assert step, f"no step program captured: {progs['programs']}"
    for p in step:
        assert p["error"] is None, p
        assert p["flops"] > 0 and p["bytes_accessed"] > 0, p
        assert len(p["fingerprint"]) == 16, p

    # part 2: instruments on == off, bit-identically, per step shape
    shape_rows = {}
    for shape in ("fused", "join", "nfa", "routed"):
        on = _shape_run(True, shape)
        off = _shape_run(False, shape)
        assert on == off, (
            f"device instruments changed {shape} outputs: "
            f"{len(on)} vs {len(off)} rows")
        assert on, f"{shape} shape produced no outputs"
        shape_rows[shape] = len(on)

    n = len(base_rows["OutA"]) + len(base_rows["OutB"])
    print(f"quick_obs_check PASS: {BATCHES} batches x {ROWS} rows, "
          f"{n} output rows bit-identical with full profiling on; "
          f"{len(progs['programs'])} programs captured, "
          f"{progs['duplicate_clusters']} duplicate cluster(s), "
          f"{spans} spans; instruments on==off bit-identical for "
          + ", ".join(f"{k}({v})" for k, v in shape_rows.items()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
