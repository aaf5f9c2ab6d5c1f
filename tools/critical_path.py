"""Render a critical-path profile report as a per-query stage table.

Three input modes:

    python tools/critical_path.py                      # built-in demo app
    python tools/critical_path.py http://host:port     # GET /profile/critical_path
    python tools/critical_path.py report.json          # saved report file

The report comes from ``siddhi_tpu/observability/journey.py`` (batch-
journey tracing): per query, per stage, service-time and queueing-time
quantiles, stage busy time vs the observed wall, and the named
bottleneck. The demo mode deploys a small app with a deliberately slow
pack stage so the rendering shows a non-trivial bottleneck.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_STAGE_ORDER = ("pack", "queue", "dispatch", "device", "emit")


def _fmt_ms(v) -> str:
    if v is None:
        return "-"
    return f"{float(v):8.3f}"


def render(report: dict) -> str:
    lines = []
    if not report.get("enabled", False):
        lines.append("(journey tracing is OFF — enable with "
                     "siddhi_tpu.profile_journeys or "
                     "POST /profile/journeys/start)")
    for app, app_rep in sorted(report.get("apps", {}).items()):
        lines.append(f"app {app}")
        queries = app_rep.get("queries", {})
        if not queries:
            lines.append("  (no journeys recorded)")
            continue
        for qname, q in sorted(queries.items()):
            lines.append(f"  query {qname}   wall {q['wall_ms']:.1f} ms")
            lines.append(
                "    {:<9} {:>7} {:>9} {:>9} {:>9} {:>9} {:>10}".format(
                    "stage", "batches", "svc p50", "svc p95",
                    "que p50", "que p95", "busy ms"))
            stages = q.get("stages", {})
            for stage in _STAGE_ORDER:
                rec = stages.get(stage)
                if rec is None:
                    continue
                svc, que = rec.get("service_ms", {}), rec.get("queue_ms", {})
                lines.append(
                    "    {:<9} {:>7} {:>9} {:>9} {:>9} {:>9} {:>10}".format(
                        stage, rec.get("batches", 0),
                        _fmt_ms(svc.get("p50")) if svc else "-",
                        _fmt_ms(svc.get("p95")) if svc else "-",
                        _fmt_ms(que.get("p50")) if que else "-",
                        _fmt_ms(que.get("p95")) if que else "-",
                        f"{rec.get('busy_ms', 0.0):.2f}"))
            b = q.get("bottleneck")
            if b is not None:
                util = (f", utilization {b['utilization']:.0%}"
                        if b.get("utilization") is not None else "")
                tail = (f" — {b['structure']}"
                        if b.get("structure") else "")
                lines.append(
                    f"    bottleneck: {b['stage']} ({b['kind']}, "
                    f"mean {b['mean_ms']:.2f} ms/batch{util}){tail}")
            st = q.get("device_structure")
            if st is not None:
                lines.append(f"    device structure: {st['text']} "
                             f"(capacity {st['capacity']:.0f})")
    return "\n".join(lines)


def _demo_report() -> dict:
    """Deploy a tiny app, plant a slow pack stage, return its report."""
    import numpy as np

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability import journey

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (sym string, v long);
        @info(name='demo')
        from S#window.length(64)
          select sym, sum(v) as total group by sym
          insert into Out;
    """)
    h = rt.get_input_handler("S")
    sym = np.array([f"S{i}" for i in range(64)], dtype=object)
    data = {"sym": sym, "v": np.arange(64, dtype=np.int64)}
    h.send_columns(data, timestamps=np.zeros(64, np.int64))   # warm jit
    journey.enable()
    journey.inject_delay("pack", 0.005)
    for i in range(20):
        h.send_columns(data, timestamps=np.full(64, i + 1, np.int64))
    journey.clear_delays()
    rep = journey.critical_path_report(m)
    m.shutdown()
    journey.disable()
    return rep


def main(argv) -> int:
    if not argv:
        report = _demo_report()
    elif argv[0].startswith("http://") or argv[0].startswith("https://"):
        import urllib.request

        url = argv[0].rstrip("/") + "/profile/critical_path"
        with urllib.request.urlopen(url, timeout=30) as r:
            report = json.loads(r.read().decode())
    else:
        with open(argv[0], encoding="utf-8") as f:
            report = json.load(f)
    print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
