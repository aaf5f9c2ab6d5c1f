"""Multi-tenant fleet soak for the process-global compiled-program cache.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Churns a fleet of PR-14 fuzz-generated apps (seeded corpus — same seed,
same fleet, byte for byte) through one process as tenants: every case is
deployed T times under distinct app names, fed its deterministic event
feed over the LIVE WIRE INGEST path (client ``WireEncoder`` frames,
dictionary deltas and all, decoded into ``send_columns`` — the zero-copy
front door), then blue/green-replaced and snapshot/restored mid-soak.
The cache claims under test (core/util/program_cache.py, ISSUE 20):

- compile counts stay bounded by DISTINCT programs: every tenant after
  the first attaches instead of compiling, so the fleet-wide compile
  total equals the cache's miss count, and /metrics agrees
  (``siddhi_program_cache_size`` == distinct live programs);
- bit-identical outputs: all T tenants of a case produce the same rows,
  a mid-soak blue/green replacement reproduces its blue's rows from the
  warm cache (0 compiles), and a snapshot/restore replay re-emits the
  restored segment exactly;
- install wall-time curve: per-app deploy+first-feed milliseconds in
  deployment order — the cache-on curve flattens after app 1
  (``--compare-off`` reruns the fleet with ``program_cache: off`` for
  the honest ratio).

Usage:
    JAX_PLATFORMS=cpu python tools/fleet_soak.py                # default
    ... fleet_soak.py --cases 40 --tenants 8 --churn 5          # soak
    ... fleet_soak.py --identical 32 --compare-off              # bench

Prints one JSON line (the record) on success; exits nonzero on any
divergence.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.stream.input.wire import (  # noqa: E402
    DecoderRegistry, WireEncoder, decode_frame)
from siddhi_tpu.core.util import program_cache  # noqa: E402
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402
from siddhi_tpu.fuzz.generator import CaseGenerator  # noqa: E402
from siddhi_tpu.fuzz.schema import np_dtype  # noqa: E402
from siddhi_tpu.observability.export import (  # noqa: E402
    PROGRAM_CACHE_SIZE_FAMILY, prometheus_text)

_CHUNK_ROWS = 24   # fuzz runner's batch grain — keep the same feed shape


class _Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def _chunked_feed(case):
    chunks = []
    for stream, ts, row in case.events:
        if chunks and chunks[-1][0] == stream \
                and len(chunks[-1][1]) < _CHUNK_ROWS:
            chunks[-1][1].append([ts, row])
        else:
            chunks.append((stream, [[ts, row]]))
    return chunks


class Tenant:
    """One deployed copy of a case, fed over the wire path."""

    def __init__(self, manager, case, name):
        self.case = case
        self.name = name
        self.rt = manager.create_siddhi_app_runtime(
            f"@app:name('{name}')\n" + case.app_text())
        self.sinks = {s: _Collector() for s in case.out_streams()}
        for s, c in self.sinks.items():
            self.rt.add_callback(s, c)
        self.rt.start()
        self._enc = {}     # per-stream wire encoder + decoder registry

    def feed_chunk(self, stream, rows):
        spec = self.case.stream(stream)
        ts = np.array([r[0] for r in rows], dtype=np.int64)
        data = {}
        for j, (attr, atype) in enumerate(spec.attrs):
            vals = [r[1][j] for r in rows]
            data[attr] = np.array(
                vals, dtype=object if atype == "string"
                else np_dtype(atype))
        if stream not in self._enc:
            self._enc[stream] = (WireEncoder(), DecoderRegistry())
        enc, reg = self._enc[stream]
        frame = enc.encode(data, timestamps=ts)
        cols, wts = decode_frame(
            frame, self.rt.junctions[stream].definition,
            self.rt.app_context.string_dictionary, reg)
        self.rt.get_input_handler(stream).send_columns(
            cols, timestamps=wts)

    def feed_all(self):
        for stream, rows in _chunked_feed(self.case):
            self.feed_chunk(stream, rows)

    def outputs(self):
        return {s: list(c.rows) for s, c in self.sinks.items()}

    def compiles(self):
        jit = self.rt.app_context.telemetry.snapshot().get("jit", {})
        return sum(r.get("compiles", 0) for r in jit.values())


def _metric_value(text, family):
    """Sum every sample of one family in prometheus exposition text."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(family + "{") or line.startswith(family + " "):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    return total if seen else None


def run_fleet(cases, tenants_per_case, cache_on, churn=0,
              do_snapshot=True):
    """Deploy cases x tenants, feed everything, churn blue/green
    replacements, and return the record. Asserts all bit-identity and
    compile-bound claims; raises AssertionError with the diff on any
    violation."""
    program_cache.cache().drain()
    base = program_cache.cache().snapshot()
    misses0, hits0 = base["misses"], base["hits"]

    m = SiddhiManager()
    if not cache_on:
        m.set_config_manager(InMemoryConfigManager(
            {"siddhi_tpu.program_cache": "0"}))
    install_ms = []
    fleet = []   # (case_index, [Tenant, ...])
    t_soak = time.time()
    for ci, case in enumerate(cases):
        row = []
        for ti in range(tenants_per_case):
            t0 = time.time()
            tenant = Tenant(m, case, f"fleet_c{ci}_t{ti}")
            tenant.feed_all()
            install_ms.append(round((time.time() - t0) * 1000.0, 1))
            row.append(tenant)
        fleet.append((ci, row))

    # ---- tenant equivalence: every copy of a case emits the same rows
    for ci, row in fleet:
        want = row[0].outputs()
        for tenant in row[1:]:
            got = tenant.outputs()
            assert got == want, (
                f"case {ci}: tenant {tenant.name} diverged from "
                f"{row[0].name} (first mismatch: "
                f"{_first_diff(want, got)})")

    # ---- mid-soak blue/green churn: replace case-0 tenant-0 `churn`
    # times; each replacement must warm-attach (0 compiles when the
    # cache is on) and reproduce its blue's rows bit for bit
    replaced_compiles = 0     # greens' compiles (0 expected when on)
    retired_compiles = 0      # blues' compiles, banked before shutdown
    for cycle in range(churn):
        ci, row = fleet[0]
        blue = row[0]
        m_green = SiddhiManager()
        if not cache_on:
            m_green.set_config_manager(InMemoryConfigManager(
                {"siddhi_tpu.program_cache": "0"}))
        green = Tenant(m_green, blue.case, blue.name)
        green.feed_all()
        assert green.outputs() == blue.outputs(), (
            f"churn {cycle}: green replacement diverged from blue")
        replaced_compiles += green.compiles()
        retired_compiles += blue.compiles()
        blue.rt.shutdown()      # blue retires; green must keep serving
        row[0] = green
    if churn and cache_on:
        assert replaced_compiles == 0, (
            f"blue/green replacements compiled {replaced_compiles} "
            f"programs instead of warm-attaching")

    # ---- snapshot/restore mid-soak: replay the whole feed after a
    # restore on a live tenant — the replayed rows must re-emit exactly
    snapshot_ok = None
    if do_snapshot:
        tenant = fleet[0][1][-1]
        snap = tenant.rt.snapshot()
        before = tenant.outputs()
        tenant.feed_all()
        tenant.rt.restore(snap)
        tenant.feed_all()
        after = tenant.outputs()
        for s, rows in before.items():
            n = len(rows)
            seg1 = after[s][n:2 * n]
            seg2 = after[s][2 * n:]
            assert seg1 == seg2, (
                f"snapshot/restore replay diverged on {s}: "
                f"{_first_diff({s: seg1}, {s: seg2})}")
        snapshot_ok = True

    # ---- compile accounting: fleet-wide compiles == distinct programs
    live = [t for _, row in fleet for t in row]
    total_compiles = (sum(t.compiles() for t in live)
                      + replaced_compiles + retired_compiles)
    snap = program_cache.cache().snapshot()
    distinct = snap["size"]
    misses = snap["misses"] - misses0
    hits = snap["hits"] - hits0
    text = prometheus_text(m)
    metrics_size = _metric_value(text, PROGRAM_CACHE_SIZE_FAMILY)
    if cache_on:
        assert total_compiles == misses == distinct, (
            f"compile count not bounded by distinct programs: "
            f"{total_compiles} compiles, {misses} misses, "
            f"{distinct} live entries")
        assert metrics_size == distinct, (
            f"/metrics size {metrics_size} != live entries {distinct}")
    record = {
        "cache": "on" if cache_on else "off",
        "cases": len(cases),
        "tenants_per_case": tenants_per_case,
        "apps_installed": len(install_ms) + churn,
        "churn_replacements": churn,
        "events_per_case": len(cases[0].events) if cases else 0,
        "total_compiles": total_compiles,
        "distinct_programs": distinct,
        "cache_hits": hits,
        "cache_misses": misses,
        "snapshot_restore_exact": snapshot_ok,
        "install_ms_curve": install_ms,
        "install_ms_first": install_ms[0] if install_ms else None,
        "install_ms_rest_mean": (
            round(sum(install_ms[1:]) / (len(install_ms) - 1), 1)
            if len(install_ms) > 1 else None),
        "soak_s": round(time.time() - t_soak, 1),
    }
    m.shutdown()
    for _, row in fleet:      # green replacements live in their own
        for t in row:         # managers; shut them down explicitly
            t.rt.shutdown()
    return record


def _first_diff(want, got):
    for s in want:
        for i, (a, b) in enumerate(zip(want[s], got.get(s, []))):
            if a != b:
                return f"{s}[{i}]: {a} vs {b}"
        if len(want[s]) != len(got.get(s, [])):
            return f"{s}: {len(want[s])} vs {len(got.get(s, []))} rows"
    return "row counts"


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cases", type=int, default=6,
                    help="distinct fuzz cases (soak-class: 40+)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="app copies per case")
    ap.add_argument("--churn", type=int, default=2,
                    help="mid-soak blue/green replacement cycles")
    ap.add_argument("--events", type=int, default=48,
                    help="events per generated case")
    ap.add_argument("--identical", type=int, default=0, metavar="N",
                    help="bench shape: ONE case deployed N times "
                         "(overrides --cases/--tenants)")
    ap.add_argument("--compare-off", action="store_true",
                    help="rerun the identical fleet with the cache off "
                         "and report the install-time ratio")
    ap.add_argument("--no-snapshot", action="store_true")
    args = ap.parse_args()

    gen = CaseGenerator(args.seed, events_per_case=args.events)
    if args.identical:
        cases = [gen.case(0)]
        tenants = args.identical
    else:
        cases = [gen.case(i) for i in range(args.cases)]
        tenants = args.tenants

    record = run_fleet(cases, tenants, cache_on=True, churn=args.churn,
                       do_snapshot=not args.no_snapshot)
    if args.compare_off:
        off = run_fleet(cases, tenants, cache_on=False, churn=0,
                        do_snapshot=False)
        record["off_install_ms_curve"] = off["install_ms_curve"]
        record["off_total_compiles"] = off["total_compiles"]
        rest_on = record["install_ms_rest_mean"]
        rest_off = (round(sum(off["install_ms_curve"][1:])
                          / (len(off["install_ms_curve"]) - 1), 1)
                    if len(off["install_ms_curve"]) > 1 else None)
        record["off_install_ms_rest_mean"] = rest_off
        if rest_on and rest_off:
            record["install_speedup_rest"] = round(rest_off / rest_on, 2)
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
