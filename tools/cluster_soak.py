"""Cluster-fabric soak: real worker processes, sustained load, a kill.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Drives a partitioned (key-local, split-exact) window app through the
full fabric — router ingest sequencing, crc32 key split, wire relay,
worker engines, ordered egress re-merge — at soak volume, with a
checkpoint barrier early and (by default) a SIGKILL of one worker at
the halfway mark. Asserts effectively-once end to end: the merged
egress stream must EXACTLY equal the uninterrupted single-process run
(zero lost rows, zero duplicated rows, identical order — an exact
recount, not a statistical one). Also prints the throughput of each
fabric width (``--no-kill`` for the pure scaling curve):

    JAX_PLATFORMS=cpu python tools/cluster_soak.py                # 2,4 + kill
    JAX_PLATFORMS=cpu python tools/cluster_soak.py --workers 1,2,4 --no-kill

The feed is bursty-per-key (each batch carries ONE key, keys rotating
round-robin) so consecutive global sequences land on different workers
and the fabric actually pipelines; aggregates are split-invariant
(integer sum, count, max) so bit-identity is well-defined under row
partitioning.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

APP = """
@app:name('soakApp')
@app:playback
define stream S (k string, v double, n long);
partition with (k of S)
begin
  @info(name='q')
  from S#window.lengthBatch(64)
  select k, sum(n) as sn, count() as c, max(v) as mv
  insert into Out;
end;
"""


def make_batches(n_batches: int, rows: int, keys: int):
    rng = np.random.default_rng(3)
    out = []
    ts = 10_000
    for b in range(n_batches):
        k = np.array([f"K{b % keys}"] * rows, dtype=object)
        v = np.round(rng.random(rows) * 100.0, 6)
        n = rng.integers(0, 10_000, rows).astype(np.int64)
        tss = np.arange(ts, ts + rows, dtype=np.int64)
        ts += rows
        out.append((k, v, n, tss))
    return out


def baseline_rows(warm, main):
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.cluster.protocol import py_value

    class C(StreamCallback):
        def __init__(self):
            self.rows = []

        def receive(self, events):
            self.rows.extend(
                (int(e.timestamp), tuple(py_value(v) for v in e.data))
                for e in events)

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP)
    c = C()
    rt.add_callback("Out", c)
    rt.start()
    h = rt.get_input_handler("S")
    for k, v, n, tss in warm:  # same warmup discipline as the fabric run
        h.send_columns({"k": k, "v": v, "n": n}, timestamps=tss)
    t0 = time.time()
    for k, v, n, tss in main:
        h.send_columns({"k": k, "v": v, "n": n}, timestamps=tss)
    elapsed = time.time() - t0
    m.shutdown()
    return c.rows, elapsed


def run_fabric(warm, main, n_workers: int, kill: bool):
    """One soak pass; returns (egress_rows, stats dict)."""
    from siddhi_tpu.cluster import ClusterRuntime

    cluster = ClusterRuntime(n_workers=n_workers, heartbeat_s=0.2)
    try:
        cluster.wait_ready(60)
        cluster.deploy(APP, partition_keys={"S": "k"}, sinks=["Out"])
        # warmup: one batch per key so EVERY worker jit-compiles its
        # engine off the clock (same discipline as the other bench
        # sections); the warmup rows stay in the comparison
        for k, v, n, tss in warm:
            cluster.send_columns("soakApp", "S",
                                 {"k": k, "v": v, "n": n},
                                 timestamps=tss)
        assert cluster.quiesce(120)
        kill_at = len(main) // 2
        t0 = time.time()
        for i, (k, v, n, tss) in enumerate(main):
            cluster.send_columns("soakApp", "S",
                                 {"k": k, "v": v, "n": n},
                                 timestamps=tss)
            if i == len(main) // 4:
                cluster.checkpoint()
            if kill and i == kill_at and n_workers > 1:
                cluster.supervisor.kill(n_workers - 1)
        assert cluster.quiesce(600), "egress never quiesced"
        elapsed = time.time() - t0
        rows = [(ts, tuple(vals)) for ts, vals in
                cluster.egress.stream_rows("soakApp", "Out")]
        eg = cluster.egress.counters()
        stats = {
            "workers": n_workers,
            "elapsed_s": round(elapsed, 3),
            "events_per_s": round(
                sum(len(b[3]) for b in main) / elapsed),
            "merged_runs": eg["merged_runs"],
            "duplicate_emits_dropped": eg["duplicate_emits"],
            "respawns": sum(cluster.supervisor.respawn_count(i)
                            for i in range(n_workers)),
            "killed": bool(kill and n_workers > 1),
        }
        return rows, stats
    finally:
        cluster.shutdown()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workers", default="2,4",
                    help="comma-separated fabric widths to soak")
    ap.add_argument("--batches", type=int, default=96)
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--keys", type=int, default=16)
    ap.add_argument("--no-kill", action="store_true",
                    help="skip the mid-soak worker kill (pure scaling)")
    ap.add_argument("--json", default=None,
                    help="write the result JSON here ('-' for stdout "
                         "only; the summary always prints last)")
    args = ap.parse_args()

    widths = [int(w) for w in args.workers.split(",") if w]
    batches = make_batches(args.batches + args.keys, args.rows, args.keys)
    warm, main = batches[:args.keys], batches[args.keys:]
    base, base_elapsed = baseline_rows(warm, main)
    n_events = sum(len(b[3]) for b in main)

    result = {
        "app": "soakApp",
        "batches": args.batches, "rows_per_batch": args.rows,
        "events": n_events,
        "host_cpus": os.cpu_count(),
        "single_process_events_per_s": round(n_events / base_elapsed),
        "curve": [],
        "exact": True,
    }
    failed = False
    for n in widths:
        rows, stats = run_fabric(warm, main, n, kill=not args.no_kill)
        exact = rows == base
        stats["exact_vs_single_process"] = exact
        stats["egress_rows"] = len(rows)
        stats["expected_rows"] = len(base)
        result["curve"].append(stats)
        if not exact:
            failed = True
            result["exact"] = False
            first = next((i for i, (a, b) in enumerate(zip(rows, base))
                          if a != b), min(len(rows), len(base)))
            print(f"[cluster-soak] FAIL n={n}: {len(rows)} egress rows "
                  f"vs {len(base)} expected, first diff at {first}",
                  flush=True)
        else:
            print(f"[cluster-soak] n={n}: exact recount OK "
                  f"({len(rows)} rows, order identical), "
                  f"{stats['events_per_s']} ev/s, "
                  f"{stats['respawns']} respawn(s)", flush=True)

    text = json.dumps(result)
    if args.json and args.json != "-":
        with open(args.json, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
