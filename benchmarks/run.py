"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, the only one that touches JAX. It refuses any platform but
``tpu`` and any device count but the cell's (``--cpu-rehearsal`` runs the
cell's ``rehearsal`` sizes on the CPU backend and says ``"platform":
"cpu"``: for rehearsals and tests, never for a number). Set-up builds the
cell's app with the engine's defaults, warms the cell's own shapes at
full key capacity and brings the state to its steady shape; the window
drives ``InputHandler.send_columns`` for ``--seconds``; afterwards the
plain reference is computed and what the window's callback received is
held to it. The last line of standard output is the contract's one JSON
object; the numbers compared, each beside its limit, are its last key
and the last lines of standard error.
"""

import time

_T0 = time.perf_counter()   # process start, to all intents: before imports

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_AFTER_S = 1.0    # the traced part of a --trace 1 window begins here
TRACE_FOR_S = 3.0      # and lasts this long: a few seconds, steady state
DRAIN_S = 60.0         # how long a result may come after the window closed


def _percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q))


def _device(chips, rehearsal):
    """JAX's device as the result line names it, or None where it is not
    what the cell asks for."""
    import jax

    if rehearsal and (jax.default_backend() != "cpu"
                      or len(jax.devices()) < chips):
        from siddhi_tpu.parallel.mesh import force_host_devices

        force_host_devices(max(chips, 1))
    devs = jax.devices()
    line = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    want = "cpu" if rehearsal else "tpu"
    enough = len(devs) >= chips if rehearsal else len(devs) == chips
    if line["platform"] != want or not enough:
        print(f"benchmarks/run.py: the cell needs {chips} {want} device(s); "
              f"JAX found {line}", file=sys.stderr)
        return None
    line["count"] = chips
    return line


def _sample_batches(check, seed, n_setup, n_sent):
    """Which batches' answers the reference computes where it computes a
    sample: the first and the last of the window, always, and
    ``sample_batches`` more drawn from the seed over the whole run."""
    if "sample_batches" not in check:
        return None
    rng = np.random.default_rng([seed, 24])
    k = min(check["sample_batches"], n_sent)
    picked = set(rng.choice(n_sent, size=k, replace=False).tolist())
    picked.update({min(n_setup, n_sent - 1), n_sent - 1})
    return np.asarray(sorted(picked), np.int64)


def _delivered(config, collector, rt, feed):
    """What the callback received, by the role each column plays in the
    reference. A column of strings comes as dictionary ids: decoded through
    the app's dictionary, as ``decode_events`` does, and the feed's table
    of that column to the indices the feed drew (-1: a string it never
    sent). Which roles are strings, and of which table, the configuration
    says in ``output["strings"]``; one that says nothing has the key."""
    out = config["output"]
    strings = out.get("strings", {"key": "key"})
    decode = rt.app_context.string_dictionary.decode
    got = {}
    for role, attr in out["columns"].items():
        col = collector.column(attr)
        if role in strings:
            ids = col.astype(np.int64)
            index_of = {s: i for i, s in
                        enumerate(feed.tables[strings[role]].tolist())}
            table = np.array([index_of.get(decode(i), -1)
                              for i in range(int(ids.max(initial=-1)) + 1)],
                             np.int64)
            col = table[ids] if len(ids) else ids
        got[role] = col
    return got


def _completion_times(rows_per_batch, collector):
    """When the callback call that delivered the last row of each batch
    was complete (host clock); inf where it never came. A batch that
    causes no row is complete when the next one that does is."""
    need = np.cumsum(rows_per_batch)
    have = np.cumsum(np.asarray(collector.rows, np.int64))
    at = np.asarray(collector.at, np.float64)
    # the first delivery by which as many rows had come; none: never
    done = np.append(at, np.inf)[np.searchsorted(have, need, side="left")]
    for i in range(len(done) - 1, -1, -1):
        if rows_per_batch[i] == 0:
            done[i] = done[i + 1] if i + 1 < len(done) else np.inf
    return done


def _keep(args, cell, what, payload):
    os.makedirs(args.keep, exist_ok=True)
    path = os.path.join(args.keep, f"{what}_{cell.name}_{args.seed}.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)


class _Tracer:
    """Switches the profiler on and off between two sends of the window.
    No Python tracer: it would slow the host whose gaps are read."""

    def __init__(self, directory):
        self.directory = directory
        self.state = "before"

    def tick(self, elapsed):
        import jax.profiler

        if self.state == "before" and elapsed >= TRACE_AFTER_S:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.directory, profiler_options=opts)
            self.state = "on"
            self.t_on = time.perf_counter()
        elif (self.state == "on"
              and time.perf_counter() - self.t_on >= TRACE_FOR_S):
            self.stop()

    def stop(self):
        import jax.profiler

        if self.state == "on":
            jax.profiler.stop_trace()
            self.state = "done"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="rehearsal sizes on the CPU backend; proves "
                         "control flow only, and says so in its last line")
    ap.add_argument("--control", action="store_true",
                    help="also hold the control (the reference in the "
                         "precision below the configuration's) to the "
                         "limits, and print its readings: it has to fail")
    ap.add_argument("--keep", metavar="DIR",
                    help="also write there, as JSON, every batch's latency "
                         "and (--trace 1) the events read from the trace")
    args = ap.parse_args(argv)

    from benchmarks import drive, manifest, peaks

    cell = manifest.Cell(args.workload)
    rehearsal = args.cpu_rehearsal
    marks = []          # the set-up's timeline: (what was done, s since start)

    def mark(name):
        marks.append((name, time.perf_counter() - _T0))

    from siddhi_tpu.core.util.compile_cache import place_compile_cache
    from siddhi_tpu.native import strdict_lib
    from siddhi_tpu.observability import journey

    mark("imported")
    # a rehearsal runs inside other processes (the tests): it leaves
    # their JAX configuration alone and keeps no cache
    cache_dir = None if rehearsal else place_compile_cache()
    device = _device(cell.chips, rehearsal)
    if device is None:
        return 2
    peak = None if rehearsal else peaks.of(device["kind"])
    mark("backend_up")
    if strdict_lib() is None:
        print("benchmarks/run.py: the native string encoder did not build; "
              "the engine would run its pure-Python fallback",
              file=sys.stderr)
        return 2

    errors = drive.ErrorLog()
    logging.getLogger().addHandler(errors)
    meter = drive.CompileMeter()
    if args.trace:
        journey.enable(ring_capacity=1 << 16)
    try:
        return _run(args, cell, device, peak, cache_dir, errors, meter,
                    mark, marks)
    finally:
        logging.getLogger().removeHandler(errors)
        if args.trace:
            journey.disable()


def _run(args, cell, device, peak, cache_dir, errors, meter, mark,
         marks) -> int:
    import jax
    from benchmarks import drive, generator, tracereduce
    from siddhi_tpu.observability import journey

    mark("native_encoder_loaded")
    config, rehearsal = cell.config, args.cpu_rehearsal
    sizes, traffic = cell.sized(rehearsal)
    feed = generator.make_feed(config, sizes, traffic, args.seed)
    mark("feed_made")
    manager, rt, collector = drive.build_app(config, sizes, cell.chips)
    sender = drive.Sender(rt, feed)
    mark("app_built")
    n_setup = len(feed.warm) + feed.fill_batches
    for i in range(n_setup):      # warm (compiles), then fill the state
        sender.send(i)
        if i + 1 == len(feed.warm):
            mark("warmed")
    mark("state_filled")
    knobs = drive.engine_knobs(rt)
    run_mark = meter.mark()
    tracer = None
    if args.trace:
        trace_dir = os.path.join(ROOT, ".bench_trace")
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = _Tracer(trace_dir)

    # ------------------------------------------------------- the window
    rate = traffic.get("rate_batches_per_s") if traffic["loop"] == "open" \
        else None
    setup_s = time.perf_counter() - _T0
    try:
        t0, n_sent, late_s = drive.run_window(
            sender, n_setup, args.seconds, rate,
            tracer.tick if tracer else None)
    finally:
        if tracer:
            tracer.stop()
    compiles_in_window = meter.programs - run_mark[0]
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    memory_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    journeys = [j for j in journey.ring() if t0 <= j["t"]] \
        if args.trace else []
    got = _delivered(config, collector, rt, feed)
    engine_jit = {k: v.get("compiles", 0) for k, v in
                  sorted(rt.app_context.telemetry.jit.items())}
    manager.shutdown()            # drains what is in flight; frees the state
    del rt, manager

    # ---------------------------------- the reference, and the comparison
    t_ref = time.perf_counter()
    sample = _sample_batches(config.get("check", {}), args.seed, n_setup,
                             n_sent)
    want = cell.family.reference(config, sizes, feed, n_sent, sample)
    numbers = list(cell.family.compare(config, want, got))
    done = _completion_times(want["rows_per_batch"], collector)
    in_window = range(n_setup, n_sent)
    # a batch that causes no row and has none after it (an A batch that
    # closed the window) was answered: there was nothing to say
    never = sum(1 for i in in_window
                if want["rows_per_batch"][i] and not np.isfinite(done[i]))
    numbers += [("batches_never_answered", never + sender.failed, 0),
                ("compiles_in_window", compiles_in_window, 0),
                ("errors_logged", len(errors.records), 0)]
    correct = all(v <= limit for _, v, limit in numbers)
    compare_s = time.perf_counter() - t_ref
    control = None
    if args.control:
        cwant = cell.family.reference(config, sizes, feed, n_sent, sample,
                                      dtype=config["control_precision"])
        cgot = {k: v for k, v in cwant.items()
                if k in config["output"]["columns"]}
        if cwant.get("rows") is not None:     # a sample: put it in place
            for k in set(cgot) - {"key"}:
                full = np.zeros(len(cwant["key"]), cgot[k].dtype)
                full[cwant["rows"]] = cgot[k]
                cgot[k] = full
        control = [list(n) for n in cell.family.compare(config, want, cgot)]

    # ------------------------------------------------------- the metrics
    lat_ms = [(done[i] - sender.created[i]) * 1e3 for i in in_window
              if want["rows_per_batch"][i] and np.isfinite(done[i])]
    # all the work over all the time: every batch sent inside --seconds
    # counts, and the clock stops when the last of them is answered (a
    # count of whole batches at a fixed close would step by one batch)
    answered = [i for i in in_window if np.isfinite(done[i])]
    events_done = sum(len(feed.batch(i).keys) for i in answered)
    window_s = max((done[i] for i in answered),
                   default=t0 + args.seconds) - t0
    values = {"events_per_s": events_done / window_s, "setup_s": setup_s}
    for entry in cell.end_to_end():    # result_latency_p<q>_ms, any q
        q = re.fullmatch(r"result_latency_p(\d+)_ms", entry["name"])
        if q and lat_ms:
            values[entry["name"]] = _percentile(lat_ms, int(q.group(1)))
    if args.keep:
        _keep(args, cell, "latency", {
            "created_s": [sender.created[i] - t0 for i in in_window],
            "done_s": [float(done[i] - t0) for i in in_window],
            "rows_out": [int(want["rows_per_batch"][i]) for i in in_window]})
    trace = None
    if args.trace:
        xplane = tracereduce.find_xplane(tracer.directory)
        events = tracereduce.load(xplane) if xplane else None
        trace = tracereduce.reduce(events) if events else None
        if args.keep and events:
            _keep(args, cell, "trace", events)
        ctx = {
            "compile_s_setup": run_mark[1],
            "compiles_in_window": compiles_in_window,
            "journeys": journeys,
            "trace": trace,
            "bytes_per_batch": cell.family.bytes_per_batch(
                config, sizes, feed.rows),
            "peaks": peak,
            "chips": cell.chips,
        }
        reported = [entry for entry, _ in cell.per_layer()]
        for entry, reader in cell.per_layer():
            values[entry["name"]] = reader.read(ctx)
    else:
        reported = cell.end_to_end()
    # a reader that found nothing to read returned nothing: left out
    metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
               for e in reported if values.get(e["name"]) is not None}

    device["memory_peak_bytes"] = memory_peak
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    facts = {
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "rehearsal": rehearsal, "knobs": knobs, "engine_jit": engine_jit,
        "compile_cache_dir": cache_dir,
        "setup": {"programs": run_mark[0], "compile_s": run_mark[1],
                  "cache_hits": run_mark[2], "batches": n_setup,
                  "timeline_s": dict(marks)},
        "window": {"batches": n_sent - n_setup, "events_done": events_done,
                   "window_s": window_s,
                   "latency_samples": len(lat_ms),
                   "latency_ms": {f"p{q}": _percentile(lat_ms, q)
                                  for q in (50, 90, 95, 99, 100)}
                   if lat_ms else None,
                   "generator_late_s": late_s,
                   "rows_out": int(sum(collector.rows))},
        "reference": {**want["facts"], "compare_s": compare_s},
        "errors_logged": errors.records[:3],
        **({"end_to_end_in_traced_run": {
            e["name"]: values.get(e["name"]) for e in cell.end_to_end()}}
           if args.trace else {}),
        **({"control": control} if control is not None else {}),
    }
    print(json.dumps(facts), flush=True)
    compared = {n: {"value": v, "limit": lim} for n, v, lim in numbers}
    result = {"correct": bool(correct), "attempted": n_sent - n_setup,
              "failed": never + sender.failed, "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = compared
    for n, v, lim in numbers:
        print(f"compared {n}: {v!r} (limit {lim!r})"
              f"{'' if v <= lim else '  <-- over'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
