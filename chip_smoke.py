"""Smoke run of the served path on the chip: does it start, and are the
answers right.

One process, the only one that touches JAX. Each phase goes through
``SiddhiManager`` -> ``create_siddhi_app_runtime`` ->
``InputHandler.send_columns`` -> ``StreamJunction`` -> jitted step -> meta
pull -> ``StreamCallback`` with the engine's defaults, and compares what
the callback received with a plain event-at-a-time reference of the same
query written here with ``collections``/``numpy`` and nothing from
``siddhi_tpu``. Phases (``BASELINE.json`` configs, at that file's sizes):

- A: global ``length(1000)`` -> ``avg/sum group by symbol``, 10,000 string
  symbols, 65,536-row batches (the north-star shape);
- B: config #2 as written — the same aggregates under ``partition with
  (symbol of StockStream)``, per-key rings resident on the device;
- C: config #4 — ``every e1=A -> e2=B[e2.v>e1.v] within 5 sec`` over
  10,000 partition keys, ``@app:playback``, A and B batches interleaved.

``python chip_smoke.py`` needs one TPU chip and refuses to run without.
``--chips 4`` runs ONLY phase B's query routed over a mesh of four chips
against the same query unsharded.
``--cpu-rehearsal`` runs the same code at a tiny size on the CPU backend
(a control-flow rehearsal; its last line says ``"platform": "cpu"``).

Every line but the last is one JSON object describing a phase; the last
line is ``{"ok": true, "device": {...}}`` and is printed only when every
phase agreed with its reference, compiled nothing after warm-up and
logged nothing at ERROR.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import logging
import sys
import time

import numpy as np

_APP_A = """
define stream StockStream (symbol string, price float, volume long);
@info(name = 'bench')
from StockStream#window.length({W})
select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
group by symbol
insert into OutStream;
"""

_APP_B = """
define stream StockStream (symbol string, price float, volume long);
partition with (symbol of StockStream)
begin
  @info(name = 'bench')
  from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
  insert into OutStream;
end;
"""

_APP_C = """
@app:playback
define stream AStream (k string, v double);
define stream BStream (k string, v double);
partition with (k of AStream, k of BStream)
begin
  @info(name = 'nfa')
  from every e1=AStream -> e2=BStream[e2.v > e1.v] within {WITHIN} sec
  select e1.v as v1, e2.v as v2
  insert into MatchStream;
end;
"""

WITHIN_S = 5
# float32 arithmetic: unit roundoff of the device dtype under "fast"
_EPS32 = float(np.finfo(np.float32).eps)


@dataclasses.dataclass(frozen=True)
class Sizes:
    keys: int          # distinct symbols / partition keys
    window: int        # length(W)
    batch: int         # rows per send_columns call, phases A and B
    batches_a: int     # measured batches after warm-up, phase A
    batches_b: int     # measured batches after warm-up, phase B
    hot_share: float   # phase B: share of keys that take most traffic
    hot_traffic: float  # phase B: share of traffic the hot keys take
    batch_c: int       # rows per batch, phase C
    rounds_c: int      # measured A+B rounds after warm-up, phase C
    batches_mesh: int  # measured batches of the --chips 4 phase
    route_slack: float  # rows_per_shard over batch / shards (exchange quota)


FULL = Sizes(keys=10_000, window=1_000, batch=65_536, batches_a=32,
             batches_b=48, hot_share=0.2, hot_traffic=0.8,
             batch_c=16_384, rounds_c=24, batches_mesh=48,
             route_slack=1.25)
TINY = Sizes(keys=40, window=8, batch=256, batches_a=4, batches_b=4,
             hot_share=0.2, hot_traffic=0.8, batch_c=128, rounds_c=12,
             batches_mesh=4, route_slack=2.0)


class SmokeFailure(AssertionError):
    """A phase disagreed with its reference (or compiled, or logged an
    ERROR, after warm-up). Never caught on the way to the last line."""


# ------------------------------------------------------------ references
# Plain event-at-a-time models of the three queries. Nothing below this
# line and above "engine side" imports siddhi_tpu or jax.


def reference_global_window(sym, price, volume, window):
    """``from S#window.length(W) select symbol, avg(price), sum(volume)
    group by symbol``: ONE deque over the stream; group-by only buckets
    the aggregation. One output row per arriving event. Returns
    (avg, sum, facts to print)."""
    ring = collections.deque()
    total = collections.defaultdict(float)
    count = collections.defaultdict(int)
    vol = collections.defaultdict(int)
    out_avg = np.empty(len(sym), np.float64)
    out_vol = np.empty(len(sym), np.int64)
    for i, (s, p, v) in enumerate(zip(sym.tolist(), price.tolist(),
                                      volume.tolist())):
        if len(ring) == window:
            s0, p0, v0 = ring.popleft()
            count[s0] -= 1
            vol[s0] -= v0
            # an emptied group restarts from zero: no drift carried over
            total[s0] = total[s0] - p0 if count[s0] else 0.0
        ring.append((s, p, v))
        total[s] += p
        count[s] += 1
        vol[s] += v
        out_avg[i] = total[s] / count[s]
        out_vol[i] = vol[s]
    return out_avg, out_vol, {}


def reference_keyed_window(sym, price, volume, window):
    """The same aggregates under ``partition with (symbol of S)``: one
    deque PER KEY. Returns (avg, sum, facts to print: how many keys'
    rings wrapped)."""
    rings = collections.defaultdict(collections.deque)
    total = collections.defaultdict(float)
    vol = collections.defaultdict(int)
    wrapped = set()
    out_avg = np.empty(len(sym), np.float64)
    out_vol = np.empty(len(sym), np.int64)
    for i, (s, p, v) in enumerate(zip(sym.tolist(), price.tolist(),
                                      volume.tolist())):
        ring = rings[s]
        if len(ring) == window:
            p0, v0 = ring.popleft()
            total[s] -= p0
            vol[s] -= v0
            wrapped.add(s)
        ring.append((p, v))
        total[s] += p
        vol[s] += v
        out_avg[i] = total[s] / len(ring)
        out_vol[i] = vol[s]
    return out_avg, out_vol, {
        "rings_wrapped": len(wrapped),
        "rings_wrapped_share": round(len(wrapped) / len(rings), 4)}


def reference_pattern(stream, key, v, ts, within_ms):
    """``every e1=A -> e2=B[e2.v > e1.v] within T`` per partition key: a
    per-key list of pending A's; a B consumes every pending A of its key
    that is still inside the bound and below it, oldest first. ``stream``
    is 0 for A rows and 1 for B rows, in arrival order. Returns the match
    rows, for each the index of the B event that produced it, and how
    many pending A's a B found already expired."""
    pending = collections.defaultdict(list)
    v1, v2, by = [], [], []
    expired = 0
    for i, (st, k, x, t) in enumerate(zip(stream.tolist(), key.tolist(),
                                          v.tolist(), ts.tolist())):
        if st == 0:
            pending[k].append((t, x))
            continue
        keep = []
        for t1, x1 in pending[k]:
            if t - t1 > within_ms:
                expired += 1
            elif x > x1:
                v1.append(x1)
                v2.append(x)
                by.append(i)
            else:
                keep.append((t1, x1))
        pending[k] = keep
    return (np.asarray(v1, np.float64), np.asarray(v2, np.float64),
            np.asarray(by, np.int64), expired)


# ----------------------------------------------------------- engine side


class _CompileMeter:
    """Counts what JAX compiles, by JAX's own monitoring events: one
    backend-compile event per program (a persistent-cache hit still fires
    it, with the retrieval time), plus the cache-hit counter."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name, secs, **_kw):
        if name == self._BACKEND:
            self.programs += 1
            self.seconds += secs

    def _on_event(self, name, **_kw):
        if name == self._HIT:
            self.cache_hits += 1

    def mark(self):
        return (self.programs, self.seconds, self.cache_hits)


class _ErrorLog(logging.Handler):
    """The junction logs and DROPS a receiver's exception (reference
    semantics), an XlaRuntimeError from a refused step included — so any
    ERROR record fails the phase."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(record.getMessage()[:2000])


def _collector(names):
    from siddhi_tpu import StreamCallback

    class Collector(StreamCallback):
        """Keeps the output columns of every delivered batch (valid rows
        only, in delivery order)."""

        def __init__(self):
            self.parts = {n: [] for n in names}
            self.rows = 0

        def receive_batch(self, batch, junction):
            valid = np.asarray(batch.cols["__valid__"])
            for n in names:
                self.parts[n].append(np.asarray(batch.cols[n])[valid])
            self.rows += int(valid.sum())

        def column(self, n):
            return (np.concatenate(self.parts[n]) if self.parts[n]
                    else np.empty(0))

    return Collector()


def _device_line():
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _memory(device):
    stats = device.memory_stats()
    if not stats:
        return None     # the CPU backend reports none
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def _engine_jit(rt):
    """The engine's own per-step compile record (telemetry.jit): which
    step programs this app built, e.g. a ``.generic`` NFA variant."""
    return {k: {"compiles": v.get("compiles", 0),
                "compile_ms": round(v.get("compile_ms", 0.0), 1)}
            for k, v in sorted(rt.app_context.telemetry.jit.items())}


def _knobs(rt):
    ac = rt.app_context
    return {k: getattr(ac, k) for k in (
        "precision", "pipeline_depth", "fuse_fanout", "program_cache",
        "profile_device_instruments", "join_partitions", "nfa_slots")}


def _float_tolerance(precision, window, max_abs):
    """What the precision in force promises for a float aggregate.

    "exact": 64-bit accumulators — agreement with a float64 reference to
    rounding of the running sums (rtol 1e-9).
    "fast": float32 on the device. The fused window step forms each
    group's running sum as a difference of prefix sums over the whole
    batch, so every add rounds at the magnitude of the WINDOW's total
    (<= W * max|x|), not of the group's own values: the bound is a few
    float32 ulps of that total, absolute."""
    if precision == "exact":
        return {"rtol": 1e-9, "atol": 1e-9}
    return {"rtol": 0.0, "atol": 16 * _EPS32 * window * max_abs}


def _double_tolerance(platform):
    """What a ``double`` attribute that only PASSES THROUGH the device
    may differ by. The CPU backend returns it bit for bit. The TPU has no
    native float64: XLA emulates it, and a double can come back an ulp
    off (PR 21, first chip run: 80% of phase C's rows did)."""
    return None if platform == "cpu" else {"rtol": 1e-12, "atol": 0.0}


def _compare(phase, name, got, want, tol=None):
    if len(got) != len(want):
        raise SmokeFailure(
            f"phase {phase}: column {name}: {len(got)} rows out, the "
            f"reference has {len(want)}")
    if tol is None:
        bad = np.nonzero(np.asarray(got) != np.asarray(want))[0]
        err = 0.0
    else:
        diff = np.abs(np.asarray(got, np.float64) - want)
        err = float(diff.max(initial=0.0))
        bad = np.nonzero(diff > tol["atol"] + tol["rtol"] * np.abs(want))[0]
    if bad.size:
        i = int(bad[0])
        raise SmokeFailure(
            f"phase {phase}: column {name}: {bad.size} of {len(want)} rows "
            f"differ from the reference; first at row {i}: got {got[i]!r}, "
            f"want {want[i]!r} (tolerance {tol})")
    return err


def _finish(phase, line, meter, warm_mark, run_mark, errors):
    """Shared tail of a phase: the set-up and steady-window compile
    counts, the ERROR log, and the printed line."""
    line["warmup"] = {
        "programs_compiled": run_mark[0] - warm_mark[0],
        "compile_seconds": round(run_mark[1] - warm_mark[1], 3),
        "persistent_cache_hits": run_mark[2] - warm_mark[2]}
    line["compiles_after_warmup"] = meter.programs - run_mark[0]
    line["errors_logged"] = list(errors.records)
    print(json.dumps(line), flush=True)
    if line["compiles_after_warmup"]:
        raise SmokeFailure(
            f"phase {phase}: {line['compiles_after_warmup']} programs "
            f"compiled after warm-up")
    if errors.records:
        raise SmokeFailure(
            f"phase {phase}: the engine logged at ERROR: "
            f"{errors.records[0]}")


def _stock_feed(rng, sizes, n_batches, skewed):
    """Warm batch (every key once over, at the measured shape) followed
    by ``n_batches`` random ones. Returns per-batch (ids, price, volume)
    and the symbol table."""
    K, B = sizes.keys, sizes.batch
    symbols = np.array([f"S{i}" for i in range(K)], dtype=object)
    feed = [(np.arange(B, dtype=np.int64) % K,
             np.ones(B, np.float32), np.ones(B, np.int64))]
    n_hot = max(1, int(K * sizes.hot_share))
    for _ in range(n_batches):
        if skewed:
            hot = rng.random(B) < sizes.hot_traffic
            ids = np.where(hot, rng.integers(0, n_hot, B),
                           rng.integers(n_hot, K, B))
        else:
            ids = rng.integers(0, K, B, dtype=np.int64)
        feed.append((ids.astype(np.int64),
                     (rng.random(B) * 100.0).astype(np.float32),
                     rng.integers(1, 1000, B, dtype=np.int64)))
    return symbols, feed


def _drive_stock(app, sizes, feed, symbols, meter, route=None):
    """Run one StockStream app over ``feed`` through the normal entry
    points. ``route`` (a mesh) installs device routing on the query
    first. Returns (collector, line, marks)."""
    import jax

    from siddhi_tpu import SiddhiManager

    manager = SiddhiManager()
    warm_mark = meter.mark()
    rt = manager.create_siddhi_app_runtime(app.format(W=sizes.window))
    out = _collector(("symbol", "avgPrice", "totalVolume"))
    rt.add_callback("OutStream", out)
    if route is not None:
        from siddhi_tpu.parallel.mesh import device_route_query_step

        n = int(route.devices.size)
        rt.start()
        device_route_query_step(
            rt.query_runtimes["bench"], route,
            rows_per_shard=int(sizes.batch / n * sizes.route_slack))
    h = rt.get_input_handler("StockStream")
    B = sizes.batch

    def send(i):
        ids, price, volume = feed[i]
        h.send_columns(
            {"symbol": symbols[ids], "price": price, "volume": volume},
            timestamps=np.arange(i * B, (i + 1) * B, dtype=np.int64))

    t0 = time.perf_counter()
    send(0)                                   # warm: full key capacity
    warm_s = time.perf_counter() - t0
    run_mark = meter.mark()
    t0 = time.perf_counter()
    for i in range(1, len(feed)):
        send(i)
    wall = time.perf_counter() - t0
    line = {
        "knobs": _knobs(rt), "engine_jit": _engine_jit(rt),
        "events_in": B * len(feed), "rows_out": out.rows,
        "warmup_seconds": round(warm_s, 3),
        "measured_events": B * (len(feed) - 1),
        "measured_wall_seconds": round(wall, 3),
        "memory": [_memory(d) for d in jax.devices()[
            :1 if route is None else int(route.devices.size)]],
    }
    # ids -> strings through the app's dictionary, as decode_events does
    out_ids = out.column("symbol").astype(np.int64)
    to_str = rt.app_context.string_dictionary.decode
    out_sym = np.array([to_str(i) for i in range(int(out_ids.max()) + 1)],
                       dtype=object)[out_ids]
    manager.shutdown()
    return out, out_sym, line, warm_mark, run_mark


def _stock_phase(phase, app, query, reference, feed_of, sizes, meter,
                 errors):
    """Shared body of phases A and B: drive the StockStream app, hold
    every output row to ``reference``'s, print the line."""
    symbols, feed = feed_of
    out, out_sym, line, warm_mark, run_mark = _drive_stock(
        app, sizes, feed, symbols, meter)
    ids, price, volume = (np.concatenate(c) for c in zip(*feed))
    want_avg, want_vol, extra = reference(ids, price, volume, sizes.window)
    tol = _float_tolerance(line["knobs"]["precision"], sizes.window, 100.0)
    _compare(phase, "symbol", out_sym, symbols[ids])
    _compare(phase, "totalVolume", out.column("totalVolume"), want_vol)
    err = _compare(phase, "avgPrice", out.column("avgPrice"), want_avg, tol)
    line = {"phase": phase, "query": query % sizes.window,
            "keys": sizes.keys, "batch": sizes.batch, **extra,
            "float_tolerance": tol, "float_max_abs_error": err,
            "equal_to_reference": True, **line}
    _finish(phase, line, meter, warm_mark, run_mark, errors)


def phase_a(sizes, seed, meter, errors):
    """Keyed aggregation over a global window: the north-star shape."""
    rng = np.random.default_rng(seed)
    _stock_phase("A", _APP_A, "length(%d) avg/sum group by symbol",
                 reference_global_window,
                 _stock_feed(rng, sizes, sizes.batches_a, skewed=False),
                 sizes, meter, errors)


def phase_b(sizes, seed, meter, errors):
    """Config #2 as written: per-key rings resident on the device."""
    rng = np.random.default_rng(seed + 1)
    _stock_phase("B", _APP_B, "partition by symbol: length(%d) avg/sum",
                 reference_keyed_window,
                 _stock_feed(rng, sizes, sizes.batches_b, skewed=True),
                 sizes, meter, errors)


def _pattern_feed(rng, sizes):
    """Rounds of one A batch and one B batch, 1 s apart in event time.
    Round 0 warms: every key once over, every A answered at once. After
    it, of each round's A rows 70% are answered in the same round by a
    higher B, 15% by a lower B (no match: the A stays pending), and 15%
    only ``WITHIN_S + 1`` rounds later — outside the bound."""
    K, B = sizes.keys, sizes.batch_c
    names = np.array([f"K{i}" for i in range(K)], dtype=object)
    # one timestamp per batch: a head batch whose
    # same-key rows span several timestamps is dispatched to the serial
    # engine instead of the two-step kernel (nfa_runtime._host_hard_batch)
    stamp = np.zeros(B, np.int64)
    late = collections.defaultdict(list)
    batches = []       # (stream, key ids, v, ts)
    for r in range(sizes.rounds_c + 1):
        t = 10_000 + r * 1_000
        if r == 0:
            ka = np.arange(B, dtype=np.int64) % K
            va = rng.random(B) * 100.0
            kb, vb = ka, va + 1.0
        else:
            ka = rng.integers(0, K, B, dtype=np.int64)
            va = rng.random(B) * 100.0
            kind = rng.random(B)
            now = kind < 0.85
            kb = ka[now]
            vb = np.where(kind[now] < 0.70, va[now] + 1.0, va[now] - 1.0)
            late[r + WITHIN_S + 1].append((ka[~now], va[~now] + 1.0))
            for lk, lv in late.pop(r, []):
                kb = np.concatenate([kb, lk])
                vb = np.concatenate([vb, lv])
            # B batches keep the measured shape: pad by repeating rows
            # below every pending A (they match nothing) up to B rows
            pad = B - len(kb)
            if pad < 0:
                kb, vb = kb[:B], vb[:B]
            elif pad:
                kb = np.concatenate([kb, ka[:pad]])
                vb = np.concatenate([vb, np.full(pad, -1.0)])
        batches.append((0, ka, va, t + stamp))
        batches.append((1, kb, vb, t + 500 + stamp))
    return names, batches


def phase_c(sizes, seed, meter, errors):
    """Config #4: the two-step pattern over 10,000 partition keys."""
    import jax

    from siddhi_tpu import SiddhiManager

    rng = np.random.default_rng(seed + 2)
    names, batches = _pattern_feed(rng, sizes)
    manager = SiddhiManager()
    warm_mark = meter.mark()
    rt = manager.create_siddhi_app_runtime(_APP_C.format(WITHIN=WITHIN_S))
    out = _collector(("v1", "v2"))
    rt.add_callback("MatchStream", out)
    handlers = (rt.get_input_handler("AStream"),
                rt.get_input_handler("BStream"))

    def send(i):
        st, k, v, ts = batches[i]
        handlers[st].send_columns({"k": names[k], "v": v}, timestamps=ts)

    t0 = time.perf_counter()
    send(0)
    send(1)
    warm_s = time.perf_counter() - t0
    run_mark = meter.mark()
    t0 = time.perf_counter()
    for i in range(2, len(batches)):
        send(i)
    wall = time.perf_counter() - t0
    n_in = sum(len(b[1]) for b in batches)
    line = {
        "phase": "C",
        "query": "every e1=A -> e2=B[e2.v>e1.v] within %d sec" % WITHIN_S,
        "keys": sizes.keys, "batch": sizes.batch_c,
        "knobs": _knobs(rt), "engine_jit": _engine_jit(rt),
        "events_in": n_in, "rows_out": out.rows,
        "warmup_seconds": round(warm_s, 3),
        "measured_events": n_in - 2 * sizes.batch_c,
        "measured_wall_seconds": round(wall, 3),
        "memory": [_memory(jax.devices()[0])],
    }
    manager.shutdown()
    want_v1, want_v2, by, expired = reference_pattern(
        np.concatenate([np.full(len(b[1]), b[0]) for b in batches]),
        np.concatenate([b[1] for b in batches]),
        np.concatenate([b[2] for b in batches]),
        np.concatenate([b[3] for b in batches]), WITHIN_S * 1000)
    got_v1, got_v2 = out.column("v1"), out.column("v2")
    # pass-through doubles: no arithmetic on them under either precision.
    # v2 in order pins which B event produced every row, in arrival order
    # (two random doubles differ by far more than the tolerance).
    tol = _double_tolerance(jax.devices()[0].platform)
    line["double_tolerance"] = tol
    line["rows_not_bit_equal"] = int((got_v2 != want_v2).sum()
                                     if len(got_v2) == len(want_v2) else -1)
    _compare("C", "v2", got_v2, want_v2, tol)
    # Where ONE B consumes several pending A's, the reference emits them
    # oldest first; the engine emits them in slot order (ops/nfa.py
    # _flatten_out), which differs once a freed slot has been reused. The
    # rows of one B are therefore compared as a set, and the rows that
    # are not in arrival order are counted and printed, not hidden.
    line["rows_not_in_e1_arrival_order"] = int(
        (np.abs(got_v1 - want_v1) > 1e-9).sum())
    _compare("C", "v1", got_v1[np.lexsort((got_v1, by))],
             want_v1[np.lexsort((want_v1, by))], tol)
    line["pending_found_expired"] = expired
    line["equal_to_reference"] = True
    _finish("C", line, meter, warm_mark, run_mark, errors)


def phase_mesh(sizes, seed, meter, errors, n_chips, device):
    """Phase B's partitioned query routed over ``n_chips`` devices
    (``device_route_query_step``) against the same query unsharded in
    this process."""
    import jax

    from siddhi_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_chips)
    devs = list(mesh.devices.flat)
    if len({d.id for d in devs}) != n_chips:
        raise SmokeFailure(f"mesh holds {devs}, not {n_chips} distinct chips")
    rng = np.random.default_rng(seed + 1)
    symbols, feed = _stock_feed(rng, sizes, sizes.batches_mesh, skewed=True)
    base, base_sym, line, warm_mark, run_mark = _drive_stock(
        _APP_B, sizes, feed, symbols, meter)
    line = {"phase": "mesh/unsharded", "devices": 1, "keys": sizes.keys,
            "batch": sizes.batch, **line}
    _finish("mesh/unsharded", line, meter, warm_mark, run_mark, errors)
    name = "mesh/routed"
    out, out_sym, line, warm_mark, run_mark = _drive_stock(
        _APP_B, sizes, feed, symbols, meter, route=mesh)
    _compare(name, "symbol", out_sym, base_sym)
    _compare(name, "totalVolume", out.column("totalVolume"),
             base.column("totalVolume"))
    # same arithmetic in another program: bit-equal on the CPU; on the
    # TPU float64 is emulated and the two programs round differently
    # in the last bits (first four-chip run, PR 21: 95 of 3.2 M rows)
    tol = _double_tolerance(device["platform"])
    not_bit_equal = int((out.column("avgPrice")
                         != base.column("avgPrice")).sum())
    err = _compare(name, "avgPrice", out.column("avgPrice"),
                   base.column("avgPrice"), tol)
    line = {"phase": name, "devices": n_chips,
            "mesh_device_ids": [d.id for d in devs],
            "outcome": "equal to unsharded, row for row",
            "double_tolerance": tol, "rows_not_bit_equal": not_bit_equal,
            "float_max_abs_error": err, **line}
    _finish(name, line, meter, warm_mark, run_mark, errors)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU backend; proves control "
                         "flow only, and says so in its last line")
    args = ap.parse_args(argv)

    import jax

    from siddhi_tpu.core.util.compile_cache import place_compile_cache
    from siddhi_tpu.native import strdict_lib

    # a rehearsal runs inside other processes (the tests): it leaves
    # their JAX configuration alone and keeps no cache
    cache_dir = None if args.cpu_rehearsal else place_compile_cache()
    if args.cpu_rehearsal:
        if jax.default_backend() != "cpu" or len(jax.devices()) < args.chips:
            from siddhi_tpu.parallel.mesh import force_host_devices

            force_host_devices(max(args.chips, 1))
        sizes = TINY
    else:
        sizes = FULL
    device = _device_line()
    want = "cpu" if args.cpu_rehearsal else "tpu"
    # a rehearsal takes the first --chips of the virtual devices; a chip
    # run holds the whole host, and its count is the count it ran on
    enough = (device["count"] >= args.chips if args.cpu_rehearsal
              else device["count"] == args.chips)
    if device["platform"] != want or not enough:
        print(f"chip_smoke: needs {args.chips} {want} device(s); JAX found "
              f"{device}", file=sys.stderr)
        return 2
    device["count"] = args.chips

    errors = _ErrorLog()
    logging.getLogger().addHandler(errors)
    meter = _CompileMeter()
    native = strdict_lib() is not None
    print(json.dumps({
        "device": device, "seed": args.seed, "sizes": dataclasses.asdict(sizes),
        "rehearsal": args.cpu_rehearsal, "compile_cache_dir": cache_dir,
        "string_encoder": "native" if native else "python"}), flush=True)
    if not native:
        raise SmokeFailure("the native string encoder did not build; the "
                           "engine would run its pure-Python fallback")
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_a(sizes, args.seed, meter, errors)
        phase_b(sizes, args.seed, meter, errors)
        phase_c(sizes, args.seed, meter, errors)
    else:
        phase_mesh(sizes, args.seed, meter, errors, args.chips, device)
    logging.getLogger().removeHandler(errors)
    print(json.dumps({"total_seconds": round(time.perf_counter() - t0, 1),
                      "programs_compiled": meter.programs,
                      "compile_seconds": round(meter.seconds, 1),
                      "persistent_cache_hits": meter.cache_hits}), flush=True)
    last = {"ok": True, "device": device}
    if args.cpu_rehearsal:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
