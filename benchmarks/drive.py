"""The engine side of a run: build the cell's app through ``SiddhiManager``
with the engine's defaults, drive ``InputHandler.send_columns`` from one
thread, take results where a user takes them (a ``StreamCallback`` that
pulls every output column to the host), and keep the clock.

The compile meter, the ERROR-log handler and the collector are copies of
``chip_smoke.py``'s (PR 21), which ran on the chip and agreed with the
references row for row; they live here so that later PRs cannot change
the yardstick.
"""

from __future__ import annotations

import logging
import time

import numpy as np


class CompileMeter:
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


class ErrorLog(logging.Handler):
    """The junction logs and DROPS a receiver's exception (reference
    semantics), an XlaRuntimeError from a refused step included, so any
    ERROR record fails the run."""

    def __init__(self):
        super().__init__(level=logging.ERROR)
        self.records = []

    def emit(self, record):
        self.records.append(record.getMessage()[:2000])


def make_collector(columns):
    """A ``StreamCallback`` that keeps the named output columns of every
    delivered batch (valid rows only, in delivery order) and the host
    clock at which each delivery was complete: the user has the rows."""
    import jax.profiler

    from siddhi_tpu import StreamCallback

    class Collector(StreamCallback):
        def __init__(self):
            self.parts = {n: [] for n in columns}
            self.at = []        # perf_counter when a delivery was complete
            self.rows = []      # valid rows of that delivery

        def receive_batch(self, batch, junction):
            with jax.profiler.TraceAnnotation("bench.callback"):
                valid = np.asarray(batch.cols["__valid__"])
                for n in columns:
                    self.parts[n].append(np.asarray(batch.cols[n])[valid])
                self.rows.append(int(valid.sum()))
                self.at.append(time.perf_counter())

        def column(self, n):
            return (np.concatenate(self.parts[n]) if self.parts[n]
                    else np.empty(0))

    return Collector()


def build_app(config, sizes, chips):
    """The cell's app, as a user deploys it: query text from the
    configuration file, engine defaults, one callback on the output
    stream. ``chips`` 4 routes the query the configuration names over a
    mesh of the local chips (``device_route_query_step``; every shard
    takes ``route_slack`` times its even share of a batch's rows), as
    ``chip_smoke.py --chips 4`` does. No cell asks for it yet (PERF.md,
    Open questions #1): rehearsed on virtual devices only."""
    from siddhi_tpu import SiddhiManager

    manager = SiddhiManager()
    rt = manager.create_siddhi_app_runtime(config["app"].format(**sizes))
    out = config["output"]
    collector = make_collector(tuple(out["columns"].values()))
    rt.add_callback(out["stream"], collector)
    if chips > 1:
        from siddhi_tpu.parallel.mesh import (device_route_query_step,
                                              make_mesh)

        route = config["route"]
        rt.start()
        device_route_query_step(
            rt.query_runtimes[route["query"]], make_mesh(chips),
            rows_per_shard=int(sizes["batch_rows"] / chips
                               * sizes["route_slack"]),
            exchange=route["exchange"])
    return manager, rt, collector


def engine_knobs(rt):
    ac = rt.app_context
    return {k: getattr(ac, k) for k in (
        "precision", "pipeline_depth", "fuse_fanout", "program_cache",
        "profile_device_instruments", "join_partitions", "nfa_slots")}


class Sender:
    """Sends batch i of the feed through the app's input handlers and
    remembers when each was created."""

    def __init__(self, rt, feed):
        self.rt = rt
        self.feed = feed
        self.handlers = [rt.get_input_handler(s) for s in feed.streams]
        self.created = {}    # batch index -> perf_counter stamp
        self.failed = 0

    def send(self, i, due=None):
        import jax.profiler

        b = self.feed.batch(i)
        ts = self.feed.timestamps(i)
        # the creation stamp: the last thing before the entry. An open
        # loop stamps the time the batch was DUE instead.
        self.created[i] = time.perf_counter() if due is None else due
        try:
            with jax.profiler.TraceAnnotation("bench.send_columns"):
                self.handlers[b.stream].send_columns(b.cols, timestamps=ts)
        except Exception:
            self.failed += 1
            raise


def run_window(sender, first, seconds, rate=None, on_tick=None):
    """The measured window: batches ``first``, ``first + 1``, ... until
    ``seconds`` have passed on the host clock. Closed loop when ``rate``
    is None: the next batch goes when the last send returned. Open loop
    otherwise: batch k of the window is due at ``k / rate`` and is sent
    then or, if the sender is late, at once; how late is returned.
    ``on_tick(elapsed)`` runs between sends (the tracer's switch).
    Returns (t0, index after the last batch sent, worst lateness s)."""
    t0 = time.perf_counter()
    i, late = first, 0.0
    while True:
        now = time.perf_counter()
        if on_tick is not None:
            on_tick(now - t0)
        due = None
        if rate is not None:
            offset = (i - first) / rate
            if offset >= seconds:
                break
            due = t0 + offset
            while now < due:
                time.sleep(min(due - now, 0.001))
                now = time.perf_counter()
            late = max(late, now - due)
        elif now - t0 >= seconds:
            break
        sender.send(i, due)
        i += 1
    return t0, i, late
