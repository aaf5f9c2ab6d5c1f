"""Cluster worker process: one full engine over one key range.

Runnable as ``python -m siddhi_tpu.cluster.worker --connect HOST:PORT
--index I --persist-dir DIR --hb-port P``. The worker dials the router,
negotiates the wire hello (version + capability bits), then serves the
router's message loop on a single reader thread — DATA runs are
processed strictly in arrival order, which is what lets the router's
egress merger reconstruct exact global order from per-run completions.

State discipline: the worker holds NO replay log — the router records
every run it sends into a per-worker ``IngestWAL`` (resilience/
replay.py), so a killed worker loses only what the router can resend.
On respawn the router re-deploys with ``restore=true`` (the worker
restores its last persisted revision from its own store directory) and
replays the WAL suffix as ordinary DATA runs; the egress merger drops
the re-emissions of already-merged tags. Liveness is the PR-1 peer-
death protocol: the worker binds a ``PeerMonitor`` heartbeat listener
the router's supervisor probes, plus in-band ``CTRL_HEARTBEAT`` frames
on the link.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import threading
import time


def _parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--connect", required=True, help="router HOST:PORT")
    ap.add_argument("--index", type=int, required=True)
    ap.add_argument("--persist-dir", required=True)
    ap.add_argument("--hb-port", type=int, default=0,
                    help="PeerMonitor heartbeat listener port")
    ap.add_argument("--heartbeat-s", type=float, default=0.5)
    ap.add_argument("--ready-flag", default=None,
                    help="file to create once the hello is on the wire")
    return ap.parse_args(argv)


class _AppHost:
    """One deployed app on this worker: manager + runtime + sink taps."""

    def __init__(self, name: str, text: str, sinks, store_dir: str,
                 config=None, restore: bool = False):
        from siddhi_tpu.core.manager import SiddhiManager
        from siddhi_tpu.core.stream.output.stream_callback import (
            StreamCallback)
        from siddhi_tpu.core.util.config import InMemoryConfigManager
        from siddhi_tpu.core.util.persistence import (
            FileSystemPersistenceStore)

        self.name = name
        self.emitted = []     # [(stream, ts, [values])] of the CURRENT run
        self.manager = SiddhiManager()
        os.makedirs(store_dir, exist_ok=True)
        self.manager.set_persistence_store(
            FileSystemPersistenceStore(store_dir))
        if config:
            self.manager.set_config_manager(InMemoryConfigManager(config))
        self.runtime = self.manager.create_siddhi_app_runtime(text)

        host = self

        class _Tap(StreamCallback):
            def __init__(self, stream):
                super().__init__()
                self._stream = stream

            def receive(self, events):
                from siddhi_tpu.cluster.protocol import py_value

                host.emitted.extend(
                    (self._stream, int(e.timestamp),
                     [py_value(v) for v in e.data]) for e in events)

        for s in sinks:
            self.runtime.add_callback(s, _Tap(s))
        self.runtime.start()
        self.restored_revision = None
        if restore:
            self.restored_revision = self.runtime.restore_last_revision()
        self.handlers = {}
        self.definitions = {
            sid: j.definition for sid, j in self.runtime.junctions.items()}

    def handler(self, stream: str):
        h = self.handlers.get(stream)
        if h is None:
            h = self.handlers[stream] = \
                self.runtime.get_input_handler(stream)
        return h

    def take_emitted(self):
        out, self.emitted = self.emitted, []
        return out

    def shutdown(self):
        try:
            self.manager.shutdown()
        except Exception:   # noqa: BLE001 — exit path, best effort
            pass


def _serve(args) -> int:
    from siddhi_tpu.cluster import protocol as P
    from siddhi_tpu.core.stream.input.wire import (
        CAP_CONTROL, CAP_DICT_DELTA, CTRL_CHECKPOINT_CUT, CTRL_HEARTBEAT,
        CTRL_SEQ_ACK, DecoderRegistry, decode_control, decode_frame,
        encode_control, encode_hello, negotiate_hello)
    from siddhi_tpu.resilience.supervisor import PeerMonitor

    host, port = args.connect.rsplit(":", 1)
    # the PR-1 liveness listener the router's supervisor probes
    monitor = PeerMonitor(listen_port=args.hb_port)
    sock = socket.create_connection((host, int(port)), timeout=30)
    link = P.MessageSocket(sock)
    link.send(P.MSG_HELLO, encode_hello(
        sender_id=args.index,
        capabilities=CAP_CONTROL | CAP_DICT_DELTA | (1 << 0)))
    mtype, body = link.recv() or (None, b"")
    if mtype != P.MSG_HELLO:
        raise P.ProtocolError(f"router answered {mtype}, expected hello")
    negotiate_hello(body, required=CAP_CONTROL | CAP_DICT_DELTA)
    link.send(P.MSG_HELLO, encode_control(
        1, a=args.index, body=P.jdump({"index": args.index,
                                       "pid": os.getpid(),
                                       "hb_port": monitor.port})))
    if args.ready_flag:
        with open(args.ready_flag, "w") as f:
            f.write("up")

    apps = {}
    registry = DecoderRegistry()
    stop = threading.Event()

    def _heartbeats():
        tick = 0
        while not stop.is_set():
            tick += 1
            try:
                link.send(P.MSG_HEARTBEAT, encode_control(
                    CTRL_HEARTBEAT, a=args.index, b=tick))
            except OSError:
                return              # router gone: the reader exits too
            stop.wait(args.heartbeat_s)

    threading.Thread(target=_heartbeats, daemon=True,
                     name="cluster-worker-heartbeat").start()

    while True:
        msg = link.recv()
        if msg is None:
            break                   # router closed the link: exit
        mtype, body = msg
        if mtype == P.MSG_DEPLOY:
            spec = P.jload(body)
            name = spec["app"]
            try:
                old = apps.pop(name, None)
                if old is not None:
                    old.shutdown()
                apps[name] = _AppHost(
                    name, spec["text"], spec.get("sinks", ()),
                    os.path.join(args.persist_dir, name),
                    config=spec.get("config"),
                    restore=bool(spec.get("restore")))
                link.send(P.MSG_DEPLOY_OK, P.jdump({
                    "app": name,
                    "revision": apps[name].restored_revision,
                    # the router partitions + decodes against these
                    "streams": {
                        sid: [[a.name, a.type.name] for a in d.attributes]
                        for sid, d in apps[name].definitions.items()}}))
            except Exception as e:      # noqa: BLE001 — reported, not fatal
                link.send(P.MSG_DEPLOY_OK, P.jdump({
                    "app": name, "error": f"{type(e).__name__}: {e}"}))
        elif mtype == P.MSG_DATA:
            seq, run, app_name, stream, frame = P.unpack_data(body)
            app = apps[app_name]
            data, ts = decode_frame(
                frame, app.definitions[stream],
                app.runtime.app_context.string_dictionary,
                registry, scope=app_name)
            app.handler(stream).send_columns(data, timestamps=ts)
            # group the run's emissions into maximal same-stream slices
            # (order preserved — the egress merger replays EMITs of one
            # tag in arrival order)
            groups = []
            for out_stream, ets, values in app.take_emitted():
                if groups and groups[-1][0] == out_stream:
                    groups[-1][1].append([ets, values])
                else:
                    groups.append((out_stream, [[ets, values]]))
            for out_stream, rows in groups:
                link.send(P.MSG_EMIT, P.jdump({
                    "seq": seq, "run": run, "app": app_name,
                    "stream": out_stream, "rows": rows}))
            link.send(P.MSG_ACK, encode_control(CTRL_SEQ_ACK, a=run,
                                                b=seq))
        elif mtype == P.MSG_CHECKPOINT:
            cf = decode_control(body)
            revisions = {}
            for name, app in apps.items():
                revisions[name] = app.runtime.persist()
            link.send(P.MSG_CHECKPOINT_OK, encode_control(
                CTRL_CHECKPOINT_CUT, a=args.index, b=cf.b,
                body=P.jdump({"barrier": cf.b, "revisions": revisions})))
        elif mtype == P.MSG_QUERY:
            q = P.jload(body)
            try:
                events = apps[q["app"]].runtime.query(q["query"])
                rows = [[int(getattr(e, "timestamp", 0) or 0),
                         [P.py_value(v) for v in e.data]]
                        for e in events]
                link.send(P.MSG_QUERY_RESULT, P.jdump({
                    "qid": q["qid"], "rows": rows}))
            except Exception as e:      # noqa: BLE001 — reported, not fatal
                link.send(P.MSG_QUERY_RESULT, P.jdump({
                    "qid": q["qid"],
                    "error": f"{type(e).__name__}: {e}"}))
        elif mtype == P.MSG_HEARTBEAT:
            pass                        # router pings are informational
        elif mtype == P.MSG_SHUTDOWN:
            break
        else:
            link.send(P.MSG_ERROR, P.jdump(
                {"context": "dispatch",
                 "error": f"unknown message type {mtype}"}))
    stop.set()
    for app in apps.values():
        app.shutdown()
    monitor.close()
    link.close()
    return 0


def main(argv=None) -> int:
    # a cluster worker runs its engine on the CPU backend today: a chip
    # belongs to one process, and N workers on one host cannot share it
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    def _die(tp, v, tb):
        # an uncaught failure must EXIT (and be seen), never park the
        # process half-dead with its heartbeat listener still up
        import traceback

        traceback.print_exception(tp, v, tb)
        sys.stderr.flush()
        os._exit(3)

    sys.excepthook = _die
    from siddhi_tpu.core.util.compile_cache import place_compile_cache

    place_compile_cache()
    args = _parse_args(argv)
    try:
        return _serve(args)
    except (ConnectionError, OSError) as e:
        print(f"[cluster-worker {args.index}] link lost: {e}",
              file=sys.stderr, flush=True)
        return 0


if __name__ == "__main__":
    # os._exit: a half-dead link must never hang in atexit teardown
    os._exit(main())
