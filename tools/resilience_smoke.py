"""Kill-one-of-two-peers recovery smoke: end-to-end in under a minute.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Spawns a REAL 2-process ``jax.distributed`` cluster running the
partitioned-NFA app, checkpoints to a shared
FileSystemPersistenceStore, kills process 1 abruptly (``os._exit``, no
cleanup) once process 0's supervisor has confirmed it alive, and
verifies process 0 recovers through the full protocol — PeerMonitor
heartbeat loss → supervisor → abandon → rebuild on
``local_survivor_mesh()`` → ``restore_last_revision`` → ingest-WAL
replay — with outputs that exactly match an uninterrupted
single-process run.

(Each process shards over its own LOCAL devices: this jaxlib's CPU
backend cannot compile cross-process computations at all — see
tests/test_multihost.py — so peer death is detected by the supervisor's
socket heartbeats, the mechanism that also covers peers dying while no
collective is in flight. The blocked-collective path is exercised by
the drop_peer test in tests/test_resilience.py.)

Run: ``python tools/resilience_smoke.py`` (prints one JSON line;
exit 0 = recovered with exact outputs).
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

APP = """
    @app:name('smokeApp')
    @app:playback
    define stream A (k string, v double);
    define stream B (k string, v double);
    partition with (k of A, k of B)
    begin
      @info(name = 'q')
      from every e1=A -> e2=B[e2.v > e1.v] within 5 sec
      select e1.v as v1, e2.v as v2
      insert into Out;
    end;
"""

SEG_A = [(1000 + i * 50, f"P{i % 2}", float(i % 5)) for i in range(4)]
SEG_B = [(2000 + i * 50, f"P{i % 2}", float((i * 3) % 5)) for i in range(3)]


def _pairs(handler_a, handler_b, seg):
    for t, k, v in seg:
        handler_a.send(t, [k, v])
        handler_b.send(t + 1, [k, v + 1.0])


def worker(coord: str, pid: int, flag: str, store_dir: str,
           my_port: int, peer_port: int) -> None:
    import traceback

    def _die(tp, v, tb):
        # a failed worker must EXIT, not park in jax.distributed's
        # atexit shutdown barrier (it waits on the already-dead peer)
        traceback.print_exception(tp, v, tb)
        sys.stderr.flush()
        os._exit(3)

    sys.excepthook = _die
    ready = flag + ".ready"
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(2)
    from siddhi_tpu.parallel.distributed import (
        initialize_cluster,
        local_survivor_mesh,
    )

    # huge heartbeat budget: the coordination service must not tear the
    # survivor down for the peer death the supervisor is going to handle
    initialize_cluster(coordinator_address=coord, num_processes=2,
                       process_id=pid, max_missing_heartbeats=10_000)
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.persistence import FileSystemPersistenceStore
    from siddhi_tpu.parallel.mesh import shard_query_step
    from siddhi_tpu.resilience import PeerMonitor, PeerRecovery

    class C(StreamCallback):
        def __init__(self):
            self.rows = []

        def receive(self, events):
            self.rows.extend([e.timestamp] + list(e.data) for e in events)

    monitor = PeerMonitor(listen_port=my_port, probe_timeout_s=0.5,
                          misses=3)
    store = FileSystemPersistenceStore(store_dir)
    m = SiddhiManager()
    m.set_persistence_store(store)
    rt = m.create_siddhi_app_runtime(APP)
    c1 = C()
    rt.add_callback("Out", c1)
    shard_query_step(rt.query_runtimes["q"], local_survivor_mesh())
    wal = rt.enable_wal()
    ha, hb = rt.get_input_handler("A"), rt.get_input_handler("B")

    _pairs(ha, hb, SEG_A)
    rt.persist()

    if pid == 1:
        # stay alive (heartbeat listener up) until the survivor confirms
        # its monitor saw this peer ALIVE, so the kill is a detected
        # transition
        t0 = time.time()
        while not os.path.exists(ready):
            assert time.time() - t0 < 120, "survivor never confirmed"
            time.sleep(0.05)
        open(flag, "w").write("dead")
        os._exit(17)                  # abrupt peer death, no cleanup

    # ---- survivor ----
    m2 = SiddhiManager()
    m2.set_persistence_store(store)
    c2 = C()

    def rebuild():
        rt2 = m2.create_siddhi_app_runtime(APP)
        rt2.add_callback("Out", c2)
        shard_query_step(rt2.query_runtimes["q"], local_survivor_mesh())
        return rt2

    monitor.watch("127.0.0.1", peer_port)
    sup = rt.supervise(interval_s=0.1,
                       peer_recovery=PeerRecovery(rebuild, wal=wal),
                       peer_monitor=monitor)
    t0 = time.time()
    while not monitor._peers[("127.0.0.1", peer_port)]["seen"]:
        assert time.time() - t0 < 120, "peer heartbeat never came up"
        time.sleep(0.05)
    open(ready, "w").write("go")      # release the victim to die

    while not os.path.exists(flag):
        time.sleep(0.05)
    # mid-death: accepted and WAL-recorded while the supervisor is still
    # counting missed heartbeats — must come back via the replay
    _pairs(ha, hb, SEG_B)

    result = sup.wait_recovered(60.0)
    assert result is not None, "recovery never ran"
    new_rt, revision = result
    assert revision is not None, "nothing restored"
    print(json.dumps({"pre": c1.rows, "post": c2.rows,
                      "replayed": wal.replayed_batches}), flush=True)
    os._exit(0)   # the half-dead cluster cannot barrier a clean teardown


def expected():
    """Uninterrupted single-process reference, split at the checkpoint."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    class C(StreamCallback):
        def __init__(self):
            self.rows = []

        def receive(self, events):
            self.rows.extend([e.timestamp] + list(e.data) for e in events)

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(APP)
    c = C()
    rt.add_callback("Out", c)
    ha, hb = rt.get_input_handler("A"), rt.get_input_handler("B")
    _pairs(ha, hb, SEG_A)
    n_pre = len(c.rows)
    _pairs(ha, hb, SEG_B)
    m.shutdown()
    return c.rows[:n_pre], c.rows[n_pre:]


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def main() -> int:
    t_start = time.time()
    coord = f"127.0.0.1:{_free_port()}"
    hb_ports = {0: _free_port(), 1: _free_port()}
    flag = tempfile.mktemp(prefix="siddhi-smoke-flag-")
    store_dir = tempfile.mkdtemp(prefix="siddhi-smoke-store-")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", coord,
             str(pid), flag, store_dir, str(hb_ports[pid]),
             str(hb_ports[1 - pid])],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for pid in (0, 1)
    ]
    # compute the reference run while the cluster works
    exp_pre, exp_post = expected()
    try:
        procs[1].communicate(timeout=120)
        out0, err0 = procs[0].communicate(timeout=180)
    except subprocess.TimeoutExpired:
        for q in procs:
            if q.poll() is None:
                q.kill()
        print(json.dumps({"ok": False, "error": "timeout"}))
        return 1
    if procs[0].returncode != 0:
        print(json.dumps({"ok": False, "error": err0[-2000:]}))
        return 1
    payload = json.loads(out0.strip().splitlines()[-1])
    # pre-death the sharded runtime matched the reference (its tail also
    # processed the doomed SEG_B batches — the replay is what re-creates
    # them for the RECOVERED stream, asserted exactly below)
    ok = (payload["pre"][:len(exp_pre)] == exp_pre
          and payload["post"] == exp_post
          and payload["replayed"] >= 1)
    print(json.dumps({
        "ok": ok,
        "elapsed_s": round(time.time() - t_start, 1),
        "pre_rows": len(payload["pre"]),
        "post_rows": len(payload["post"]),
        "replayed_batches": payload["replayed"],
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--worker":
        worker(sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5],
               int(sys.argv[6]), int(sys.argv[7]))
    else:
        sys.exit(main())
