"""Kill 1 of 2 REAL jax.distributed processes mid-stream; the survivor
recovers through the full resilience protocol — the supervisor's peer
heartbeat monitor notices the death, abandons the old runtime, rebuilds
on ``local_survivor_mesh()``, restores the last persisted revision from
the shared store, replays the ingest-WAL suffix, and resumes — and its
post-recovery output stream exactly matches an uninterrupted run
(VERDICT next-item #5's "done" bar; ISSUE 1 acceptance).

Detection note: this jaxlib's CPU backend cannot compile cross-process
computations at all ("Multiprocess computations aren't implemented on
the CPU backend" — see test_multihost.py), so the blocked-collective
detection path (``guarded_pull`` → ``ClusterPeerError``) is exercised by
the single-process drop_peer test in test_resilience.py; here the REAL
kill is detected by the supervisor's ``PeerMonitor`` socket heartbeats —
the mechanism that also covers peers dying while no collective is in
flight."""

import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap

APP = """
    @app:name('recoApp')
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

SEG_A = [(1000 + i * 50, f"P{i % 4}", float((i * 3) % 7)) for i in range(6)]
SEG_B = [(2000 + i * 50, f"P{i % 4}", float((i * 5) % 7)) for i in range(4)]
SEG_C = [(3000 + i * 50, f"P{i % 4}", float((i * 2) % 7)) for i in range(4)]

# Two real jax.distributed processes; each also binds a PeerMonitor
# heartbeat listener on a pre-allocated port and watches the other's.
# Process 1 dies abruptly right after the shared checkpoint — but only
# once process 0 confirms (ready flag) that its monitor saw the peer
# ALIVE, so the death is a detected TRANSITION, not a never-seen peer.
# Process 0's supervisor then loses the heartbeat and drives recovery.
_WORKER = textwrap.dedent("""
    import json
    import os
    import sys
    import time
    import traceback

    sys.path.insert(0, os.getcwd())

    (coord, pid, flag, store_dir, my_port, peer_port) = (
        sys.argv[1], int(sys.argv[2]), sys.argv[3], sys.argv[4],
        int(sys.argv[5]), int(sys.argv[6]))
    ready = flag + ".ready"

    def _die(tp, v, tb):
        # an uncaught failure must EXIT, not park in jax.distributed's
        # atexit shutdown barrier (it waits on the already-dead peer)
        traceback.print_exception(tp, v, tb)
        sys.stderr.flush()
        os._exit(3)
    sys.excepthook = _die
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(2)
    from siddhi_tpu.parallel.distributed import (
        initialize_cluster, local_survivor_mesh)

    # huge heartbeat budget: the coordination service must not tear the
    # survivor down for the peer death the supervisor is going to handle
    initialize_cluster(coordinator_address=coord, num_processes=2,
                       process_id=pid, max_missing_heartbeats=10_000)
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.persistence import FileSystemPersistenceStore
    from siddhi_tpu.parallel.mesh import shard_query_step
    from siddhi_tpu.resilience import PeerMonitor, PeerRecovery

    APP = %r
    SEG_A = %r
    SEG_B = %r
    SEG_C = %r

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
    # this jaxlib cannot compile cross-process computations on CPU (see
    # module docstring): state shards over each process's LOCAL devices
    shard_query_step(rt.query_runtimes["q"], local_survivor_mesh())
    wal = rt.enable_wal()
    ha = rt.get_input_handler("A")
    hb = rt.get_input_handler("B")

    for t, k, v in SEG_A:
        ha.send(t, [k, v])
        hb.send(t + 1, [k, v + 1.0])
    rt.persist()

    if pid == 1:
        # stay alive (heartbeat listener up) until the survivor confirms
        # its monitor saw this peer ALIVE — the kill must be a detected
        # transition, not a peer that never came up
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
    sup = rt.supervise(interval_s=0.2,
                       peer_recovery=PeerRecovery(rebuild, wal=wal),
                       peer_monitor=monitor)
    # confirm the monitor saw the peer ALIVE before it dies (no
    # false-positive detection path)
    t0 = time.time()
    while not monitor._peers[("127.0.0.1", peer_port)]["seen"]:
        assert time.time() - t0 < 120, "peer heartbeat never came up"
        time.sleep(0.05)
    open(ready, "w").write("go")      # release the victim to die

    while not os.path.exists(flag):
        time.sleep(0.05)
    # mid-stream: these batches land after the checkpoint — accepted,
    # WAL-recorded, and processed by the doomed incarnation while the
    # supervisor is still counting missed heartbeats
    for t, k, v in SEG_B:
        ha.send(t, [k, v])
        hb.send(t + 1, [k, v + 1.0])

    result = sup.wait_recovered(120.0)
    assert result is not None, "peer death was never detected"
    new_rt, revision = result
    assert revision is not None, "no revision restored"

    for t, k, v in SEG_C:
        ha2 = new_rt.get_input_handler("A")
        hb2 = new_rt.get_input_handler("B")
        ha2.send(t, [k, v])
        hb2.send(t + 1, [k, v + 1.0])

    print(json.dumps({
        "pre": c1.rows, "post": c2.rows,
        "replayed": wal.replayed_batches,
    }), flush=True)
    os._exit(0)   # the half-dead cluster cannot barrier a clean teardown
""") % (APP, SEG_A, SEG_B, SEG_C)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _expected_rows():
    """The same feed against a plain single-process runtime, split at the
    checkpoint."""
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
    ha = rt.get_input_handler("A")
    hb = rt.get_input_handler("B")
    for t, k, v in SEG_A:
        ha.send(t, [k, v])
        hb.send(t + 1, [k, v + 1.0])
    n_pre = len(c.rows)
    for t, k, v in SEG_B + SEG_C:
        ha.send(t, [k, v])
        hb.send(t + 1, [k, v + 1.0])
    m.shutdown()
    return c.rows[:n_pre], c.rows[n_pre:]


def test_kill_one_of_two_peers_supervised_recovery_exact_outputs():
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    hb_ports = {0: _free_port(), 1: _free_port()}
    flag = tempfile.mktemp(prefix="siddhi-reco-flag-")
    store_dir = tempfile.mkdtemp(prefix="siddhi-reco-store-")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, coord, str(pid), flag,
             store_dir, str(hb_ports[pid]), str(hb_ports[1 - pid])],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for pid in (0, 1)
    ]
    try:
        out1, _err1 = procs[1].communicate(timeout=300)
        assert procs[1].returncode == 17          # victim died on cue
        try:
            out0, err0 = procs[0].communicate(timeout=300)
        except subprocess.TimeoutExpired:
            raise AssertionError("survivor hung after peer death")
        assert procs[0].returncode == 0, f"survivor failed:\n{err0[-4000:]}"
    finally:
        for q in procs:
            if q.poll() is None:
                q.kill()

    payload = json.loads(out0.strip().splitlines()[-1])
    expected_pre, expected_post = _expected_rows()
    # pre-death: the sharded runtime matched the single-process run
    assert payload["pre"][:len(expected_pre)] == expected_pre
    # post-recovery: restore + WAL replay + resumed feed — the output
    # stream continues exactly where the checkpoint left off (the
    # mid-death batches came back via the replay; nothing lost, nothing
    # doubled in the recovered stream)
    assert payload["post"] == expected_post
    assert payload["replayed"] == 2 * len(SEG_B)


# --------------------------------------------------- router-side fabric


def _column_feed(send):
    """The same A/B interleave as the segments, one row per batch (the
    pattern is order-sensitive across both streams)."""
    import numpy as np

    for seg in (SEG_A, SEG_B, SEG_C):
        for t, k, v in seg:
            send("A", {"k": np.array([k], object),
                       "v": np.array([v])},
                 np.array([t], np.int64))
            send("B", {"k": np.array([k], object),
                       "v": np.array([v + 1.0])},
                 np.array([t + 1], np.int64))
        yield


def test_router_kill_one_of_two_workers_exact_egress():
    """The cluster-fabric half of the recovery story (ISSUE 17): the
    ROUTER owns the WAL and the supervisor owns the processes. One of
    two REAL worker processes is SIGKILLed between segments — after the
    deploy handshake proved it up (the ready-flag discipline) and after
    a checkpoint barrier cut its WAL — and the merged egress stream
    must exactly match an uninterrupted single-process run: zero lost
    rows, zero doubled rows, original order."""
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.cluster import ClusterRuntime
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

    def base_send(stream, data, tss):
        rt.get_input_handler(stream).send_columns(data, timestamps=tss)

    for _ in _column_feed(base_send):
        pass
    m.shutdown()

    cluster = ClusterRuntime(n_workers=2, heartbeat_s=0.2)
    try:
        cluster.wait_ready(60)
        cluster.deploy(APP, partition_keys={"A": "k", "B": "k"},
                       sinks=["Out"])

        def cl_send(stream, data, tss):
            cluster.send_columns("recoApp", stream, data, timestamps=tss)

        feed = _column_feed(cl_send)
        next(feed)                       # SEG_A delivered
        cluster.checkpoint()             # cut + trim both worker WALs
        cluster.supervisor.kill(1)       # SIGKILL mid-stream
        for _ in feed:                   # SEG_B + SEG_C keep flowing
            pass
        assert cluster.quiesce(180), "egress never quiesced after kill"
        got = [(ts, tuple(vals)) for ts, vals in
               cluster.egress.stream_rows("recoApp", "Out")]
        assert got == c.rows
        assert sum(cluster.supervisor.respawns) >= 1
    finally:
        cluster.shutdown()
