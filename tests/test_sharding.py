"""Sharded == unsharded equivalence on the virtual 8-device CPU mesh.

Drives the same event sequences through an unsharded app and one whose
query state is sharded over the key axis (``parallel/mesh.py``), asserting
identical outputs — the suite-level guarantee behind ``dryrun_multichip``
(SURVEY.md §2.13: key-space sharding over ICI).
"""

import numpy as np

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.parallel.mesh import make_mesh, shard_query_step


class Collector(StreamCallback):
    def __init__(self):
        super().__init__()
        self.events = []

    def receive(self, events):
        self.events.extend(events)


def _build(app, out_stream):
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(app)
    c = Collector()
    rt.add_callback(out_stream, c)
    return m, rt, c


def _drive_pair(app, out_stream, shard_query, feed):
    """Run `feed(rt)` against unsharded and sharded runtimes; return the
    two sorted output lists."""
    m1, rt1, c1 = _build(app, out_stream)
    feed(rt1)
    m1.shutdown()

    m2, rt2, c2 = _build(app, out_stream)
    mesh = make_mesh(8)
    shard_query_step(rt2.query_runtimes[shard_query], mesh)
    feed(rt2)
    m2.shutdown()
    # identical event order in == identical output order out
    return c1.events, c2.events


def test_sharded_group_by_window_aggregation():
    # BASELINE config #2/#3 family: length window -> group-by avg/sum
    app = """
        define stream S (symbol string, price double, volume long);
        @info(name = 'q')
        from S#window.length(16)
        select symbol, avg(price) as ap, sum(volume) as tv
        group by symbol
        insert into Out;
    """
    rng = np.random.default_rng(7)

    def feed(rt):
        h = rt.get_input_handler("S")
        for i in range(120):
            h.send([f"K{int(rng.integers(0, 24)) if False else i % 24}",
                    float(i % 13) + 0.5, int(i)])

    a, b = _drive_pair(app, "Out", "q", feed)
    assert len(a) > 0
    assert [e.data for e in a] == [e.data for e in b]


def test_sharded_partitioned_keyed_window():
    app = """
        @app:playback
        define stream S (k string, v double);
        partition with (k of S)
        begin
          @info(name = 'q')
          from S#window.length(4) select k, sum(v) as s insert into Out;
        end;
    """
    rng = np.random.default_rng(11)

    def feed(rt):
        h = rt.get_input_handler("S")
        for i in range(200):
            h.send(1000 + i, [f"P{int(rng.integers(0, 32))}", float(i % 7)])

    # second runtime must see identical key arrival order: regenerate rng
    def feed2(rt):
        r = np.random.default_rng(11)
        h = rt.get_input_handler("S")
        for i in range(200):
            h.send(1000 + i, [f"P{int(r.integers(0, 32))}", float(i % 7)])

    m1, rt1, c1 = _build(app, "Out")
    feed2(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app, "Out")
    shard_query_step(rt2.query_runtimes["q"], make_mesh(8))
    feed2(rt2)
    m2.shutdown()
    assert len(c1.events) > 0
    assert [e.data for e in c1.events] == [e.data for e in c2.events]


def test_sharded_partitioned_nfa_pattern():
    # BASELINE config #4 family: every A -> B[v > e1.v] within, partitioned
    app = """
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

    def feed(rt):
        r = np.random.default_rng(3)
        ha = rt.get_input_handler("A")
        hb = rt.get_input_handler("B")
        t = 1000
        for i in range(60):
            k = f"P{int(r.integers(0, 24))}"
            va = float(r.random() * 10)
            ha.send(t, [k, va])
            hb.send(t + 1, [k, va + (1.0 if i % 3 else -1.0)])
            t += 50

    m1, rt1, c1 = _build(app, "Out")
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app, "Out")
    shard_query_step(rt2.query_runtimes["q"], make_mesh(8))
    feed(rt2)
    m2.shutdown()
    assert len(c1.events) > 0
    assert [e.data for e in c1.events] == [e.data for e in c2.events]


def test_distributed_single_process_cluster():
    """jax.distributed bring-up: a 1-process cluster initializes, the
    global mesh spans its devices, and a sharded query runs over it —
    exercised in a subprocess (distributed init is process-global)."""
    import subprocess
    import sys

    script = r'''
from siddhi_tpu.parallel.mesh import force_host_devices
force_host_devices(4)   # a CPU test: four virtual host devices
from siddhi_tpu.parallel.distributed import (
    global_mesh, initialize_cluster, process_info)
initialize_cluster(coordinator_address="127.0.0.1:18476",
                   num_processes=1, process_id=0)
info = process_info()
assert info["process_count"] == 1 and info["global_devices"] == 4, info

from siddhi_tpu import SiddhiManager, StreamCallback
from siddhi_tpu.parallel.mesh import shard_query_step
m = SiddhiManager()
rt = m.create_siddhi_app_runtime("""
    define stream S (sym string, v int);
    @info(name='q')
    from S select sym, sum(v) as s group by sym insert into Out;
""")
seen = []
class C(StreamCallback):
    def receive(self, events):
        seen.extend(tuple(e.data) for e in events)
rt.add_callback("Out", C())
shard_query_step(rt.query_runtimes["q"], global_mesh())
h = rt.get_input_handler("S")
h.send(["a", 1]); h.send(["b", 2]); h.send(["a", 3])
m.shutdown()
assert seen == [("a", 1), ("b", 2), ("a", 4)], seen
print("DIST_OK")
'''
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=300)
    assert "DIST_OK" in r.stdout, r.stderr[-2000:]


def test_sharded_partitioned_absent_pattern():
    """Absent deadlines + scheduler TIMER sweeps over key-sharded [K, S]
    NFA state must match the unsharded run."""
    app = """
        @app:playback
        define stream A (k string, v double);
        define stream B (k string, v double);
        partition with (k of A, k of B)
        begin
          @info(name = 'q')
          from every e1=A -> not B[v > e1.v] for 200 milliseconds
          select e1.v as v1
          insert into Out;
        end;
    """

    def feed(rt):
        r = np.random.default_rng(9)
        ha = rt.get_input_handler("A")
        hb = rt.get_input_handler("B")
        t = 1000
        for i in range(50):
            k = f"P{int(r.integers(0, 12))}"
            va = float(int(r.random() * 10))
            ha.send(t, [k, va])
            if i % 3 == 0:
                hb.send(t + 50, [k, va + 1.0])   # violates that key's wait
            t += 120   # advances past earlier deadlines -> timer sweeps
        ha.send(t + 1000, ["PX", 0.0])           # final clock advance

    m1, rt1, c1 = _build(app, "Out")
    feed(rt1)
    m1.shutdown()
    m2, rt2, c2 = _build(app, "Out")
    shard_query_step(rt2.query_runtimes["q"], make_mesh(8))
    feed(rt2)
    m2.shutdown()
    assert len(c1.events) > 0
    assert [e.data for e in c1.events] == [e.data for e in c2.events]
