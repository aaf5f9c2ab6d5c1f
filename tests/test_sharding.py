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


def test_shard_map_routed_keyed_window_matches_unsharded():
    """Round-5 zero-collective path: host router + shard_map over local
    [K/n] keyed state must reproduce the unsharded per-key output
    sequences exactly (tools/hlo_audit.py separately asserts the compiled
    HLO carries no collectives)."""
    import jax

    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY
    from siddhi_tpu.parallel.mesh import (
        route_batch_to_shards, shard_keyed_query_step)

    APP = """
        define stream S (symbol string, price float, volume long);
        partition with (symbol of S)
        begin
          @info(name = 'q')
          from S#window.length(8)
          select symbol, avg(price) as ap, sum(volume) as tv
          insert into Out;
        end;
    """
    NUM_KEYS, B, N = 40, 64, 8
    rng = np.random.default_rng(0)

    def make_batch(i):
        sym = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
        return {
            TS_KEY: np.arange(i * B, (i + 1) * B, dtype=np.int64),
            TYPE_KEY: np.zeros(B, np.int8),
            VALID_KEY: np.ones(B, bool),
            "symbol": sym, "symbol?": np.zeros(B, bool),
            "price": (rng.random(B) * 100).astype(np.float32),
            "price?": np.zeros(B, bool),
            "volume": rng.integers(1, 1000, B, np.int64),
            "volume?": np.zeros(B, bool),
            GK_KEY: sym.astype(np.int32), PK_KEY: sym.astype(np.int32),
        }

    batches = [make_batch(i) for i in range(3)]

    def collect(outs, n_shards=None):
        rows = {}
        for out in outs:
            v = np.asarray(out[VALID_KEY])
            pk = np.asarray(out[PK_KEY])
            r_local = len(v) // (n_shards or 1)
            for j in np.nonzero(v)[0]:
                k = int(pk[j])
                if n_shards is not None:
                    k = k * n_shards + j // r_local  # local id -> global
                rows.setdefault(k, []).append((
                    int(out[TS_KEY][j]), int(out[TYPE_KEY][j]),
                    round(float(out["ap"][j]), 3), int(out["tv"][j])))
        return rows

    m1 = SiddhiManager()
    rt1 = m1.create_siddhi_app_runtime(APP)
    rt1.start()
    q1 = rt1.query_runtimes["q"]
    q1.selector_plan.num_keys = 64
    q1._win_keys = 64
    state = q1._init_state()
    step = jax.jit(q1.build_step_fn())
    uns = []
    for i, b in enumerate(batches):
        state, out = step(state, b, np.int64(10_000 + i))
        uns.append(jax.device_get(out))
    m1.shutdown()

    m2 = SiddhiManager()
    rt2 = m2.create_siddhi_app_runtime(APP)
    rt2.start()
    q2 = rt2.query_runtimes["q"]
    q2.selector_plan.num_keys = 16   # local capacity: ceil(40/8) -> 16
    q2._win_keys = 16
    sstep, sstate = shard_keyed_query_step(q2, make_mesh(8), rows_per_shard=B)
    sh = []
    for i, b in enumerate(batches):
        rb = route_batch_to_shards(b, 8, B)
        sstate, out = sstep(sstate, rb, np.int64(10_000 + i))
        sh.append(jax.device_get(out))
    m2.shutdown()

    u, s = collect(uns), collect(sh, n_shards=8)
    assert set(u) == set(s)
    assert all(u[k] == s[k] for k in u)


def test_route_batch_overflow_raises():
    """Round-6: the legacy host router's overflow follows the
    FatalQueryError + knob-naming convention (it used to die with a bare
    ValueError), and the router itself is a deprecated shim."""
    import warnings

    import pytest

    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.core.stream.junction import FatalQueryError
    from siddhi_tpu.ops.expressions import PK_KEY, VALID_KEY
    from siddhi_tpu.parallel.mesh import route_batch_to_shards

    cols = {PK_KEY: np.zeros(16, np.int32), GK_KEY: np.zeros(16, np.int32),
            VALID_KEY: np.ones(16, bool)}
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with pytest.raises(DeprecationWarning):
            route_batch_to_shards(cols, 4, 16)   # shim warns
    with pytest.raises(FatalQueryError, match="rows_per_shard"):
        route_batch_to_shards(cols, 4, 2)  # 16 rows all on shard 0 > 2
