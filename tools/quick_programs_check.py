"""Quick proof of the process-global compiled-program cache (~10 s).

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Three facts, each asserted exactly (core/util/program_cache.py,
ISSUE 20):

1. Two identical apps -> ONE compile: the second app's step attaches to
   the first's executable (jit record shows compiles=0, a hit), outputs
   bit-identical, one cache entry refcounted by both.
2. Blue/green replace warm-starts: a new runtime under the SAME app
   name attaches to the warm cache, and the OLD runtime's shutdown
   does not evict the survivor's program (owner tokens are
   identity-pinned, not name-keyed).
3. `siddhi_tpu.program_cache: off` restores private compiles —
   bit-identical outputs either way.

Run: JAX_PLATFORMS=cpu python tools/quick_programs_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

t00 = time.time()
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.util import program_cache  # noqa: E402
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402

APP = """
@app:name('{name}')
define stream S (sym string, price float, vol long);
@info(name = 'q')
from S#window.length(16)
select sym, sum(price) as total, count() as c
group by sym
insert into Out;
"""

ROWS = [("A", 10.5, 3), ("B", 2.25, 1), ("A", 7.75, 9),
        ("C", 100.0, 2), ("B", 0.5, 4)]


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend((e.timestamp, tuple(e.data)) for e in events)


def deploy(manager, name):
    rt = manager.create_siddhi_app_runtime(APP.format(name=name))
    c = Collector()
    rt.add_callback("Out", c)
    rt.start()
    return rt, c


def feed(rt):
    h = rt.get_input_handler("S")
    for i, row in enumerate(ROWS):
        h.send(100 + i, list(row))


def jit_step(rt):
    return rt.app_context.telemetry.snapshot()["jit"]["query.q.step"]


def entry():
    entries = program_cache.cache().snapshot()["entries"]
    assert len(entries) == 1, f"expected 1 cache entry, got {entries}"
    return entries[0]


program_cache.cache().drain()

# ---- 1. two identical apps, one compile --------------------------------
m = SiddhiManager()
rt1, c1 = deploy(m, "qp_a1")
rt2, c2 = deploy(m, "qp_a2")
feed(rt1)
feed(rt2)
assert c1.rows == c2.rows and c1.rows, (
    f"shared-executable outputs diverged: {c1.rows} vs {c2.rows}")
j1, j2 = jit_step(rt1), jit_step(rt2)
assert j1["compiles"] == 1, j1
assert j2["compiles"] == 0 and j2["hits"] >= 1, j2
e = entry()
assert e["refcount"] == 2 and sorted(e["shared_by"]) == ["qp_a1", "qp_a2"], e
print(f"1: two apps, one compile (fingerprint {e['fingerprint']}, "
      f"refcount 2) [{time.time() - t00:.1f}s]", flush=True)

# ---- 2. blue/green: warm attach, identity-pinned release ---------------
m_new = SiddhiManager()
rt_new, c_new = deploy(m_new, "qp_a1")     # replacement for rt1's name
feed(rt_new)
assert jit_step(rt_new)["compiles"] == 0, jit_step(rt_new)
assert entry()["refcount"] == 3
m.shutdown()                               # blue retires BOTH rt1 and rt2
e = entry()
assert e["refcount"] == 1 and e["shared_by"] == ["qp_a1"], e
feed(rt_new)                               # survivor still serves
assert c_new.rows[:len(c1.rows)] == c1.rows
m_new.shutdown()
assert program_cache.cache().snapshot()["size"] == 0, "entry leaked"
print(f"2: blue/green warm attach + identity-pinned eviction "
      f"[{time.time() - t00:.1f}s]", flush=True)

# ---- 3. knob off: private compiles, same bits --------------------------
m_off = SiddhiManager()
m_off.set_config_manager(InMemoryConfigManager(
    {"siddhi_tpu.program_cache": "0"}))
rt3, c3 = deploy(m_off, "qp_off1")
rt4, c4 = deploy(m_off, "qp_off2")
feed(rt3)
feed(rt4)
assert c3.rows == c4.rows == c1.rows, "knob-off outputs diverged"
assert jit_step(rt3)["compiles"] == 1 and jit_step(rt4)["compiles"] == 1
assert program_cache.cache().snapshot()["size"] == 0
m_off.shutdown()
print(f"3: program_cache off -> private compiles, identical bits "
      f"[{time.time() - t00:.1f}s]", flush=True)

print(f"OK quick_programs_check in {time.time() - t00:.1f}s", flush=True)
