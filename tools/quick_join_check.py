"""Quick device-join check: engine output == legacy synchronous output.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Drives one app whose queries cover the eligibility matrix — inner x
length windows, left-outer x time window (+ residual condition),
unidirectional x length x grouped selector — through the PanJoin-style
device engine (``siddhi_tpu/core/join/``) at pipeline depth {1, 4} and
asserts every output stream is **bit-identical and identically ordered**
to the legacy synchronous probe path (``siddhi_tpu.join_engine: legacy``
at depth 1, which also pins joins off the CompletionPump).

Part of the quick-check set next to ``pipeline_check.py`` /
``quick_fanout_check.py`` (registered in ``tools/quick_all.py``):

    JAX_PLATFORMS=cpu python tools/quick_join_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu import SiddhiManager, StreamCallback  # noqa: E402
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402

# the time-window case runs as externalTime with EXPLICIT timestamps:
# plain window.time expires off the wall clock (scheduler timers), so
# two separate runs are only approximately comparable — externalTime is
# the same TimeWindowStage with data-driven expiry, which makes the
# bit-identity assertion deterministic. That lesson is now codified in
# siddhi_tpu/fuzz/determinism.py (DETERMINISTIC_WINDOWS) — new
# differential checks should draw their window kinds from there
# instead of rediscovering it; the assertion below keeps THIS app
# honest against the shared list.
APP = """
define stream L (ts long, sym string, lv long);
define stream R (sym string, rv long);
@info(name='inner') from L#window.length(40) join R#window.length(40)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into InnerOut;
@info(name='outer') from L#window.externalTime(ts, 1 sec) left outer join
  R#window.length(16) on L.sym == R.sym and L.lv > R.rv
  select L.sym as sym, R.rv as rv insert into OuterOut;
@info(name='uni') from L#window.length(16) join R#window.length(16)
  unidirectional on L.sym == R.sym
  select L.sym as sym, sum(R.rv) as total group by L.sym
  insert into GroupedOut;
"""

OUT_STREAMS = ("InnerOut", "OuterOut", "GroupedOut")
N_EVENTS = 120

# every window this differential app uses must be in the shared
# deterministic set (fuzz/determinism.py) — the wall-clock lesson above
from siddhi_tpu.fuzz.determinism import is_deterministic  # noqa: E402

for _kind in ("length", "externalTime"):
    assert is_deterministic(_kind), \
        f"quick_join_check uses window.{_kind} but the shared " \
        f"deterministic-window list disagrees — see fuzz/determinism.py"


class Collector(StreamCallback):
    def __init__(self):
        self.rows = []

    def receive(self, events):
        self.rows.extend(tuple(e.data) for e in events)


def run(mode: str, depth: int):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager({
        "siddhi_tpu.join_engine": mode,
        "siddhi_tpu.pipeline_depth": str(depth),
    }))
    rt = m.create_siddhi_app_runtime(APP)
    outs = {s: Collector() for s in OUT_STREAMS}
    for s, c in outs.items():
        rt.add_callback(s, c)
    rt.start()
    q = rt.query_runtimes["inner"]
    if mode == "device":
        assert q.engine is not None, f"engine not attached: {q.engine_reason}"
        assert q._pipeline_ok, f"not pipeline-eligible: {q.pipeline_reason}"
    else:
        assert not q._pipeline_ok, "legacy mode must stay synchronous"
    hl = rt.get_input_handler("L")
    hr = rt.get_input_handler("R")
    rng = np.random.default_rng(7)
    t = 1000
    for _ in range(N_EVENTS):
        sym = f"S{rng.integers(0, 6)}"
        val = int(rng.integers(0, 50))
        t += int(rng.integers(0, 120))   # ~12ms mean step: the 1 s
        if rng.random() < 0.5:           # externalTime window slides
            hl.send(t, [t, sym, val])
        else:
            hr.send(t, [sym, val])
    m.shutdown()
    rows = {s: c.rows for s, c in outs.items()}
    for s in OUT_STREAMS:
        assert rows[s], f"{s}: produced no rows (mode={mode} depth={depth})"
    return rows


ref = run("legacy", 1)
print(f"legacy depth=1 reference done at {time.time() - t00:.1f}s",
      flush=True)
for depth in (1, 4):
    got = run("device", depth)
    for s in OUT_STREAMS:
        assert got[s] == ref[s], (
            f"{s}: device depth={depth} diverged from legacy "
            f"({len(got[s])} vs {len(ref[s])} rows)")
    print(f"device depth={depth}: "
          + ", ".join(f"{s}={len(ref[s])}" for s in OUT_STREAMS)
          + f" rows bit-identical at {time.time() - t00:.1f}s", flush=True)
print(f"PASS device join engine == legacy in {time.time() - t00:.1f}s",
      flush=True)
