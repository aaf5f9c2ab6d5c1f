"""Collective-op / host-transfer audit of every jitted step's HLO.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Round 4 shipped this as a hand-kept pair of lowerings; it is now a
REGISTRY-driven audit: every entry in
``siddhi_tpu/analysis/step_registry.py`` (the declarative list of all
jitted step builders — query, fused fan-out, GSPMD and device-routed
sharding, device join, sharded-agg serving) must have a
matching ``@audit`` function here, so a new step builder fails the
quick tier until it is audited — coverage by construction, not memory.

Per audit, the assertions that caught real regressions:
- ONE HLO module per fused/routed step (fusion actually fused);
- collective kinds ⊆ the expected set (device-routed keeps its
  all_to_all; nothing sneaks in an all-reduce per batch);
- ZERO host transfers inside step bodies (infeed/outfeed/send/recv) —
  the R5 bug class at the XLA level.

Run: ``python tools/hlo_audit.py`` (prints one JSON line).
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "partition-id",
)
HOST_TRANSFER_MARKERS = ("infeed", "outfeed", " send(", " recv(",
                         "send-start", "recv-start")

NUM_KEYS = 10_000
WINDOW = 1_000
B = 8_192
N_DEV = 8

_APP = """
define stream StockStream (symbol string, price float, volume long);
partition with (symbol of StockStream)
begin
  @info(name = 'bench')
  from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
  insert into OutStream;
end;
""".format(W=WINDOW)

# audit name -> callable(ctx) -> report fragment; must cover EVERY
# entry of analysis/step_registry.JIT_STEP_BUILDERS (asserted in main)
AUDITS = {}


def audit(name):
    def deco(fn):
        AUDITS[name] = fn
        return fn
    return deco


def _count_collectives(hlo_text: str) -> dict:
    counts = {}
    for ln in hlo_text.splitlines():
        m = re.search(r"= \S+ ([a-z-]+)(?:-start|-done)?\(", ln)
        if not m:
            continue
        op = m.group(1)
        for c in COLLECTIVE_OPS:
            if op.startswith(c):
                counts[c] = counts.get(c, 0) + 1
    return counts


def _assert_no_host_transfers(hlo: str, what: str) -> None:
    for marker in HOST_TRANSFER_MARKERS:
        assert marker not in hlo, f"{what} contains a host transfer: {marker}"


def _assert_one_module(hlo: str, what: str) -> int:
    n = hlo.count("ENTRY")
    assert n == 1, f"{what} lowered to {n} HLO modules, want 1"
    return n


def _assert_instrumented_meta(q, out, what: str) -> list:
    """The step's packed meta must carry EXACTLY the runtime's declared
    instrument spec behind the [overflow, notify, count] prefix — the
    device telemetry plane rides the existing meta pull, with no extra
    module, no extra transfer (observability/instruments.py)."""
    spec = q.instrument_slots()
    meta = np.asarray(out["__meta__"])
    want = 3 + sum(s.width for s in spec)
    assert meta.shape[0] == want, (
        f"{what}: meta carries {meta.shape[0]} lanes, spec declares "
        f"{want} ({[s.name for s in spec]})")
    return [s.name for s in spec]


def _make_batch(rng):
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY, TS_KEY, TYPE_KEY, VALID_KEY

    sym = rng.integers(0, NUM_KEYS, B, dtype=np.int64)
    return {
        TS_KEY: np.arange(B, dtype=np.int64),
        TYPE_KEY: np.zeros(B, np.int8),
        VALID_KEY: np.ones(B, bool),
        "symbol": sym, "symbol?": np.zeros(B, bool),
        "price": (rng.random(B) * 100.0).astype(np.float32),
        "price?": np.zeros(B, bool),
        "volume": rng.integers(1, 1000, B, dtype=np.int64),
        "volume?": np.zeros(B, bool),
        GK_KEY: sym.astype(np.int32),
        PK_KEY: sym.astype(np.int32),
    }


class Ctx:
    """Shared audit fixtures (mesh, rng, lazily-built batch)."""

    def __init__(self):
        self.rng = np.random.default_rng(0)
        self.mesh = None
        self._batch = None

    @property
    def batch(self):
        if self._batch is None:
            self._batch = _make_batch(self.rng)
        return self._batch


# --------------------------------------------------------------- audits

@audit("query_step")
def _audit_query_step(ctx):
    """A plain single-stream query's jitted step: one module, zero host
    transfers, zero collectives (nothing sharded here)."""
    import jax

    from siddhi_tpu import SiddhiManager

    _Q = """
define stream StockStream (symbol string, price float, volume long);
@info(name='q') from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice group by symbol
  insert into OutStream;
""".format(W=WINDOW)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_Q)
    rt.start()
    q = rt.query_runtimes["q"]
    q._state = q._init_state()
    step = jax.jit(q.build_step_fn())
    hlo = step.lower(q._state, ctx.batch, np.int64(0)).compile().as_text()
    n = _assert_one_module(hlo, "single-query step")
    _assert_no_host_transfers(hlo, "single-query step")
    cols = _count_collectives(hlo)
    assert not cols, f"unsharded query step has collectives: {cols}"
    # instrumented meta: the device telemetry plane adds lanes to the
    # SAME module's meta output, never a second computation or transfer
    _st2, out = step(q._init_state(), ctx.batch, np.int64(0))
    slots = _assert_instrumented_meta(q, out, "single-query step")
    assert slots, "default-on instruments declared no slots"
    m.shutdown()
    return {"hlo_modules": n, "collectives": cols, "host_transfers": 0,
            "instrument_slots": slots}


@audit("gspmd_replicated_batch")
def _audit_gspmd(ctx):
    """Round-4 strategy: replicated batch, GSPMD-sharded state."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.parallel.mesh import shard_query_step

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_APP)
    rt.start()
    q = rt.query_runtimes["bench"]
    q.selector_plan.num_keys = 16_384
    q._win_keys = 16_384
    jitted, state = shard_query_step(q, ctx.mesh, donate=False)
    hlo = jitted.lower(state, ctx.batch, np.int64(0)).compile().as_text()
    _assert_no_host_transfers(hlo, "gspmd replicated-batch step")
    counts = _count_collectives(hlo)
    unexpected = set(counts) - {"all-reduce", "all-gather",
                                "collective-permute", "partition-id"}
    assert not unexpected, (
        f"gspmd step has unexpected collective kinds: {unexpected}")
    m.shutdown()
    return counts


@audit("fused_fanout")
def _audit_fused_fanout(ctx):
    """A fused 3-query group must lower to ONE module."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.event import HostBatch

    _FANOUT_APP = """
define stream StockStream (symbol string, price float, volume long);
@info(name='f0') from StockStream[price > 10.0]
  select symbol, price insert into Out0;
@info(name='f1') from StockStream#window.length({W})
  select symbol, avg(price) as avgPrice group by symbol insert into Out1;
@info(name='f2') from StockStream
  select symbol, volume insert into Out2;
""".format(W=WINDOW)
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_FANOUT_APP)
    rt.start()
    (group,) = rt.fused_fanout_groups
    hlo = group.lower_hlo_text(HostBatch(_make_batch(ctx.rng)))
    n = _assert_one_module(hlo, "fused fan-out group")
    report = {
        "members": len(group.members),
        "hlo_modules": n,
        "collectives": _count_collectives(hlo),
    }
    m.shutdown()
    return report


@audit("device_join")
def _audit_device_join(ctx):
    """An eligible stream-stream window join's fused insert+probe side
    step: ONE module, ZERO host transfers (the in-state layout that
    makes joins pipeline/fusion-eligible)."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY

    _JOIN_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(256) join R#window.length(256)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_JOIN_APP)
    rt.start()
    q = rt.query_runtimes["jq"]
    assert q.engine is not None, (
        f"join engine did not attach: {q.engine_reason}")
    assert q._pipeline_ok, (
        f"eligible join not pipeline-ok: {q.pipeline_reason}")
    q._state = q._init_state()
    Bj = 512
    jsym = ctx.rng.integers(0, 64, Bj, dtype=np.int64)
    jcols = {
        TS_KEY: np.arange(Bj, dtype=np.int64),
        TYPE_KEY: np.zeros(Bj, np.int8),
        VALID_KEY: np.ones(Bj, bool),
        "sym": jsym.astype(np.int32), "sym?": np.zeros(Bj, bool),
        "lv": ctx.rng.integers(0, 1000, Bj, dtype=np.int64),
        "lv?": np.zeros(Bj, bool),
        GK_KEY: np.zeros(Bj, np.int32),
    }
    jstep = jax.jit(q.build_side_step_fn("left"))
    hlo = jstep.lower(q._state, {}, jnp.zeros((1,), bool), jcols,
                      np.int64(0)).compile().as_text()
    n = _assert_one_module(hlo, "device join side step")
    _assert_no_host_transfers(hlo, "device join side step")
    # instrumented meta: seq + the pairs matched ride the same module's
    # meta output
    _st2, out = jstep(q._init_state(), {}, jnp.zeros((1,), bool), jcols,
                      np.int64(0))
    slots = _assert_instrumented_meta(q, out, "device join side step")
    assert "seq" in slots and "join_matches" in slots, \
        f"join instrument spec incomplete: {slots}"
    report = {
        "keyed_probe": q.engine.keyed["left"],
        "hlo_modules": n,
        "collectives": _count_collectives(hlo),
        "host_transfers": 0,
        "instrument_slots": slots,
    }
    m.shutdown()
    return report


@audit("device_routed")
def _audit_device_routed(ctx):
    """Round-6 strategy: device-routed batch — dense all_to_all exchange
    + local step + ordered re-merge inside ONE jitted module, zero host
    transfers."""
    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.parallel.mesh import device_route_query_step

    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_APP)
    rt.start()
    q = rt.query_runtimes["bench"]
    q.selector_plan.num_keys = 16_384   # global capacity; split per shard
    q._win_keys = 16_384
    rows = B // N_DEV * 2
    device_route_query_step(q, ctx.mesh, rows_per_shard=rows)
    lowered = q._step._routed_raw.lower(
        q._state, ctx.batch, q._route_layout.device_luts(), np.int64(0))
    pre = lowered.as_text()   # pre-optimization: the exchange is explicit
    assert "all_to_all" in pre, (
        "device-routed step lost its all_to_all exchange in lowering")
    hlo = lowered.compile().as_text()
    n = _assert_one_module(hlo, "device-routed step")
    dev_counts = _count_collectives(hlo)
    assert dev_counts, "device-routed step compiled with NO collectives"
    allowed = {"all-to-all", "all-gather", "all-reduce",
               "collective-permute", "partition-id"}
    unexpected = set(dev_counts) - allowed
    assert not unexpected, (
        f"device-routed step has unexpected collective kinds: {unexpected}")
    _assert_no_host_transfers(hlo, "device-routed step")
    # the routed meta layout = route slots + inner instrument slots
    slots = [s.name for s in q.instrument_slots()]
    assert slots[:2] == ["route_overflow", "shard_rows"], slots
    m.shutdown()
    return {"hlo_modules": n, "collectives": dev_counts,
            "host_transfers": 0, "instrument_slots": slots}


@audit("sharded_agg")
def _audit_sharded_agg(ctx):
    """Serving tier: the on-demand selector PROGRAM over a shard's
    device-resident rollup view. The eager scatter-gather path runs this
    same SelectorPlan.apply; lowering it as one jit proves the probe
    program is a single module with zero host transfers, and that the
    pow2-padded device view is stable (the PR-6 recompile-storm fix:
    raw-n capacity meant a recompile per query under live ingest)."""
    import jax
    import jax.numpy as jnp

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.core.util.config import InMemoryConfigManager
    from siddhi_tpu.query_api.definitions import Duration

    _AGG_APP = """
define stream Trades (symbol string, price double, volume long);
define aggregation TradeAgg
  from Trades
  select symbol, avg(price) as avgPrice, sum(volume) as totalVolume
  group by symbol
  aggregate every sec ... hour;
"""
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.agg_shards": "4"}))
    rt = m.create_siddhi_app_runtime(_AGG_APP)
    rt.start()
    agg = rt.aggregations["TradeAgg"]
    h = rt.get_input_handler("Trades")
    base = 1_600_000_000_000
    for i in range(256):
        h.send(base + i * 250, [f"S{i % 37}", 10.0 + (i % 11), 1 + i % 5])
    sec = Duration.SECONDS
    definition, cols, valid = agg.shard_device_contents(0, sec)
    # epoch caching: a second read between folds returns the SAME view
    again = agg.shard_device_contents(0, sec)
    assert again[1] is cols, "shard device view not epoch-cached"
    # pow2 probe surface (shape stability across ingest deltas)
    n_slots = int(valid.shape[0])
    assert n_slots & (n_slots - 1) == 0, (
        f"shard view capacity {n_slots} is not pow2-padded — recompile "
        f"per query under live ingest (the PR-6 soak regression)")
    # the probe program: valid-mask reduction + per-column gather is
    # what every scatter-gather read runs per shard; lower it as ONE jit
    def probe(cols, valid):
        keep = jnp.nonzero(valid, size=valid.shape[0], fill_value=0)[0]
        return {k: jnp.take(v, keep, axis=0) for k, v in cols.items()}, \
            jnp.sum(valid)

    hlo = jax.jit(probe).lower(cols, valid).compile().as_text()
    n = _assert_one_module(hlo, "sharded-agg probe program")
    _assert_no_host_transfers(hlo, "sharded-agg probe program")
    colls = _count_collectives(hlo)
    assert not colls, f"per-shard probe has collectives: {colls}"
    report = {"shards": agg.n_shards, "view_slots": n_slots,
              "hlo_modules": n, "collectives": colls, "host_transfers": 0}
    m.shutdown()
    return report


# ----------------------------------------------------------------- main

def _scrape_zero_pulls() -> dict:
    """A full /metrics scrape must perform ZERO device pulls — verified
    under jax's transfer guard with live device-instrument state (the
    join window gauges read the engine's host mirrors)."""
    import jax

    from siddhi_tpu import SiddhiManager
    from siddhi_tpu.observability import export

    _JOIN_APP = """
define stream L (sym string, lv long);
define stream R (sym string, rv long);
@info(name='jq') from L#window.length(64) join R#window.length(64)
  on L.sym == R.sym
  select L.sym as sym, L.lv as lv, R.rv as rv insert into JOut;
"""
    m = SiddhiManager()
    rt = m.create_siddhi_app_runtime(_JOIN_APP)
    rt.start()
    hl, hr = rt.get_input_handler("L"), rt.get_input_handler("R")
    for i in range(16):
        hl.send([f"S{i % 5}", i])
        hr.send([f"S{i % 5}", 100 + i])
    with jax.transfer_guard("disallow"):
        text = export.prometheus_text(m)
    # family literals below assert on exposition OUTPUT, they declare
    # nothing (R3's central-declaration rule targets registrations)
    want = ("siddhi_join_window_rows",      # graftlint: disable=R3
            "siddhi_device_instrument")     # graftlint: disable=R3
    for fam in want:
        assert fam in text, f"family {fam} missing from scrape"
    # a guarded pull inside a gauge closure surfaces as NaN — the join
    # occupancy and device-instrument families must be real numbers
    for line in text.splitlines():
        if line.startswith(want):
            assert not line.endswith("NaN"), f"guarded gauge pulled: {line}"
    m.shutdown()
    return {"device_pulls": 0, "transfer_guard": "disallow"}


def main():
    from siddhi_tpu.parallel.mesh import force_host_devices

    force_host_devices(N_DEV)

    from siddhi_tpu.analysis.step_registry import (
        INSTRUMENTED_STEP_BUILDERS, JIT_STEP_BUILDERS, resolve)

    missing = sorted(set(JIT_STEP_BUILDERS) - set(AUDITS))
    assert not missing, (
        f"jitted step builders registered without an HLO audit: {missing} "
        f"— add an @audit function in tools/hlo_audit.py")
    extra = sorted(set(AUDITS) - set(JIT_STEP_BUILDERS))
    assert not extra, (
        f"audits not backed by a step_registry entry: {extra} — declare "
        f"the builder in siddhi_tpu/analysis/step_registry.py")
    bad = sorted(set(INSTRUMENTED_STEP_BUILDERS) - set(JIT_STEP_BUILDERS))
    assert not bad, f"INSTRUMENTED_STEP_BUILDERS not in registry: {bad}"
    for name in JIT_STEP_BUILDERS:
        resolve(name)   # moved/renamed builders fail loudly here

    from siddhi_tpu.parallel.mesh import make_mesh

    ctx = Ctx()
    ctx.mesh = make_mesh(N_DEV)
    report = {}
    for name in sorted(AUDITS):
        report[name] = AUDITS[name](ctx)
    for name in INSTRUMENTED_STEP_BUILDERS:
        assert report[name].get("instrument_slots"), (
            f"builder '{name}' is declared instrumented but its audit "
            f"verified no instrument lanes")
    report["metrics_scrape"] = _scrape_zero_pulls()
    report["devices"] = N_DEV
    report["batch"] = B
    print(json.dumps(report))


if __name__ == "__main__":
    main()
