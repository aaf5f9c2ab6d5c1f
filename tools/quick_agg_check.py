"""Quick sharded-aggregation check: sharded == unsharded, bit-identical.

CPU tool: forces the CPU backend and is never on the chip path
(``chip_smoke.py`` is).

Feeds one fixed random corpus (columnar bulk sends) through the same
multi-granularity aggregation app four times — unsharded and with the
serving tier's mesh sharding at 2/4/8 shards — then runs a battery of
on-demand `within ... per ...` store queries (every granularity, ranges
straddling bucket boundaries, grouped/having/on-condition selectors) and
compares every row EXACTLY (float bits included; rows canonically sorted
— the selector, not storage order, owns output ordering). Runnable from
a clean shell, ~5 s of corpus work per configuration (the battery's jit
compiles dominate; well under 30 s total on the CPU backend):

    JAX_PLATFORMS=cpu python tools/quick_agg_check.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

t00 = time.time()
from siddhi_tpu import SiddhiManager  # noqa: E402
from siddhi_tpu.core.util.config import InMemoryConfigManager  # noqa: E402

APP = """
@app:name('AggCheck')
define stream TradeStream (symbol string, price double, volume long, ts long);
define aggregation TradeAgg
from TradeStream
select symbol, sum(price) as total, avg(price) as avgPrice, count() as n,
       min(price) as lo, max(price) as hi, distinctCount(volume) as dv
group by symbol
aggregate by ts every sec ... year;
"""

WIDE = ("from TradeAgg within 0L, 200000000L per '{p}' "
        "select AGG_TIMESTAMP, symbol, total, avgPrice, n, lo, hi, dv")

BATTERY = (
    [WIDE.format(p=p) for p in ("seconds", "minutes", "hours", "days")]
    + [
        # within straddling bucket boundaries mid-bucket on both ends
        "from TradeAgg within 1500L, 3500L per 'seconds' "
        "select AGG_TIMESTAMP, symbol, total, n",
        "from TradeAgg within 30000L, 90000L per 'minutes' "
        "select AGG_TIMESTAMP, symbol, total, n",
        # condition + aggregate-of-aggregates
        "from TradeAgg on symbol == 'S3' within 0L, 200000000L per "
        "'seconds' select sum(total) as grand, sum(n) as events",
        "from TradeAgg within 0L, 200000000L per 'hours' "
        "select symbol, sum(total) as t group by symbol "
        "order by symbol limit 5",
    ])


def run(shards: int):
    m = SiddhiManager()
    m.set_config_manager(InMemoryConfigManager(
        {"siddhi_tpu.agg_shards": str(shards)}))
    rt = m.create_siddhi_app_runtime(APP)
    h = rt.get_input_handler("TradeStream")
    rng = np.random.default_rng(42)
    n_batches, B = 6, 256
    for i in range(n_batches):
        ids = rng.integers(0, 37, B)
        h.send_columns(
            {"symbol": np.array([f"S{k}" for k in ids], dtype=object),
             "price": (rng.random(B) * 100.0).astype(np.float64),
             "volume": rng.integers(1, 9, B, dtype=np.int64),
             "ts": rng.integers(0, 100_000_000, B, dtype=np.int64)},
            timestamps=np.arange(i * B, (i + 1) * B, dtype=np.int64))
    agg = rt.aggregations["TradeAgg"]
    if shards > 1:
        assert getattr(agg, "n_shards", 1) == shards, "sharding not active"
        occupied = sum(1 for s in agg.shards if s.store[agg.durations[0]])
        assert occupied == shards, \
            f"expected all {shards} shards occupied, got {occupied}"
    results = [sorted(tuple(e.data) for e in rt.query(q)) for q in BATTERY]
    m.shutdown()
    return results


ref = run(1)
assert any(len(r) > 20 for r in ref), "corpus too small to mean anything"
for shards in (2, 4, 8):
    got = run(shards)
    for qi, (a, b) in enumerate(zip(ref, got)):
        assert a == b, (
            f"shards={shards} query#{qi}: {len(a)} vs {len(b)} rows; "
            f"first diff: "
            f"{next((x, y) for x, y in zip(a, b) if x != y) if len(a) == len(b) else 'row count'}")
    print(f"[quick_agg_check] shards={shards}: "
          f"{sum(len(r) for r in got)} rows across {len(BATTERY)} queries "
          f"bit-identical to unsharded")

print(f"[quick_agg_check] OK in {time.time() - t00:.1f}s")
