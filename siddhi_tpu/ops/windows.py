"""Window processors as ring-buffer tensor stages.

Replaces the reference's window processor classes
(``query/processor/stream/window/*.java``, 27 classes / 6,866 LoC of
per-event queue surgery) with columnar ring buffers + masked emission.
Exact semantics reproduced per window (event order, CURRENT/EXPIRED/RESET
interleaving, timestamps patched to processing time where the reference
does so):

- length  (``LengthWindowProcessor.java:106-142``): sliding; when full each
  arrival emits [EXPIRED(oldest, ts=now), CURRENT] in that order.
- time    (``TimeWindowProcessor.java:133-168``): expired drained before
  each event with ts set to now; TIMER chunks consumed; notifyAt(ts+t).
- externalTime (``ExternalTimeWindowProcessor``): like time but the cutoff
  advances with each event's own timestamp; no timers; expired keep ts.
- lengthBatch (``LengthBatchWindowProcessor.java:153-260``): flush at exact
  count boundaries (possibly mid-chunk): [EXPIRED(prev batch, ts=now),
  RESET, CURRENT batch...] per flush.
- timeBatch  (``TimeBatchWindowProcessor.java:263-345``): flush check once
  per chunk; the arriving chunk's rows join the flushing batch; order
  [EXPIRED(prev, ts=now), RESET, CURRENT...].
- batch   (``BatchWindowProcessor``): every chunk is its own batch; expired
  = previous chunk.

A stage is ``apply(state, cols, ctx) -> (state, out_cols)``, traced inside
the query's jitted step; output capacity is a static function of the input
batch size. Stages needing timers return ``__notify__`` (next wanted wake
time, -1 if none) for the host scheduler; bounded buffers report
``__overflow__`` so the host can raise instead of silently dropping.

Emission order is produced by one order-key sort. The unified key scheme,
with STRIDE = Wc + B + 4:
  ring-expired item j  (drains before row r): key r*STRIDE + j
  in-batch expired of row i (before row r):   key r*STRIDE + Wc + i
  current row i:                              key i*STRIDE + Wc + B + 2
so expired events always precede the current event they are drained before,
FIFO order among them, exactly as ``insertBeforeCurrent`` produces.

Windows are per-query instances (K=1) exactly as in the reference, where
group-by does NOT partition a window — only `partition with` does (M3 vmaps
these stages over the partition-key axis).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.ops.expressions import (
    OKEY_KEY, RIDX_KEY, TS_KEY, TYPE_KEY, VALID_KEY, CompileError)
from siddhi_tpu.query_api.definitions import AttrType
from siddhi_tpu.query_api.execution import Window
from siddhi_tpu.query_api.expressions import Constant, TimeConstant

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3
NOTIFY_KEY = "__notify__"
OVERFLOW_KEY = "__overflow__"
FLUSH_KEY = "__flush__"

# numpy on purpose: a jnp scalar here would materialize a device array
# at import and initialize the backend before force_host_devices can
# configure the virtual mesh (graftlint R1); np.int64 promotes
# identically inside the jitted arithmetic below
_BIG = np.int64(2**62)


def _data_keys(cols: Dict) -> List[str]:
    # '#set'/'#setm' companions ([B, H] element snapshots of multi-element
    # set values) never enter window buffers — only the scalar base column
    # is buffered; a downstream unionSet that NEEDS the snapshot raises
    # (ops/aggregators.py arg_is_multi guard)
    return sorted(
        k for k in cols
        if k not in (TYPE_KEY, VALID_KEY, NOTIFY_KEY, OVERFLOW_KEY, FLUSH_KEY,
                     RIDX_KEY, OKEY_KEY)
        and "#set" not in k
    )


def _zero_rows(cols: Dict, n: int):
    return {k: jnp.zeros((n,), cols[k].dtype) for k in _data_keys(cols)}


def _order_emit(parts) -> Tuple[Dict, jnp.ndarray]:
    """Concatenate (data_cols, types, valid, order_key) groups and sort by
    order key with invalid rows last. Returns (out_cols, sorted_keys)."""
    keys = _data_keys(parts[0][0])
    data = {k: jnp.concatenate([p[0][k] for p in parts]) for k in keys}
    types = jnp.concatenate([p[1] for p in parts])
    valid = jnp.concatenate([p[2] for p in parts])
    okey = jnp.concatenate([p[3] for p in parts])
    okey = jnp.where(valid, okey, _BIG)
    order = jnp.argsort(okey, stable=True)
    out = {k: v[order] for k, v in data.items()}
    out[TYPE_KEY] = types[order]
    out[VALID_KEY] = valid[order]
    return out, okey[order]


def _row_order_base(cols: Dict, B: int):
    """Per-row base for emission order keys: the row's position in the
    ORIGINAL batch. Plain steps see ``arange(B)``; under device-routed
    sharding (``parallel/mesh.device_route_query_step``) the route wrapper
    attaches ``RIDX_KEY`` — each row's index in the pre-exchange global
    batch — so a stage's order keys stay comparable ACROSS shards and the
    egress merge can reproduce the exact unsharded emission order."""
    ridx = cols.get(RIDX_KEY)
    if ridx is not None:
        return jnp.asarray(ridx, jnp.int64)
    return jnp.arange(B, dtype=jnp.int64)


def _insert_ranks(valid_cur):
    """(rank per valid row, total inserts) — rank = segmented arrival index."""
    rank = jnp.cumsum(valid_cur.astype(jnp.int64)) - 1
    n_ins = jnp.sum(valid_cur.astype(jnp.int64))
    return rank, n_ins


class WindowStage:
    batch_mode = False
    needs_scheduler = False

    def init_state(self, num_keys: int = 1) -> dict:
        raise NotImplementedError

    def conform(self, cols: Dict) -> Dict:
        """Cast batch columns to this stage's declared ring dtypes.

        Hand-built batches (sharded routers, benches, dry runs) commonly
        carry int64 key/id columns where the ring buffer stores the
        dictionary's int32 ids; scattering int64 values into an int32 ring
        is a JAX FutureWarning today and an error in future releases. A
        matching batch traces to a no-op."""
        specs = getattr(self, "col_specs", None)
        if not specs:
            return cols
        out = dict(cols)
        for k, dt in specs.items():
            v = out.get(k)
            if v is not None and getattr(v, "dtype", dt) != dt:
                out[k] = v.astype(dt)
        return out

    def apply(self, state: dict, cols: Dict, ctx: Dict):
        raise NotImplementedError

    def contents(self, state: dict):
        """(cols [W], valid [W]) view of the currently-held events — the
        probe surface for joins (the role of FindableProcessor.find on
        window processors, reference ``JoinProcessor.java:134-147``)."""
        raise CompileError(
            f"{type(self).__name__} cannot be probed (used as a join side)"
        )


def conform_cols(stage, cols: Dict) -> Dict:
    """``stage.conform(cols)`` for any stage-like object: duck-typed
    stages that slot into the window position without subclassing
    WindowStage (``ops/fused_agg.FusedSlidingAggStage``) pass through."""
    fn = getattr(stage, "conform", None)
    return fn(cols) if fn is not None else cols


class PassthroughWindowStage(WindowStage):
    """A join side that retains nothing itself: a bare (window-less) stream
    side (reference ``EmptyWindowProcessor``; CURRENT only), or a named
    window's emission stream (``pass_expired=True``: the shared window
    already emitted typed CURRENT/EXPIRED events)."""

    def __init__(self, col_specs: Dict[str, np.dtype], pass_expired: bool = False,
                 empty_window: bool = False, expired_needed: bool = False,
                 emit_reset: bool = True):
        self.col_specs = col_specs
        self.pass_expired = pass_expired
        # empty_window: reference EmptyWindowProcessor.java:84 — every
        # arriving event becomes [CURRENT, EXPIRED(clone, ts=now) when the
        # output expects expireds, RESET], so per-trigger aggregates in
        # windowless joins restart per event (JoinTableTestCase query9).
        # emit_reset=False skips the RESET rows when the query has no
        # aggregate state to restart (pure projection joins).
        self.empty_window = empty_window
        self.expired_needed = expired_needed
        self.emit_reset = emit_reset

    def init_state(self, num_keys: int = 1) -> dict:
        return {"empty": jnp.zeros((1,), jnp.int32)}

    def apply(self, state, cols, ctx):
        if self.empty_window:
            keys = _data_keys(cols)
            B = cols[VALID_KEY].shape[0]
            now = jnp.int64(ctx["current_time"])
            valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
            rank, _n = _insert_ranks(valid_cur)
            parts = [({k: cols[k] for k in keys},
                      jnp.full((B,), CURRENT, jnp.int8), valid_cur, rank * 3)]
            if self.expired_needed:
                exp = {k: cols[k] for k in keys}
                exp[TS_KEY] = jnp.where(valid_cur, now, cols[TS_KEY])
                parts.append((exp, jnp.full((B,), EXPIRED, jnp.int8),
                              valid_cur, rank * 3 + 1))
            if self.emit_reset:
                reset_rows = _zero_rows(cols, B)
                reset_rows[TS_KEY] = jnp.where(valid_cur, now, jnp.int64(0))
                parts.append((reset_rows, jnp.full((B,), RESET, jnp.int8),
                              valid_cur, rank * 3 + 2))
            out, _ = _order_emit(parts)
            return state, out
        out = {k: cols[k] for k in _data_keys(cols)}
        out[TYPE_KEY] = cols[TYPE_KEY]
        live = cols[TYPE_KEY] == CURRENT
        if self.pass_expired:
            live = live | (cols[TYPE_KEY] == EXPIRED)
        out[VALID_KEY] = cols[VALID_KEY] & live
        return state, out

    def contents(self, state):
        cols = {k: jnp.zeros((1,), dt) for k, dt in self.col_specs.items()}
        return cols, jnp.zeros((1,), bool)


def _const_param(window: Window, i: int, name: str):
    if i >= len(window.parameters):
        raise CompileError(f"{window.name} window missing parameter '{name}'")
    p = window.parameters[i]
    if isinstance(p, TimeConstant):
        return int(p.value)
    if isinstance(p, Constant):
        return p.value
    raise CompileError(f"{window.name} window parameter '{name}' must be a constant")


def _int_const_param(window: Window, i: int, name: str):
    """A parameter that must be an int/long constant (or time constant) —
    the reference processors reject FLOAT/DOUBLE here at init
    (e.g. ``LengthWindowProcessor.init``, ``TimeWindowProcessor.init``)."""
    v = _const_param(window, i, name)
    if isinstance(v, (float, str, bool)):
        raise CompileError(
            f"{window.name} window parameter '{name}' must be int or long, "
            f"found a {type(v).__name__} constant")
    return int(v)


def _bool_const_param(window: Window, i: int, name: str) -> bool:
    p = window.parameters[i]
    if not (isinstance(p, Constant) and isinstance(p.value, bool)):
        raise CompileError(
            f"{window.name} window parameter '{name}' must be a bool constant")
    return p.value


def _expect_arity(window: Window, low: int, high: int):
    n = len(window.parameters)
    if not (low <= n <= high):
        want = str(low) if low == high else f"{low}..{high}"
        raise CompileError(
            f"{window.name} window expects {want} parameter(s), found {n}")


# ------------------------------------------------------------------ length

class LengthWindowStage(WindowStage):
    """Sliding length window."""

    def __init__(self, length: int, col_specs: Dict[str, np.dtype]):
        if length <= 0:
            raise CompileError("length window needs a positive length")
        self.length = length
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        W = self.length
        buf = {k: jnp.zeros((W,), dt) for k, dt in self.col_specs.items()}
        return {"buf": buf, "total": jnp.int64(0)}

    @property
    def ring_capacity(self) -> int:
        return self.length

    def live_fill(self, state):
        """Live rows in the ring (device scalar) — the ``win_fill``
        instrument slot (``observability/instruments.py``): computed
        inside the jitted step from state it already holds, so ring
        occupancy reaches /metrics with zero extra host transfers."""
        return jnp.minimum(state["total"], jnp.int64(self.length))

    def apply(self, state, cols, ctx):
        W = self.length
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        total0 = state["total"]
        rank, n_ins = _insert_ranks(valid_cur)
        seq = total0 + rank  # global per-window sequence of each inserted row

        # rank -> original row index (for evictees inserted earlier this batch)
        rank_to_row = jnp.zeros((B,), jnp.int32).at[
            jnp.where(valid_cur, rank, B).astype(jnp.int32)
        ].set(jnp.arange(B, dtype=jnp.int32), mode="drop")

        evicts = valid_cur & (seq >= W)
        evict_seq = seq - W
        from_batch = evict_seq >= total0
        ring_slot = (evict_seq % W).astype(jnp.int32)
        batch_row = rank_to_row[jnp.clip(evict_seq - total0, 0, B - 1).astype(jnp.int32)]

        expired = {}
        for k in keys:
            ring_v = state["buf"][k][ring_slot]
            expired[k] = jnp.where(from_batch, cols[k][batch_row], ring_v)
        expired[TS_KEY] = jnp.broadcast_to(now, (B,))  # LengthWindowProcessor:120

        # ring update: write the last min(W, n_ins) inserted rows (unique slots)
        write = valid_cur & (rank >= n_ins - W)
        slot = jnp.where(write, (seq % W).astype(jnp.int32), W)
        new_buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop") for k in state["buf"]}

        idx = jnp.arange(B, dtype=jnp.int64)
        parts = [
            (expired, jnp.full((B,), EXPIRED, jnp.int8), evicts, 2 * idx),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, 2 * idx + 1),
        ]
        out, _ = _order_emit(parts)
        return {"buf": new_buf, "total": total0 + n_ins}, out

    def contents(self, state):
        valid = jnp.arange(self.length, dtype=jnp.int64) < state["total"]
        return dict(state["buf"]), valid


# -------------------------------------------------------------------- time

class TimeWindowStage(WindowStage):
    """Sliding time window; ``external=True`` drives the cutoff from event
    timestamps (externalTime) instead of the runtime clock."""

    def __init__(self, time_ms: int, col_specs: Dict[str, np.dtype], capacity: int,
                 external: bool = False, ts_key: str = TS_KEY):
        self.time_ms = time_ms
        self.capacity = capacity
        self.col_specs = col_specs
        self.external = external
        # externalTime clock column: the named timestamp ATTRIBUTE (falls
        # back to the event timestamp) — expiry cutoffs read this column,
        # expired emissions keep the original event timestamps
        self.ts_key = ts_key
        self.needs_scheduler = not external

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        buf = {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}
        return {"buf": buf, "total": jnp.int64(0), "expired_upto": jnp.int64(0)}

    @property
    def ring_capacity(self) -> int:
        return self.capacity

    def live_fill(self, state):
        """Live (unexpired) rows in the ring — ``win_fill`` instrument
        slot; near ``capacity`` means the ring is one skewed batch away
        from overflow."""
        return jnp.maximum(state["total"] - state["expired_upto"],
                           jnp.int64(0))

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        t = jnp.int64(self.time_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        ts = cols[TS_KEY]
        now = jnp.int64(ctx["current_time"])
        STRIDE = jnp.int64(Wc + B + 4)

        total0 = state["total"]
        exp0 = state["expired_upto"]

        # FIFO view: item j holds sequence exp0 + j (arrival timestamps are
        # monotone, so expiry always removes a FIFO prefix)
        fifo_seq = exp0 + jnp.arange(Wc, dtype=jnp.int64)
        occupied = fifo_seq < total0
        fifo_slot = (fifo_seq % Wc).astype(jnp.int32)
        ring_ts = state["buf"][TS_KEY][fifo_slot]

        if self.external:
            # cutoff for row i: clock_i - t (running max for safety)
            ck = cols[self.ts_key]
            ring_ck = state["buf"][self.ts_key][fifo_slot]
            run_max = lax.cummax(jnp.where(valid_cur, ck, jnp.int64(-(2**62))))
            final_cutoff = run_max[B - 1] - t
            expire_ring = occupied & (ring_ck <= final_cutoff)
            # first row whose cutoff covers item j
            covers = (run_max[None, :] - t) >= ring_ck[:, None]  # [Wc, B]
            first_row = jnp.where(
                jnp.any(covers, axis=1), jnp.argmax(covers, axis=1), 0
            ).astype(jnp.int64)
            exp_ts_ring = ring_ts  # externalTime keeps original timestamps
        else:
            expire_ring = occupied & (ring_ts + t <= now)
            first_row = jnp.zeros((Wc,), jnp.int64)  # all drain before row 0
            exp_ts_ring = jnp.broadcast_to(now, (Wc,))

        n_exp_ring = jnp.sum(expire_ring.astype(jnp.int64))

        # within-batch expiry: row i's clone expires before a later row r
        if self.external:
            # coverage by the clock attribute, not the event timestamp
            nxt = _first_later_covering(cols[self.ts_key], valid_cur, t)  # [B] (B if none)
            batch_exp = valid_cur & (nxt < B)
            exp_ts_batch = ts
        else:
            nxt = _next_valid_index(valid_cur)
            batch_exp = valid_cur & (ts + t <= now) & (nxt < B)
            exp_ts_batch = jnp.broadcast_to(now, (B,))

        idx = jnp.arange(B, dtype=jnp.int64)
        ring_okey = first_row * STRIDE + jnp.arange(Wc, dtype=jnp.int64)
        batch_okey = nxt.astype(jnp.int64) * STRIDE + Wc + idx
        cur_okey = idx * STRIDE + Wc + B + 2

        ring_rows = {k: state["buf"][k][fifo_slot] for k in state["buf"]}
        ring_rows[TS_KEY] = jnp.where(expire_ring, exp_ts_ring, ring_rows[TS_KEY])
        batch_exp_rows = {k: cols[k] for k in keys}
        batch_exp_rows[TS_KEY] = exp_ts_batch

        parts = [
            (ring_rows, jnp.full((Wc,), EXPIRED, jnp.int8), expire_ring, ring_okey),
            (batch_exp_rows, jnp.full((B,), EXPIRED, jnp.int8), batch_exp, batch_okey),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, cur_okey),
        ]
        out, _ = _order_emit(parts)

        # ring update: append inserted rows, advance the expired prefix
        rank, n_ins = _insert_ranks(valid_cur)
        seq = total0 + rank
        write = valid_cur & (rank >= n_ins - Wc)
        slot = jnp.where(write, (seq % Wc).astype(jnp.int32), Wc)
        new_buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop") for k in state["buf"]}
        new_total = total0 + n_ins
        n_batch_exp = jnp.sum(batch_exp.astype(jnp.int64))
        new_exp = exp0 + n_exp_ring + n_batch_exp

        live = new_total - new_exp
        out[OVERFLOW_KEY] = (live > Wc).astype(jnp.int32)
        if self.external:
            out[NOTIFY_KEY] = jnp.int64(-1)
        else:
            fifo2 = new_exp + jnp.arange(Wc, dtype=jnp.int64)
            occ2 = fifo2 < new_total
            ts2 = new_buf[TS_KEY][(fifo2 % Wc).astype(jnp.int32)]
            nxt_notify = jnp.min(jnp.where(occ2, ts2 + t, _BIG))
            out[NOTIFY_KEY] = jnp.where(jnp.any(occ2), nxt_notify, jnp.int64(-1))

        return {"buf": new_buf, "total": new_total, "expired_upto": new_exp}, out

    def contents(self, state):
        Wc = self.capacity
        total = state["total"]
        # slot j holds the newest sequence s < total with s % Wc == j
        j = jnp.arange(Wc, dtype=jnp.int64)
        s_j = total - 1 - ((total - 1 - j) % Wc)
        valid = (total > 0) & (s_j >= 0) & (s_j >= state["expired_upto"])
        return dict(state["buf"]), valid


def _next_valid_index(valid):
    """For each i: the smallest valid index j > i (B if none)."""
    B = valid.shape[0]
    idx = jnp.where(valid, jnp.arange(B, dtype=jnp.int64), jnp.int64(2 * B))
    suffix_min = lax.cummin(idx[::-1])[::-1]
    nxt = jnp.concatenate([suffix_min[1:], jnp.full((1,), 2 * B, jnp.int64)])
    return jnp.minimum(nxt, B)


def _first_later_covering(ts, valid, t):
    """First valid row j > i with ts_j >= ts_i + t (B if none)."""
    B = ts.shape[0]
    idx = jnp.arange(B)
    later = (idx[None, :] > idx[:, None]) & valid[None, :]
    ge = later & (ts[None, :] >= ts[:, None] + t)
    return jnp.where(jnp.any(ge, axis=1), jnp.argmax(ge, axis=1), B)


# ------------------------------------------------------------- lengthBatch

class LengthBatchWindowStage(WindowStage):
    """Tumbling count window; flushes exactly at count boundaries, possibly
    several times within one device batch. Each flush emits
    [EXPIRED(prev flush, ts=now), RESET, CURRENT rows].

    ``stream_current`` mirrors the reference's streamCurrentEvents overload
    (``LengthBatchWindowProcessor.processStreamCurrentEvents``): every
    arrival is emitted as CURRENT immediately; when the (W+1)-th event of a
    cycle arrives, [EXPIRED(previous W events, ts=now), RESET] are emitted
    just before it."""

    batch_mode = True

    def __init__(self, length: int, col_specs: Dict[str, np.dtype], expired_needed: bool = True,
                 stream_current: bool = False):
        if length < 0:
            raise CompileError("lengthBatch window needs a non-negative length")
        self.length = length
        self.col_specs = col_specs
        self.expired_needed = expired_needed
        self.stream_current = stream_current

    def _apply_zero(self, state, cols, ctx):
        """length 0: every arrival is its own instant batch —
        [CURRENT, EXPIRED(clone, ts=now), RESET] per event
        (``LengthBatchWindowProcessor.processLengthZeroBatch``)."""
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        rank, _n = _insert_ranks(valid_cur)

        parts = [({k: cols[k] for k in keys},
                  jnp.full((B,), CURRENT, jnp.int8), valid_cur, rank * 3)]
        if self.expired_needed:
            exp = {k: cols[k] for k in keys}
            exp[TS_KEY] = jnp.where(valid_cur, now, cols[TS_KEY])
            parts.append((exp, jnp.full((B,), EXPIRED, jnp.int8), valid_cur, rank * 3 + 1))
        reset_rows = _zero_rows(cols, B)
        reset_rows[TS_KEY] = jnp.where(valid_cur, now, jnp.int64(0))
        parts.append((reset_rows, jnp.full((B,), RESET, jnp.int8), valid_cur, rank * 3 + 2))
        out, okeys = _order_emit(parts)
        out[FLUSH_KEY] = jnp.where(okeys == _BIG, 0, okeys // 3).astype(jnp.int32)
        return state, out

    def init_state(self, num_keys: int = 1) -> dict:
        W = self.length
        zero = lambda: {k: jnp.zeros((W,), dt) for k, dt in self.col_specs.items()}  # noqa: E731
        return {"cur": zero(), "prev": zero(),
                "count": jnp.int64(0), "prev_count": jnp.int64(0)}

    def _apply_stream(self, state, cols, ctx):
        """streamCurrentEvents mode: CURRENT rows pass through at arrival;
        each cycle boundary (an arrival at seq ≡ 0 mod W, seq > 0) first
        emits [EXPIRED(previous W events, ts=now), RESET]."""
        W = self.length
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        count0 = state["count"]           # events buffered since last boundary
        rank, n_ins = _insert_ranks(valid_cur)
        seq = count0 + rank               # position since the last boundary
        total_after = count0 + n_ins
        S = jnp.int64(W + 2)              # per-trigger span: W expired, RESET, CURRENT
        lead = jnp.arange(W, dtype=jnp.int64)

        parts = []
        if self.expired_needed:
            # buffered rows all expire at the first boundary (trigger rank
            # r0 = W - count0), batch rows at the boundary closing their cycle
            r0 = jnp.int64(W) - count0
            buf_valid = (lead < count0) & (n_ins > r0)
            buf_rows = {k: state["cur"][k][lead.astype(jnp.int32)] for k in state["cur"]}
            buf_rows[TS_KEY] = jnp.where(buf_valid, now, buf_rows[TS_KEY])
            parts.append((buf_rows, jnp.full((W,), EXPIRED, jnp.int8), buf_valid, r0 * S + lead))

            rb = (seq // W + 1) * W - count0      # trigger rank of the closing boundary
            bexp_valid = valid_cur & (n_ins > rb)
            bexp = {k: cols[k] for k in keys}
            bexp[TS_KEY] = jnp.where(bexp_valid, now, cols[TS_KEY])
            parts.append((bexp, jnp.full((B,), EXPIRED, jnp.int8), bexp_valid, rb * S + seq % W))

        is_bnd = valid_cur & (seq > 0) & (seq % W == 0)
        reset_rows = _zero_rows(cols, B)
        reset_rows[TS_KEY] = jnp.where(is_bnd, now, jnp.int64(0))
        parts.append((reset_rows, jnp.full((B,), RESET, jnp.int8), is_bnd, rank * S + W))

        parts.append(({k: cols[k] for k in keys}, jnp.full((B,), CURRENT, jnp.int8),
                      valid_cur, rank * S + W + 1))

        out, okeys = _order_emit(parts)
        # selector chunk segmentation (QuerySelector batch dedup): each
        # arrival is one reference chunk — at a boundary that chunk holds
        # [expired×W, RESET, current] and collapses to its LAST type-valid
        # row (the current for `all events`, the last expired for
        # `expired events` — LengthBatchWindowTestCase test21/test12)
        out[FLUSH_KEY] = jnp.where(okeys == _BIG, 0, okeys // S).astype(jnp.int32)

        # state: rows of the still-open cycle stay buffered
        new_count = jnp.where(total_after > 0,
                              total_after - W * ((total_after - 1) // W),
                              jnp.int64(0))
        base_seq = total_after - new_count
        keep_old = base_seq == 0
        is_rem = valid_cur & (seq >= base_seq)
        slot = jnp.where(is_rem, (seq - base_seq).astype(jnp.int32), W)
        new_cur = {}
        for k in state["cur"]:
            base = jnp.where(keep_old, state["cur"][k], jnp.zeros_like(state["cur"][k]))
            new_cur[k] = base.at[slot].set(cols[k], mode="drop")
        return {"cur": new_cur, "prev": state["prev"],
                "count": new_count, "prev_count": state["prev_count"]}, out

    def apply(self, state, cols, ctx):
        if self.length == 0:
            return self._apply_zero(state, cols, ctx)
        if self.stream_current:
            return self._apply_stream(state, cols, ctx)
        W = self.length
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        count0 = state["count"]
        rank, n_ins = _insert_ranks(valid_cur)
        seq = count0 + rank               # position in the accumulating stream
        total_after = count0 + n_ins
        n_flush = total_after // W
        flush_id = seq // W               # which flush a row's CURRENT belongs to
        pos_in_flush = seq % W

        # per-flush emission spans: flush f occupies [f*S, (f+1)*S):
        #   expired block at +0..W-1, RESET at +W, currents at +W+1..2W
        S = jnp.int64(2 * W + 2)
        lead = jnp.arange(W, dtype=jnp.int64)

        parts = []
        if self.expired_needed:
            # pre-step prev flush expires in flush 0
            prev_valid = (lead < state["prev_count"]) & (n_flush > 0)
            prev_rows = {k: state["prev"][k][lead.astype(jnp.int32)] for k in state["prev"]}
            prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
            parts.append((prev_rows, jnp.full((W,), EXPIRED, jnp.int8), prev_valid, lead))
            # leftover buffered rows (in flush 0) expire in flush 1
            lead_exp_valid = (lead < count0) & (n_flush > 1)
            lead_exp = {k: state["cur"][k][lead.astype(jnp.int32)] for k in state["cur"]}
            lead_exp[TS_KEY] = jnp.where(lead_exp_valid, now, lead_exp[TS_KEY])
            parts.append((lead_exp, jnp.full((W,), EXPIRED, jnp.int8), lead_exp_valid, S + lead))
            # batch rows of flush f expire in flush f+1
            bexp_valid = valid_cur & (flush_id + 1 < n_flush)
            bexp = {k: cols[k] for k in keys}
            bexp[TS_KEY] = jnp.where(bexp_valid, now, cols[TS_KEY])
            parts.append((bexp, jnp.full((B,), EXPIRED, jnp.int8), bexp_valid,
                          (flush_id + 1) * S + pos_in_flush))

        n_reset_cap = B // W + 2
        ridx = jnp.arange(n_reset_cap, dtype=jnp.int64)
        reset_valid = ridx < n_flush
        reset_rows = _zero_rows(cols, n_reset_cap)
        reset_rows[TS_KEY] = jnp.where(reset_valid, now, jnp.int64(0))
        parts.append((reset_rows, jnp.full((n_reset_cap,), RESET, jnp.int8),
                      reset_valid, ridx * S + W))

        # currents: leftover buffer rows flush in flush 0...
        lead_valid = (lead < count0) & (n_flush > 0)
        lead_rows = {k: state["cur"][k][lead.astype(jnp.int32)] for k in state["cur"]}
        parts.append((lead_rows, jnp.full((W,), CURRENT, jnp.int8), lead_valid, W + 1 + lead))
        # ...batch rows of completed flushes flush now
        emitted_now = valid_cur & (flush_id < n_flush)
        parts.append(({k: cols[k] for k in keys}, jnp.full((B,), CURRENT, jnp.int8),
                      emitted_now, flush_id * S + W + 1 + pos_in_flush))

        out, okeys = _order_emit(parts)
        out[FLUSH_KEY] = jnp.where(okeys == _BIG, 0, okeys // S).astype(jnp.int32)

        # state update: remainder rows -> cur buffer
        keep_old = n_flush == 0
        rem_slot_val = jnp.where(keep_old, seq, seq - n_flush * W)
        is_rem = valid_cur & (flush_id == n_flush)
        slot = jnp.where(is_rem, rem_slot_val.astype(jnp.int32), W)
        new_cur = {}
        for k in state["cur"]:
            base = jnp.where(keep_old, state["cur"][k], jnp.zeros_like(state["cur"][k]))
            new_cur[k] = base.at[slot].set(cols[k], mode="drop")
        new_count = total_after - n_flush * W

        # prev buffer <- rows of the last completed flush
        last_flush = n_flush - 1
        in_last = valid_cur & (flush_id == last_flush)
        lead_in_last = (lead < count0) & (n_flush == 1)
        pslot_lead = jnp.where(lead_in_last, lead.astype(jnp.int32), W)
        pslot_batch = jnp.where(in_last, pos_in_flush.astype(jnp.int32), W)
        new_prev = {}
        for k in state["prev"]:
            base = jnp.where(n_flush > 0, jnp.zeros_like(state["prev"][k]), state["prev"][k])
            base = base.at[pslot_lead].set(state["cur"][k], mode="drop")
            base = base.at[pslot_batch].set(cols[k], mode="drop")
            new_prev[k] = base
        new_prev_count = jnp.where(n_flush > 0, jnp.int64(W), state["prev_count"])

        return {"cur": new_cur, "prev": new_prev,
                "count": new_count, "prev_count": new_prev_count}, out

    def contents(self, state):
        """Join/find probes hit the reference's ``expiredEventQueue``
        (LengthBatchWindowProcessor.java:288-299): the LAST COMPLETED batch
        in full-batch mode; the current cycle's arrivals in
        streamCurrentEvents mode (clones queue on arrival there)."""
        if self.length == 0:
            return dict(state["cur"]), jnp.zeros((0,), bool)
        if self.stream_current:
            valid = jnp.arange(self.length, dtype=jnp.int64) < state["count"]
            return dict(state["cur"]), valid
        valid = jnp.arange(self.length, dtype=jnp.int64) < state["prev_count"]
        return dict(state["prev"]), valid


# --------------------------------------------------------------- timeBatch

def time_batch_boundary(time_ms: int, start_time: int, next_emit0, now):
    """(the next boundary, whether this step flushes) of a ``timeBatch``
    window: the boundary is set on the first chunk
    (TimeBatchWindowProcessor:266-276) and moves one window on when a
    step's clock has reached it. Shared by the buffered stage below and
    the folded one (``ops/tumbling_agg.py``)."""
    t = jnp.int64(time_ms)
    if start_time >= 0:
        st = jnp.int64(start_time)
        init_emit = now + (t - ((now - st) % t))
    else:
        init_emit = now + t
    next_emit = jnp.where(next_emit0 < 0, init_emit, next_emit0)
    send = now >= next_emit
    return jnp.where(send, next_emit + t, next_emit), send


class TimeBatchWindowStage(WindowStage):
    """Tumbling time window; flush check once per chunk (arriving rows join
    the flushing batch), exactly as the reference processes chunks.

    ``stream_current`` mirrors the reference's streamCurrentEvents overload
    (``TimeBatchWindowProcessor.java:297-335``): CURRENT rows pass through
    at arrival (never queued); each flush emits [EXPIRED(arrivals since the
    last flush, ts=now), RESET] after any currents of the flushing chunk."""

    batch_mode = True
    needs_scheduler = True

    def __init__(self, time_ms: int, col_specs: Dict[str, np.dtype], capacity: int,
                 expired_needed: bool = True, start_time: int = -1,
                 stream_current: bool = False):
        self.time_ms = time_ms
        self.capacity = capacity
        self.col_specs = col_specs
        self.expired_needed = expired_needed
        self.start_time = start_time
        self.stream_current = stream_current

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        zero = lambda: {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}  # noqa: E731
        return {"cur": zero(), "prev": zero(),
                "count": jnp.int64(0), "prev_count": jnp.int64(0),
                "next_emit": jnp.int64(-1)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        keys = _data_keys(cols)
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        next_emit, send = time_batch_boundary(
            self.time_ms, self.start_time, state["next_emit"], now)

        count0 = state["count"]
        rank, n_ins = _insert_ranks(valid_cur)
        slot = jnp.where(valid_cur, (count0 + rank).astype(jnp.int32), Wc)
        cur_buf = {k: state["cur"][k].at[slot].set(cols[k], mode="drop") for k in state["cur"]}
        count = count0 + n_ins

        widx = jnp.arange(Wc, dtype=jnp.int64)

        if self.stream_current:
            B = cols[VALID_KEY].shape[0]
            parts = [({k: cols[k] for k in keys},
                      jnp.full((B,), CURRENT, jnp.int8), valid_cur, rank)]
            if self.expired_needed:
                # the whole queue — arrivals before AND inside the flushing
                # chunk — expires at the flush (clones join the queue before
                # it drains, TimeBatchWindowProcessor.java:298-314)
                qrows = {k: cur_buf[k][widx.astype(jnp.int32)] for k in cur_buf}
                q_valid = (widx < count) & send
                qrows[TS_KEY] = jnp.where(q_valid, now, qrows[TS_KEY])
                parts.append((qrows, jnp.full((Wc,), EXPIRED, jnp.int8),
                              q_valid, jnp.int64(B) + widx))
            reset_rows = _zero_rows(cols, 1)
            reset_rows[TS_KEY] = jnp.broadcast_to(now, (1,))
            parts.append((reset_rows, jnp.full((1,), RESET, jnp.int8),
                          jnp.broadcast_to(send & (count > 0), (1,)),
                          jnp.full((1,), jnp.int64(B) + Wc, jnp.int64)))
            out, okeys = _order_emit(parts)
            # chunk ids for the selector's per-chunk collapse: currents are
            # singleton chunks; the flush's EXPIRED rows share one chunk
            out[FLUSH_KEY] = jnp.minimum(okeys, jnp.int64(B)).astype(jnp.int32)
            new_state = {
                "cur": {k: jnp.where(send, jnp.zeros_like(v), v) for k, v in cur_buf.items()},
                "prev": state["prev"],
                "count": jnp.where(send, jnp.int64(0), count),
                "prev_count": state["prev_count"],
                "next_emit": next_emit,
            }
            out[NOTIFY_KEY] = next_emit
            out[OVERFLOW_KEY] = (count > Wc).astype(jnp.int32)
            return new_state, out

        parts = []
        if self.expired_needed:
            prev_valid = (widx < state["prev_count"]) & send
            prev_rows = {k: state["prev"][k][widx.astype(jnp.int32)] for k in state["prev"]}
            prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
            parts.append((prev_rows, jnp.full((Wc,), EXPIRED, jnp.int8), prev_valid, widx))
        reset_rows = _zero_rows(cols, 1)
        reset_rows[TS_KEY] = jnp.broadcast_to(now, (1,))
        parts.append((reset_rows, jnp.full((1,), RESET, jnp.int8),
                      jnp.broadcast_to(send & (count > 0), (1,)), jnp.full((1,), Wc, jnp.int64)))
        cur_valid = (widx < count) & send
        cur_rows = {k: cur_buf[k][widx.astype(jnp.int32)] for k in cur_buf}
        parts.append((cur_rows, jnp.full((Wc,), CURRENT, jnp.int8), cur_valid, Wc + 1 + widx))
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        zero_count = jnp.int64(0)
        # prev (the findable expiredEventQueue): with expired outputs an
        # empty flush drains it (its expireds were just emitted); find-only
        # queries never drain it, so an empty flush RETAINS the last batch
        # for join probes (TimeBatchWindowProcessor flush: the expired
        # drain is gated on outputExpectsExpiredEvents)
        replace_prev = send & (self.expired_needed | (count > 0))
        new_state = {
            "cur": {k: jnp.where(send, jnp.zeros_like(v), v) for k, v in cur_buf.items()},
            "prev": {k: jnp.where(replace_prev, cur_buf[k], state["prev"][k])
                     for k in state["prev"]},
            "count": jnp.where(send, zero_count, count),
            "prev_count": jnp.where(replace_prev, count, state["prev_count"]),
            "next_emit": next_emit,
        }
        out[NOTIFY_KEY] = next_emit
        out[OVERFLOW_KEY] = (count > Wc).astype(jnp.int32)
        return new_state, out

    def contents(self, state):
        """Join/find probes hit the reference's ``expiredEventQueue``
        (TimeBatchWindowProcessor.java:368-380): the last flushed batch in
        full-batch mode; the arrivals since the last flush in
        streamCurrentEvents mode."""
        if self.stream_current:
            valid = jnp.arange(self.capacity, dtype=jnp.int64) < state["count"]
            return dict(state["cur"]), valid
        valid = jnp.arange(self.capacity, dtype=jnp.int64) < state["prev_count"]
        return dict(state["prev"]), valid


class HoppingWindowStage(WindowStage):
    """``hopping(windowTime, hopTime)``: every hop, emit the events of the
    trailing windowTime as a batch (reference HopingWindowProcessor — a
    time batch whose emission period is decoupled from its retention)."""

    batch_mode = True
    needs_scheduler = True

    def __init__(self, window_ms: int, hop_ms: int,
                 col_specs: Dict[str, np.dtype], capacity: int):
        if hop_ms <= 0 or window_ms <= 0:
            raise CompileError("hopping window needs positive window and hop times")
        self.window_ms = window_ms
        self.hop_ms = hop_ms
        self.capacity = capacity
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        zero = lambda: {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}  # noqa: E731
        return {"buf": zero(), "prev": zero(),
                "total": jnp.int64(0), "expired_upto": jnp.int64(0),
                "prev_count": jnp.int64(0), "next_emit": jnp.int64(-1)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        w = jnp.int64(self.window_ms)
        hop = jnp.int64(self.hop_ms)
        keys = _data_keys(cols)
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        next_emit0 = state["next_emit"]
        next_emit = jnp.where(next_emit0 < 0, now + hop, next_emit0)
        send = now >= next_emit
        next_emit = jnp.where(send, next_emit + hop, next_emit)

        # append arrivals to the ts-monotone FIFO ring
        total0 = state["total"]
        exp0 = state["expired_upto"]
        rank, n_ins = _insert_ranks(valid_cur)
        slot = jnp.where(valid_cur, ((total0 + rank) % Wc).astype(jnp.int32), Wc)
        buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop") for k in state["buf"]}
        total = total0 + n_ins

        # live FIFO view; rows older than the trailing window can never be
        # emitted again — drop them from the live range
        widx = jnp.arange(Wc, dtype=jnp.int64)
        fifo_seq = exp0 + widx
        occ = fifo_seq < total
        flat = (fifo_seq % Wc).astype(jnp.int32)
        ring_ts = buf[TS_KEY][flat]
        stale = occ & (ring_ts <= now - w)
        new_exp = exp0 + jnp.sum(stale.astype(jnp.int64))

        in_window = occ & ~stale & send
        cur_rows = {k: buf[k][flat] for k in buf}
        n_emit = jnp.sum(in_window.astype(jnp.int64))

        parts = []
        prev_valid = (widx < state["prev_count"]) & send
        prev_rows = {k: state["prev"][k][widx.astype(jnp.int32)] for k in state["prev"]}
        prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
        parts.append((prev_rows, jnp.full((Wc,), EXPIRED, jnp.int8), prev_valid, widx))
        reset_rows = _zero_rows(cols, 1)
        reset_rows[TS_KEY] = jnp.broadcast_to(now, (1,))
        parts.append((reset_rows, jnp.full((1,), RESET, jnp.int8),
                      jnp.broadcast_to(send & (state["prev_count"] > 0), (1,)),
                      jnp.full((1,), Wc, jnp.int64)))
        parts.append((cur_rows, jnp.full((Wc,), CURRENT, jnp.int8), in_window,
                      Wc + 1 + widx))
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        # the emitted snapshot becomes the next expiry batch (packed)
        emit_rank = jnp.cumsum(in_window.astype(jnp.int64)) - 1
        pslot = jnp.where(in_window, emit_rank.astype(jnp.int32), Wc)
        new_prev = {}
        for k in state["prev"]:
            base = jnp.where(send, jnp.zeros_like(state["prev"][k]), state["prev"][k])
            new_prev[k] = base.at[pslot].set(cur_rows[k], mode="drop")
        new_state = {
            "buf": buf,
            "prev": new_prev,
            "total": total,
            "expired_upto": new_exp,
            "prev_count": jnp.where(send, n_emit, state["prev_count"]),
            "next_emit": next_emit,
        }
        out[NOTIFY_KEY] = next_emit
        out[OVERFLOW_KEY] = ((total - new_exp) > Wc).astype(jnp.int32)
        return new_state, out

    def contents(self, state):
        Wc = self.capacity
        widx = jnp.arange(Wc, dtype=jnp.int64)
        fifo_seq = state["expired_upto"] + widx
        occ = fifo_seq < state["total"]
        flat = (fifo_seq % Wc).astype(jnp.int32)
        return {k: v[flat] for k, v in state["buf"].items()}, occ


# ------------------------------------------------------------------- batch

class BatchWindowStage(WindowStage):
    """`#window.batch([chunkLength])`: each chunk is its own batch; the
    previous chunk expires first. With ``chunkLength`` the arriving chunk is
    split into sub-batches of at most that many rows, each flushed in turn
    (``BatchWindowProcessor.java:91-118``; the trailing partial group still
    flushes at chunk end — nothing carries over unflushed)."""

    batch_mode = True

    def __init__(self, col_specs: Dict[str, np.dtype], capacity: int, expired_needed: bool = True,
                 chunk_length: int = 0):
        if chunk_length < 0:
            raise CompileError(
                "batch window chunkLength must be greater than zero")
        self.col_specs = col_specs
        self.capacity = capacity
        self.expired_needed = expired_needed
        self.chunk_length = chunk_length

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        prev = {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}
        return {"prev": prev, "prev_count": jnp.int64(0)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        any_cur = jnp.any(valid_cur)
        rank, n_ins = _insert_ranks(valid_cur)

        widx = jnp.arange(Wc, dtype=jnp.int64)

        if self.chunk_length:
            # split the chunk into n-row flushes: flush f emits
            # [EXPIRED(flush f-1, or prev chunk for f=0), RESET, CURRENTs]
            n = jnp.int64(self.chunk_length)
            flush_id = rank // n
            n_flush = (n_ins + n - 1) // n
            S = jnp.int64(Wc + 1 + self.chunk_length)

            parts = []
            if self.expired_needed:
                prev_valid = (widx < state["prev_count"]) & any_cur
                prev_rows = {k: state["prev"][k][widx.astype(jnp.int32)] for k in state["prev"]}
                prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
                parts.append((prev_rows, jnp.full((Wc,), EXPIRED, jnp.int8), prev_valid, widx))
                bexp_valid = valid_cur & (flush_id + 1 < n_flush)
                bexp = {k: cols[k] for k in keys}
                bexp[TS_KEY] = jnp.where(bexp_valid, now, cols[TS_KEY])
                parts.append((bexp, jnp.full((B,), EXPIRED, jnp.int8), bexp_valid,
                              (flush_id + 1) * S + rank % n))
            n_reset_cap = B // self.chunk_length + 2
            ridx = jnp.arange(n_reset_cap, dtype=jnp.int64)
            reset_valid = (ridx < n_flush) & ((ridx > 0) | (state["prev_count"] > 0))
            reset_rows = _zero_rows(cols, n_reset_cap)
            reset_rows[TS_KEY] = jnp.where(reset_valid, now, jnp.int64(0))
            parts.append((reset_rows, jnp.full((n_reset_cap,), RESET, jnp.int8),
                          reset_valid, ridx * S + Wc))
            parts.append(({k: cols[k] for k in keys}, jnp.full((B,), CURRENT, jnp.int8),
                          valid_cur, flush_id * S + Wc + 1 + rank % n))
            out, okeys = _order_emit(parts)
            out[FLUSH_KEY] = jnp.where(okeys == _BIG, 0, okeys // S).astype(jnp.int32)

            # prev <- rows of the trailing (possibly partial) flush
            last = n_flush - 1
            base_rank = last * n
            is_last = valid_cur & (flush_id == last)
            slot = jnp.where(is_last, (rank - base_rank).astype(jnp.int32), Wc)
            new_prev = {}
            for k in state["prev"]:
                base = jnp.where(any_cur, jnp.zeros_like(state["prev"][k]), state["prev"][k])
                new_prev[k] = base.at[slot].set(cols[k], mode="drop")
            new_count = jnp.where(any_cur, n_ins - base_rank, state["prev_count"])
            out[OVERFLOW_KEY] = jnp.int32(0)
            return {"prev": new_prev, "prev_count": new_count}, out

        parts = []
        if self.expired_needed:
            prev_valid = (widx < state["prev_count"]) & any_cur
            prev_rows = {k: state["prev"][k][widx.astype(jnp.int32)] for k in state["prev"]}
            prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
            parts.append((prev_rows, jnp.full((Wc,), EXPIRED, jnp.int8), prev_valid, widx))
        reset_rows = _zero_rows(cols, 1)
        reset_rows[TS_KEY] = jnp.broadcast_to(now, (1,))
        parts.append((reset_rows, jnp.full((1,), RESET, jnp.int8),
                      jnp.broadcast_to(any_cur & (state["prev_count"] > 0), (1,)),
                      jnp.full((1,), Wc, jnp.int64)))
        idx = jnp.arange(B, dtype=jnp.int64)
        parts.append(({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, Wc + 1 + idx))
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        slot = jnp.where(valid_cur, rank.astype(jnp.int32), Wc)
        new_prev = {}
        for k in state["prev"]:
            base = jnp.where(any_cur, jnp.zeros_like(state["prev"][k]), state["prev"][k])
            new_prev[k] = base.at[slot].set(cols[k], mode="drop")
        new_count = jnp.where(any_cur, n_ins, state["prev_count"])
        out[OVERFLOW_KEY] = (n_ins > Wc).astype(jnp.int32)
        return {"prev": new_prev, "prev_count": new_count}, out

    def contents(self, state):
        valid = jnp.arange(self.capacity, dtype=jnp.int64) < state["prev_count"]
        return dict(state["prev"]), valid


# -------------------------------------------------------------- timeLength

class TimeLengthWindowStage(WindowStage):
    """Sliding window bounded by time AND count
    (``TimeLengthWindowProcessor``): entries older than t drain on timers;
    when the window holds `length` live entries, each arrival evicts the
    oldest. Both evictions are FIFO-prefix drops, so one ring of exactly
    ``length`` slots suffices. Within-batch time expiry (playback jumps
    inside one chunk) is deferred to the immediately-scheduled timer.
    """

    needs_scheduler = True

    def __init__(self, time_ms: int, length: int, col_specs: Dict[str, np.dtype]):
        if length <= 0:
            raise CompileError("timeLength window needs a positive length")
        self.time_ms = time_ms
        self.length = length
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        L = self.length
        buf = {k: jnp.zeros((L,), dt) for k, dt in self.col_specs.items()}
        return {"buf": buf, "total": jnp.int64(0), "expired_upto": jnp.int64(0)}

    def apply(self, state, cols, ctx):
        L = self.length
        t = jnp.int64(self.time_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        now = jnp.int64(ctx["current_time"])
        STRIDE = jnp.int64(L + B + 4)

        total0 = state["total"]
        exp0 = state["expired_upto"]

        # ---- time drain (FIFO prefix), before the batch
        j = jnp.arange(L, dtype=jnp.int64)
        fifo_seq = exp0 + j
        occupied = fifo_seq < total0
        fifo_slot = (fifo_seq % L).astype(jnp.int32)
        ring_ts = state["buf"][TS_KEY][fifo_slot]
        time_exp = occupied & (ring_ts + t <= now)
        n_time = jnp.sum(time_exp.astype(jnp.int64))
        exp1 = exp0 + n_time
        live0 = total0 - exp1

        # ---- length evictions per insert: insert rank r evicts FIFO entry
        # j = live0 + r - L (when >= 0); entry seq exp1 + j
        rank, n_ins = _insert_ranks(valid_cur)
        n_len = jnp.clip(live0 + n_ins - L, 0, n_ins)
        rank_to_row = jnp.zeros((B,), jnp.int32).at[
            jnp.where(valid_cur, rank, B).astype(jnp.int32)
        ].set(jnp.arange(B, dtype=jnp.int32), mode="drop")

        lev_seq = exp1 + j                       # candidate eviction seqs
        lev = (j < n_len) & (lev_seq < total0 + n_ins)
        from_batch = lev_seq >= total0
        batch_row = rank_to_row[jnp.clip(lev_seq - total0, 0, B - 1).astype(jnp.int32)]
        lev_slot = (lev_seq % L).astype(jnp.int32)
        # eviction j precedes the row of insert rank r = L - live0 + j
        lev_rank = jnp.clip(L - live0 + j, 0, B - 1)
        lev_row = rank_to_row[lev_rank.astype(jnp.int32)].astype(jnp.int64)

        time_rows = {k: state["buf"][k][fifo_slot] for k in state["buf"]}
        time_rows[TS_KEY] = jnp.where(time_exp, now, time_rows[TS_KEY])
        lev_rows = {}
        for k in state["buf"]:
            ring_v = state["buf"][k][lev_slot]
            lev_rows[k] = jnp.where(from_batch, cols[k][batch_row], ring_v)
        lev_rows[TS_KEY] = jnp.broadcast_to(now, (L,))

        idx = jnp.arange(B, dtype=jnp.int64)
        parts = [
            (time_rows, jnp.full((L,), EXPIRED, jnp.int8), time_exp, j),
            (lev_rows, jnp.full((L,), EXPIRED, jnp.int8), lev, lev_row * STRIDE + L + j),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur,
             idx * STRIDE + L + B + 2),
        ]
        out, _ = _order_emit(parts)

        # ---- ring update: write the last min(L, n_ins) inserts
        seq = total0 + rank
        write = valid_cur & (rank >= n_ins - L)
        slot = jnp.where(write, (seq % L).astype(jnp.int32), L)
        new_buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop")
                   for k in state["buf"]}
        new_total = total0 + n_ins
        new_exp = exp1 + n_len

        fifo2 = new_exp + j
        occ2 = fifo2 < new_total
        ts2 = new_buf[TS_KEY][(fifo2 % L).astype(jnp.int32)]
        nxt = jnp.min(jnp.where(occ2, ts2 + t, _BIG))
        out[NOTIFY_KEY] = jnp.where(jnp.any(occ2), nxt, jnp.int64(-1))
        return {"buf": new_buf, "total": new_total, "expired_upto": new_exp}, out

    def contents(self, state):
        L = self.length
        total = state["total"]
        j = jnp.arange(L, dtype=jnp.int64)
        s_j = total - 1 - ((total - 1 - j) % L)
        valid = (total > 0) & (s_j >= 0) & (s_j >= state["expired_upto"])
        return dict(state["buf"]), valid


# ------------------------------------------------------------------- delay

class DelayWindowStage(WindowStage):
    """``delay(t)``: events are held for t, then released downstream as
    CURRENT with the release time as timestamp
    (``DelayWindowProcessor.java:135-143``). Nothing is emitted on arrival."""

    needs_scheduler = True

    def __init__(self, delay_ms: int, col_specs: Dict[str, np.dtype], capacity: int):
        self.delay_ms = delay_ms
        self.capacity = capacity
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        buf = {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}
        return {"buf": buf, "total": jnp.int64(0), "released_upto": jnp.int64(0)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        d = jnp.int64(self.delay_ms)
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        now = jnp.int64(ctx["current_time"])

        total0 = state["total"]
        rel0 = state["released_upto"]
        j = jnp.arange(Wc, dtype=jnp.int64)
        fifo_seq = rel0 + j
        occupied = fifo_seq < total0
        fifo_slot = (fifo_seq % Wc).astype(jnp.int32)
        ring_ts = state["buf"][TS_KEY][fifo_slot]
        release = occupied & (ring_ts + d <= now)
        n_rel = jnp.sum(release.astype(jnp.int64))

        rel_rows = {k: state["buf"][k][fifo_slot] for k in state["buf"]}
        rel_rows[TS_KEY] = jnp.where(release, now, rel_rows[TS_KEY])
        out, _ = _order_emit([
            (rel_rows, jnp.full((Wc,), CURRENT, jnp.int8), release, j),
        ])

        rank, n_ins = _insert_ranks(valid_cur)
        seq = total0 + rank
        write = valid_cur & (rank >= n_ins - Wc)
        slot = jnp.where(write, (seq % Wc).astype(jnp.int32), Wc)
        new_buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop")
                   for k in state["buf"]}
        new_total = total0 + n_ins
        new_rel = rel0 + n_rel

        out[OVERFLOW_KEY] = (new_total - new_rel > Wc).astype(jnp.int32)
        fifo2 = new_rel + j
        occ2 = fifo2 < new_total
        ts2 = new_buf[TS_KEY][(fifo2 % Wc).astype(jnp.int32)]
        nxt = jnp.min(jnp.where(occ2, ts2 + d, _BIG))
        out[NOTIFY_KEY] = jnp.where(jnp.any(occ2), nxt, jnp.int64(-1))
        return {"buf": new_buf, "total": new_total, "released_upto": new_rel}, out


# -------------------------------------------------------- externalTimeBatch

class ExternalTimeBatchWindowStage(WindowStage):
    """Tumbling batches by an event-time attribute
    (``ExternalTimeBatchWindowProcessor``): when an event's time crosses the
    window end, the accumulated batch flushes as CURRENT (previous batch as
    EXPIRED + RESET) and the window slides by whole multiples of t. Several
    flushes can happen inside one chunk."""

    batch_mode = True

    def __init__(self, ts_fn, time_ms: int, col_specs: Dict[str, np.dtype],
                 capacity: int, expired_needed: bool = True,
                 start_time: int = -1, timeout: int = 0):
        self.expired_needed = expired_needed
        self.ts_fn = ts_fn          # compiled expr for the time attribute
        self.time_ms = time_ms
        self.capacity = capacity
        self.col_specs = col_specs
        self.start_time = start_time
        # timeout > 0: flush the open batch when no event arrives for
        # `timeout` ms of runtime-clock time (scheduler-driven); the window
        # end does NOT advance, and the next event-time crossing APPENDS to
        # the already-flushed output instead of re-expiring it
        # (ExternalTimeBatchWindowProcessor.java:256-307 timer path)
        self.timeout = timeout
        self.needs_scheduler = timeout > 0

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        zero = lambda: {k: jnp.zeros((Wc,), dt) for k, dt in self.col_specs.items()}  # noqa: E731
        return {"cur": zero(), "prev": zero(),
                "count": jnp.int64(0), "prev_count": jnp.int64(0),
                "end": jnp.int64(-1),
                "flushed": jnp.bool_(False), "last_sched": jnp.int64(-1)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        t = jnp.int64(self.time_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)

        tsv, _m = self.ts_fn(cols, ctx)
        tsv = jnp.asarray(tsv).astype(jnp.int64)
        tsv = jnp.broadcast_to(tsv, (B,))

        # window end: first event initializes it (startTime anchors the grid)
        first_ts = jnp.max(jnp.where(
            valid_cur & (jnp.cumsum(valid_cur) == 1), tsv, jnp.int64(0)))
        if self.start_time >= 0:
            st = jnp.int64(self.start_time)
            init_end = first_ts - jnp.maximum(first_ts - st, 0) % t + t
        else:
            init_end = first_ts + t
        end0 = jnp.where(state["end"] < 0, init_end, state["end"])

        # Grid distance per row (how many whole windows past end0 its ts
        # lies), monotone-ized against out-of-order timestamps. Flushes are
        # ORDINAL: one per crossing event, regardless of how far the time
        # jumped — the reference emits a single flush and snaps endTime to
        # cover the event (ExternalTimeBatchWindowProcessor.java:285-297),
        # never synthesizing empty intermediate batches. b_i = the ordinal
        # batch a row belongs to (0 = the carried open window).
        raw_b = jnp.where(tsv >= end0, (tsv - end0) // t + 1, 0)
        rawm = lax.cummax(jnp.where(valid_cur, raw_b, jnp.int64(0)))
        prev_rawm = jnp.concatenate([jnp.zeros((1,), jnp.int64), rawm[:-1]])
        jump = valid_cur & (rawm > prev_rawm)
        b_i = jnp.cumsum(jump.astype(jnp.int64))
        n_flush = b_i[B - 1]
        max_raw = rawm[B - 1]             # grid distance the end advances by

        count0 = state["count"]
        flushed0 = state["flushed"]
        last_sched0 = state["last_sched"]
        if self.timeout > 0:
            # timer-driven flush: no event arrived within `timeout`
            has_timer = jnp.any(cols[VALID_KEY] & (cols[TYPE_KEY] == TIMER))
            due = (has_timer & (last_sched0 >= 0) & (now >= last_sched0)
                   & (state["end"] >= 0) & ((count0 > 0) | ~flushed0)
                   & (n_flush == 0))
        else:
            due = jnp.bool_(False)
        n_flush_eff = jnp.where(due, jnp.int64(1), n_flush)
        # flush 1 appends to the already-timeout-flushed batch: its prev
        # expiry and RESET are suppressed, prev grows instead of replacing
        append1 = flushed0 & (n_flush_eff > 0)
        rank, n_ins = _insert_ranks(valid_cur)
        pos = rank  # arrival position among the batch's inserts

        # flush-k span layout (k >= 1): expired [0, Wc+B), RESET at Wc+B,
        # currents [Wc+B+1, 2Wc+2B+1)
        S = jnp.int64(2 * Wc + 2 * B + 2)
        RESET_OFF = jnp.int64(Wc + B)
        CUR_OFF = jnp.int64(Wc + B + 1)
        lead = jnp.arange(Wc, dtype=jnp.int64)
        parts = []
        # prev state buffer expires at flush 1 — except in append mode,
        # where the appended output IS the prev batch continued, so prev
        # expires together with it at flush 2 (if the chunk crosses twice)
        prev_exp_flush = jnp.where(append1, jnp.int64(2), jnp.int64(1))
        prev_valid = (lead < state["prev_count"]) & (n_flush_eff >= prev_exp_flush)
        prev_rows = {k: state["prev"][k][lead.astype(jnp.int32)] for k in state["prev"]}
        prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
        parts.append((prev_rows, jnp.full((Wc,), EXPIRED, jnp.int8), prev_valid,
                      prev_exp_flush * S + lead))
        # carry-over cur buffer (window 0): CURRENT at flush 1, EXPIRED at flush 2
        carry_valid = (lead < count0) & (n_flush_eff > 0)
        carry_rows = {k: state["cur"][k][lead.astype(jnp.int32)] for k in state["cur"]}
        parts.append((carry_rows, jnp.full((Wc,), CURRENT, jnp.int8), carry_valid,
                      S + CUR_OFF + lead))
        carry_exp_valid = (lead < count0) & (n_flush_eff > 1)
        carry_exp = dict(carry_rows)
        carry_exp[TS_KEY] = jnp.where(carry_exp_valid, now, carry_exp[TS_KEY])
        parts.append((carry_exp, jnp.full((Wc,), EXPIRED, jnp.int8), carry_exp_valid,
                      2 * S + lead))
        # batch rows of window k: CURRENT at flush k+1, EXPIRED at flush k+2
        cur_valid = valid_cur & (b_i < n_flush_eff)
        parts.append(({k: cols[k] for k in keys}, jnp.full((B,), CURRENT, jnp.int8),
                      cur_valid, (b_i + 1) * S + CUR_OFF + Wc + pos))
        bexp_valid = valid_cur & (b_i + 1 < n_flush_eff)
        bexp = {k: cols[k] for k in keys}
        bexp[TS_KEY] = jnp.where(bexp_valid, now, cols[TS_KEY])
        parts.append((bexp, jnp.full((B,), EXPIRED, jnp.int8), bexp_valid,
                      (b_i + 2) * S + Wc + pos))
        # one RESET per flush, between that flush's expired and currents
        n_reset_cap = B + 2
        ridx = jnp.arange(n_reset_cap, dtype=jnp.int64)
        reset_valid = (ridx >= 1) & (ridx <= n_flush_eff) & ~(append1 & (ridx == 1))
        reset_rows = _zero_rows(cols, n_reset_cap)
        reset_rows[TS_KEY] = jnp.where(reset_valid, now, jnp.int64(0))
        parts.append((reset_rows, jnp.full((n_reset_cap,), RESET, jnp.int8),
                      reset_valid, ridx * S + RESET_OFF))

        out, okeys = _order_emit(parts)
        out[FLUSH_KEY] = jnp.where(okeys == _BIG, 0, okeys // S).astype(jnp.int32)

        # ---- state update
        keep_old = n_flush_eff == 0
        is_rem = valid_cur & (b_i == n_flush_eff)          # open window rows
        rem_rank = jnp.cumsum(is_rem.astype(jnp.int64)) - 1
        base_cnt = jnp.where(keep_old, count0, 0)
        slot = jnp.where(is_rem, (base_cnt + rem_rank).astype(jnp.int32), Wc)
        new_cur = {}
        for k in state["cur"]:
            base = jnp.where(keep_old, state["cur"][k], jnp.zeros_like(state["cur"][k]))
            new_cur[k] = base.at[slot].set(cols[k], mode="drop")
        n_rem = jnp.sum(is_rem.astype(jnp.int64))
        new_count = base_cnt + n_rem

        # prev <- window n_flush_eff-1 (carry buffer if n_flush_eff == 1 and no batch
        # rows in window 0... both can contribute: carry + batch B==0 rows)
        in_last = valid_cur & (b_i == n_flush_eff - 1) & (n_flush_eff > 0)
        last_rank = jnp.cumsum(in_last.astype(jnp.int64)) - 1
        carry_in_last = (lead < count0) & (n_flush_eff == 1)
        # append mode: the flushed batch is already in prev — grow it
        app = append1 & (n_flush_eff == 1)
        app_off = jnp.where(app, state["prev_count"], 0).astype(jnp.int32)
        pslot_carry = jnp.where(carry_in_last, app_off + lead.astype(jnp.int32), Wc)
        n_carry_last = jnp.where(n_flush_eff == 1, count0, 0)
        pslot_batch = jnp.where(
            in_last, app_off + (n_carry_last + last_rank).astype(jnp.int32), Wc)
        new_prev = {}
        for k in state["prev"]:
            base = jnp.where((n_flush_eff > 0) & ~app,
                             jnp.zeros_like(state["prev"][k]), state["prev"][k])
            base = base.at[pslot_carry].set(state["cur"][k], mode="drop")
            base = base.at[pslot_batch].set(cols[k], mode="drop")
            new_prev[k] = base
        n_last = jnp.sum(in_last.astype(jnp.int64)) + n_carry_last
        new_prev_count = jnp.where(
            n_flush_eff > 0,
            n_last + jnp.where(app, state["prev_count"], 0),
            state["prev_count"])

        any_first = jnp.any(valid_cur)
        new_end = jnp.where(state["end"] < 0,
                            jnp.where(any_first, end0 + max_raw * t, jnp.int64(-1)),
                            end0 + max_raw * t)
        out[OVERFLOW_KEY] = ((new_count > Wc) | (new_prev_count > Wc)).astype(jnp.int32)
        new_flushed = jnp.where(n_flush > 0, jnp.bool_(False),
                                jnp.where(due, jnp.bool_(True), flushed0))
        new_sched = last_sched0
        if self.timeout > 0:
            # a firing timer ALWAYS advances the schedule, due or not —
            # the reference's timer branch reschedules unconditionally
            # (ExternalTimeBatchWindowProcessor.java:270-274); leaving a
            # stale last_sched <= now would re-notify the same past instant
            # and spin the playback sweep forever
            timer_fired = has_timer & (last_sched0 >= 0) & (now >= last_sched0)
            resched = (due | timer_fired | (n_flush > 0)
                       | ((state["end"] < 0) & any_first))
            new_sched = jnp.where(resched, now + jnp.int64(self.timeout),
                                  last_sched0)
            out[NOTIFY_KEY] = jnp.where(new_sched >= 0, new_sched, jnp.int64(-1))
        return {"cur": new_cur, "prev": new_prev, "count": new_count,
                "prev_count": new_prev_count, "end": new_end,
                "flushed": new_flushed, "last_sched": new_sched}, out

    def contents(self, state):
        valid = jnp.arange(self.capacity, dtype=jnp.int64) < state["count"]
        return dict(state["cur"]), valid


# ----------------------------------------------------------------- factory

def _external_ts_key(window, input_def) -> str:
    """externalTime clock column: must be a plain LONG attribute reference
    (anything else fails app creation, as in the reference processor)."""
    from siddhi_tpu.query_api.expressions import Variable

    p0 = window.parameters[0] if window.parameters else None
    if isinstance(p0, Variable):
        attr = input_def.attribute(p0.attribute_name)
        if attr.type != AttrType.LONG:
            raise CompileError(
                "externalTime timestamp attribute must be long (ms epoch)")
        return attr.name
    raise CompileError(
        f"{window.name} window's first parameter must be a long attribute "
        "reference (the external timestamp)")


def window_col_specs(input_def, extra: Tuple[str, ...] = ()) -> Dict[str, np.dtype]:
    """Column dtypes a window ring buffer must carry for a stream: every
    attribute + its null mask, the timestamp, and reserved id columns."""
    from siddhi_tpu.ops.types import dtype_of

    col_specs: Dict[str, np.dtype] = {}
    for a in input_def.attributes:
        col_specs[a.name] = dtype_of(a.type)
        col_specs[a.name + "?"] = np.bool_
    col_specs[TS_KEY] = np.int64
    col_specs["__gk__"] = np.int32
    for name in extra:
        col_specs[name] = np.int32
    return col_specs


def create_window_stage(window: Window, input_def, resolver, app_context,
                        expired_needed: bool = True) -> WindowStage:
    """Build a window stage from a ``#window.<name>(params)`` handler — the
    factory role of reference ``SingleInputStreamParser.generateProcessor``
    plus each window's ``init`` validation.

    ``expired_needed=False`` mirrors the reference's
    outputExpectsExpiredEvents=false: batch windows skip expired emission
    and their findable queue is never drained by empty flushes (join sides
    of `insert into` queries keep probing the last non-empty batch)."""
    name = window.name.lower()
    col_specs = window_col_specs(input_def)

    capacity = getattr(app_context, "window_capacity", 4096)

    if name == "length":
        _expect_arity(window, 1, 1)
        return LengthWindowStage(_int_const_param(window, 0, "length"), col_specs)
    if name == "lengthbatch":
        # lengthBatch(length[, streamCurrentEvents])
        _expect_arity(window, 1, 2)
        stream_current = False
        if len(window.parameters) == 2:
            stream_current = _bool_const_param(window, 1, "streamCurrentEvents")
        return LengthBatchWindowStage(_int_const_param(window, 0, "length"), col_specs,
                                      expired_needed=expired_needed,
                                      stream_current=stream_current)
    if name == "time":
        _expect_arity(window, 1, 1)
        return TimeWindowStage(_int_const_param(window, 0, "time"), col_specs, capacity)
    if name == "externaltime":
        # externalTime(tsAttr, time) — expiry driven by the named
        # long timestamp attribute
        _expect_arity(window, 2, 2)
        ts_key = _external_ts_key(window, input_def)
        return TimeWindowStage(_int_const_param(window, 1, "time"), col_specs, capacity,
                               external=True, ts_key=ts_key)
    if name == "timebatch":
        # overloads (TimeBatchWindowProcessor.init): (time),
        # (time, startTime int/long), (time, streamCurrentEvents bool),
        # (time, startTime, streamCurrentEvents)
        _expect_arity(window, 1, 3)
        start_time = -1
        stream_current = False
        if len(window.parameters) == 2:
            p1 = window.parameters[1]
            if isinstance(p1, Constant) and isinstance(p1.value, bool):
                stream_current = p1.value
            elif (isinstance(p1, TimeConstant)
                  or (isinstance(p1, Constant)
                      and p1.type in (AttrType.INT, AttrType.LONG))):
                start_time = int(p1.value)
            else:
                raise CompileError(
                    "timeBatch second parameter must be an int/long startTime "
                    "or a bool streamCurrentEvents constant")
        elif len(window.parameters) == 3:
            start_time = _int_const_param(window, 1, "startTime")
            stream_current = _bool_const_param(window, 2, "streamCurrentEvents")
        return TimeBatchWindowStage(_int_const_param(window, 0, "time"), col_specs,
                                    capacity, expired_needed=expired_needed,
                                    start_time=start_time,
                                    stream_current=stream_current)
    if name == "batch":
        # batch([chunkLength]) — BatchWindowProcessor.java:107-118
        _expect_arity(window, 0, 1)
        chunk_length = 0
        if window.parameters:
            chunk_length = _int_const_param(window, 0, "chunkLength")
        return BatchWindowStage(col_specs, capacity, expired_needed=expired_needed,
                                chunk_length=chunk_length)
    if name == "timelength":
        _expect_arity(window, 2, 2)
        return TimeLengthWindowStage(_int_const_param(window, 0, "time"),
                                     _int_const_param(window, 1, "length"), col_specs)
    if name == "delay":
        _expect_arity(window, 1, 1)
        return DelayWindowStage(_int_const_param(window, 0, "delay"), col_specs, capacity)
    if name == "externaltimebatch":
        # externalTimeBatch(tsAttr, time[, startTime[, timeout]])
        from siddhi_tpu.ops.expressions import compile_expr

        _expect_arity(window, 2, 4)
        ts_fn, _t = compile_expr(window.parameters[0], resolver)
        start_time = -1
        if len(window.parameters) >= 3:
            p = window.parameters[2]
            if not isinstance(p, (Constant, TimeConstant)):
                raise CompileError(
                    "externalTimeBatch startTime must be a constant")
            start_time = int(p.value)
        timeout = 0
        if len(window.parameters) >= 4:
            timeout = _int_const_param(window, 3, "timeout")
        return ExternalTimeBatchWindowStage(
            ts_fn, _int_const_param(window, 1, "time"), col_specs, capacity,
            expired_needed=expired_needed, start_time=start_time,
            timeout=timeout)
    if name == "hopping":
        _expect_arity(window, 2, 2)
        return HoppingWindowStage(
            _int_const_param(window, 0, "windowTime"),
            _int_const_param(window, 1, "hopTime"), col_specs, capacity)
    if name in ("sort", "frequent", "lossyfrequent", "session", "cron",
                "expression", "expressionbatch"):
        from siddhi_tpu.ops.host_windows import create_host_window_stage

        return create_host_window_stage(window, input_def, resolver, app_context)
    raise CompileError(f"window '{window.name}' is not implemented yet")
