"""Tumbling-window aggregation folded into keys-wide accumulators.

``from S#window.timeBatch(T) select <group keys>, count(), min(x), max(y)
group by <keys> insert into O`` reads nothing of the window's events but
the group key and aggregates that RESET at each flush. The buffered stage
(``ops/windows.py`` ``TimeBatchWindowStage``, after
``TimeBatchWindowProcessor.java:263-345``) keeps every event of the open
window in a ``window_capacity`` buffer and hands the selector ``2 x
capacity + 1`` padded rows a step to collapse to one row a group. This
stage keeps, per group, what that collapse would leave:

- one accumulator per aggregator call (``count``: int64; ``min`` / ``max``:
  the argument's own type beside the count of non-null values folded in),
- the group-by attributes and the timestamp of the group's LAST event in
  the open window (the row ``QuerySelector.processInBatchGroupBy`` keeps),
- that event's arrival position in the window (``-1``: no event yet).

A step folds its batch into them (32-bit scatters of ``batch`` updates
into ``[K]``, then keys-wide selects) and, when the window closes, emits
the groups seen in it in the order the buffered stage's chunk leaves them
(each group at the position of its last event) and resets. State and
output are as wide as the key capacity, whatever the events in a window:
``window_capacity`` is not consulted and no overflow can happen.

The flush rule is the buffered stage's own, line for line: the boundary is
set from the first step's clock (or ``startTime``), a step whose clock has
reached it folds its rows and THEN flushes (the arriving chunk joins the
flushing batch), and the next wake time goes to the scheduler as
``__notify__``. Under ``@app:playback`` the scheduler's TIMER step fires
before the chunk that crosses the boundary is delivered, so that chunk
opens the next window (playbackTest1).

The planner (``plan_tumbling_fold``) takes this stage only where the
query's shape allows it; everything else keeps the buffered stage.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.ops import aggregators as agg_ops
from siddhi_tpu.ops import types as T
from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY, CompileError
from siddhi_tpu.ops.windows import (NOTIFY_KEY, OVERFLOW_KEY,
                                    TimeBatchWindowStage, time_batch_boundary)
from siddhi_tpu.query_api.expressions import (AttributeFunction, Expression,
                                              Variable)

CURRENT = 0
GK_KEY = "__gk__"
# what a flush computes and emits, on the device: the benchmark's
# ``step_flush_ms`` reads this scope (it nests in ``siddhi.state``)
FLUSH_SCOPE = "siddhi.flush"

# aggregators whose value over a window is a fold that needs no order and
# no event kept: exact whatever the order the batch's rows are combined in
FOLDABLE = ("count", "min", "max")


class TumblingAggStage:
    """``#window.timeBatch(T[, startTime])`` straight into per-group
    accumulators. Slots into the query step where a window stage goes; its
    output already carries the aggregate columns, one row a group, so the
    selector runs in precomputed mode (projection and ``having`` only)."""

    batch_mode = True
    needs_scheduler = True
    counts_flushes = True     # the runtime counts window.<query>.flushes

    def __init__(self, time_ms: int, specs: List[agg_ops.AggSpec],
                 group_cols: Dict[str, np.dtype], num_keys_ref,
                 start_time: int = -1):
        self.time_ms = time_ms
        self.specs = specs
        self.group_cols = group_cols     # column key -> dtype, masks too
        self._num_keys_ref = num_keys_ref
        self.start_time = start_time

    @property
    def num_keys(self) -> int:
        return self._num_keys_ref()

    def _value_dtype(self, spec) -> np.dtype:
        return np.dtype(T.dtype_of(spec.arg_type))

    def init_state(self, num_keys: int = 1) -> dict:
        K = self.num_keys
        state = {
            "next_emit": jnp.int64(-1),
            "n_win": jnp.int64(0),                      # rows the open window took
            "last_seq": jnp.full((K,), -1, jnp.int64),  # of a group's last event
            "last_ts": jnp.zeros((K,), jnp.int64),
            "group": {c: jnp.zeros((K,), dt)
                      for c, dt in self.group_cols.items()},
        }
        for i, spec in enumerate(self.specs):
            if spec.kind == "count":
                state[f"a{i}"] = jnp.zeros((K,), jnp.int64)
            else:
                dt = self._value_dtype(spec)
                state[f"a{i}"] = jnp.full(
                    (K,), agg_ops._identity(spec.kind, dt), dt)
                state[f"n{i}"] = jnp.zeros((K,), jnp.int64)
        return state

    def _fold(self, state, cols, ctx, valid_cur, idx):
        """The batch's rows into the accumulators. ``idx`` is each row's
        group, ``K`` (dropped) for a row that takes no part."""
        K = self.num_keys

        def rows_of(at):
            return jnp.zeros((K + 1,), jnp.int32).at[at].add(
                1, mode="drop")[:K].astype(jnp.int64)

        n_all = rows_of(idx)        # every row of the batch, counted once
        new = {}
        for i, spec in enumerate(self.specs):
            at, n_b, v = idx, n_all, None
            if spec.arg_fn is not None:
                v, null_mask = spec.arg_fn(cols, ctx)
                v = jnp.broadcast_to(jnp.asarray(v), valid_cur.shape)
                if null_mask is not None:
                    # a null argument leaves the aggregate as it is; only
                    # a batch that holds one pays a count of its own
                    null = valid_cur & jnp.asarray(null_mask)
                    at = jnp.where(null, K, idx)
                    n_b = lax.cond(jnp.any(null), rows_of,
                                   lambda _at: n_all, at)
            if spec.kind == "count":
                new[f"a{i}"] = state[f"a{i}"] + n_b
                continue
            dt = self._value_dtype(spec)
            ident = jnp.asarray(agg_ops._identity(spec.kind, dt))
            into = jnp.full((K + 1,), ident, dt)
            v = v.astype(dt)
            folded = (into.at[at].min(v, mode="drop") if spec.kind == "min"
                      else into.at[at].max(v, mode="drop"))[:K]
            comb = jnp.minimum if spec.kind == "min" else jnp.maximum
            new[f"a{i}"] = comb(state[f"a{i}"], folded)
            new[f"n{i}"] = state[f"n{i}"] + n_b
        return new

    def _emit(self, acc, group, last_ts, last_seq):
        """One row a group seen in the window, each where its last event
        stood among the window's events."""
        with jax.named_scope(FLUSH_SCOPE):
            seen = last_seq >= 0
            order = jnp.argsort(
                jnp.where(seen, last_seq, jnp.int64(2**62)), stable=True)
            out = {c: v[order] for c, v in group.items()}
            out[TS_KEY] = last_ts[order]
            out[GK_KEY] = order.astype(jnp.int32)
            out[VALID_KEY] = seen[order]
            for i, spec in enumerate(self.specs):
                value = acc[f"a{i}"][order]
                out[spec.out_key] = value.astype(T.dtype_of(spec.out_type))
                if spec.kind != "count":
                    # null until a non-null datum folds in
                    out[spec.out_key + "?"] = acc[f"n{i}"][order] == 0
            return out

    def apply(self, state: dict, cols: Dict, ctx: Dict):
        K = self.num_keys
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        next_emit, send = time_batch_boundary(
            self.time_ms, self.start_time, state["next_emit"], now)

        gk = jnp.clip(cols[GK_KEY].astype(jnp.int32), 0, K - 1)
        idx = jnp.where(valid_cur, gk, K)
        acc = self._fold(state, cols, ctx, valid_cur, idx)
        # each group's last row of this batch, by one 32-bit scatter
        last = jnp.full((K + 1,), -1, jnp.int32).at[idx].max(
            jnp.arange(B, dtype=jnp.int32), mode="drop")[:K]
        touched = last >= 0
        row = jnp.maximum(last, 0)
        group = {c: jnp.where(touched, cols[c][row].astype(v.dtype), v)
                 for c, v in state["group"].items()}
        last_ts = jnp.where(touched, cols[TS_KEY][row], state["last_ts"])
        last_seq = jnp.where(touched, state["n_win"] + last.astype(jnp.int64),
                             state["last_seq"])
        n_win = state["n_win"] + B

        def emit():
            return self._emit(acc, group, last_ts, last_seq)

        def no_rows():      # the same columns, none valid
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(emit))

        out = lax.cond(send, emit, no_rows)
        out[TYPE_KEY] = jnp.full((K,), CURRENT, jnp.int8)
        out[NOTIFY_KEY] = next_emit
        out[OVERFLOW_KEY] = jnp.int32(0)

        # RESET: a flush leaves every group as init_state has it
        init = self.init_state()
        new_state = {k: jnp.where(send, init[k], v) for k, v in acc.items()}
        new_state.update(
            next_emit=next_emit,
            n_win=jnp.where(send, jnp.int64(0), n_win),
            last_seq=jnp.where(send, init["last_seq"], last_seq),
            last_ts=last_ts, group=group)
        return new_state, out

    def contents(self, state):  # pragma: no cover
        raise CompileError(
            "a folded tumbling window cannot be probed as a join side")


def _reads_only(expr, allowed) -> bool:
    """Does ``expr`` read nothing of an event but the attributes in
    ``allowed``? An aggregator call is a leaf (its argument is folded row
    by row), and so is the synthetic variable the planner put in its place."""
    if isinstance(expr, Variable):
        return (expr.attribute_name in allowed
                or expr.attribute_name.startswith("__agg"))
    if isinstance(expr, AttributeFunction):
        if (not expr.namespace
                and expr.name.lower() in agg_ops.supported_aggregators()):
            return True
        return all(_reads_only(p, allowed) for p in expr.parameters)
    if not isinstance(expr, Expression):
        return True
    return all(_reads_only(child, allowed) for child in vars(expr).values()
               if isinstance(child, Expression))


def plan_tumbling_fold(window_stage, selector, selector_plan,
                       resolver) -> Optional[TumblingAggStage]:
    """The folded stage where the (window, selector) pair qualifies, else
    None (the buffered stage stays). Decided from the query's shape alone:

    - a plain ``timeBatch`` (no ``streamCurrentEvents``),
    - CURRENT events out only, the selector collapsing batch chunks,
    - every aggregator one of ``FOLDABLE``,
    - an explicit selection whose expressions, like ``having``, read
      nothing of an event but group-by attributes and aggregates,
    - no ``order by`` / ``limit`` / ``offset``.

    A query that needs the window's rows (``select *``, a non-key
    attribute, expired output, another aggregator) is not one of these.
    """
    if type(window_stage) is not TimeBatchWindowStage \
            or window_stage.stream_current:
        return None
    sel = selector_plan
    if (not sel.batch_mode or not sel.current_on or sel.expired_on
            or sel.order_by or sel.limit is not None
            or sel.offset is not None or sel.uuid_cols or sel.set_cols):
        return None
    if not sel.specs or any(s.kind not in FOLDABLE for s in sel.specs):
        return None
    if selector is None or selector.select_all or not selector.selection_list:
        return None
    if not all(type(v) is Variable for v in selector.group_by_list):
        return None
    by = {v.attribute_name for v in selector.group_by_list}
    if not all(_reads_only(oa.expression, by)
               for oa in selector.selection_list):
        return None
    outputs = {name for name, _t in sel.output_attrs}
    if selector.having is not None \
            and not _reads_only(selector.having, by | outputs):
        return None
    group_cols = {}
    for var in selector.group_by_list:
        key = resolver.resolve(var).key
        for c in (key, key + "?"):
            if c in window_stage.col_specs:
                group_cols[c] = np.dtype(window_stage.col_specs[c])
    stage = TumblingAggStage(
        window_stage.time_ms, sel.specs, group_cols,
        num_keys_ref=lambda: sel.num_keys,
        start_time=window_stage.start_time)
    sel.precomputed = True
    return stage
