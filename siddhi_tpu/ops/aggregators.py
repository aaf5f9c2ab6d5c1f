"""Attribute aggregators as segmented prefix scans over dense keyed state.

Replaces the reference's per-(group,aggregator) State objects updated one
event at a time (``query/selector/attribute/aggregator/*.java``, 13 files;
state addressing via thread-local flows, ``PartitionStateHolder.java:43-48``)
with:

- per-aggregator state tuples of ``[K]`` arrays (K = padded key capacity);
- one **segmented associative scan** per batch that reproduces the exact
  sequential semantics: CURRENT -> processAdd, EXPIRED -> processRemove,
  RESET -> all-group reset (``AttributeAggregatorExecutor.processReset``
  calls ``cleanGroupByStates()``), with the per-event running value emitted
  for every event, as ``QuerySelector.processGroupBy`` does.

The scan sorts the batch by (group, position), pre-folds persistent state
into each group's first row, marks segment starts / in-batch RESET epochs as
"blocked" rows, runs ``lax.associative_scan`` with the aggregator's combine
op, and has each group read its last row's value back into the state.

Invertible aggregators (sum/count/avg/stdDev/and/or) encode EXPIRED as
negative deltas. min/max over windows that emit EXPIRED events need the
ring-recompute path (``ops/windows.py``); without expired input they are
plain monoid scans here.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.ops import types as T
from siddhi_tpu.ops.expressions import TS_KEY, TYPE_KEY, VALID_KEY, CompileError
from siddhi_tpu.query_api.definitions import AttrType

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3


@dataclass
class AggSpec:
    """One aggregator call site in the selection list."""

    kind: str                      # 'sum' | 'count' | 'avg' | ...
    arg_fn: Optional[Callable]     # compiled arg expr fn(cols, ctx) -> (v, mask); None for count()
    arg_type: Optional[AttrType]
    out_key: str                   # synthetic output column name (__agg<i>__)
    out_type: AttrType = AttrType.DOUBLE
    distinct_capacity: int = 64    # distinctCount/unionSet: per-group value slots
    arg_key: Optional[str] = None  # unionSet: raw column key of a bare-Variable
    #                                arg (to find '#set' companions on re-union)
    elem_type: Optional[AttrType] = None  # unionSet: set element type (decode)
    arg_is_multi: bool = False     # unionSet: arg is a MULTI-element set attr
    #                                (companions REQUIRED; base col is a count)

    # filled by the planner:
    @property
    def slots(self) -> int:
        return _AGG_DEFS[self.kind].slots


@dataclass
class _AggDef:
    slots: int
    combine: str  # 'add' | 'min' | 'max'


_AGG_DEFS = {
    "sum": _AggDef(2, "add"),      # (sum, non-null count): empty -> null
    "count": _AggDef(1, "add"),
    "avg": _AggDef(2, "add"),        # (sum, count)
    "stddev": _AggDef(3, "add"),     # (sum, sumsq, count)
    "and": _AggDef(1, "add"),        # false-count
    "or": _AggDef(1, "add"),         # true-count
    # (extreme, non-null count): the presence slot distinguishes "nothing
    # folded" (null) from a datum equal to the fold identity
    "min": _AggDef(2, "min"),
    "max": _AggDef(2, "max"),
    "minforever": _AggDef(2, "min"),
    "maxforever": _AggDef(2, "max"),
    # multiset state, handled by its own scan path (_apply_distinct)
    "distinctcount": _AggDef(1, "add"),
    # union of sets over the window: the same multiset value-table as
    # distinctCount, additionally emitting the live-element snapshot as
    # bounded [B, H] '#set'/'#setm' companions
    # (UnionSetAttributeAggregatorExecutor.java processAdd/processRemove)
    "unionset": _AggDef(1, "add"),
}


def agg_result_type(kind: str, arg_type: Optional[AttrType]) -> AttrType:
    """Return types per the reference aggregators (e.g. sum: LONG for
    int/long input, DOUBLE for float/double — ``SumAttributeAggregatorExecutor``;
    avg/stdDev always DOUBLE; min/max preserve the input type)."""
    if kind == "count":
        return AttrType.LONG
    if kind in ("avg", "stddev"):
        return AttrType.DOUBLE
    if kind == "sum":
        if arg_type in (AttrType.INT, AttrType.LONG):
            return AttrType.LONG
        return AttrType.DOUBLE
    if kind in ("and", "or"):
        return AttrType.BOOL
    if kind in ("min", "max", "minforever", "maxforever"):
        return arg_type
    if kind == "distinctcount":
        return AttrType.LONG
    if kind == "unionset":
        return AttrType.OBJECT
    raise KeyError(kind)


def supported_aggregators() -> Tuple[str, ...]:
    return tuple(_AGG_DEFS)


def _identity(kind: str, dtype) -> np.ndarray:
    d = _AGG_DEFS[kind]
    if d.combine == "add":
        return np.zeros((), dtype)
    if d.combine == "min":
        return np.asarray(np.inf if np.issubdtype(dtype, np.floating) else np.iinfo(dtype).max, dtype)
    return np.asarray(-np.inf if np.issubdtype(dtype, np.floating) else np.iinfo(dtype).min, dtype)


def _slot_dtype(spec: AggSpec):
    """Accumulation dtype: Java accumulates sums in long/double."""
    d = _AGG_DEFS[spec.kind]
    if d.combine == "add":
        if spec.kind in ("count", "and", "or"):
            return np.int64
        if spec.kind == "sum" and spec.arg_type in (AttrType.INT, AttrType.LONG):
            return np.int64
        return np.float64
    return T.dtype_of(spec.arg_type)


def init_agg_state(specs: List[AggSpec], num_keys: int) -> dict:
    """State pytree: per spec a [slots, K] array (plus a seen-flag per key)."""
    state = {}
    for i, spec in enumerate(specs):
        if spec.kind in ("distinctcount", "unionset"):
            H = spec.distinct_capacity
            state[f"a{i}"] = {
                "vk": jnp.zeros((num_keys, H), jnp.int64),     # value keys
                "vc": jnp.full((num_keys, H), -1, jnp.int32),  # counts; -1 = empty
                "stamp": jnp.zeros((num_keys,), jnp.int64),    # lazy-clear epoch
                "eb": jnp.int64(0),                            # global epoch base
            }
            continue
        dtype = _slot_dtype(spec)
        init = _slot_identities(spec.kind, dtype)
        state[f"a{i}"] = jnp.broadcast_to(
            jnp.asarray(init)[:, None], (spec.slots, num_keys)).astype(dtype)
    return state


def _deltas(spec: AggSpec, cols, ctx, xp):
    """Per-event delta tuple [slots, B] + identity substitution for
    non-participating rows (invalid / TIMER / RESET / null arg)."""
    types = cols[TYPE_KEY]
    valid = cols[VALID_KEY]
    is_cur = valid & (types == CURRENT)
    is_exp = valid & (types == EXPIRED)
    dtype = _slot_dtype(spec)
    ident = jnp.asarray(_identity(spec.kind, dtype))

    if spec.arg_fn is not None:
        v, null_mask = spec.arg_fn(cols, ctx)
        v = xp.asarray(v).astype(dtype)
        if null_mask is not None:
            # null arguments leave the state untouched (reference aggregators
            # guard `if (data == null) return currentValue()`)
            is_cur = is_cur & ~null_mask
            is_exp = is_exp & ~null_mask
    else:
        v = None

    k = spec.kind
    if k == "sum":
        d = xp.where(is_cur, v, xp.where(is_exp, -v, ident))
        sgn = xp.where(is_cur, 1, xp.where(is_exp, -1, 0)).astype(dtype)
        return xp.stack([d, sgn])
    if k == "count":
        d = xp.where(is_cur, 1, xp.where(is_exp, -1, 0)).astype(dtype)
        return d[None, :]
    if k == "avg":
        sgn = xp.where(is_cur, 1.0, xp.where(is_exp, -1.0, 0.0))
        return xp.stack([sgn * v, sgn])
    if k == "stddev":
        sgn = xp.where(is_cur, 1.0, xp.where(is_exp, -1.0, 0.0))
        return xp.stack([sgn * v, sgn * v * v, sgn])
    if k == "and":
        # false-count (reference AndAttributeAggregatorExecutor)
        is_false = ~v.astype(bool)
        d = (xp.where(is_cur & is_false, 1, 0) - xp.where(is_exp & is_false, 1, 0)).astype(dtype)
        return d[None, :]
    if k == "or":
        is_true = v.astype(bool)
        d = (xp.where(is_cur & is_true, 1, 0) - xp.where(is_exp & is_true, 1, 0)).astype(dtype)
        return d[None, :]
    if k in ("min", "max"):
        d = xp.where(is_cur, v, ident)
        pres = xp.where(is_cur, 1, 0).astype(dtype)
        return xp.stack([d, pres])
    if k in ("minforever", "maxforever"):
        # forever variants also fold EXPIRED events in (processRemove updates
        # the same way — reference MaxForeverAttributeAggregatorExecutor)
        d = xp.where(is_cur | is_exp, v, ident)
        pres = xp.where(is_cur | is_exp, 1, 0).astype(dtype)
        return xp.stack([d, pres])
    raise KeyError(k)


def _slot_identities(kind: str, dtype) -> np.ndarray:
    """[slots] per-slot fold identities (extreme slots pair with an
    add-combined presence counter at identity 0)."""
    d = _AGG_DEFS[kind]
    prim = _identity(kind, dtype)
    if d.combine in ("min", "max") and d.slots == 2:
        return np.stack([prim, np.zeros((), dtype)])
    return np.broadcast_to(prim, (d.slots,)).copy()


def _combine(kind: str):
    """Combine fn over slot-LAST arrays [..., slots] (add/1-slot combines
    are axis-agnostic; min/max pair the extreme slot with an added
    presence slot)."""
    d = _AGG_DEFS[kind]
    if d.combine == "add":
        return lambda a, b: a + b
    prim = jnp.minimum if d.combine == "min" else jnp.maximum
    if d.slots == 1:
        return lambda a, b: prim(a, b)

    def comb(a, b):
        return jnp.concatenate([prim(a, b)[..., :1], (a + b)[..., 1:]],
                               axis=-1)

    return comb


def _output(spec: AggSpec, slots, ctx):
    """Running value -> (value, null_mask) per the reference return rules."""
    xp = ctx["xp"]
    k = spec.kind
    if k == "sum":
        # SumAttributeAggregatorExecutor: null until a non-null folds in
        return slots[0], slots[1] == 0
    if k == "count":
        return slots[0], None
    if k == "avg":
        s, c = slots[0], slots[1]
        empty = c == 0
        v = s / xp.where(empty, 1.0, c)
        return v, empty  # avg over empty -> null (AvgAttributeAggregatorStateDouble)
    if k == "stddev":
        s, sq, c = slots
        empty = c == 0
        n = xp.where(empty, 1.0, c)
        mean = s / n
        var = xp.maximum(sq / n - mean * mean, 0.0)
        return xp.sqrt(var), empty
    if k == "and":
        return slots[0] == 0, None
    if k == "or":
        return slots[0] > 0, None
    # min/max family: null until a non-null datum folds in (the presence
    # slot counts folded rows — a datum equal to the fold identity still
    # reports correctly)
    return slots[0], slots[1] == 0




def _encode_distinct_value(spec: AggSpec, cols, ctx):
    """Value column -> int64 identity keys (floats by bit pattern; strings
    are already dictionary ids), plus the null mask. Shares ONE encoding
    with createSet/unionSet set elements (ops/expressions.py) so
    distinctCount and set features always agree on value identity."""
    from siddhi_tpu.ops.expressions import _encode_set_element

    v, m = spec.arg_fn(cols, ctx)
    return _encode_set_element(ctx["xp"], v, spec.arg_type), m


def _apply_distinct(spec: AggSpec, st: dict, cols: dict, ctx: dict,
                    num_keys: int, gk, participates, epoch_before,
                    final_epoch):
    """distinctCount / unionSet: exact per-event running multiset of live
    values per group (DistinctCountAttributeAggregatorExecutor /
    UnionSetAttributeAggregatorExecutor semantics: +1 on a value's
    CURRENT, -1 on its EXPIRED; a value is live while its count > 0).

    State is a per-group open table of (value, count) pairs with lazy
    RESET clearing via epoch stamps; the batch is processed by one
    sequential ``lax.scan`` in arrival order — exact, not the fast path
    (opt in by using the aggregator). unionSet additionally emits the
    per-row live-element snapshot as bounded ``[B, H]`` '#set'/'#setm'
    companion columns, and folds multi-element input sets (an upstream
    unionSet's companions) element-wise — the processAdd loop over the
    incoming java.util.Set."""
    types = cols[TYPE_KEY]
    B = gk.shape[0]
    H = spec.distinct_capacity
    K = num_keys
    emit_set = spec.kind == "unionset"

    v, null_m = _encode_distinct_value(spec, cols, ctx)
    set_in = set_in_m = None
    if emit_set and spec.arg_key is not None:
        set_in = cols.get(spec.arg_key + "#set")
        if set_in is not None:
            set_in_m = cols[spec.arg_key + "#setm"]
        elif spec.arg_is_multi:
            # the base column of a multi set is its live COUNT — folding
            # counts as element codes would be silent garbage
            raise CompileError(
                f"unionSet over multi-element set attribute "
                f"'{spec.arg_key}' requires its element snapshot, but the "
                f"'#set' companions were dropped (a window between the "
                f"producing unionSet and this one buffers only the base "
                f"column); apply unionSet before the window instead")
    part = participates
    if null_m is not None and set_in is None:
        part = part & ~jnp.asarray(null_m)
    delta = jnp.where(types == CURRENT, jnp.int32(1), jnp.int32(-1))
    g = jnp.clip(gk.astype(jnp.int32), 0, K - 1)
    ep = st["eb"] + epoch_before.astype(jnp.int64)

    def insert_one(vk_row, vc_row, vi, di, apply_i):
        # a slot whose count returned to 0 is dead: reclaimable, no longer
        # matching — the table tracks LIVE values, not all-time cardinality
        occupied = vc_row > 0
        match = occupied & (vk_row == vi)
        has = jnp.any(match)
        empty = ~occupied
        slot = jnp.where(has, jnp.argmax(match), jnp.argmax(empty))
        ok = has | jnp.any(empty)
        cnt = jnp.where(has, vc_row[slot], jnp.int32(0))
        newc = jnp.maximum(cnt + di, 0)
        applied = apply_i & ok
        vk2 = jnp.where(applied, vk_row.at[slot].set(vi), vk_row)
        vc2 = jnp.where(applied, vc_row.at[slot].set(newc), vc_row)
        return vk2, vc2, applied, apply_i & ~ok

    def body(carry, x):
        vk, vc, stamp, of = carry
        if set_in is not None:
            gi, vis, mis, di, pi, ei = x          # vis/mis: [Cin]
        else:
            gi, vi, di, pi, ei = x
        vk_row = lax.dynamic_index_in_dim(vk, gi, 0, keepdims=False)   # [H]
        vc_orig = lax.dynamic_index_in_dim(vc, gi, 0, keepdims=False)
        fresh = stamp[gi] != ei
        vc_row = jnp.where(fresh, jnp.int32(-1), vc_orig)
        if set_in is None:
            vk_w2, vc_w2, any_applied, ofl = insert_one(
                vk_row, vc_row, vi, di, pi)
        else:
            Cin = set_in.shape[1]

            def fold(c, acc):
                vkr, vcr, anya, ofa = acc
                vk2, vc2, ap, ofl_c = insert_one(vkr, vcr, vis[c], di,
                                                 pi & mis[c])
                return vk2, vc2, anya | ap, ofa | ofl_c

            vk_w2, vc_w2, any_applied, ofl = lax.fori_loop(
                0, Cin, fold,
                (vk_row, vc_row, jnp.bool_(False), jnp.bool_(False)))
        vk_w = jnp.where(any_applied, vk_w2, vk_row)
        vc_w = jnp.where(any_applied, vc_w2, vc_orig)
        vk = lax.dynamic_update_index_in_dim(vk, vk_w, gi, 0)
        vc = lax.dynamic_update_index_in_dim(vc, vc_w, gi, 0)
        stamp = stamp.at[gi].set(jnp.where(any_applied, ei, stamp[gi]))
        live = jnp.where(any_applied, vc_w2, vc_row) > 0
        nd = jnp.sum(live).astype(jnp.int64)
        of = of | ofl
        if emit_set:
            snap_vk = jnp.where(any_applied, vk_w2, vk_row)
            return (vk, vc, stamp, of), (nd, snap_vk, live)
        return (vk, vc, stamp, of), nd

    xs = ((g, set_in, set_in_m, delta, part, ep) if set_in is not None
          else (g, v, delta, part, ep))
    (vk, vc, stamp, of), ys = lax.scan(
        body, (st["vk"], st["vc"], st["stamp"], jnp.bool_(False)), xs)
    new_st = {"vk": vk, "vc": vc, "stamp": stamp,
              "eb": st["eb"] + final_epoch.astype(jnp.int64)}
    cols = dict(cols)
    if emit_set:
        nd, snap_vk, snap_live = ys
        cols[spec.out_key + "#set"] = snap_vk          # [B, H]
        cols[spec.out_key + "#setm"] = snap_live       # [B, H]
    else:
        nd = ys
    cols[spec.out_key] = nd
    prev = cols.get("__agg_overflow__")
    ov = of.astype(jnp.int32)
    cols["__agg_overflow__"] = ov if prev is None else jnp.maximum(prev, ov)
    return new_st, cols


# Key capacity per batch row above which the write-back scatters the batch
# instead of reading K-wide. TPU v5e, two [2, K] 64-bit aggregates (PR 26,
# PERF.md): the scatter costs 71 ns a row and aggregate, the read 5 ns a
# row once plus 22 ns a key and aggregate above 32,768 keys. Scatter / read
# ms at B = 4,096: 0.72 / 0.42 (K = 32,768), 1.11 / 1.44 (65,536), 3.35 /
# 5.87 (131,072); at K = 131,072 they meet at B = 16,384 (5.36 / 5.95) and
# the read wins beyond (B = 131,072: 29.4 / 15.6).
_SCATTER_ABOVE_KEYS_PER_ROW = 8


def apply_aggregators(specs: List[AggSpec], state: dict, cols: dict, ctx: dict,
                      num_keys: int) -> Tuple[dict, dict]:
    """Run all aggregator scans for one batch.

    Requires cols['__gk__'] (int32 group ids; all-zero when no group-by).
    Adds per-spec output columns spec.out_key (+ '?' null masks) with the
    post-event running value for every row. Returns (new_state, cols).

    The new [slots, K] state is formed K-wide: every key reads the scanned
    value at its group's last sorted row (found by one 32-bit scatter of
    row positions shared by all aggregates) or keeps its old value. Only
    where the key capacity dwarfs the batch (the static shapes, see
    ``_SCATTER_ABOVE_KEYS_PER_ROW``) are the batch's values scattered.
    """
    xp = ctx["xp"]
    gk = cols["__gk__"]
    valid = cols[VALID_KEY]
    types = cols[TYPE_KEY]
    B = gk.shape[0]

    participates = valid & ((types == CURRENT) | (types == EXPIRED))
    is_reset = valid & (types == RESET)
    any_reset = jnp.any(is_reset)

    # sort rows by group; pad/invalid rows go last (gk = num_keys)
    sort_gk = jnp.where(participates | is_reset, gk, num_keys).astype(jnp.int32)
    # RESET rows apply to ALL groups: they act through the epoch counter, so
    # exclude them from any single group's run (sort them to the end too).
    sort_gk = jnp.where(is_reset, num_keys, sort_gk)
    order = jnp.argsort(sort_gk, stable=True)
    inv_order = jnp.argsort(order, stable=True)

    gk_sorted = sort_gk[order]
    pos_sorted = order  # original positions, ascending within each group
    epoch = jnp.cumsum(is_reset.astype(jnp.int32))  # epoch AFTER position i resets
    # epoch id of each row = number of resets strictly before it
    epoch_before = epoch - is_reset.astype(jnp.int32)
    epoch_sorted = epoch_before[order]
    final_epoch = epoch[B - 1]

    prev_same_group = jnp.concatenate([jnp.zeros(1, bool), gk_sorted[1:] == gk_sorted[:-1]])
    prev_same_epoch = jnp.concatenate([jnp.zeros(1, bool), epoch_sorted[1:] == epoch_sorted[:-1]])
    blocked = ~(prev_same_group & prev_same_epoch)  # segment starts
    # state folds in only at a group's first row in epoch 0
    fold_state = blocked & (epoch_sorted == 0) & (gk_sorted < num_keys)

    last_of_group = jnp.concatenate([gk_sorted[1:] != gk_sorted[:-1], jnp.ones(1, bool)])
    in_final_epoch = epoch_sorted == final_epoch
    # Write-back. ``landing[i]`` is the key whose new state is sorted row
    # i's scanned value (a group's last row, in the final epoch), else the
    # drop index ``num_keys``: at most one row lands per key, so a key can
    # READ its row. Scattering the B scanned values themselves is, for
    # 64-bit state (emulated as two 32-bit planes), a two-operand scatter,
    # which the chip's compiler does not sort first.
    landing = jnp.where(last_of_group & in_final_epoch & (gk_sorted < num_keys),
                        gk_sorted, num_keys)
    read_landing_rows = num_keys <= _SCATTER_ABOVE_KEYS_PER_ROW * B
    if read_landing_rows:
        last = jnp.full(num_keys + 1, -1, jnp.int32).at[landing].set(
            jnp.arange(B, dtype=jnp.int32))[:num_keys]
        touched = last >= 0
        last = jnp.maximum(last, 0)

    new_state = dict(state)
    cols = dict(cols)
    for i, spec in enumerate(specs):
        key = f"a{i}"
        if spec.kind in ("distinctcount", "unionset"):
            new_state[key], cols = _apply_distinct(
                spec, state[key], cols, ctx, num_keys, gk, participates,
                epoch_before, final_epoch)
            continue
        st = state[key]  # [slots, K]
        deltas = _deltas(spec, cols, ctx, xp)  # [slots, B]
        deltas_sorted = deltas[:, order]
        comb = _combine(spec.kind)   # slot-LAST combine
        safe_gk = jnp.minimum(gk_sorted, num_keys - 1)
        folded = comb(st[:, safe_gk].T, deltas_sorted.T).T
        vals = jnp.where(fold_state[None, :], folded, deltas_sorted)

        def scan_op(a, b):
            ab, av = a
            bb, bv = b
            return (ab | bb, jnp.where(bb[:, None], bv, comb(av, bv)))

        # scan along the batch axis: flags [B], values [B, slots]
        _, scanned_bs = lax.associative_scan(scan_op, (blocked, vals.T), axis=0)
        scanned = scanned_bs.T  # [slots, B]

        # per-row running values back in original row order
        out = scanned[:, inv_order]

        # new persistent state: all-init on any RESET, then last-row-per-group
        # values for groups active in the final epoch
        dtype = st.dtype
        idents = jnp.asarray(_slot_identities(spec.kind, np.dtype(dtype)))
        base = jnp.where(any_reset,
                         jnp.broadcast_to(idents[:, None], st.shape).astype(dtype),
                         st)
        if read_landing_rows:
            new_state[key] = jnp.where(touched[None, :], scanned[:, last], base)
        else:
            new_state[key] = base.at[:, landing].set(scanned, mode="drop")

        value, null_mask = _output(spec, [out[s] for s in range(spec.slots)], ctx)
        value = value.astype(T.dtype_of(spec.out_type))
        cols[spec.out_key] = value
        if null_mask is not None:
            cols[spec.out_key + "?"] = null_mask
    return new_state, cols
