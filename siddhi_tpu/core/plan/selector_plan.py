"""Selector planning: select/group by/having/order by/limit -> device stage.

The compile-time analog of reference ``SelectorParser.java`` +
``QuerySelector.java``: aggregator call sites in the selection are split out
(reference ``ExpressionParser`` detects aggregators via extension holders),
computed by segmented scans (``ops/aggregators.py``), and the remaining
scalar expressions become fused projections.

Semantics reproduced (``QuerySelector.processGroupBy``/``processInBatch*``):
- every CURRENT/EXPIRED row updates aggregators and yields an output row;
- RESET rows reset all group states and yield nothing;
- TIMER rows are dropped;
- currentOn/expiredOn filtering, then `having`;
- batch chunks (from batch windows) keep only the last row per group
  (``processInBatchGroupBy``) or overall (``processInBatchNoGroupBy``);
- order by / offset / limit apply per output chunk.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from siddhi_tpu.core.plan.resolvers import OutputColsResolver
from siddhi_tpu.ops import aggregators as agg_ops
from siddhi_tpu.ops.expressions import (
    OKEY_KEY,
    PK_KEY,
    RIDX_KEY,
    TS_KEY,
    TYPE_KEY,
    VALID_KEY,
    CompileError,
    Resolver,
    compile_condition,
    compile_expr,
)
from siddhi_tpu.query_api.definitions import AttrType
from siddhi_tpu.query_api.execution import Selector
from siddhi_tpu.query_api.expressions import (
    Add,
    And,
    AttributeFunction,
    Compare,
    Divide,
    Expression,
    IsNull,
    Mod,
    Multiply,
    Not,
    Or,
    Subtract,
    Variable,
)

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3
GK_KEY = "__gk__"
FLUSH_KEY = "__flush__"
STR_RANK = "__strrank__"   # [dict_capacity] lexicographic rank per string id


def _rewrite_aggregators(expr: Expression, specs: List[agg_ops.AggSpec], resolver: Resolver) -> Expression:
    """Replace aggregator calls with synthetic Variables bound to scan
    output columns (the split the reference does in ExpressionParser when it
    routes AttributeFunctions to AttributeAggregatorExecutors)."""
    if isinstance(expr, AttributeFunction) and not expr.namespace \
            and expr.name.lower() in agg_ops.supported_aggregators():
        kind = expr.name.lower()
        # arity/type validation mirroring the reference executors'
        # @ParameterOverload contracts (e.g. SumAttributeAggregatorExecutor
        # accepts exactly one numeric attribute; extra or string arguments
        # fail app creation)
        if kind == "count":
            if len(expr.parameters) > 1:
                raise CompileError("count() accepts at most one argument")
        elif len(expr.parameters) != 1:
            raise CompileError(f"{kind}() expects exactly one argument, "
                               f"found {len(expr.parameters)}")
        if expr.parameters:
            arg_f, arg_t = compile_expr(expr.parameters[0], resolver)
        else:
            arg_f, arg_t = None, None
        if kind != "count" and arg_f is None:
            raise CompileError(f"{kind}() requires an argument")
        if kind in ("sum", "avg", "stddev", "min", "max",
                    "minforever", "maxforever") and arg_t not in (
                AttrType.INT, AttrType.LONG, AttrType.FLOAT, AttrType.DOUBLE):
            raise CompileError(
                f"{kind}() expects a numeric attribute but found "
                f"{arg_t.value if arg_t else None}")
        if kind in ("and", "or") and arg_t != AttrType.BOOL:
            raise CompileError(
                f"{kind}() expects a bool attribute but found "
                f"{arg_t.value if arg_t else None}")
        out_key = f"__agg{len(specs)}__"
        out_type = agg_ops.agg_result_type(kind, arg_t)
        spec = agg_ops.AggSpec(kind=kind, arg_fn=arg_f, arg_type=arg_t,
                               out_key=out_key, out_type=out_type)
        if kind == "unionset":
            from siddhi_tpu.ops.expressions import take_object_elem_marker

            if arg_t != AttrType.OBJECT:
                raise CompileError(
                    "Parameter passed to unionSet aggregator should be of "
                    f"type object but found: {arg_t.value if arg_t else None}")
            # element type for decode: a nested createSet() marks it; a
            # bare set attribute carries it on its stream definition (and
            # its column key locates '#set' companions for re-union)
            spec.elem_type = take_object_elem_marker()
            param = expr.parameters[0]
            if isinstance(param, Variable):
                spec.arg_key = resolver.resolve(param).key
                spec.arg_is_multi = _is_multi(resolver, param)
                if spec.elem_type is None:
                    spec.elem_type = _elem_type_of(resolver, param)
        specs.append(spec)
        return Variable(attribute_name=out_key)
    for attr_name in ("left", "right", "expression"):
        child = getattr(expr, attr_name, None)
        if isinstance(child, Expression):
            setattr(expr, attr_name, _rewrite_aggregators(child, specs, resolver))
    if isinstance(expr, AttributeFunction):
        expr.parameters = [_rewrite_aggregators(p, specs, resolver) for p in expr.parameters]
    return expr


def _elem_type_of(resolver, var: Variable):
    """Set-element type of an object attribute, recorded on its stream
    definition by the app assembler (best effort; None = decode raw)."""
    defn = getattr(resolver, "definition", None)
    meta = getattr(defn, "object_elem_types", None) if defn is not None else None
    if meta:
        return meta.get(var.attribute_name)
    return None


def _is_multi(resolver, var: Variable) -> bool:
    """Whether an object attribute is a MULTI-element set (unionSet
    output), per its stream definition's assembler metadata."""
    defn = getattr(resolver, "definition", None)
    multi = getattr(defn, "object_multi_attrs", None) if defn is not None else None
    return bool(multi) and var.attribute_name in multi


@dataclass
class SelectorPlan:
    """Compiled selector; `apply` is traced inside the query step."""

    @property
    def needs_str_rank(self) -> bool:
        """True when an order-by key is a string column — the runtime must
        inject the dictionary's lexicographic rank table as cols[STR_RANK]."""
        return any(is_str for _c, _d, is_str in self.order_by)

    specs: List[agg_ops.AggSpec]
    projections: List[Tuple[str, Callable, AttrType]]  # (out name, fn, type)
    output_attrs: List[Tuple[str, AttrType]]
    having_fn: Optional[Callable]
    group_by: bool
    group_key_exprs: List
    current_on: bool
    expired_on: bool
    batch_mode: bool          # upstream emits batch chunks (batch windows)
    order_by: List[Tuple[str, bool, bool]]  # (out col, descending, is_str)
    limit: Optional[int]
    offset: Optional[int]
    num_keys: int = 16
    # a fused upstream stage (ops/fused_agg.py, ops/tumbling_agg.py)
    # already computed the aggregate columns (and, of a batch chunk, left
    # one row a group); skip the scans and the collapse, project/filter
    precomputed: bool = False
    # output columns whose value is a host-generated UUID per row (the
    # device step emits placeholders; QueryRuntime._emit fills them)
    uuid_cols: List[str] = field(default_factory=list)
    # OBJECT set outputs: (out name, source column key) pairs whose
    # '#set'/'#setm' companions must ride along, and out name -> element
    # AttrType for event decode (None = raw int codes)
    set_cols: List[Tuple[str, str]] = field(default_factory=list)
    object_meta: Dict[str, Optional[AttrType]] = field(default_factory=dict)
    # outputs that are MULTI-element sets (unionSet results): their base
    # column is the live COUNT; singletons' base column is the element code
    object_multi: List[str] = field(default_factory=list)
    # output positions whose projection contains an aggregator call —
    # drives snapshot-limiter variant selection
    # (WrappedSnapshotOutputRateLimiter.java:67-74)
    agg_positions: List[int] = field(default_factory=list)

    @property
    def contains_aggregator(self) -> bool:
        return bool(self.specs)

    def init_state(self) -> dict:
        if self.precomputed:
            return {}
        return agg_ops.init_agg_state(self.specs, self.num_keys)

    def apply(self, state: dict, cols: dict, ctx: dict):
        xp = ctx["xp"]
        if self.specs and not self.precomputed:
            state, cols = agg_ops.apply_aggregators(self.specs, state, cols, ctx, self.num_keys)

        out: Dict[str, object] = {
            TS_KEY: cols[TS_KEY],
            TYPE_KEY: cols[TYPE_KEY],
            VALID_KEY: cols[VALID_KEY],
            GK_KEY: cols.get(GK_KEY, jnp.zeros_like(cols[TS_KEY], dtype=jnp.int32)),
        }
        if FLUSH_KEY in cols:
            out[FLUSH_KEY] = cols[FLUSH_KEY]
        if "__agg_overflow__" in cols:
            # distinctCount value-table saturation rides the meta channel
            out["__overflow__"] = cols["__agg_overflow__"]
        if PK_KEY in cols:
            out[PK_KEY] = cols[PK_KEY]  # partition id rides along to the edge
        if OKEY_KEY in cols:
            # device-routed sharding: the window's emission-order key rides
            # to the route wrapper's cross-shard merge
            out[OKEY_KEY] = cols[OKEY_KEY]
        elif RIDX_KEY in cols:
            # no window stage: rows are input-aligned, so the original
            # batch position IS the emission order
            out[OKEY_KEY] = cols[RIDX_KEY]
        B = cols[TS_KEY].shape[0]
        for name, fn, _t in self.projections:
            v, m = fn(cols, ctx)
            v = xp.asarray(v)
            if v.ndim == 0:
                v = xp.broadcast_to(v, (B,))
            out[name] = v
            if m is not None:
                m = xp.asarray(m)
                if m.ndim == 0:
                    # scalar masks (typed null literals) must take row
                    # shape: to_events indexes mask columns per row
                    m = xp.broadcast_to(m, (B,))
                out[name + "?"] = m
        for name, src in self.set_cols:
            # a set-valued output's element snapshot rides beside its count
            for suf in ("#set", "#setm"):
                if src + suf in cols:
                    out[name + suf] = cols[src + suf]

        types = cols[TYPE_KEY]
        valid = cols[VALID_KEY]
        type_ok = ((types == CURRENT) & self.current_on) | ((types == EXPIRED) & self.expired_on)
        valid = valid & type_ok
        if self.having_fn is not None:
            valid = valid & self.having_fn(out, ctx)

        if (self.batch_mode and not self.precomputed
                and (self.contains_aggregator or self.group_by)):
            # keep only the last valid row per (flush epoch, group) — GK is
            # the partition id for keyless partitioned queries, so per-key
            # flushes in one multi-key chunk stay distinct
            gk = out[GK_KEY]
            flush = out.get(FLUSH_KEY, jnp.zeros(B, jnp.int32))
            combo = flush.astype(jnp.int64) * jnp.int64(self.num_keys + 1) + gk.astype(jnp.int64)
            combo = jnp.where(valid, combo, jnp.int64(2**62))  # invalid rows last
            order = jnp.argsort(combo, stable=True)
            combo_sorted = combo[order]
            seg_last = jnp.concatenate([combo_sorted[1:] != combo_sorted[:-1], jnp.ones(1, bool)])
            is_last_sorted = valid[order] & seg_last
            valid = jnp.zeros(B, bool).at[order].set(is_last_sorted)

        out[VALID_KEY] = valid

        def _apply_limit(v):
            rank = jnp.cumsum(v.astype(jnp.int32)) - 1
            lo = self.offset or 0
            keep = rank >= lo
            if self.limit is not None:
                keep = keep & (rank < lo + self.limit)
            return v & keep

        has_limit = self.limit is not None or self.offset is not None
        if self.order_by:
            # jnp.lexsort: last key is the primary sort key
            scalar_ov = out.pop("__overflow__", None)  # 0-d: not row-shaped
            keys = []
            for col, desc, is_str in reversed(self.order_by):
                # order-by may name a non-projected INPUT column (reference
                # `order by AGG_TIMESTAMP` without selecting it) — input
                # rows are index-aligned with the outputs
                k = out[col] if col in out else cols[col]
                if is_str and STR_RANK in cols:
                    # dictionary ids -> lexicographic ranks (nulls, id -1,
                    # wrap to the table's end and sort last among equals)
                    k = cols[STR_RANK][jnp.asarray(k, jnp.int32)]
                if k.dtype == jnp.bool_:
                    k = k.astype(jnp.int32)
                keys.append(-k if desc else k)
            keys.append(jnp.where(valid, 0, 1))  # valid rows first (primary)
            order = jnp.lexsort(keys)
            out = {k: v[order] for k, v in out.items()}
            valid = out[VALID_KEY]
            if scalar_ov is not None:
                out["__overflow__"] = scalar_ov

        # sort-then-limit, store queries included: QuerySelector always
        # orders the chunk before offset/limit (QuerySelector.java:192-198)
        if has_limit:
            out[VALID_KEY] = _apply_limit(valid)

        return state, out


def _lexsort(keys):
    order = jnp.argsort(keys[-1], stable=True)
    for k in reversed(keys[:-1]):
        order = order[jnp.argsort(k[order], stable=True)]
    return order


def plan_selector(
    selector: Selector,
    input_attrs: List[Tuple[str, AttrType]],
    resolver: Resolver,
    output_event_type: str,
    batch_mode: bool,
    dictionary,
    app_context=None,
    internal_names=frozenset(),
) -> SelectorPlan:
    specs: List[agg_ops.AggSpec] = []

    selections: List[Tuple[str, Expression]] = []
    if selector.select_all or not selector.selection_list:
        for name, _t in input_attrs:
            if name in internal_names:
                # synthetic planner internals (the `<cond> in Table`
                # exists-probe column, string-cast LUT columns) never reach
                # `select *` output — the reference's in-condition is a
                # plain filter expression
                continue
            selections.append((name, Variable(attribute_name=name)))
    else:
        for oa in selector.selection_list:
            selections.append((oa.name, oa.expression))

    from siddhi_tpu.ops.expressions import take_uuid_marker

    from siddhi_tpu.ops.expressions import take_object_elem_marker

    take_uuid_marker()  # clear any stale flag
    take_object_elem_marker()
    projections = []
    output_attrs: List[Tuple[str, AttrType]] = []
    uuid_cols: List[str] = []
    set_cols: List[Tuple[str, str]] = []
    object_meta: Dict[str, Optional[AttrType]] = {}
    object_multi: List[str] = []
    agg_positions: List[int] = []
    for name, expr in selections:
        n_specs = len(specs)
        rewritten = _rewrite_aggregators(expr, specs, resolver)
        if (len(specs) > n_specs and isinstance(rewritten, Variable)
                and rewritten.attribute_name.startswith("__agg")):
            # only TOP-LEVEL aggregator projections count — `sum(v)+0` is a
            # non-aggregate output to the snapshot-variant chooser
            # (WrappedSnapshotOutputRateLimiter.java:70 checks the outermost
            # executor's type)
            agg_positions.append(len(output_attrs))
        # synthetic agg columns resolve through the same resolver
        _augment_synthetic(resolver, specs)
        fn, t = compile_expr(rewritten, resolver)
        if take_uuid_marker():
            uuid_cols.append(name)  # host fills fresh UUIDs post-step
        if t == AttrType.OBJECT:
            # set-valued output: record element type (for decode) and the
            # source column (for '#set' companion pass-through)
            elem = take_object_elem_marker()     # createSet in this expr
            if isinstance(rewritten, Variable):
                src = resolver.resolve(rewritten).key
                for s in specs[n_specs:]:
                    if s.out_key == src and s.kind == "unionset":
                        elem = s.elem_type
                        object_multi.append(name)
                set_cols.append((name, src))
                if elem is None:
                    elem = _elem_type_of(resolver, rewritten)
                if name not in object_multi and _is_multi(resolver, rewritten):
                    object_multi.append(name)   # pass-through of a multi set
            object_meta[name] = elem
        projections.append((name, fn, t))
        output_attrs.append((name, t))

    having_fn = None
    out_resolver = OutputColsResolver(output_attrs, dictionary, fallback=resolver)
    if selector.having is not None:
        having = _rewrite_aggregators(selector.having, specs, resolver)
        _augment_synthetic(resolver, specs)
        having_fn = compile_condition(having, out_resolver)

    order_by = []
    for ob in selector.order_by_list:
        ref = out_resolver.resolve(ob.variable)
        # string keys are dictionary ids (arrival order) — sort them by
        # the lexicographic rank table the runtime injects per batch
        order_by.append((ref.key, ob.order == "desc",
                         ref.type == AttrType.STRING))

    current_on = output_event_type in ("current", "all")
    expired_on = output_event_type in ("expired", "all")

    if app_context is not None:
        for spec in specs:
            if spec.kind in ("distinctcount", "unionset"):
                spec.distinct_capacity = getattr(
                    app_context, "distinct_values_capacity", 64)

    return SelectorPlan(
        specs=specs,
        projections=projections,
        output_attrs=output_attrs,
        having_fn=having_fn,
        group_by=bool(selector.group_by_list),
        group_key_exprs=list(selector.group_by_list),
        current_on=current_on,
        expired_on=expired_on,
        batch_mode=batch_mode,
        order_by=order_by,
        limit=selector.limit,
        offset=selector.offset,
        uuid_cols=uuid_cols,
        set_cols=set_cols,
        object_meta=object_meta,
        object_multi=object_multi,
        agg_positions=agg_positions,
    )


def _augment_synthetic(resolver, specs):
    synthetic = getattr(resolver, "synthetic", None)
    if synthetic is not None:
        for s in specs:
            synthetic[s.out_key] = s.out_type
