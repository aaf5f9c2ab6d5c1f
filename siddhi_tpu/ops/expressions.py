"""Expression compiler: query-api expression AST -> columnar functions.

Replaces the reference's interpreted executor tree (``core/executor/**``:
``ExpressionExecutor.execute(ComplexEvent)`` called per event per node,
built by ``util/parser/ExpressionParser.java``) with a one-time lowering to
vectorized ops over batch columns. Under jit the whole tree fuses into the
surrounding step function.

Null semantics follow the reference executors:
- comparisons with a null operand are false (e.g.
  ``EqualCompareConditionExpressionExecutor.java`` null guards);
- arithmetic with a null operand is null (``DivideExpressionExecutorInt.java:43``);
- and/or treat null conditions as false; ``isNull``/``coalesce``/``default``
  observe nullness.

A compiled node is ``fn(cols, ctx) -> (value, null_mask_or_None)`` where
``cols`` maps column keys to arrays and ``ctx`` carries the backend module
(``ctx['xp']``), the batch timestamps key and scalars like current time.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from siddhi_tpu.ops import types as T
from siddhi_tpu.query_api.definitions import AttrType
from siddhi_tpu.query_api.expressions import (
    Add,
    And,
    AttributeFunction,
    Compare,
    Constant,
    Divide,
    Expression,
    InOp,
    IsNull,
    Mod,
    Multiply,
    Not,
    Or,
    Subtract,
    TimeConstant,
    Variable,
)

# Reserved column keys present in every device batch.
TS_KEY = "__ts__"
TYPE_KEY = "__type__"
VALID_KEY = "__valid__"
PK_KEY = "__pk__"  # partition-key id column (dense, host-computed)
# An NFA step's OUTPUT whose columns hold the valid rows compacted to a
# narrower static width (ops/compact.py) carries, under this key, a nested
# dict: the same columns at their padded width, for when the rows do not fit
PADDED_KEY = "__padded__"
# Device-routed sharding (parallel/mesh.device_route_query_step) carries
# TWO dense id spaces per row: the partition key (PK_KEY, owner = pk % n,
# local id = pk // n) and the group-by key (GK_KEY, owned by its pk's
# shard, local id assigned per shard in allocation order) — the split that
# lifts the old GK == PK routing restriction. RIDX_KEY is the row's
# position in the ORIGINAL unrouted batch, attached on device before the
# shard exchange; window stages derive their emission order keys from it
# so sharded output re-merges into the exact unsharded order (OKEY_KEY,
# attached by the window/selector and consumed by the route wrapper).
RIDX_KEY = "__ridx__"
OKEY_KEY = "__okey__"


@dataclass
class ColumnRef:
    key: str
    type: AttrType


class Resolver:
    """Maps Variables to batch columns. Query planners subclass this
    (single-stream, join two-sided, pattern state) — the analog of meta-event
    position resolution in reference ``QueryParserHelper.updateVariablePosition``."""

    def resolve(self, var: Variable) -> ColumnRef:
        raise NotImplementedError

    def encode_string(self, s: str) -> int:
        raise NotImplementedError


class CompileError(Exception):
    pass


Compiled = Tuple[Callable, AttrType]


def _const(value, attr_type: AttrType) -> Compiled:
    def fn(cols, ctx):
        return value, None

    return fn, attr_type


def compile_expr(expr: Expression, resolver: Resolver) -> Compiled:
    """Lower `expr`; returns (fn, result_type)."""
    if isinstance(expr, Constant):
        if expr.value is None:
            # typed null literal (select * over capture-less pattern
            # elements): zero placeholder + an always-true null mask
            zero = (np.int32(0) if expr.type == AttrType.STRING
                    else np.zeros((), T.dtype_of(expr.type))[()])

            def null_fn(cols, ctx, _z=zero):
                return _z, np.True_

            return null_fn, expr.type
        if expr.type == AttrType.STRING:
            return _const(np.int32(resolver.encode_string(expr.value)), AttrType.STRING)
        return _const(np.asarray(expr.value, dtype=T.dtype_of(expr.type))[()], expr.type)
    if isinstance(expr, TimeConstant):
        return _const(np.int64(expr.value), AttrType.LONG)
    if isinstance(expr, Variable):
        ref = resolver.resolve(expr)
        key, mask_key = ref.key, ref.key + "?"

        def fn(cols, ctx):
            return cols[key], cols.get(mask_key)

        return fn, ref.type
    if isinstance(expr, (Add, Subtract, Multiply, Divide, Mod)):
        return _compile_math(expr, resolver)
    if isinstance(expr, Compare):
        return _compile_compare(expr, resolver)
    if isinstance(expr, And):
        lf, lt = compile_expr(expr.left, resolver)
        rf, rt = compile_expr(expr.right, resolver)
        _require_bool(lt, rt)

        def fn(cols, ctx):
            lv, lm = lf(cols, ctx)
            rv, rm = rf(cols, ctx)
            return _false_if_null(ctx, lv, lm) & _false_if_null(ctx, rv, rm), None

        return fn, AttrType.BOOL
    if isinstance(expr, Or):
        lf, lt = compile_expr(expr.left, resolver)
        rf, rt = compile_expr(expr.right, resolver)
        _require_bool(lt, rt)

        def fn(cols, ctx):
            lv, lm = lf(cols, ctx)
            rv, rm = rf(cols, ctx)
            return _false_if_null(ctx, lv, lm) | _false_if_null(ctx, rv, rm), None

        return fn, AttrType.BOOL
    if isinstance(expr, Not):
        inner_f, inner_t = compile_expr(expr.expression, resolver)
        _require_bool(inner_t)

        def fn(cols, ctx):
            v, m = inner_f(cols, ctx)
            return ~_false_if_null(ctx, v, m), None

        return fn, AttrType.BOOL
    if isinstance(expr, IsNull):
        inner_f, _t = compile_expr(expr.expression, resolver)

        def fn(cols, ctx):
            v, m = inner_f(cols, ctx)
            xp = ctx["xp"]
            if m is None:
                return xp.zeros(_shape_of(xp, v, cols), dtype=bool), None
            return m, None

        return fn, AttrType.BOOL
    if isinstance(expr, AttributeFunction):
        return _compile_function(expr, resolver)
    if isinstance(expr, InOp):
        raise CompileError(
            "'in <table>' conditions are supported in single-stream filter "
            "handlers (rewritten to a table exists-probe by the planner)")
    raise CompileError(f"cannot compile expression {expr!r}")


def compile_condition(expr: Expression, resolver: Resolver) -> Callable:
    """Boolean condition: fn(cols, ctx) -> bool array (nulls -> False)."""
    f, t = compile_expr(expr, resolver)
    if t != AttrType.BOOL:
        raise CompileError(f"filter condition must be bool, got {t}")

    def fn(cols, ctx):
        v, m = f(cols, ctx)
        return _false_if_null(ctx, v, m)

    return fn


def _shape_of(xp, v, cols):
    shape = getattr(v, "shape", ())
    if shape:
        return shape
    return cols[TS_KEY].shape


def _false_if_null(ctx, value, mask):
    if mask is None:
        return value
    return value & ~mask


def _require_bool(*ts: AttrType):
    for t in ts:
        if t != AttrType.BOOL:
            raise CompileError(f"expected bool operand, got {t}")


def _compile_math(expr, resolver) -> Compiled:
    lf, lt = compile_expr(expr.left, resolver)
    rf, rt = compile_expr(expr.right, resolver)
    out_t = T.promote(lt, rt)
    dtype = T.dtype_of(out_t)
    op = type(expr).__name__

    def fn(cols, ctx):
        xp = ctx["xp"]
        lv, lm = lf(cols, ctx)
        rv, rm = rf(cols, ctx)
        a = xp.asarray(lv).astype(dtype)
        b = xp.asarray(rv).astype(dtype)
        if op == "Add":
            v = a + b
        elif op == "Subtract":
            v = a - b
        elif op == "Multiply":
            v = a * b
        elif op == "Divide":
            v = T.java_div(xp, a, b, out_t)
        else:
            v = T.java_mod(xp, a, b, out_t)
        mask = _or_masks(xp, lm, rm)
        return v, mask

    return fn, out_t


def _or_masks(xp, a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a | b


def _compile_compare(expr: Compare, resolver) -> Compiled:
    lf, lt = compile_expr(expr.left, resolver)
    rf, rt = compile_expr(expr.right, resolver)
    op = expr.operator
    if AttrType.STRING in (lt, rt) or AttrType.BOOL in (lt, rt):
        # Strings are dictionary ids; only ==/!= defined (the reference has
        # only EqualCompareConditionExpressionExecutorStringString /
        # BoolBool — no ordering executors for these types).
        if op not in ("==", "!=") or lt != rt:
            raise CompileError(f"'{op}' not defined between {lt} and {rt}")
    else:
        T.promote(lt, rt)  # validates numeric

    def fn(cols, ctx):
        xp = ctx["xp"]
        lv, lm = lf(cols, ctx)
        rv, rm = rf(cols, ctx)
        if op == "<":
            v = lv < rv
        elif op == "<=":
            v = lv <= rv
        elif op == ">":
            v = lv > rv
        elif op == ">=":
            v = lv >= rv
        elif op == "==":
            v = lv == rv
        else:
            v = lv != rv
        mask = _or_masks(xp, lm, rm)
        # null comparison -> false (reference null guards return false)
        return _false_if_null(ctx, v, mask), None

    return fn, AttrType.BOOL


# ------------------------------------------------------------- functions

_TYPE_NAMES = {
    "string": AttrType.STRING,
    "int": AttrType.INT,
    "long": AttrType.LONG,
    "float": AttrType.FLOAT,
    "double": AttrType.DOUBLE,
    "bool": AttrType.BOOL,
}


def _compile_function(expr: AttributeFunction, resolver) -> Compiled:
    name = (f"{expr.namespace}:{expr.name}" if expr.namespace else expr.name).lower()
    args = expr.parameters

    if name in ("cast", "convert"):
        # cast(x, 'double') — reference Cast/ConvertFunctionExecutor
        if len(args) != 2:
            raise CompileError(
                f"{name}() needs exactly (value, '<type>'), got {len(args)} "
                f"arguments")
        src_f, src_t = compile_expr(args[0], resolver)
        if not isinstance(args[1], Constant) or args[1].type != AttrType.STRING:
            raise CompileError(f"{name}() target type must be a string constant")
        if args[1].value.lower() not in _TYPE_NAMES:
            raise CompileError(
                f"{name}() target '{args[1].value}' is not a type name")
        target = _TYPE_NAMES[args[1].value.lower()]
        if AttrType.STRING in (src_t, target) and src_t != target:
            raise CompileError("string<->numeric cast runs host-side; not supported on device yet")
        dtype = T.dtype_of(target)

        if target == AttrType.BOOL and src_t != AttrType.BOOL:
            # numeric -> bool is `value == 1` (ConvertFunctionExecutor:
            # 2f converts to false, 1f to true — ConvertFunctionTestCase)
            def fn(cols, ctx):
                v, m = src_f(cols, ctx)
                return ctx["xp"].asarray(v) == 1, m
        else:
            def fn(cols, ctx):
                v, m = src_f(cols, ctx)
                return ctx["xp"].asarray(v).astype(dtype), m

        return fn, target

    if name == "ifthenelse":
        cond_f = compile_condition(args[0], resolver)
        then_f, then_t = compile_expr(args[1], resolver)
        else_f, else_t = compile_expr(args[2], resolver)
        out_t = then_t if then_t == else_t else T.promote(then_t, else_t)
        dtype = T.dtype_of(out_t)

        def fn(cols, ctx):
            xp = ctx["xp"]
            c = cond_f(cols, ctx)
            tv, tm = then_f(cols, ctx)
            ev, em = else_f(cols, ctx)
            v = xp.where(c, xp.asarray(tv).astype(dtype), xp.asarray(ev).astype(dtype))
            if tm is None and em is None:
                return v, None
            zeros = xp.zeros(_shape_of(xp, v, cols), dtype=bool)
            m = xp.where(c, tm if tm is not None else zeros, em if em is not None else zeros)
            return v, m

        return fn, out_t

    if name == "coalesce":
        compiled = [compile_expr(a, resolver) for a in args]
        out_t = compiled[0][1]
        for _, t in compiled[1:]:
            if t != out_t:
                raise CompileError("coalesce() arguments must share one type")
        dtype = T.dtype_of(out_t)

        def fn(cols, ctx):
            xp = ctx["xp"]
            v, m = compiled[0][0](cols, ctx)
            v = xp.asarray(v).astype(dtype)
            if m is None:
                return v, None
            for f, _t in compiled[1:]:
                nv, nm = f(cols, ctx)
                v = xp.where(m, xp.asarray(nv).astype(dtype), v)
                if nm is None:
                    m = xp.zeros_like(m)
                    break
                m = m & nm
            return v, m

        return fn, out_t

    if name == "default":
        if len(args) != 2:
            raise CompileError(
                f"default() needs exactly (attribute, value), got "
                f"{len(args)} arguments")
        src_f, src_t = compile_expr(args[0], resolver)
        dft_f, dft_t = compile_expr(args[1], resolver)
        if src_t != dft_t:
            raise CompileError("default() value type must match attribute type")

        def fn(cols, ctx):
            xp = ctx["xp"]
            v, m = src_f(cols, ctx)
            if m is None:
                return v, None
            dv, _dm = dft_f(cols, ctx)
            return xp.where(m, dv, v), None

        return fn, src_t

    if name in ("maximum", "minimum"):
        compiled = [compile_expr(a, resolver) for a in args]
        out_t = compiled[0][1]
        for _, t in compiled[1:]:
            out_t = T.promote(out_t, t)
        dtype = T.dtype_of(out_t)
        is_max = name == "maximum"

        def fn(cols, ctx):
            xp = ctx["xp"]
            v, m = compiled[0][0](cols, ctx)
            v = xp.asarray(v).astype(dtype)
            for f, _t in compiled[1:]:
                nv, nm = f(cols, ctx)
                nv = xp.asarray(nv).astype(dtype)
                v = xp.maximum(v, nv) if is_max else xp.minimum(v, nv)
                m = _or_masks(xp, m, nm)
            return v, m

        return fn, out_t

    if name.startswith("instanceof"):
        target = {"instanceofboolean": AttrType.BOOL, "instanceofstring": AttrType.STRING,
                  "instanceofinteger": AttrType.INT, "instanceoflong": AttrType.LONG,
                  "instanceoffloat": AttrType.FLOAT, "instanceofdouble": AttrType.DOUBLE}[name]
        src_f, src_t = compile_expr(args[0], resolver)
        matches = src_t == target

        def fn(cols, ctx):
            xp = ctx["xp"]
            v, m = src_f(cols, ctx)
            shape = _shape_of(xp, v, cols)
            res = xp.full(shape, matches, dtype=bool)
            if m is not None:
                res = res & ~m  # null is not an instance of anything
            return res, None

        return fn, AttrType.BOOL

    if name == "eventtimestamp":
        if args:
            raise CompileError(
                f"eventTimestamp() takes no arguments, got {len(args)}")

        def fn(cols, ctx):
            return cols[TS_KEY], None

        return fn, AttrType.LONG

    if name == "currenttimemillis":
        def fn(cols, ctx):
            # host pump injects batch-receive wall time (scalar broadcast)
            return ctx["current_time"], None

        return fn, AttrType.LONG

    if name == "uuid":
        # reference UUIDFunctionExecutor: a fresh UUID string per event.
        # Random strings cannot be produced inside the jitted step (string
        # columns are dictionary-encoded); the compiled fn emits a
        # placeholder and flags the output column for a host-side fill
        # after the step (QueryRuntime._emit).
        mark_uuid_seen()

        def fn(cols, ctx):
            xp = ctx["xp"]
            shape = _shape_of(xp, None, cols)
            return xp.zeros(shape, T.dtype_of(AttrType.STRING)), None

        return fn, AttrType.STRING

    if name == "createset":
        # reference CreateSetFunctionExecutor: wraps one value in a
        # singleton set. TPU inversion: the set IS its element's int64
        # identity code (strings: dict ids; floats: bit patterns) — a
        # scalar column, so windows/joins buffer it natively; multi-element
        # sets only arise as unionSet outputs (bounded [B,H] snapshots).
        if len(args) != 1:
            raise CompileError(
                "createSet() function has to have exactly 1 parameter, "
                f"currently {len(args)} parameters provided")
        src_f, src_t = compile_expr(args[0], resolver)
        if src_t == AttrType.OBJECT:
            raise CompileError("createSet() argument must be a primitive type")
        mark_object_elem(src_t)

        def fn(cols, ctx):
            xp = ctx["xp"]
            v, m = src_f(cols, ctx)
            return _encode_set_element(xp, v, src_t), m

        return fn, AttrType.OBJECT

    if name == "sizeofset":
        # reference SizeOfSetFunctionExecutor: cardinality of a set value.
        # unionSet outputs carry their live count in the base column and
        # their elements in '#set'/'#setm' companions; a singleton (from
        # createSet) is size 1, or 0 when null.
        if len(args) != 1 or not isinstance(args[0], Variable):
            raise CompileError(
                "sizeOfSet() expects exactly one set-typed attribute reference")
        ref = resolver.resolve(args[0])
        if ref.type != AttrType.OBJECT:
            raise CompileError(
                f"sizeOfSet() argument must be of type object, "
                f"found {ref.type.value}")
        key = ref.key
        # a unionSet output's base column IS the live count (its element
        # snapshot travels in '#set' companions that windows drop); a
        # createSet singleton's base column is the element code
        defn = getattr(resolver, "definition", None)
        multi = key in (getattr(defn, "object_multi_attrs", None) or set())

        def fn(cols, ctx):
            xp = ctx["xp"]
            sm = cols.get(key + "#setm")
            if sm is not None:      # multi-element set: count live slots
                return xp.sum(sm, axis=-1).astype(xp.int64), None
            if multi:               # companions dropped: count column stands
                return xp.asarray(cols[key]).astype(xp.int64), None
            m = cols.get(key + "?")
            one = xp.ones_like(xp.asarray(cols[key]), dtype=xp.int64)
            if m is None:
                return one, None
            return xp.where(m, 0, one), None

        return fn, AttrType.INT

    if name == "log":
        # reference LogFunctionExecutor: logs its arguments per event and
        # passes true; device-side via jax.debug.print (TPU-safe)
        compiled = [compile_expr(a, resolver) for a in args]

        def fn(cols, ctx):
            xp = ctx["xp"]
            vals = [f(cols, ctx)[0] for f, _t in compiled]
            if xp is np:
                print("siddhi:", *[np.asarray(v) for v in vals])
            else:
                import jax

                fmt = "siddhi: " + " ".join("{}" for _ in vals)
                jax.debug.print(fmt, *[xp.asarray(v) for v in vals])
            shape = _shape_of(xp, vals[0] if vals else None, cols)
            return xp.ones(shape, bool), None

        return fn, AttrType.BOOL

    ext = resolve_extension("function", name)
    if ext is not None:
        # custom scalar function (reference SiddhiExtensionLoader resolving
        # FunctionExecutor @Extension classes): vectorized over columns
        compiled = [compile_expr(a, resolver) for a in args]
        out_t = ext.return_type
        if callable(out_t):
            out_t = out_t([t for _, t in compiled])

        def fn(cols, ctx):
            xp = ctx["xp"]
            vals, m = [], None
            for f, _t in compiled:
                v, vm = f(cols, ctx)
                vals.append(v)
                m = _or_masks(xp, m, vm)
            return ext.apply(xp, *vals), m

        return fn, out_t

    raise CompileError(f"unknown function '{name}'")


# ------------------------------------------------------------- extensions

# Extension registry active during query compilation. Every compile entry
# point (app construction, on-demand queries) points this at its
# SiddhiContext.extensions before compiling, making
# ``SiddhiManager.set_extension`` a live lookup path (the role of reference
# ``SiddhiExtensionLoader.java:58-98``). Thread-local so two managers
# compiling concurrently cannot see each other's registries.
import threading as _threading

_ACTIVE = _threading.local()
_UUID_MARK = _threading.local()


def mark_uuid_seen():
    _UUID_MARK.flag = True


def take_uuid_marker() -> bool:
    """True if a uuid() call was compiled since the last take (consumed by
    plan_selector to flag the output column for host fill)."""
    flag = getattr(_UUID_MARK, "flag", False)
    _UUID_MARK.flag = False
    return flag


_OBJ_MARK = _threading.local()


def mark_object_elem(elem_type):
    _OBJ_MARK.elem = elem_type


def take_object_elem_marker():
    """Element type of the set produced by a createSet() compiled since the
    last take (consumed by plan_selector to record decode metadata)."""
    elem = getattr(_OBJ_MARK, "elem", None)
    _OBJ_MARK.elem = None
    return elem


def _encode_set_element(xp, v, elem_type):
    """Value column -> int64 set-element identity codes (shared with the
    distinctCount/unionSet value tables: floats by bit pattern, strings
    already dictionary ids)."""
    from siddhi_tpu.query_api.definitions import AttrType as _AT

    v = xp.asarray(v)
    if elem_type == _AT.FLOAT:
        if xp is np:
            v = v.astype(np.float32).view(np.int32)
        else:
            from jax import lax as _lax

            v = _lax.bitcast_convert_type(v.astype(xp.float32), xp.int32)
    elif elem_type == _AT.DOUBLE:
        if xp is np:
            v = v.astype(np.float64).view(np.int64)
        else:
            from jax import lax as _lax

            v = _lax.bitcast_convert_type(v.astype(xp.float64), xp.int64)
    return v.astype(xp.int64)


def encode_set_value(val, elem_type, dictionary) -> int:
    """Host-side inverse of ``decode_set_element`` for Event ingestion:
    encode one Python element to its int64 identity code, honouring the
    stream's recorded element type (FLOAT -> float32 bit pattern, DOUBLE
    -> float64 — matching the device-side ``_encode_set_element``)."""
    from siddhi_tpu.query_api.definitions import AttrType as _AT

    if isinstance(val, str):
        return int(dictionary.encode(val))
    if isinstance(val, bool):
        return int(val)
    if isinstance(val, float):
        if elem_type == _AT.FLOAT:
            return int(np.float32(val).view(np.int32))
        return int(np.float64(val).view(np.int64))
    return int(val)


def decode_set_element(code: int, elem_type, dictionary):
    """Inverse of ``_encode_set_element`` for host-side event decode."""
    from siddhi_tpu.query_api.definitions import AttrType as _AT

    if elem_type == _AT.STRING:
        return dictionary.decode(int(code))
    if elem_type == _AT.FLOAT:
        return float(np.int32(code).view(np.float32))
    if elem_type == _AT.DOUBLE:
        return float(np.int64(code).view(np.float64))
    if elem_type == _AT.BOOL:
        return bool(code)
    return int(code)


def set_active_extensions(extensions: dict) -> None:
    _ACTIVE.extensions = extensions if extensions is not None else {}


def resolve_in(extensions: dict, kind: str, name: str):
    """Shared 'kind:name, then bare name, case-insensitive' lookup rule."""
    for key in (f"{kind}:{name}", name):
        cls = extensions.get(key) or extensions.get(key.lower())
        if cls is not None:
            return cls
    return None


def resolve_extension(kind: str, name: str):
    return resolve_in(getattr(_ACTIVE, "extensions", {}), kind, name)
