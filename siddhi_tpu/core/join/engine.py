"""Device-resident join engine.

One jitted step per arriving chunk inserts the chunk into its own side's
window and probes the other side's window, and stamps an explicit
cross-stream sequence number into the meta so that left and right
batches have a total order the CompletionPump can respect
(``join_runtime._pipeline_ok``).

**The keyed probe.** Where the ``on`` condition has an equality conjunct
over hashable key types (int/long/bool/string; floats keep the broadcast
compare: ``-0.0 == 0.0`` and NaN break equal values => equal keys) and
the host mirrors the probed side's ring exactly (a length or time window
with no filter or transform: ``_RowMirror``), a probe does work and
writes output in proportion to its matches, not to N x W:

- a key-ordered view of the probed ring: its slots sorted by (key, rank),
  the rank being the row's arrival order among the slots (dead rows and
  rows with a null key rank after every live one);
- each trigger row's range of matching rows in that view (a binary
  search), which is its key's live rows, oldest first;
- one expansion of those ranges into ``width`` output rows, trigger row
  after trigger row, where ``width`` is a power of two the host knows is
  enough BEFORE dispatch: its mirrors count every key's live rows, so
  the batch's candidate pairs are known exactly (``prepare_batch``).
  The width is a high-water mark: it grows (at least ``_GROWTH`` times
  where the probed ring grew since, its window filling), it never
  shrinks, so a steady stream compiles no new program.

The rest of the ``on`` condition is evaluated on the expanded pairs. The
emission order is the reference's: trigger row, then the probed rows in
their arrival order (``JoinProcessor`` walks the findable window oldest
first), then the trigger's one-sided row (outer join, RESET). The
device scope is ``siddhi.join_probe`` inside ``siddhi.state``; the pairs
a step emitted ride the meta as the ``join_matches`` lane.

**Growing time windows.** A time window's live rows depend on the
traffic, not the query, so a mirrored time side's ring grows with its
content: before a batch is dispatched, the host mirror says how many rows
will be live after it, and a ring too small is re-laid into the power of
two that holds them and at least ``_GROWTH`` times the ring
(``TimeWindowStage.grown``, leaf by leaf, under a ``siddhi.grow``
span), so it never overflows.

Every other shape (no equality key, a filtered or transformed side, a
window-less other side) probes the other side's whole window with the
broadcast ``[N, W]`` compare of the legacy path, read oldest first.
"""

from __future__ import annotations

import collections
import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.core.plan.selector_plan import FLUSH_KEY, GK_KEY, STR_RANK
from siddhi_tpu.observability.instruments import (META_SCOPE, SELECT_SCOPE,
                                                  STATE_SCOPE)
from siddhi_tpu.ops.expressions import (
    OKEY_KEY, TS_KEY, TYPE_KEY, VALID_KEY)
from siddhi_tpu.ops.windows import (
    LengthWindowStage, PassthroughWindowStage, TimeWindowStage, conform_cols)
from siddhi_tpu.query_api.definitions import AttrType

_LOG = logging.getLogger("siddhi_tpu.join.engine")

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3
# the keyed probe's least output width: a few hundred pairs compile no
# program of their own (a width is a program; 1,024 rows cost nothing)
_MIN_WIDTH = 1024
# the least factor by which a growing time ring (and the width of a keyed
# probe of it) grows: each size is a program of every side step that reads
# it, so a window that fills from empty passes through few of them (a 10 s
# window at 262,144 rows/s: rings of 65,536, 524,288 and 4,194,304 slots)
_GROWTH = 8

# device scope of the keyed probe (inside STATE_SCOPE): the sort of the
# probed ring, the range search and the expansion into matched pairs
PROBE_SCOPE = "siddhi.join_probe"

# state key of the cross-stream sequence counter — stripped from
# snapshots (canonical capture is the legacy ring layout) and reset at
# restore (rebuild_probe_state)
SEQ_KEY = "jseq"
ENGINE_STATE_KEYS = (SEQ_KEY,)

_HASHABLE = (AttrType.INT, AttrType.LONG, AttrType.BOOL, AttrType.STRING)


# ------------------------------------------------------------ eligibility

def engine_ineligibility(rt) -> Optional[str]:
    """Why this join runtime cannot run the device engine (None = it
    can). v1 scope: non-partitioned stream-stream joins whose sides are
    device length/time/externalTime windows or windowless passthroughs.
    Shared-store sides (tables, named windows, aggregations), host-mode
    windows and `partition with` joins keep the legacy probe path (the
    keyed ``[K, W]`` ring of a partitioned join is already
    partition-local by construction). Reasons are
    ``core.eligibility.Reason`` strings (stable ``.code`` + free-text
    detail)."""
    from siddhi_tpu.core.eligibility import ReasonCode as RC
    from siddhi_tpu.core.eligibility import reason

    if rt.partition_ctx is not None:
        return reason(RC.PARTITIONED,
                      "partitioned join (keyed rings are already "
                      "partition-local)")
    if rt.index_probe is not None:
        return reason(RC.INDEXED_PROBE, "indexed table probe")
    for side in rt.sides.values():
        if side.store is not None:
            return reason(RC.STORE_SIDE,
                          f"shared-store side '{side.stream_id}'")
        if side.host_window is not None:
            return reason(RC.HOST_WINDOW,
                          f"host-mode window side '{side.stream_id}'")
        stage = side.window_stage
        if not isinstance(stage, (LengthWindowStage, TimeWindowStage,
                                  PassthroughWindowStage)):
            return reason(RC.WINDOW_KIND,
                          f"window stage {type(stage).__name__} on side "
                          f"'{side.stream_id}' (no partition adapter yet)")
    return None


def pipeline_ineligibility(rt) -> Optional[str]:
    """Why this join runtime's batches may NOT ride the CompletionPump
    (None = they may). Wider than engine eligibility: any stream-stream
    join whose probe surfaces live inside the jitted state can pipeline —
    the per-side ``__notify__`` is attributed to the side's own timer
    callback at drain, and the pump's per-owner FIFO preserves the
    cross-stream dispatch order (which the engine additionally stamps
    into the meta as an explicit sequence number)."""
    from siddhi_tpu.core.eligibility import ReasonCode as RC
    from siddhi_tpu.core.eligibility import reason

    for side in rt.sides.values():
        if side.store is not None:
            return reason(RC.STORE_SIDE,
                          f"shared-store probe side '{side.stream_id}' "
                          f"(host-interleaved contents)")
        if side.host_window is not None:
            return reason(RC.HOST_WINDOW,
                          f"host-mode window side '{side.stream_id}'")
        if side.window_stage is None:
            return reason(RC.NO_WINDOW,
                          f"side '{side.stream_id}' has no window stage")
    if rt.keyer is not None:
        return reason(RC.GROUPED_SELECT,
                      "grouped selector (host keyed select between stages)")
    if rt.index_probe is not None:
        return reason(RC.INDEXED_PROBE, "indexed table probe")
    return None


# ---------------------------------------------------- equality extraction

def extract_join_keys(on_expr, left, right, dictionary):
    """Find an equality conjunct ``<left-side expr> == <right-side expr>``
    in the ``on`` condition (top level, or one conjunct of a top-level
    And) whose two values are hashable types, and compile each side's
    value closure against that side's OWN (unprefixed) columns. Returns
    ``{"left": fn, "right": fn, "dtype": int32 | int64}`` or None (int64
    where a side's key is a long). Both closures' values are compared as
    integers, so equal values always sort together."""
    from siddhi_tpu.core.plan.resolvers import SingleStreamResolver
    from siddhi_tpu.ops.expressions import compile_expr
    from siddhi_tpu.ops.types import promote
    from siddhi_tpu.query_api.expressions import (
        And, AttributeFunction, Compare, Variable)

    def vars_of(e, out):
        if isinstance(e, Variable):
            out.append(e)
        for name in ("left", "right", "expression"):
            c = getattr(e, name, None)
            if c is not None and not isinstance(c, (str, int, float, bool)):
                vars_of(c, out)
        if isinstance(e, AttributeFunction):
            for p in e.parameters:
                vars_of(p, out)
        return out

    def side_ids(s):
        return {s.stream_id, s.ref_id} - {None}

    def owner_of(expr):
        """Which side an expression reads (None = mixed/unqualified)."""
        vs = vars_of(expr, [])
        if not vs or any(v.stream_id is None for v in vs):
            return None
        owners = set()
        for v in vs:
            in_l = v.stream_id in side_ids(left)
            in_r = v.stream_id in side_ids(right)
            if in_l == in_r:      # ambiguous (self-join raw id) or neither
                return None
            owners.add("left" if in_l else "right")
        return owners.pop() if len(owners) == 1 else None

    def try_eq(e):
        if not isinstance(e, Compare) or e.operator != "==":
            return None
        oa, ob = owner_of(e.left), owner_of(e.right)
        if oa is None or ob is None or oa == ob:
            return None
        by_side = {oa: e.left, ob: e.right}
        fns = {}
        types = {}
        for key, side in (("left", left), ("right", right)):
            res = SingleStreamResolver(side.definition, dictionary,
                                       ref_id=side.ref_id)
            try:
                fn, t = compile_expr(by_side[key], res)
            except Exception:  # noqa: BLE001 — fall back to broadcast probe
                return None
            fns[key] = fn
            types[key] = t
        if any(t not in _HASHABLE for t in types.values()):
            return None
        if types["left"] != types["right"]:
            # mixed types: only numeric pairs with a lossless promotion
            # keep the equal-values => equal-hash invariant (promote
            # raises on strings/bools, which must match exactly)
            from siddhi_tpu.ops.types import is_numeric

            if not (is_numeric(types["left"])
                    and is_numeric(types["right"])):
                return None
            try:
                promote(types["left"], types["right"])
            except Exception:  # noqa: BLE001 — incomparable types
                return None
        # the keyed probe sorts and searches keys in this width
        fns["dtype"] = (np.int64 if AttrType.LONG in types.values()
                        else np.int32)
        return fns

    hit = try_eq(on_expr)
    if hit is not None:
        return hit
    if isinstance(on_expr, And):
        for part in (on_expr.left, on_expr.right):
            hit = try_eq(part)
            if hit is not None:
                return hit
    return None


# ------------------------------------------------------------ the probe

def _lower_bound(sorted_cols, queries):
    """For each query, the first position of the lexicographically sorted
    columns ``sorted_cols`` not below it (``queries``: one array a column):
    a binary search of as many steps as the length has bits, all queries
    at once. (``jnp.searchsorted`` right after the sort that feeds it
    compiles three times as long on the TPU: 70 s against 25 s at 4 M.)"""
    n = sorted_cols[0].shape[0]
    lo = jnp.zeros(queries[0].shape, jnp.int32)
    hi = jnp.full(queries[0].shape, n, jnp.int32)

    def halve(_, bounds):
        lo, hi = bounds
        mid = (lo + hi) // 2
        at = jnp.minimum(mid, n - 1)
        below = jnp.zeros(lo.shape, bool)
        equal = jnp.ones(lo.shape, bool)
        for col, q in zip(sorted_cols, queries):
            v = col[at]
            below = below | (equal & (v < q))
            equal = equal & (v == q)
        go = lo < hi
        return (jnp.where(go & below, mid + 1, lo),
                jnp.where(go & ~below, mid, hi))

    return lax.fori_loop(0, n.bit_length(), halve, (lo, hi))[0]


def _key_ranges(okey, orank, tkey, tbase, Wo: int):
    """The probed ring sorted by (key, rank), and each trigger's range in
    it: (sorted ranks, lo, hi), the range of trigger i being the rows of
    key ``tkey[i]`` whose rank lies in ``[tbase[i], tbase[i] + Wo)``. A
    32-bit key and its rank pack into one int64 and sort alone (a
    one-operand sort compiles in half the time of a two-operand one on
    the TPU); a long key sorts as two operands."""
    n = tkey.shape[0]
    lo_hi = jnp.concatenate([tbase, tbase + Wo])
    if okey.dtype == jnp.int32:
        packed = lax.sort((okey.astype(jnp.int64) << 32) + orank)
        srank = (packed & 0xFFFFFFFF).astype(jnp.int32)
        bounds = _lower_bound(
            (packed,), ((jnp.concatenate([tkey, tkey]).astype(jnp.int64)
                         << 32) + lo_hi,))
    else:
        skey, srank = lax.sort((okey, orank), num_keys=2)
        bounds = _lower_bound((skey, srank),
                              (jnp.concatenate([tkey, tkey]), lo_hi))
    return srank, bounds[:n], bounds[n:]


def _other(side_key: str) -> str:
    return "right" if side_key == "left" else "left"


def _pow2(n: int) -> int:
    k = 1
    while k < n:
        k *= 2
    return k


# ------------------------------------------------------------ side plans

class _SidePlan:
    """Per-side parameters: the window kind and ring size, and whether
    the host mirrors the ring (``mirrored``) and so may grow it
    (``grows``: a time window)."""

    __slots__ = ("kind", "W", "key_fn", "win_key", "stage", "mirrored",
                 "grows")

    def __init__(self, side_key: str, side, pspec):
        stage = self.stage = side.window_stage
        if isinstance(stage, LengthWindowStage):
            self.kind, self.W = "length", int(stage.length)
        elif isinstance(stage, TimeWindowStage):
            self.kind, self.W = "time", int(stage.capacity)
        else:
            self.kind, self.W = "none", 1
        self.win_key = "lwin" if side_key == "left" else "rwin"
        self.key_fn = pspec[side_key] if pspec is not None else None
        # the host can follow the ring row for row only where every valid
        # CURRENT row goes in: in-step filters/transforms drop or rewrite
        # rows device-side
        self.mirrored = (self.kind != "none" and self.key_fn is not None
                         and not side.filters and not side.transforms)
        self.grows = self.mirrored and self.kind == "time"

    def live_floor_np(self, win_state):
        """The sequence number of the oldest row still in the window: the
        rows the ``contents()`` view reports live are those at or above
        it."""
        if self.kind == "length":
            return max(int(win_state["total"]) - self.W, 0)
        return max(int(win_state["expired_upto"]), 0)


class _Vocab:
    """Dense ids for key values, shared by both sides: a value keeps the
    id it was first given, so per-key counts never move."""

    def __init__(self):
        self._vals = np.zeros(0, np.int64)     # sorted values
        self._ids = np.zeros(0, np.int64)      # their ids
        self.n = 0

    def ids(self, vals) -> np.ndarray:
        vals = np.asarray(vals, np.int64)
        pos = np.searchsorted(self._vals, vals)
        hit = (self._vals[np.minimum(pos, self.n - 1)] == vals if self.n
               else np.zeros(vals.shape, bool))
        if not hit.all():
            new = np.unique(vals[~hit])
            allv = np.concatenate([self._vals, new])
            alli = np.concatenate([self._ids, np.arange(
                self.n, self.n + len(new), dtype=np.int64)])
            order = np.argsort(allv, kind="stable")
            self._vals, self._ids = allv[order], alli[order]
            self.n += len(new)
            pos = np.searchsorted(self._vals, vals)
        return self._ids[pos]


def _key_ids(plan, vocab: _Vocab, cols, rows) -> np.ndarray:
    """Key ids of the ``rows`` (a mask) of a host batch, by the side's
    own key closure; -1 where the key is null."""
    B = rows.shape[0]
    vals, mask = plan.key_fn(cols, {"xp": np})
    vals = np.broadcast_to(np.asarray(vals), (B,))[rows]
    null = (np.broadcast_to(np.asarray(mask, bool), (B,))[rows]
            if mask is not None else np.zeros(len(vals), bool))
    ids = np.full(len(vals), -1, np.int64)
    ids[~null] = vocab.ids(vals[~null])
    return ids


class _RowMirror:
    """Host copy of one side's window as the device's ring holds it: each
    live row's key id (-1: a null key) and, for a time window, its clock,
    oldest first, and the live rows per key. ``advance`` applies a step's
    own bookkeeping (a length window keeps its last ``W`` rows; a time
    window advances its expired prefix by the count of the device's
    ``apply``), so ``held`` and ``counts`` are exactly what the device
    holds after the step, with no pull."""

    def __init__(self, plan, vocab: _Vocab):
        self.plan = plan
        self.vocab = vocab
        # [ids, clock or None, the clock's least]: a chunk a step
        self.chunks = collections.deque()
        self.held = 0
        self.total = 0
        self.counts = np.zeros(0, np.int64)  # live non-null rows per id
        self.nulls = 0                       # live null-key rows

    def count_of(self, ids) -> int:
        """Live rows that share a key with each of ``ids``, summed: the
        candidate pairs of trigger rows with those keys."""
        ids = np.asarray(ids, np.int64)
        known = ids[(ids >= 0) & (ids < len(self.counts))]
        return int(self.counts[known].sum()
                   + self.nulls * np.count_nonzero(ids < 0))

    def _add(self, ids, sign: int) -> None:
        real = ids[ids >= 0]
        if len(self.counts) < self.vocab.n:
            self.counts = np.concatenate([self.counts, np.zeros(
                self.vocab.n - len(self.counts), np.int64)])
        self.counts += sign * np.bincount(real, minlength=len(self.counts))
        self.nulls += sign * int(np.count_nonzero(ids < 0))

    def advance(self, ids, clock, now: int) -> np.ndarray:
        """Insert one step's valid CURRENT rows (``ids``, ``clock``: the
        rows' timestamps, or the clock attribute of an externalTime
        window) and expire what the step's window expires at ``now``;
        returns the ids of the rows that left, oldest first (the rows the
        window emits as EXPIRED)."""
        plan = self.plan
        n = len(ids)
        if plan.kind == "time":
            n_exp = self._time_expiries(clock, now)
        if n:
            self.chunks.append(
                [ids, clock, int(clock.min())] if plan.kind == "time"
                else [ids, None, None])
            self._add(ids, +1)
            self.held += n
            self.total += n
        if plan.kind == "length":
            n_exp = self.held - min(self.total, plan.W)
        return self._drop(n_exp)

    def _time_expiries(self, clock, now: int) -> int:
        """How many rows the time window's ``apply`` expires in this step:
        held rows whose clock is past the cutoff, and batch rows past it
        with a later valid row (``TimeWindowStage.apply_current``)."""
        stage = self.plan.stage
        t = int(stage.time_ms)
        if stage.external:
            cutoff = int(clock.max()) - t if len(clock) else -(2 ** 62)
        else:
            cutoff = now - t
        n = sum(int(np.count_nonzero(clock_c <= cutoff))
                for _ids, clock_c, least in self.chunks if least <= cutoff)
        if len(clock) > 1:
            if stage.external:
                later = np.maximum.accumulate(clock[::-1])[::-1][1:]
                n += int(np.count_nonzero(later >= clock[:-1] + t))
            else:
                n += int(np.count_nonzero(clock[:-1] <= cutoff))
        return n

    def _drop(self, n: int) -> np.ndarray:
        out = []
        while n > 0:
            ids_c, clock_c, _least = self.chunks[0]
            k = min(n, len(ids_c))
            out.append(ids_c[:k])
            if k == len(ids_c):
                self.chunks.popleft()
            elif clock_c is None:
                self.chunks[0] = [ids_c[k:], None, None]
            else:
                self.chunks[0] = [ids_c[k:], clock_c[k:],
                                  int(clock_c[k:].min())]
            n -= k
        gone = (np.concatenate(out) if out else np.zeros(0, np.int64))
        if len(gone):
            self._add(gone, -1)
            self.held -= len(gone)
        return gone

    def reset_from(self, ids, clock, total: int) -> None:
        """Start over from the rows a restored ring holds, oldest first."""
        self.chunks.clear()
        self.counts = np.zeros(0, np.int64)
        self.nulls = self.held = 0
        self.total = total
        if len(ids):
            self.chunks.append([ids, clock, None if clock is None
                                else int(clock.min())])
            self._add(ids, +1)
            self.held = len(ids)


class DeviceJoinEngine:
    """Owns the per-side plans and row mirrors and builds the fused
    insert+probe step of each side (``JoinQueryRuntime`` delegates its
    ``build_side_step_fn`` here when attached)."""

    def __init__(self, runtime, pspec):
        self.rt = runtime
        self.plans: Dict[str, _SidePlan] = {
            k: _SidePlan(k, runtime.sides[k], pspec)
            for k in ("left", "right")
        }
        self.key_dtype = pspec["dtype"] if pspec is not None else None
        # each mirrored side's rows, and each keyed side's output width
        # (a high-water mark: the power of two its largest batch needed)
        self.vocab = _Vocab()
        self.rows: Dict[str, _RowMirror] = {
            k: _RowMirror(p, self.vocab)
            for k, p in self.plans.items() if p.mirrored}
        self.keyed = {k: self._keyed(k) for k in self.plans}
        self.width = {k: _MIN_WIDTH for k in self.plans}
        # the probed ring's slots when each side's width was last set
        self._width_ring = {k: self.plans[_other(k)].W for k in self.plans}
        self.last_width = 0

    def _keyed(self, side_key: str) -> bool:
        """Does this side's step probe with the keyed probe? It needs the
        equality key, the probed side's mirror, this side's keys on the
        host (no transform), and its triggers known there: rows it emits
        as EXPIRED are known only where it is mirrored or window-less."""
        plan = self.plans[side_key]
        side = self.rt.sides[side_key]
        other = _other(side_key)
        return (plan.key_fn is not None and self.plans[other].mirrored
                and not side.transforms
                and (plan.mirrored or plan.kind == "none"
                     or not self.rt.expired_read))

    @property
    def ring_capacity(self) -> int:
        """Slots of the largest growing ring (the ``window.<query>.
        capacity`` gauge); 0 where no side grows."""
        return max((p.W for p in self.plans.values() if p.grows),
                   default=0)

    def window_rows(self, side_key: str) -> int:
        """Live rows of a mirrored side's window, from its host mirror
        (no device pull): the ``join.window_rows`` gauge."""
        return self.rows[side_key].held

    def hot_key_rows(self, side_key: str) -> int:
        """Live rows of a mirrored side's most frequent key (a null key
        counts as one key), from its host mirror: the key skew, the
        ``join.hot_key_rows`` gauge."""
        mirror = self.rows[side_key]
        return max(int(mirror.counts.max(initial=0)), mirror.nulls)

    # ------------------------------------------------------------- state

    def init_state(self) -> dict:
        """Engine-private state keys to merge into the runtime's state
        pytree: the cross-stream sequence."""
        return {SEQ_KEY: jnp.int64(0)}

    # ------------------------------------------------------ restore path

    def rebuild_probe_state(self) -> None:
        """Re-derive the host row mirrors from the (canonical) ring state
        after a snapshot restore — the snapshot stores only the legacy
        ``[W]`` ring layout (``strip_engine_state``), so a legacy revision
        restores into the engine and vice versa bit-identically."""
        if self.rt._state is None:
            return
        for side_key in self.rows:
            self._rebuild_rows(side_key)
        self.rt._steps.clear()
        state = dict(self.rt._state)
        if SEQ_KEY not in state:
            state[SEQ_KEY] = jnp.int64(0)
        self.rt._state = state

    # ------------------------------------------------- adaptive capacity

    def prepare_batch(self, side_key: str, cols) -> bool:
        """Pre-dispatch host bookkeeping of one side's batch (a TIMER
        batch included): advance this side's row mirror by the step's own
        bookkeeping, grow its ring BEFORE the step could overflow it, and
        make sure the keyed probe's output width holds every candidate
        pair of the batch: the live rows of the other side that share a
        key with each trigger row, plus a row for each trigger that may
        emit a one-sided row. The other side's mirror is exact: its state
        does not change in this step. Returns True when capacities
        changed (the runtime's compiled side steps were dropped; fused
        groups must drop theirs too)."""
        plan = self.plans[side_key]
        mirror = self.rows.get(side_key)
        keyed = self.keyed[side_key]
        if mirror is None and not keyed:
            return False
        cur = (np.asarray(cols[VALID_KEY], bool)
               & (np.asarray(cols[TYPE_KEY]) == CURRENT))
        ids = _key_ids(plan, self.vocab, cols, cur)
        changed = False
        gone = ids[:0]
        if mirror is not None:
            clock = None
            if plan.kind == "time":
                ck = plan.stage.ts_key if plan.stage.external else TS_KEY
                clock = np.asarray(cols[ck], np.int64)[cur]
            gone = mirror.advance(ids, clock, self.rt._now())
            if plan.grows and mirror.held > plan.W:
                # the power of two the rows need, at least _GROWTH times
                # the ring
                self._grow_ring(side_key, max(_pow2(mirror.held),
                                              _GROWTH * plan.W))
                changed = True
        if keyed:
            side = self.rt.sides[side_key]
            trig, n_reset = self._triggers(side_key, cols, ids, gone)
            other = self.rows[_other(side_key)]
            need = n_reset
            if side.triggers:
                need += other.count_of(trig)
                if side.outer:
                    need += len(trig)
            width = self.width[side_key]
            if need > width:
                # the power of two the pairs need; where the probed ring
                # grew since, its window is filling and the pairs will
                # keep growing: at least _GROWTH times the last width
                ring = self.plans[_other(side_key)].W
                filling = ring > self._width_ring[side_key]
                self._width_ring[side_key] = ring
                self.width[side_key] = max(
                    _pow2(need), _GROWTH * width if filling else 0)
                self.rt._steps.pop(side_key, None)
                changed = True
            self.last_width = self.width[side_key]
        return changed

    def _triggers(self, side_key: str, cols, ids, gone):
        """(key ids of the rows of this step's window output that probe,
        RESET rows): its CURRENT rows, and, where anything reads the
        joined EXPIRED rows, the rows it emits as EXPIRED (those that
        left a length or time window; a window-less side's clones)."""
        plan = self.plans[side_key]
        stage = plan.stage
        parts = [ids]
        n_reset = 0
        if plan.kind == "none":
            if stage.empty_window:
                if stage.expired_needed:
                    parts.append(ids)
                if stage.emit_reset:
                    n_reset = len(ids)
        elif self.rt.expired_read:
            parts.append(gone)
        return np.concatenate(parts), n_reset

    def _grow_ring(self, side_key: str, capacity: int) -> None:
        """Re-lay one time side's ring into ``capacity`` slots, under a
        ``siddhi.grow`` span, holding the grown state once: the runtime
        lets go of the old state first, and the ring moves leaf by leaf
        (``TimeWindowStage.grown``). Both sides' steps re-jit (the other
        side's probe reads this ring)."""
        from siddhi_tpu.core.stream.junction import FatalQueryError
        from siddhi_tpu.observability.tracing import span

        rt = self.rt
        plan = self.plans[side_key]
        before = rt.state_bytes()
        old = rt._state
        state = dict(old)
        rt._state = old = None
        win = dict(state.pop(plan.win_key))
        slot_bytes = sum(v.dtype.itemsize for v in win["buf"].values())
        with span("grow", query=rt.name, side=side_key, from_slots=plan.W,
                  to_slots=capacity, bytes_before=before,
                  bytes_after=before + (capacity - plan.W) * slot_bytes
                  ) as sp:
            try:
                state[plan.win_key] = plan.stage.grown(win, capacity)
            except Exception as e:
                raise FatalQueryError(
                    f"query '{rt.name}': growing the {side_key} window's "
                    f"ring ({plan.W}->{capacity} slots) failed with its "
                    f"state half moved: {e}") from e
        plan.stage.capacity = plan.W = capacity
        rt._state = state
        rt._steps.clear()
        rt.note_growth(sp.ms)
        _LOG.info("query '%s': %s time window ring grown to %d slots",
                  rt.name, side_key, capacity)

    def _rebuild_rows(self, side_key: str) -> None:
        """The row mirror of one side from the ring a restore left: its
        live rows, oldest first (a grown ring restores at its own size)."""
        plan = self.plans[side_key]
        win = self.rt._state[plan.win_key]
        buf = {k: np.asarray(v) for k, v in win["buf"].items()}
        W = len(next(iter(buf.values())))
        if plan.grows:
            plan.stage.capacity = plan.W = W
        total = int(np.asarray(win["total"]))
        floor = plan.live_floor_np({k: np.asarray(v) for k, v in win.items()
                                    if k != "buf"})
        seq = np.arange(max(floor, total - W, 0), total)
        slots = seq % W
        rows = {k: v[slots] for k, v in buf.items()}
        ids = _key_ids(plan, self.vocab, rows, np.ones(len(seq), bool))
        clock = None
        if plan.kind == "time":
            ck = plan.stage.ts_key if plan.stage.external else TS_KEY
            clock = rows[ck].astype(np.int64)
        self.rows[side_key].reset_from(ids, clock, total)

    # -------------------------------------------------------- step build

    def build_side_step(self, side_key: str):
        """The fused (state, probe_cols, probe_valid, cols, now) ->
        (state', out) step of one side: transforms/filters -> window
        insert -> post-filters -> the keyed or the broadcast probe of the
        other side -> selector. The
        signature matches the legacy step's; the probe placeholders are
        unused (both surfaces live inside the state), and the runtime
        jits it as ``(state, cols, now)`` (``join_runtime._inert_probe``)."""
        rt = self.rt
        side = rt.sides[side_key]
        other_key = _other(side_key)
        other = rt.sides[other_key]
        splan = self.plans[side_key]
        oplan = self.plans[other_key]
        sel = rt.selector_plan
        on_cond = rt.on_cond
        split = rt.keyer is not None
        keyed = self.keyed[side_key]
        width = self.width[side_key]
        kdt = self.key_dtype
        expired_read = self.rt.expired_read
        other_view = getattr(other.window_stage, "arrival_contents",
                             other.window_stage.contents)
        # device instruments: with the knob on, the step also ships the
        # pairs it matched behind the sequence lane — the layout
        # JoinQueryRuntime._step_instrument_slots declares and the drain
        # decodes
        ins_on = rt._instruments_on()

        def _meta_suffix(seq, n_match):
            return ([seq.reshape(1), n_match.reshape(1)] if ins_on
                    else [seq.reshape(1)])

        def _materialize(wout, ev, match, one_sided, N, S):
            """Joined rows of the broadcast probe: [N, S] probe
            candidates + the one-sided column S flatten to row-major
            [N*(S+1)] columns, the layout the legacy path emits."""
            NW = N * (S + 1)
            joined: Dict[str, jnp.ndarray] = {}
            for a in side.definition.attributes:
                v = jnp.broadcast_to(wout[a.name][:, None], (N, S + 1))
                mk = jnp.broadcast_to(wout[a.name + "?"][:, None],
                                      (N, S + 1))
                joined[side.prefix + a.name] = v.reshape(NW)
                joined[side.prefix + a.name + "?"] = mk.reshape(NW)
            for a in other.definition.attributes:
                pc_ = jnp.broadcast_to(ev[other.prefix + a.name], (N, S))
                pm_ = jnp.broadcast_to(ev[other.prefix + a.name + "?"],
                                       (N, S))
                joined[other.prefix + a.name] = jnp.concatenate(
                    [pc_, jnp.zeros((N, 1), pc_.dtype)], axis=1).reshape(NW)
                joined[other.prefix + a.name + "?"] = jnp.concatenate(
                    [pm_, jnp.ones((N, 1), bool)], axis=1).reshape(NW)
            joined[VALID_KEY] = jnp.concatenate(
                [match, one_sided[:, None]], axis=1).reshape(NW)
            joined[TS_KEY] = jnp.repeat(wout[TS_KEY], S + 1)
            joined[TYPE_KEY] = jnp.repeat(wout[TYPE_KEY], S + 1)
            joined[GK_KEY] = jnp.zeros(NW, jnp.int32)
            joined[FLUSH_KEY] = jnp.repeat(
                jnp.arange(N, dtype=jnp.int32), S + 1)
            return joined

        def _keys(plan, cols, n, ctx):
            """A side's key column as the probe sorts it, and its nulls."""
            vals, mask = plan.key_fn(cols, ctx)
            vals = jnp.broadcast_to(jnp.asarray(vals), (n,)).astype(kdt)
            null = (jnp.broadcast_to(jnp.asarray(mask, bool), (n,))
                    if mask is not None else jnp.zeros((n,), bool))
            return jnp.where(null, jnp.zeros((), kdt), vals), null

        def _keyed_probe(state, wout, row_live, ctx):
            """The keyed probe (module docstring): the other side's ring
            sorted by (key, rank), each trigger row's range in it, and one
            expansion into ``width`` rows, trigger row by trigger row.
            Returns (joined columns, pairs matched, width overflow)."""
            N = wout[VALID_KEY].shape[0]
            M = width
            owin = state[oplan.win_key]
            ring = owin["buf"]
            Wo = oplan.W
            tkey, tnull = _keys(splan, wout, N, ctx)
            base = jnp.where(tnull, Wo, 0).astype(jnp.int32)
            if side.triggers:
                seq, live = oplan.stage.ring_seq(owin)
                rank = (seq - (owin["total"] - Wo)).astype(jnp.int32)
                okey, onull = _keys(oplan, ring, Wo, ctx)
                # live rows with a key, then live null keys, then the
                # dead: a range [base, base + Wo) holds one group alone
                rank = rank + jnp.where(live, jnp.where(onull, Wo, 0),
                                        2 * Wo).astype(jnp.int32)
                srank, lo, hi = _key_ranges(okey, rank, tkey, base, Wo)
                cnt = jnp.where(row_live, hi - lo, 0)
            else:
                srank = jnp.zeros((1,), jnp.int32)
                lo = cnt = jnp.zeros((N,), jnp.int32)
            reset = wout[VALID_KEY] & (wout[TYPE_KEY] == RESET)
            os_ = (row_live & side.outer & side.triggers) | reset
            blk = cnt + os_.astype(jnp.int32)
            ends = jnp.cumsum(blk)
            m = jnp.arange(M, dtype=jnp.int32)
            # each position's trigger row: the rows that ended at or before
            # it, a scatter and a running sum (``searchsorted`` of a million
            # positions took 62 ms on the chip, this 0.4)
            r = jnp.minimum(jnp.cumsum(
                jnp.zeros((M + 1,), jnp.int32).at[jnp.minimum(ends, M)].add(1)
            )[:M], N - 1)
            j = m - (ends[r] - blk[r])
            in_range = m < ends[N - 1]
            is_cand = in_range & (j < cnt[r])
            is_os = in_range & (j == cnt[r]) & os_[r]
            pos = jnp.clip(lo[r] + j, 0, srank.shape[0] - 1)
            slot = ((owin["total"] - Wo + (srank[pos] - base[r]))
                    % Wo).astype(jnp.int32)
            ev: Dict[str, jnp.ndarray] = {TS_KEY: wout[TS_KEY][r]}
            for a in side.definition.attributes:
                ev[side.prefix + a.name] = wout[a.name][r]
                ev[side.prefix + a.name + "?"] = wout[a.name + "?"][r]
            for a in other.definition.attributes:
                ev[other.prefix + a.name] = ring[a.name][slot]
                ev[other.prefix + a.name + "?"] = ring[a.name + "?"][slot]
            cond = (jnp.broadcast_to(on_cond(ev, ctx), (M,))
                    if on_cond is not None else True)
            match = is_cand & cond
            hit = jnp.zeros((N,), jnp.int32).at[r].add(
                match.astype(jnp.int32)) > 0
            one_sided = (row_live & ~hit & side.outer & side.triggers) | reset
            joined = dict(ev)
            for a in other.definition.attributes:
                v = joined[other.prefix + a.name]
                joined[other.prefix + a.name] = jnp.where(
                    is_os, jnp.zeros((), v.dtype), v)
                joined[other.prefix + a.name + "?"] = (
                    joined[other.prefix + a.name + "?"] | is_os)
            joined[VALID_KEY] = match | (is_os & one_sided[r])
            joined[TYPE_KEY] = wout[TYPE_KEY][r]
            joined[GK_KEY] = jnp.zeros((M,), jnp.int32)
            joined[FLUSH_KEY] = r
            return (joined, jnp.sum(match, dtype=jnp.int64),
                    ends[N - 1] > M)

        def step(state, probe_cols, probe_valid, cols, current_time):
            ctx = {"xp": jnp, "current_time": current_time}
            cols = dict(cols)
            strrank = cols.pop(STR_RANK, None)
            cols.pop(OKEY_KEY, None)
            for t in side.transforms:
                cols = t.apply(cols, ctx)
            valid = cols[VALID_KEY]
            timer = cols[TYPE_KEY] == TIMER
            for f in side.filters:
                valid = valid & (f(cols, ctx) | timer)
            cols[VALID_KEY] = valid
            new_state = dict(state)
            win_before = state[splan.win_key]
            conformed = conform_cols(side.window_stage, cols)
            with jax.named_scope(STATE_SCOPE):
                new_win, wout = side.window_stage.apply(win_before,
                                                        conformed, ctx)
            new_state[splan.win_key] = new_win
            wout = dict(wout)
            notify = wout.pop("__notify__", None)
            overflow = wout.pop("__overflow__", None)
            wout.pop("__flush__", None)
            wout.pop(OKEY_KEY, None)
            pvalid = wout[VALID_KEY]
            ptimer = wout[TYPE_KEY] == TIMER
            for f in side.post_filters:
                pvalid = pvalid & (f(wout, ctx) | ptimer)
            wout[VALID_KEY] = pvalid

            # overflow bitmask: 1 = window ring, 8 = selector
            # (distinctCount), 16 = keyed probe width, 32 =
            # a growing ring — decoded by JoinQueryRuntime.
            # overflow_knob_msg into the exact knob (16 and 32 name none:
            # the host sized both, so either is a bug)
            ovbits = jnp.int32(0)
            if overflow is not None:
                ovbits = ovbits | jnp.where(
                    jnp.asarray(overflow).astype(jnp.int32) > 0,
                    32 if splan.grows else 1, 0)

            with jax.named_scope(STATE_SCOPE):   # the probe
                N = wout[VALID_KEY].shape[0]
                if keyed:
                    # EXPIRED triggers probe only where something reads
                    # the joined EXPIRED rows (the selector drops them)
                    live_types = ((wout[TYPE_KEY] == CURRENT)
                                  | ((wout[TYPE_KEY] == EXPIRED)
                                     & expired_read))
                    with jax.named_scope(PROBE_SCOPE):
                        joined, n_match, wov = _keyed_probe(
                            state, wout, wout[VALID_KEY] & live_types, ctx)
                    ovbits = ovbits | (wov.astype(jnp.int32) * 16)
                else:
                    # ---- broadcast probe of the other side's whole
                    # window, oldest first (no key, a filtered or
                    # window-less side): the legacy path's surface
                    row_live = wout[VALID_KEY] & (
                        (wout[TYPE_KEY] == CURRENT)
                        | (wout[TYPE_KEY] == EXPIRED))
                    pcols, pvalid_o = other_view(state[oplan.win_key])
                    Wo = pvalid_o.shape[0]
                    ev = {TS_KEY: wout[TS_KEY][:, None]}
                    for a in other.definition.attributes:
                        ev[other.prefix + a.name] = pcols[a.name][None, :]
                        ev[other.prefix + a.name + "?"] = \
                            pcols[a.name + "?"][None, :]
                    for a in side.definition.attributes:
                        ev[side.prefix + a.name] = wout[a.name][:, None]
                        ev[side.prefix + a.name + "?"] = \
                            wout[a.name + "?"][:, None]
                    pv = pvalid_o[None, :]
                    if side.triggers:
                        cond = (on_cond(ev, ctx) if on_cond is not None
                                else jnp.ones((N, Wo), bool))
                        cond = jnp.broadcast_to(cond, (N, Wo))
                        match = row_live[:, None] & jnp.broadcast_to(
                            pv, (N, Wo)) & cond
                    else:
                        match = jnp.zeros((N, Wo), bool)
                    no_match = (row_live & ~jnp.any(match, axis=1)
                                & side.outer & side.triggers)
                    one_sided = no_match | (
                        wout[VALID_KEY] & (wout[TYPE_KEY] == RESET))
                    joined = _materialize(wout, ev, match, one_sided, N, Wo)
                    n_match = jnp.sum(match, dtype=jnp.int64)

            if strrank is not None:
                joined[STR_RANK] = strrank

            # ---- cross-stream total order: every dispatched step (either
            # side) increments ONE sequence; the meta carries it so the
            # pump's drain can verify FIFO == dispatch order
            seq = state[SEQ_KEY] + 1
            new_state[SEQ_KEY] = seq

            from siddhi_tpu.core.query.runtime import pack_meta

            if split:
                if notify is not None:
                    joined["__notify__"] = notify
                joined["__overflow__"] = ovbits
                with jax.named_scope(META_SCOPE):
                    out = pack_meta(joined)
                    out["__meta__"] = jnp.concatenate(
                        [out["__meta__"]] + _meta_suffix(seq, n_match))
                return new_state, out

            with jax.named_scope(SELECT_SCOPE):
                new_state["sel"], out = sel.apply(state["sel"], joined, ctx)
            sel_ov = out.pop("__overflow__", None)
            if sel_ov is not None:
                ovbits = ovbits | jnp.where(
                    jnp.asarray(sel_ov).astype(jnp.int32) > 0, 8, 0)
            out["__overflow__"] = ovbits
            if notify is not None:
                out["__notify__"] = notify
            with jax.named_scope(META_SCOPE):
                out = pack_meta(out)
                out["__meta__"] = jnp.concatenate(
                    [out["__meta__"]] + _meta_suffix(seq, n_match))
            return new_state, out

        return step
