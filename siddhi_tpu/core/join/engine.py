"""Device-resident partitioned join engine (PanJoin on device).

The legacy probe path (``core/query/join_runtime.build_side_step_fn``)
evaluates the ``on`` condition as one ``[N, W]`` broadcast compare of the
N trigger rows against the other side's whole W-slot ring and then
materializes every ``[N, W+1]`` joined column. This module replaces that
probe surface for eligible stream-stream window joins with a
PanJoin-style partitioned sub-structure ("A Partition-based Adaptive
Stream Join", PAPERS.md): each side's build state is indexed by a
hash-partitioned ``[P, W/P]`` sub-window directory with per-partition
occupancy, and a trigger row gathers ONLY its own hash partition of the
other side — the condition evaluates on ``[N, Wp]`` and the join
materializes ``[N, Wp+1]`` instead of ``[N, W+1]``, a ~P-fold cut of the
probe surface. One jitted step per arriving chunk performs
insert-into-own-side + the masked partition-local probe of the other
side, and stamps an explicit cross-stream sequence number into the meta
so left/right batches have a total order the CompletionPump can respect
(``join_runtime._pipeline_ok``).

Bit-identity with the legacy path (``tools/quick_join_check.py``) is
preserved by construction:

- the sub-window directory stores each member's global arrival sequence
  number (``gseq``); the member's legacy ring slot is ``gseq % W`` and
  its liveness is ``gseq >= floor`` (length windows: ``total - W``; time
  windows: ``expired_upto``) — the directory enumerates exactly the rows
  ``WindowStage.contents`` would, just partition-major;
- matched pairs re-sort by an explicit emission-order key
  ``trigger_row * (W + 1) + legacy_slot`` (one-sided/outer rows take
  slot ``W``), reproducing the legacy row-major ``[N, W+1]`` order
  exactly — the PR-7 okey convention applied within one step.

Partitioning engages only when the ``on`` condition carries an equality
conjunct over hashable key types (int/long/bool/string — floats keep the
broadcast compare: ``-0.0 == 0.0`` and NaN would break the equal-values
=> equal-hash invariant); without one the engine runs the same fused
step with the legacy-layout probe (P = 1), which is what keeps the
pipeline/fusion eligibility wins independent of the probe pruning.
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

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
_BIG = np.int64(2 ** 62)

# state keys of the per-side partition directories + the cross-stream
# sequence counter — stripped from snapshots (canonical capture is the
# legacy ring layout) and rebuilt at restore (rebuild_probe_state)
PIDX_KEYS = ("lpidx", "rpidx")
SEQ_KEY = "jseq"
ENGINE_STATE_KEYS = PIDX_KEYS + (SEQ_KEY,)

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

def extract_partition_keys(on_expr, left, right, dictionary):
    """Find an equality conjunct ``<left-side expr> == <right-side expr>``
    in the ``on`` condition (top level, or one conjunct of a top-level
    And) whose two values are hashable types, and compile each side's
    value closure against that side's OWN (unprefixed) columns. Returns
    ``{"left": fn, "right": fn}`` or None. Both closures cast to the
    promoted dtype before hashing so equal values always co-partition."""
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


# ------------------------------------------------------------ hashing

_MIX1 = 0xBF58476D1CE4E5B9
_MIX2 = 0x94D049BB133111EB


def hash_partition_dev(vals, P: int):
    """splitmix64 finalizer -> partition id [0, P) (device). P pow2."""
    h = jnp.asarray(vals).astype(jnp.int64).astype(jnp.uint64)
    h = (h ^ (h >> jnp.uint64(30))) * jnp.uint64(_MIX1)
    h = (h ^ (h >> jnp.uint64(27))) * jnp.uint64(_MIX2)
    h = h ^ (h >> jnp.uint64(31))
    return (h & jnp.uint64(P - 1)).astype(jnp.int32)


def hash_partition_np(vals, P: int):
    """Host mirror of ``hash_partition_dev`` — MUST stay bit-identical
    (snapshot rebuild re-partitions the restored rings with it)."""
    h = np.asarray(vals).astype(np.int64).astype(np.uint64)
    h = (h ^ (h >> np.uint64(30))) * np.uint64(_MIX1)
    h = (h ^ (h >> np.uint64(27))) * np.uint64(_MIX2)
    h = h ^ (h >> np.uint64(31))
    return (h & np.uint64(P - 1)).astype(np.int32)


def _pow2(n: int, start: int = 1) -> int:
    k = max(start, 1)
    while k < n:
        k *= 2
    return k


# ------------------------------------------------------------ side plans

class _SidePlan:
    """Per-side partition-directory parameters (``use_pidx`` False =
    this side keeps the legacy-layout probe surface)."""

    __slots__ = ("kind", "W", "use_pidx", "Wp", "key_fn", "pidx_key",
                 "win_key")

    def __init__(self, side_key: str, side, pspec, P: int, slack: int):
        stage = side.window_stage
        if isinstance(stage, LengthWindowStage):
            self.kind, self.W = "length", int(stage.length)
        elif isinstance(stage, TimeWindowStage):
            self.kind, self.W = "time", int(stage.capacity)
        else:
            self.kind, self.W = "none", 1
        self.win_key = "lwin" if side_key == "left" else "rwin"
        self.pidx_key = "lpidx" if side_key == "left" else "rpidx"
        self.key_fn = pspec[side_key] if pspec is not None else None
        # partitioning pays only when the ring meaningfully exceeds the
        # partition count (tiny rings keep the full-surface probe), and
        # engages only when the host can mirror the ring's partition
        # occupancy EXACTLY for the adaptive sub-window growth: every
        # valid CURRENT row inserts at slot seq % W (length AND time
        # rings share that mechanic), so in-step filters/transforms —
        # which drop or rewrite rows device-side — keep the full-surface
        # probe (still fused, pipelined and fusion-eligible)
        self.use_pidx = (self.kind != "none" and self.key_fn is not None
                         and P > 1 and self.W >= 4 * P
                         and not side.filters and not side.transforms)
        self.Wp = (_pow2((self.W * slack + P - 1) // P)
                   if self.use_pidx else 0)

    # liveness floor: members with gseq >= floor are exactly the rows the
    # legacy contents() view reports live
    def live_floor(self, win_state):
        if self.kind == "length":
            return jnp.maximum(win_state["total"] - self.W, jnp.int64(0))
        return jnp.maximum(win_state["expired_upto"], jnp.int64(0))

    def live_floor_np(self, win_state):
        if self.kind == "length":
            return max(int(win_state["total"]) - self.W, 0)
        return max(int(win_state["expired_upto"]), 0)


class DeviceJoinEngine:
    """Owns the per-side partition plans and builds the fused
    insert+probe step of each side (``JoinQueryRuntime`` delegates its
    ``build_side_step_fn`` here when attached)."""

    def __init__(self, runtime, pspec):
        self.rt = runtime
        ac = runtime.app_context
        cfg_p = int(getattr(ac, "join_partitions", 0) or 0)
        if cfg_p <= 0:
            # auto: partition pruning is meant to pay where gathers are
            # wide and cheap (accelerators; P=8 there is NOT measured on
            # a chip yet — PERF.md); the CPU backend keeps the fused
            # full-surface probe, which holds legacy throughput while
            # still buying pipeline/fusion/mesh eligibility. A backend
            # that cannot initialize raises here.
            import jax

            cfg_p = 1 if jax.default_backend() == "cpu" else 8
        P = _pow2(cfg_p)
        self.P = max(1, min(P, 64))
        self.slack = max(1, int(getattr(ac, "join_partition_slack", 2)))
        # adaptive sub-window growth (PanJoin's re-partitioning): when a
        # batch would push one partition's ring occupancy past Wp, the
        # host grows Wp BEFORE dispatch (capped at pow2(W), where skew
        # cannot overflow) instead of dying mid-stream. Off = static
        # provisioning; overflow is then a FatalQueryError naming
        # siddhi_tpu.join_partition_slack.
        self.grow = bool(getattr(ac, "join_partition_grow", True))
        # host mirrors of each side's ring partition occupancy: slot =
        # seq % W is pure ring mechanics (length AND time rings), so the
        # mirror is EXACT with zero device pulls — a partition's live
        # members are a subset of its ring slots, which bounds the
        # directory pressure (see prepare_batch)
        self._mirror: Dict[str, dict] = {}
        # per-side (total, [P] occ) memo of the mirror bincount: the P
        # registered partition gauges each read one lane, and a scrape
        # must not pay P ring passes (content-keyed, not time-keyed —
        # exactness is preserved)
        self._occ_memo: Dict[str, tuple] = {}
        self.plans: Dict[str, _SidePlan] = {
            k: _SidePlan(k, runtime.sides[k], pspec, self.P, self.slack)
            for k in ("left", "right")
        }

    @property
    def partitioned_probe(self) -> bool:
        return any(p.use_pidx for p in self.plans.values())

    # ------------------------------------------------------------- state

    def init_pidx_state(self) -> dict:
        """Engine-private state keys to merge into the runtime's state
        pytree (empty directories + the cross-stream sequence)."""
        st = {SEQ_KEY: jnp.int64(0)}
        for plan in self.plans.values():
            if plan.use_pidx:
                st[plan.pidx_key] = {
                    "gseq": jnp.full((self.P, plan.Wp), -1, jnp.int64),
                    "cnt": jnp.zeros((self.P,), jnp.int64),
                }
        return st

    def partition_occupancy(self, side_key: str) -> np.ndarray:
        """Live members per partition of one side ([P] int64) — the
        ``siddhi_join_partition_rows`` gauge backend. ZERO device pulls
        by construction (a /metrics scrape must never touch the device,
        transfer-guard-verified): the primary source is the last drained
        ``fill.<side>`` instrument lanes, which the step computes from
        the directory it already holds and ships on the meta pull that
        happens anyway (``observability/instruments.py``); with
        instruments off (``profile_device_instruments: false``) the
        host ring-occupancy mirror answers instead — exact for length
        rings, an upper bound for time rings whose expired rows linger
        in their slots until overwritten."""
        plan = self.plans[side_key]
        if not plan.use_pidx:
            return np.zeros(self.P, np.int64)
        last = getattr(self.rt, "_instr_last", {}).get(f"fill.{side_key}")
        if last is not None and np.asarray(last).shape[0] == self.P:
            return np.asarray(last, np.int64)
        mir = self._mirror.get(side_key)
        if mir is None:
            return np.zeros(self.P, np.int64)
        memo = self._occ_memo.get(side_key)
        if memo is not None and memo[0] == mir["total"]:
            return memo[1]
        ring = mir["ring"]
        occ = np.bincount(ring[ring >= 0],
                          minlength=self.P).astype(np.int64)[: self.P]
        self._occ_memo[side_key] = (mir["total"], occ)
        return occ

    # ------------------------------------------------------ restore path

    def rebuild_probe_state(self) -> None:
        """Re-derive the partition directories (and host occupancy
        mirrors) from the (canonical) ring state after a snapshot
        restore — the snapshot stores only the legacy ``[W]`` ring layout
        (``strip_engine_state``), so a legacy revision restores into the
        engine and vice versa bit-identically. Live rows re-insert in
        global-sequence order; partition offsets may differ from the
        never-restored trajectory, but probe results cannot (membership
        and ``gseq`` are identical)."""
        if self.rt._state is None:
            return
        for side_key in self.plans:
            self._rebuild_side(side_key)
        state = dict(self.rt._state)
        if SEQ_KEY not in state:
            state[SEQ_KEY] = jnp.int64(0)
        self.rt._state = state

    def _ring_partitions(self, plan, win) -> np.ndarray:
        """Partition id of every OCCUPIED ring slot of one side ([W]
        int32, -1 = empty) — hashed from the ring's own buffered values,
        host-side."""
        total = int(np.asarray(win["total"]))
        filled = min(total, plan.W)
        ring_p = np.full(plan.W, -1, np.int32)
        if filled:
            buf = {k: np.asarray(v) for k, v in win["buf"].items()}
            vals, mask = plan.key_fn(buf, {"xp": np})
            vals = np.broadcast_to(np.asarray(vals), (plan.W,))
            pr = hash_partition_np(vals, self.P).astype(np.int32)
            if mask is not None:
                pr = np.where(
                    np.broadcast_to(np.asarray(mask, bool), (plan.W,)),
                    np.int32(0), pr)
            ring_p[:filled] = pr[:filled]
        return ring_p

    def _rebuild_side(self, side_key: str) -> None:
        """Rebuild ONE side's directory + host mirror from its ring
        (restore path and adaptive growth). Auto-sizes Wp up to pow2(W)
        when the restored ring is hotter than the current sub-windows
        (growth on); with growth off an unrepresentable ring is fatal,
        naming the static knob."""
        from siddhi_tpu.core.stream.junction import FatalQueryError

        plan = self.plans[side_key]
        if not plan.use_pidx or self.rt._state is None:
            return
        state = dict(self.rt._state)
        win = state[plan.win_key]
        win_h = {k: np.asarray(v) for k, v in win.items()
                 if k in ("total", "expired_upto")}
        total = int(np.asarray(win["total"]))
        ring_p = self._ring_partitions(plan, win)
        occ = np.bincount(ring_p[ring_p >= 0], minlength=self.P)
        need = int(occ.max(initial=0))
        if need > plan.Wp and self.grow:
            plan.Wp = min(_pow2(2 * need), _pow2(plan.W))
        floor = plan.live_floor_np(win_h)
        gseqs = np.arange(floor, total, dtype=np.int64)
        gseq_grid = np.full((self.P, plan.Wp), -1, np.int64)
        cnt = np.zeros(self.P, np.int64)
        if gseqs.size:
            slots = (gseqs % plan.W).astype(np.int64)
            p = ring_p[slots].astype(np.int64)
            for i in range(gseqs.size):     # gseq-ascending fill
                pi = int(p[i])
                if cnt[pi] >= plan.Wp:
                    raise FatalQueryError(
                        f"query '{self.rt.name}': "
                        f"{self.rt.overflow_knob_msg(code=4)}")
                gseq_grid[pi, cnt[pi]] = gseqs[i]
                cnt[pi] += 1
        state[plan.pidx_key] = {"gseq": jnp.asarray(gseq_grid),
                                "cnt": jnp.asarray(cnt)}
        self.rt._state = state
        self._mirror[side_key] = {"ring": ring_p, "total": total}

    # ------------------------------------------------- adaptive capacity

    def prepare_batch(self, side_key: str, cols) -> bool:
        """Pre-dispatch host bookkeeping of one side's batch: advance the
        side's ring-occupancy mirror with the batch's hashed keys and
        GROW the sub-window capacity BEFORE the step could overflow it —
        PanJoin's adaptive re-partitioning, keyed off exact ring
        mechanics (slot = seq % W) with zero device pulls. A partition's
        live members are always a subset of its ring slots, so
        ``Wp >= max ring occupancy`` makes directory overflow impossible.
        Returns True when capacities changed (the runtime's compiled
        side steps were dropped; fused groups must drop theirs too)."""
        plan = self.plans[side_key]
        if not plan.use_pidx:
            return False
        valid = (np.asarray(cols[VALID_KEY], bool)
                 & (np.asarray(cols[TYPE_KEY]) == CURRENT))
        n = int(valid.sum())
        if n == 0:
            return False
        B = valid.shape[0]
        hvals, hmask = plan.key_fn(cols, {"xp": np})
        hvals = np.broadcast_to(np.asarray(hvals), (B,))
        p = hash_partition_np(hvals, self.P).astype(np.int32)
        if hmask is not None:
            p = np.where(np.broadcast_to(np.asarray(hmask, bool), (B,)),
                         np.int32(0), p)
        p = p[valid]
        mir = self._mirror.get(side_key)
        if mir is None:
            mir = self._mirror[side_key] = {
                "ring": np.full(plan.W, -1, np.int32), "total": 0}
        W = plan.W
        ring = mir["ring"]
        if n >= W:
            slots = (mir["total"] + np.arange(n - W, n)) % W
            ring[:] = -1
            ring[slots] = p[n - W:]
        else:
            slots = (mir["total"] + np.arange(n)) % W
            ring[slots] = p
        mir["total"] += n
        occ = np.bincount(ring[ring >= 0], minlength=self.P)
        need = int(occ.max(initial=0))
        if need <= plan.Wp or not self.grow:
            # growth off: the in-step overflow check surfaces the skew as
            # FatalQueryError naming siddhi_tpu.join_partition_slack
            return False
        plan.Wp = min(_pow2(2 * need), _pow2(plan.W))
        _LOG.info(
            "query '%s': join partition sub-windows of side %s grown to "
            "%d (ring occupancy %d) — adaptive re-partition",
            self.rt.name, side_key, plan.Wp, need)
        # rebuild the directory from the PRE-batch device ring (the step
        # inserts this batch into the grown directory), then restore the
        # batch-advanced mirror — it is the post-dispatch truth
        self._rebuild_side(side_key)
        self._mirror[side_key] = mir
        self.rt._steps.clear()
        return True

    def _shrink_target(self, side_key: str) -> Optional[tuple]:
        """(current Wp, shrink target) for one side, or None when the
        side is already right-sized. The target keeps the same 2x
        headroom the growth path provisions (``_pow2(2 * need)``) and
        never drops below the configured-slack initial sizing — the
        autopilot may only release what adaptive growth added. Host
        mirror / drained instrument lanes only (zero device pulls)."""
        plan = self.plans[side_key]
        if not plan.use_pidx:
            return None
        occ = self.partition_occupancy(side_key)
        need = int(occ.max(initial=0))
        floor = _pow2((plan.W * self.slack + self.P - 1) // self.P)
        target = max(_pow2(2 * need), floor)
        if target >= plan.Wp:
            return None
        return plan.Wp, target

    def shrink_candidates(self) -> Dict[str, tuple]:
        """Read-only autopilot signal: sides whose Wp could shrink back
        after a skew burst passed — {side: (wp, target)}."""
        out = {}
        for side_key in self.plans:
            t = self._shrink_target(side_key)
            if t is not None:
                out[side_key] = t
        return out

    def shrink_partitions(self) -> Dict[str, tuple]:
        """Release over-provisioned sub-window capacity — the reverse of
        ``prepare_batch``'s adaptive growth, through the SAME directory
        rebuild path (so probe membership and gseq order are identical
        by construction, only the capacity changes). Caller holds the
        runtime's owner lock; pipelined state futures are safe — the
        rebuild materializes the logical current state exactly as the
        growth path does. Returns {side: (old_wp, new_wp)}."""
        shrunk: Dict[str, tuple] = {}
        if self.rt._state is None:
            return shrunk
        for side_key in self.plans:
            t = self._shrink_target(side_key)
            if t is None:
                continue
            old_wp, target = t
            plan = self.plans[side_key]
            plan.Wp = target
            # _rebuild_side auto-grows if the ring is hotter than the
            # occupancy signal suggested — shrink can never overflow
            self._rebuild_side(side_key)
            shrunk[side_key] = (old_wp, plan.Wp)
            _LOG.info(
                "query '%s': join partition sub-windows of side %s "
                "shrunk %d -> %d (ring occupancy fell) — autopilot "
                "re-partition", self.rt.name, side_key, old_wp, plan.Wp)
        if shrunk:
            self.rt._steps.clear()
        return shrunk

    # -------------------------------------------------------- step build

    def build_side_step(self, side_key: str):
        """The fused (state, probe_cols, probe_valid, cols, now) ->
        (state', out) step of one side: transforms/filters -> window
        insert -> post-filters -> directory insert (own side) + masked
        partition-local probe (other side) -> selector. The signature
        matches the legacy builder so ``process_side_batch`` stays the
        single host driver; the probe placeholders are unused (both
        surfaces live inside the state)."""
        rt = self.rt
        side = rt.sides[side_key]
        other_key = "right" if side_key == "left" else "left"
        other = rt.sides[other_key]
        splan = self.plans[side_key]
        oplan = self.plans[other_key]
        sel = rt.selector_plan
        on_cond = rt.on_cond
        split = rt.keyer is not None
        P, slack = self.P, self.slack
        # device instruments: with the knob on, the step also ships each
        # partitioned side's per-partition directory fill behind the
        # sequence lane — the layout JoinQueryRuntime._step_instrument_
        # slots declares and the drain decodes (captured at build; the
        # step cache is cleared whenever capacities change)
        ins_on = rt._instruments_on()

        def _meta_suffix(new_state, seq):
            suffix = [seq.reshape(1)]
            if ins_on:
                for plan in (self.plans["left"], self.plans["right"]):
                    if not plan.use_pidx:
                        continue
                    gseq = new_state[plan.pidx_key]["gseq"]
                    floor = plan.live_floor(new_state[plan.win_key])
                    suffix.append(jnp.sum(
                        (gseq >= floor) & (gseq >= 0),
                        axis=1, dtype=jnp.int64))
            return suffix

        def _pidx_insert(pidx, cols, win_before, win_after):
            """Scatter this batch's inserted rows into the side's own
            partition directory; returns (pidx', overflow_flag)."""
            valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
            B = valid_cur.shape[0]
            total0 = win_before["total"]
            rank = jnp.cumsum(valid_cur.astype(jnp.int64)) - 1
            gseq = total0 + rank
            floor_after = splan.live_floor(win_after)
            # rows evicted/expired within this very batch never enter the
            # directory (the legacy ring drops them the same way)
            ins = valid_cur & (gseq >= floor_after)
            vals, mask = splan.key_fn(cols, {"xp": jnp})
            vals = jnp.broadcast_to(jnp.asarray(vals), (B,))
            p = hash_partition_dev(vals, P).astype(jnp.int64)
            if mask is not None:
                p = jnp.where(jnp.broadcast_to(jnp.asarray(mask, bool), (B,)),
                              jnp.int64(0), p)
            p = jnp.where(ins, p, jnp.int64(P))          # P = dropped
            maskp = p[None, :] == jnp.arange(P, dtype=jnp.int64)[:, None]
            pos = jnp.cumsum(maskp.astype(jnp.int64), axis=1) - 1
            pc = jnp.clip(p, 0, P - 1).astype(jnp.int32)
            pos_row = jnp.take_along_axis(pos, pc[None, :], axis=0)[0]
            n_per = jnp.sum(maskp.astype(jnp.int64), axis=1)
            off = (pidx["cnt"][pc] + pos_row) % splan.Wp
            flat = jnp.where(p < P, pc.astype(jnp.int64) * splan.Wp + off,
                             jnp.int64(P * splan.Wp))
            gflat = pidx["gseq"].reshape(-1)
            occupant = gflat[jnp.clip(flat, 0, P * splan.Wp - 1)]
            # overwriting a LIVE occupant (or >Wp inserts into one
            # partition this batch) silently drops probe members — fatal,
            # named knob (join_partition_slack / join_partitions)
            ov = (jnp.any((flat < P * splan.Wp)
                          & (occupant >= floor_after) & (occupant >= 0))
                  | jnp.any(n_per > splan.Wp)).astype(jnp.int32)
            g2 = gflat.at[flat].set(gseq, mode="drop").reshape(P, splan.Wp)
            return {"gseq": g2, "cnt": pidx["cnt"] + n_per}, ov

        def _materialize(wout, ev, match, one_sided, N, S):
            """Joined-row materialization shared by BOTH probe branches
            (partition-gathered and legacy-layout): [N, S] probe
            candidates + the one-sided column S flatten to row-major
            [N*(S+1)] columns, the layout the legacy broadcast probe
            emits — keep this the single source of truth so the two
            branches cannot drift apart."""
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

            # overflow bitmask: 1 = window ring, 4 = partition sub-window,
            # 8 = selector (distinctCount) — decoded by
            # JoinQueryRuntime.overflow_knob_msg into the exact knob
            ovbits = jnp.int32(0)
            if overflow is not None:
                ovbits = ovbits | jnp.where(
                    jnp.asarray(overflow).astype(jnp.int32) > 0, 1, 0)

            with jax.named_scope(STATE_SCOPE):   # directory insert + probe
                # ---- insert this batch into OUR OWN partition directory
                if splan.use_pidx:
                    new_state[splan.pidx_key], pov = _pidx_insert(
                        state[splan.pidx_key], conformed, win_before, new_win)
                    ovbits = ovbits | (pov * 4)

                N = wout[VALID_KEY].shape[0]
                W = oplan.W if oplan.kind != "none" else None
                row_live = wout[VALID_KEY] & (
                    (wout[TYPE_KEY] == CURRENT) | (wout[TYPE_KEY] == EXPIRED))
                gathered = oplan.use_pidx and side.triggers

                if gathered:
                    # ---- masked partition-local probe: gather only the
                    # trigger row's hash partition of the other side
                    opidx = state[oplan.pidx_key]
                    oring = state[oplan.win_key]["buf"]
                    ofloor = oplan.live_floor(state[oplan.win_key])
                    vals, mask = splan.key_fn(wout, ctx)
                    vals = jnp.broadcast_to(jnp.asarray(vals), (N,))
                    p_i = hash_partition_dev(vals, P)
                    if mask is not None:
                        p_i = jnp.where(
                            jnp.broadcast_to(jnp.asarray(mask, bool), (N,)),
                            jnp.int32(0), p_i)
                    cand_g = opidx["gseq"][p_i]                     # [N, Wp]
                    cand_live = (cand_g >= ofloor) & (cand_g >= 0)
                    cand_slot = (jnp.clip(cand_g, 0) % W).astype(jnp.int32)
                    Wp = oplan.Wp
                    ev: Dict[str, jnp.ndarray] = {TS_KEY: wout[TS_KEY][:, None]}
                    for a in other.definition.attributes:
                        ev[other.prefix + a.name] = oring[a.name][cand_slot]
                        ev[other.prefix + a.name + "?"] = \
                            oring[a.name + "?"][cand_slot]
                    for a in side.definition.attributes:
                        ev[side.prefix + a.name] = wout[a.name][:, None]
                        ev[side.prefix + a.name + "?"] = \
                            wout[a.name + "?"][:, None]
                    cond = (on_cond(ev, ctx) if on_cond is not None
                            else jnp.ones((N, Wp), bool))
                    cond = jnp.broadcast_to(cond, (N, Wp))
                    match = row_live[:, None] & cand_live & cond
                    no_match = (row_live & ~jnp.any(match, axis=1)
                                & side.outer & side.triggers)
                    one_sided = no_match | (
                        wout[VALID_KEY] & (wout[TYPE_KEY] == RESET))
                    NW = N * (Wp + 1)
                    joined = _materialize(wout, ev, match, one_sided, N, Wp)
                    # emission-order key: (trigger row, LEGACY ring slot) —
                    # sorting by it reproduces the [N, W+1] row-major order
                    # of the broadcast probe exactly (one-sided rows at W)
                    stride = jnp.int64(W + 1)
                    slot_cols = jnp.concatenate(
                        [cand_slot.astype(jnp.int64),
                         jnp.full((N, 1), W, jnp.int64)], axis=1)
                    okey = (jnp.arange(N, dtype=jnp.int64)[:, None] * stride
                            + slot_cols).reshape(NW)
                    okey = jnp.where(joined[VALID_KEY], okey, _BIG)
                    order = jnp.argsort(okey, stable=True)
                    joined = {k: v[order] for k, v in joined.items()}
                else:
                    # ---- legacy-layout probe (P=1 / untriggering side /
                    # passthrough other side): identical to the broadcast path
                    pcols, pvalid_o = other.window_stage.contents(
                        state[oplan.win_key])
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
                        [out["__meta__"]] + _meta_suffix(new_state, seq))
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
                    [out["__meta__"]] + _meta_suffix(new_state, seq))
            return new_state, out

        return step
