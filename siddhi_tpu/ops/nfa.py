"""NFA pattern/sequence engine over dense per-key match-slot tensors.

Replaces the reference's pending-state-event lists
(``query/input/stream/state/StreamPreStateProcessor.java:364-403`` — a
sequential scan of a linked list of partial matches per incoming event) with
fixed-capacity slot tensors:

    active  [K, S] bool      — slot holds a partial match
    stepi   [K, S] int32     — pattern position the slot is resting at
    bits    [K, S] int32     — matched-sides mask for logical and/or steps
    vbits   [K, S] int32     — violated absent sides of a logical step
    sts     [K, S] int64     — first-event timestamp (drives `within`)
    adl/adl2 [K, S] int64    — absent-side deadlines (`not X for t`)
    wts<g>  [K, S] int64     — per-`within`-scope start timestamps
    capdone [K, S] int32     — bitmask of capture-ids already filled
                               (top bits flag started within-scopes)
    caps    {c<cid>__<col>: [K, S]} — captured attribute values per ref
            (count refs also keep per-index slots c<cid>i<i>__<col> and an
             occurrence counter c<cid>__#n)

K = partition keys (1 when unpartitioned), S = slot capacity. One device
step processes a whole batch: rows are grouped per key (`_per_key_layout`)
into *rounds*, round r holding each key's r-th row of the batch, so the
rows of a round have distinct keys. A ``lax.while_loop`` runs every round
as key-distinct tiles of at most W rows (``_tile_rows``: a share of the
batch, never more than K), one iteration a tile: it gathers its keys'
slot views [W, S], updates them in parallel and scatters them back, so a
round costs what its own rows cost and not what the batch's B x S would.

Semantics reproduced (reference file:line):
- PATTERN keeps pending matches across non-matching events; SEQUENCE kills
  every pending match an event fails to extend
  (``StreamPreStateProcessor.java:382-395``).
- ``every`` re-arms the start state for every event
  (``addEveryState``:230-247); without it the start arms exactly once.
  Mid-chain ``every`` marks the wrapped element *sticky*: a slot resting at
  a sticky step never advances itself — each match forks an advanced child
  (reference EveryInnerStateRuntime re-initialisation).
- ``within`` expires partial matches lazily against the triggering event's
  timestamp (``isExpired``:118, ``expireEvents``:326); sub-pattern
  ``(...) within t`` scopes clock from the scope's first captured event
  (reference WithinStateElement / StateInputStream.java:61-75).
- Absent states (``not X [filter] for t`` — reference
  ``AbsentStreamPreStateProcessor.java``): a slot *waits* at the absent
  step with a deadline; a matching event before the deadline kills the
  wait (violation), the deadline passing advances it. Deadlines fire
  lazily against same-key traffic and eagerly via the scheduler's TIMER
  sweep (``apply_timer``). Logical steps may have absent sides with or
  without ``for`` (``LogicalPreStateProcessor``): without a wait the
  absent side is satisfied-unless-violated; with a wait it completes at
  its deadline.
- Count states ``e<min:max>`` accumulate into ONE partial match (no
  per-event forking — ``CountPatternTestCase.testQuery1`` expects a single
  match for 3 accumulated events); once ``min`` is reached the match is
  eligible for the next step, and min-0 count steps are skippable
  (``testQuery7``: B alone matches ``A<0:5> -> B``). Unindexed references
  (``e1.price``) read the **last** captured event
  (``StateEvent.getStreamEvent``: CURRENT walks to chain end,
  ``event/state/StateEvent.java:152-156``); ``e1[i].price`` reads
  occurrence i (null when fewer were captured).
- Logical ``and``/``or`` match sides in any order
  (``LogicalPreStateProcessor``); when ONE event matches both sides,
  side 1 captures (executor order — SequenceTestCase.testQuery8).
- An event matching both a count's absorb and the next step's advance
  takes the ADVANCE ("furthest-advanced transition wins") — validated
  against the reference corpus (CountPatternTestCase testQuery10-12
  expect exactly this: one match with the ambiguous event advanced, no
  absorb fork). ``e[last]``/``e[last-k]`` indexing is supported.

Known gaps (reported as CompileError): absent states inside SEQUENCE
queries (the reference forbids them too).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.ops.expressions import (
    PK_KEY,
    TS_KEY,
    TYPE_KEY,
    VALID_KEY,
    ColumnRef,
    CompileError,
    Resolver,
)
from siddhi_tpu.ops.keyed_windows import _per_key_layout
from siddhi_tpu.query_api.definitions import AttrType, StreamDefinition
from siddhi_tpu.query_api.execution import (
    AbsentStreamStateElement,
    CountStateElement,
    EveryStateElement,
    LogicalStateElement,
    NextStateElement,
    StateInputStream,
    StateInputStreamType,
    StreamStateElement,
)
from siddhi_tpu.query_api.expressions import Expression, Variable

CURRENT, EXPIRED, TIMER, RESET = 0, 1, 2, 3
# the generic engine's round loop (a ``while_loop`` inside the state
# scope): the benchmark's ``step_nfa_rounds_ms`` reads it from a trace
ROUNDS_SCOPE = "siddhi.nfa_rounds"
ANY_MAX = 2 ** 30
# numpy on purpose: jnp scalars at module level initialize the backend
# at import (graftlint R1 — the force_host_devices breaker class)
FAR_FUTURE = np.int64(2 ** 62)
# T0 sentinel for capture-less armed heads: within counts from the first
# capture; 2**60 keeps T0 + within far below int64 overflow
_T0_FAR = np.int64(2 ** 60)
# the generic engine's tile: a loop iteration takes at most 1/_TILE_SHARE
# of a batch's rows, and no fewer than _TILE_FLOOR where the batch and the
# key capacity allow. The share was chosen on a TPU v5e at 16,384-row
# batches over 16,384 keys: a batch's loop took 49.9 ms at W = 1,024, 54.6
# at 2,048 and 77.8 at 4,096 (PERF.md, cell 8): an iteration costs about
# 1.0 ms of its own there and a row 0.8 us, so the rows of a 128-row tile
# cost a tenth of its iteration and narrower tiles only add iterations
_TILE_SHARE = 16
_TILE_FLOOR = 128


def _tile_rows(B: int, K: int) -> int:
    """W, the rows of one iteration of the generic engine's loop: never
    wider than the batch, nor than the key capacity (a round holds at most
    K distinct keys, so an unpartitioned pattern takes a row a tile)."""
    return min(B, K, max(_TILE_FLOOR, B // _TILE_SHARE))


# --------------------------------------------------------------------- plan


@dataclass
class CaptureSpec:
    """One capturable stream reference (``e1=...``)."""

    cid: int
    ref_id: Optional[str]
    stream_id: str
    definition: StreamDefinition
    is_count: bool = False
    n_idx: int = 0               # indexed slots kept (max referenced idx + 1)
    last_offsets: set = field(default_factory=set)  # e[last - k] offsets used
    last_ring: int = 0           # shift-register depth for e[last - k] on
    #                              OPEN-ENDED counts (`+`/`<n:>`): the last
    #                              k+1 events ride dense ring columns instead
    #                              of bounded indexed slots


@dataclass
class SideSpec:
    """One stream-consuming side of a step (logical steps have two)."""

    stream_id: str
    definition: StreamDefinition
    capture: Optional[CaptureSpec]       # None for absent sides
    filter_exprs: list = field(default_factory=list)  # query-api Expressions
    cond: Optional[Callable] = None                   # compiled later
    bit: int = 1
    absent: bool = False
    wait_ms: Optional[int] = None        # absent `for <t>` deadline


@dataclass
class StepSpec:
    index: int
    kind: str                    # 'stream' | 'count' | 'absent' | 'and' | 'or'
    sides: List[SideSpec]
    min_count: int = 1
    max_count: int = 1
    sticky: bool = False         # mid-chain `every` re-arm point
    wait_ms: Optional[int] = None  # absent steps

    @property
    def need_bits(self) -> int:
        """Sides that must affirmatively fire for an 'and' step to
        complete: present sides plus absent sides with a deadline (absent
        sides *without* a wait are satisfied-unless-violated)."""
        b = 0
        for s in self.sides:
            if not s.absent or s.wait_ms is not None:
                b |= s.bit
        return b

    @property
    def skippable(self) -> bool:
        return self.kind == "count" and self.min_count == 0

    @property
    def waitish(self) -> bool:
        """The step holds resting slots with deadlines."""
        if self.kind == "absent":
            return True
        return self.kind in ("and", "or") and any(
            s.absent and s.wait_ms is not None for s in self.sides)


@dataclass
class NFAPlan:
    steps: List[StepSpec]
    captures: List[CaptureSpec]
    every: bool
    sequence: bool
    within: Optional[int]        # milliseconds, whole-pattern
    slots: int
    stream_ids: List[str]        # unique consumed stream ids, stable order
    scopes: List[Tuple[int, int, int]] = field(default_factory=list)
    # `every (...)` group spans: start step -> end step. A new group
    # iteration arms only when no live slot is still INSIDE the span —
    # the reference starts the next iteration on group COMPLETION
    # (StreamPostStateProcessor.process -> addEveryState), so grouped
    # chains are non-overlapping (EveryPatternTestCase:282) while
    # single-state every (span start==end) stays per-event
    every_groups: Dict[int, int] = field(default_factory=dict)
    # non-every pattern whose head is a COUNT state: the start state
    # re-arms when no chain is live (after a completed match or a
    # within-expiry) — CountPreStateProcessor keeps collecting on the
    # shared state event (CountPatternTestCase.testQuery20 expects two
    # matches); plain stream heads match once, as our corpus pins
    rearm_on_empty: bool = False

    @property
    def last_step(self) -> int:
        return len(self.steps) - 1

    @property
    def eager_tail_start(self) -> int:
        """First index t such that steps t..last are ALL min-0 counts: a
        chain resting at/after t is already complete and emits eagerly
        (reference processMinCountReached fires at min 0 on addState —
        SequenceTestCase.testQuery3 `every e1, e2*` emits per e1)."""
        t = len(self.steps)
        for st in reversed(self.steps):
            if st.kind == "count" and st.min_count == 0:
                t = st.index
            else:
                break
        return t

    @property
    def has_absent(self) -> bool:
        return any(
            st.kind == "absent" or any(s.absent for s in st.sides)
            for st in self.steps
        )

    def scope_bit(self, g: int) -> int:
        """capdone bit flagging scope g as started (top bits, below sign)."""
        return 1 << (30 - g)

    def arm_step(self) -> Optional[int]:
        """Head step that needs an *armed* waiting slot at key creation:
        the first non-skippable step when it is absent-ish (a pure-present
        head arms lazily through fresh starts instead)."""
        for st in self.steps:
            if st.skippable:
                continue
            if st.kind == "absent" or (
                st.kind in ("and", "or") and any(s.absent for s in st.sides)
            ):
                return st.index
            return None
        return None


def _flatten(el, elements: List, scopes: List, sticky_at: set, depth: int,
             groups: Dict[int, int]):
    """Linearize the state-element tree; record `within` scopes as element
    index ranges, mid-chain `every` re-arm points, and every-group spans."""
    if isinstance(el, NextStateElement):
        a = len(elements)
        _flatten(el.state, elements, scopes, sticky_at, depth + 1, groups)
        _flatten(el.next, elements, scopes, sticky_at, depth + 1, groups)
        if el.within is not None:
            scopes.append((a, len(elements) - 1, el.within))
        return
    if isinstance(el, EveryStateElement):
        a = len(elements)
        _flatten(el.state, elements, scopes, sticky_at, depth + 1, groups)
        groups[a] = len(elements) - 1
        if a > 0:
            sticky_at.add(a)          # mid-chain every: re-arm point
        if el.within is not None:
            scopes.append((a, len(elements) - 1, el.within))
        return
    a = len(elements)
    elements.append(el)
    if getattr(el, "within", None) is not None:
        scopes.append((a, a, el.within))


def build_nfa_plan(
    state_stream: StateInputStream,
    definitions: Dict[str, StreamDefinition],
    slots: int,
) -> NFAPlan:
    """Linearize the state-element tree into step specs (the role of
    ``StateInputStreamParser.java:76-210`` building the InnerStateRuntime
    tree — flat here because the chain is executed as step indices)."""
    within = state_stream.within
    root = state_stream.state_element

    elements: List = []
    scopes: List[Tuple[int, int, int]] = []
    sticky_at: set = set()
    every_groups: Dict[int, int] = {}
    _flatten(root, elements, scopes, sticky_at, 0, every_groups)

    # `every` wrapping the head (whole pattern or first element) is the
    # global re-arm flag; scopes recorded at element 0 spanning everything
    # with the root's within fold into the whole-pattern within
    every = False
    if isinstance(root, EveryStateElement):
        every = True
        if root.within is not None:
            w = root.within
            within = w if within is None else min(within, w)
            scopes = [s for s in scopes
                      if not (s[0] == 0 and s[1] == len(elements) - 1 and s[2] == w)]
    elif elements:
        # `every A -> B` parses as Next(Every(A), B): an every wrapping the
        # FIRST element is the global re-arm flag (_flatten only marks
        # every at positions > 0 as sticky)
        first = root
        while isinstance(first, NextStateElement):
            first = first.state
        if isinstance(first, EveryStateElement):
            every = True

    sequence = state_stream.state_type == StateInputStreamType.SEQUENCE

    captures: List[CaptureSpec] = []
    steps: List[StepSpec] = []

    from siddhi_tpu.query_api.execution import Filter

    def make_side(stream_el: StreamStateElement, is_count: bool,
                  absent: bool) -> SideSpec:
        s = stream_el.stream
        sid = s.stream_id
        if sid not in definitions:
            raise CompileError(f"pattern stream '{sid}' is not defined")
        cap = None
        if not absent:
            cap = CaptureSpec(
                cid=len(captures),
                ref_id=s.stream_reference_id,
                stream_id=sid,
                definition=definitions[sid],
                is_count=is_count,
            )
            captures.append(cap)
        elif s.stream_reference_id is not None:
            raise CompileError(
                "absent (`not`) pattern streams cannot be captured with e=")
        filters = []
        for h in s.handlers:
            if isinstance(h, Filter):
                filters.append(h.expression)
            else:
                raise CompileError(
                    "only [filter] handlers are allowed on pattern streams"
                )
        wait = getattr(stream_el, "waiting_time", None) if absent else None
        return SideSpec(
            stream_id=sid,
            definition=definitions[sid],
            capture=cap,
            filter_exprs=filters,
            absent=absent,
            wait_ms=wait,
        )

    for ei, el in enumerate(elements):
        idx = len(steps)
        sticky = ei in sticky_at
        if isinstance(el, AbsentStreamStateElement):
            if el.waiting_time is None:
                raise CompileError(
                    "a chained absent pattern needs `for <time>`")
            side = make_side(el, is_count=False, absent=True)
            steps.append(StepSpec(index=idx, kind="absent", sides=[side],
                                  sticky=sticky, wait_ms=el.waiting_time))
        elif isinstance(el, CountStateElement):
            side = make_side(el.state, is_count=True, absent=False)
            mn = el.min_count if el.min_count != CountStateElement.ANY else 0
            mx = el.max_count if el.max_count != CountStateElement.ANY else ANY_MAX
            steps.append(StepSpec(index=idx, kind="count", sides=[side],
                                  min_count=mn, max_count=mx, sticky=sticky))
        elif isinstance(el, LogicalStateElement):
            sides = []
            for sub in (el.stream1, el.stream2):
                absent = isinstance(sub, AbsentStreamStateElement)
                sides.append(make_side(sub, is_count=False, absent=absent))
            sides[0].bit, sides[1].bit = 1, 2
            if el.type == "or":
                for s in sides:
                    if s.absent and s.wait_ms is None:
                        raise CompileError(
                            "an absent `or` side needs `for <time>`")
            if all(s.absent for s in sides) and el.type == "and":
                for s in sides:
                    if s.wait_ms is None:
                        raise CompileError(
                            "an all-absent `and` needs `for <time>` on both sides")
            steps.append(StepSpec(index=idx, kind=el.type, sides=sides,
                                  sticky=sticky))
        elif isinstance(el, StreamStateElement):
            side = make_side(el, is_count=False, absent=False)
            steps.append(StepSpec(index=idx, kind="stream", sides=[side],
                                  sticky=sticky))
        else:
            raise CompileError(f"unsupported state element {type(el).__name__}")

    stream_ids: List[str] = []
    for st in steps:
        for side in st.sides:
            if side.stream_id not in stream_ids:
                stream_ids.append(side.stream_id)

    for st in steps:
        # sticky counts re-arm by forking on advance; the forked child's
        # entry is only implemented for plain stream successors (and
        # emission when the count is the last step)
        if (st.kind == "count" and st.sticky and st.index < len(steps) - 1
                and steps[st.index + 1].kind != "stream"):
            raise CompileError(
                "`every` on a count state followed by a "
                f"{steps[st.index + 1].kind} state is not supported")

    # `every` wrapping an ABSENT head (plain or all-absent logical) can't
    # restart through fresh starts (absent heads live as armed waiting
    # slots) — make the armed slot sticky so each elapsed quiet window
    # forks a pending successor (EveryAbsentSequenceTestCase /
    # EveryAbsentPatternTestCase re-arming). Heads with a present side
    # carry captures and keep the non-sticky path.
    if (every and len(steps) > 1 and steps[0].waitish
            and all(s.absent for s in steps[0].sides)):
        steps[0].sticky = True

    if len(scopes) > 8:
        raise CompileError("at most 8 nested `within` scopes are supported")
    if len(captures) > 30 - len(scopes):
        raise CompileError("too many pattern captures for one query")

    return NFAPlan(
        steps=steps,
        captures=captures,
        every=every,
        sequence=sequence,
        within=within,
        slots=slots,
        stream_ids=stream_ids,
        scopes=scopes,
        every_groups=every_groups,
        rearm_on_empty=(not every and not sequence and bool(steps)
                        and steps[0].kind == "count"),
    )


def _walk_expressions(expr, visit):
    if expr is None:
        return
    visit(expr)
    for attr_name in ("left", "right", "expression"):
        child = getattr(expr, attr_name, None)
        if isinstance(child, Expression):
            _walk_expressions(child, visit)
    params = getattr(expr, "parameters", None)
    if params:
        for p in params:
            _walk_expressions(p, visit)


def assign_indexed_captures(plan: NFAPlan, exprs: List) -> None:
    """Scan expressions for ``e1[i].attr`` references and size each
    capture's indexed storage (reference keeps the full StreamEvent chain;
    here only statically-referenced indices are materialized)."""

    def visit(e):
        if not isinstance(e, Variable) or e.stream_index is None:
            return
        idx = e.stream_index
        for cap in plan.captures:
            if e.stream_id not in (cap.ref_id, cap.stream_id):
                continue
            if idx == "last":
                return   # the unindexed capture IS the last event
            if isinstance(idx, tuple) and idx[0] == "last":
                k = -idx[1]
                if not cap.is_count:
                    raise CompileError(
                        "e[last - k] needs a count capture (e<min:max>)")
                mx = _count_max_of(plan, cap)
                if mx >= ANY_MAX:
                    # open-ended count (`+`, `<n:>`): the last k+1 events
                    # ride a dense shift register (ring columns) — the
                    # bounded-slot scheme can't size the chain
                    cap.last_ring = max(cap.last_ring, k)
                else:
                    # bounded: the k-th from the end is a runtime position;
                    # keep every indexed slot up to the bounded max
                    cap.n_idx = max(cap.n_idx, mx)
                cap.last_offsets.add(k)
                return
            if not isinstance(idx, int):
                raise CompileError(
                    f"event index '{idx}' is not supported (e[<int>], "
                    f"e[last], e[last - k])")
            if cap.is_count:  # non-count refs hold a single event
                cap.n_idx = max(cap.n_idx, idx + 1)
            return
        raise CompileError(f"unknown pattern reference '{e.stream_id}'")

    for expr in exprs:
        _walk_expressions(expr, visit)


# ----------------------------------------------------------------- columns


def cap_col(cid: int, attr: str) -> str:
    return f"c{cid}__{attr}"


def cap_idx_col(cid: int, i: int, attr: str) -> str:
    return f"c{cid}i{i}__{attr}"


def cap_cnt_col(cid: int) -> str:
    return f"c{cid}__#n"


def cap_lastk_col(cid: int, j: int, attr: str) -> str:
    """Ring column: the j-th-from-last captured event of an OPEN count —
    'R' namespace, distinct from cap_last_col's bounded-slot 'L' derived
    columns so the two storage schemes can never alias."""
    return f"c{cid}R{j}__{attr}"


PRESENT = "@present"   # synthetic attr: StateEvent presence (bare `e2 is
#                        null` / `e2[last-k] is null` checks read its mask)


def cap_last_col(cid: int, k: int, attr: str) -> str:
    return f"c{cid}L{k}__{attr}"


def _count_max_of(plan: NFAPlan, cap: CaptureSpec) -> int:
    for st in plan.steps:
        for side in st.sides:
            if side.capture is cap:
                return st.max_count
    return ANY_MAX


def scope_col(g: int) -> str:
    return f"wts{g}"


def _resolve_cap(plan: NFAPlan, var: Variable) -> Optional[Tuple[CaptureSpec, object]]:
    from siddhi_tpu.query_api.definitions import Attribute

    sid = var.stream_id
    if var.attribute_name is None:
        # bare indexed ref (`e2[last-1] is null`): StateEvent presence —
        # a synthetic BOOL column whose null mask is exactly absence
        for cap in plan.captures:
            if sid in (cap.ref_id, cap.stream_id):
                return cap, Attribute(PRESENT, AttrType.BOOL)
        return None
    for cap in plan.captures:
        if sid is not None and sid not in (cap.ref_id, cap.stream_id):
            continue
        try:
            attr = cap.definition.attribute(var.attribute_name)
        except Exception:
            continue
        return cap, attr
    if sid is None:
        # bare capture name (`e2 is null` — StateEvent presence check)
        for cap in plan.captures:
            if var.attribute_name == cap.ref_id:
                return cap, Attribute(PRESENT, AttrType.BOOL)
    return None


def _cap_ref(plan: NFAPlan, var: Variable) -> Optional[ColumnRef]:
    got = _resolve_cap(plan, var)
    if got is None:
        return None
    cap, attr = got
    idx = var.stream_index
    if idx is not None:
        if idx == "last":
            return ColumnRef(cap_col(cap.cid, attr.name), attr.type)
        if isinstance(idx, tuple) and idx[0] == "last":
            k = -idx[1]
            if cap.last_ring >= k > 0:
                # open-count shift register: live in state, so usable in
                # mid-chain side filters too
                return ColumnRef(cap_lastk_col(cap.cid, k, attr.name),
                                 attr.type)
            # bounded count: derived column materialized by the flatten stage
            return ColumnRef(cap_last_col(cap.cid, k, attr.name), attr.type)
        if not isinstance(idx, int):
            raise CompileError(
                "only e[<int>], e[last], e[last - k] indexing is supported")
        if idx >= max(cap.n_idx, 1) and cap.is_count:
            raise CompileError(
                f"index {idx} out of the capture's sized range"
            )
        if not cap.is_count and idx != 0:
            raise CompileError("only count states capture multiple events")
        if cap.is_count:
            return ColumnRef(cap_idx_col(cap.cid, idx, attr.name), attr.type)
    return ColumnRef(cap_col(cap.cid, attr.name), attr.type)


class NFASideResolver(Resolver):
    """Resolve variables inside a step-side filter: the side's own stream
    attributes read the current event; references to other captures read
    capture columns (last event by default, e[i] for indexed)."""

    def __init__(self, side: SideSpec, plan: NFAPlan, dictionary):
        self.side = side
        self.plan = plan
        self.dictionary = dictionary

    def resolve(self, var: Variable) -> ColumnRef:
        sid = var.stream_id
        side = self.side
        ref_id = side.capture.ref_id if side.capture is not None else None
        own = sid is None or sid == ref_id or (ref_id is None and sid == side.stream_id)
        if own and var.stream_index is None:
            try:
                attr = side.definition.attribute(var.attribute_name)
                return ColumnRef(attr.name, attr.type)
            except Exception:
                if sid is not None and _cap_ref(self.plan, var) is None:
                    raise
        ref = _cap_ref(self.plan, var)
        if ref is not None:
            return ref
        raise CompileError(
            f"cannot resolve '{(sid + '.') if sid else ''}{var.attribute_name}' "
            f"in pattern filter"
        )

    def encode_string(self, s: str) -> int:
        return self.dictionary.encode(s)


class NFAOutputResolver(Resolver):
    """Resolve selector variables of a pattern query against capture
    columns (``e1.price``, ``e1[0].price``, or bare stream names)."""

    def __init__(self, plan: NFAPlan, dictionary):
        self.plan = plan
        self.dictionary = dictionary
        self.synthetic: Dict[str, AttrType] = {}

    def resolve(self, var: Variable) -> ColumnRef:
        if var.attribute_name in self.synthetic and var.stream_id is None:
            return ColumnRef(var.attribute_name, self.synthetic[var.attribute_name])
        ref = _cap_ref(self.plan, var)
        if ref is not None:
            return ref
        raise CompileError(
            f"cannot resolve '{(var.stream_id + '.') if var.stream_id else ''}"
            f"{var.attribute_name}' in pattern selector"
        )

    def encode_string(self, s: str) -> int:
        return self.dictionary.encode(s)


# ------------------------------------------------------------ device stage


def _cap_state_cols(plan: NFAPlan) -> Dict[str, np.dtype]:
    """State columns for captured values (value + null-mask per attribute,
    per capture; indexed slots and an occurrence counter for counts)."""
    from siddhi_tpu.ops.types import dtype_of

    cols: Dict[str, np.dtype] = {}
    for cap in plan.captures:
        for a in cap.definition.attributes:
            cols[cap_col(cap.cid, a.name)] = dtype_of(a.type)
            cols[cap_col(cap.cid, a.name) + "?"] = np.bool_
            for i in range(cap.n_idx):
                cols[cap_idx_col(cap.cid, i, a.name)] = dtype_of(a.type)
                cols[cap_idx_col(cap.cid, i, a.name) + "?"] = np.bool_
            for j in range(1, cap.last_ring + 1):
                cols[cap_lastk_col(cap.cid, j, a.name)] = dtype_of(a.type)
                cols[cap_lastk_col(cap.cid, j, a.name) + "?"] = np.bool_
        cols[cap_col(cap.cid, TS_KEY)] = np.int64
        if cap.is_count:
            cols[cap_cnt_col(cap.cid)] = np.int32
    return cols


class NFAStage:
    """Device NFA: per-input-stream step functions over shared slot state."""

    def __init__(self, plan: NFAPlan):
        self.plan = plan
        self.cap_cols = _cap_state_cols(plan)
        self.scope_cols = [scope_col(g) for g in range(len(plan.scopes))]
        # loop-free kernel for simple two-step chains (see _fast_side);
        # differential tests flip this off to pin fast == generic
        self.fast_enabled = True

    def init_state(self, num_keys: int = 1) -> dict:
        K, S = num_keys, self.plan.slots
        state = {
            "active": jnp.zeros((K, S), bool),
            "stepi": jnp.zeros((K, S), jnp.int32),
            "bits": jnp.zeros((K, S), jnp.int32),
            "vbits": jnp.zeros((K, S), jnp.int32),
            "sts": jnp.zeros((K, S), jnp.int64),
            "adl": jnp.zeros((K, S), jnp.int64),
            "adl2": jnp.zeros((K, S), jnp.int64),
            "capdone": jnp.zeros((K, S), jnp.int32),
            "consumed": jnp.zeros((K,), bool),
            "armed": jnp.zeros((K,), bool),
            "nfa_overflow": jnp.int32(0),
        }
        for g in self.scope_cols:
            state[g] = jnp.zeros((K, S), jnp.int64)
        for name, dt in self.cap_cols.items():
            # '?' mask columns start TRUE: an uncaptured reference (e.g.
            # e1[0].price before anything collected) is NULL, and null
            # comparisons are false (reference StateEvent returns null
            # for absent events; CompareConditionExecutor null guards)
            state[name] = (jnp.ones((K, S), dt) if name.endswith("?")
                           else jnp.zeros((K, S), dt))
        return state

    # ............................................ static eligibility chains

    def _advance_sources(self, j: int) -> List[int]:
        """Resting positions p < j a slot can advance from when step j's
        event arrives: walk back across count steps; positions before a
        count with min > 0 are unreachable."""
        out = []
        p = j - 1
        while p >= 0:
            st = self.plan.steps[p]
            if st.kind != "count":
                break
            out.append(p)
            if st.min_count != 0:
                break
            p -= 1
        return out

    def _fresh_ok(self, j: int) -> bool:
        """A fresh (unstarted) match can begin at step j iff every earlier
        step is a skippable min-0 count and step j itself has no absent
        machinery (absent heads run through *armed* waiting slots)."""
        st = self.plan.steps[j]
        if st.kind == "absent" or any(s.absent for s in st.sides):
            return False
        return all(self.plan.steps[p].skippable for p in range(j))

    # ........................................................ slot entering

    def _enter(self, V: dict, mask2d, j: int, ts2d):
        """Slots (masked) come to rest at step j: set position, clear the
        logical bookkeeping, arm absent deadlines, start entry scopes.
        ``ts2d`` broadcasts against [B, S]."""
        plan = self.plan
        w = lambda dst, val: jnp.where(mask2d, val, dst)  # noqa: E731
        V["ST"] = w(V["ST"], j)
        V["BT"] = w(V["BT"], 0)
        V["VB"] = w(V["VB"], 0)
        if j <= plan.last_step:
            st = plan.steps[j]
            if st.kind == "absent":
                V["ADL"] = w(V["ADL"], ts2d + jnp.int64(st.wait_ms))
            elif st.kind in ("and", "or"):
                for side in st.sides:
                    if side.absent and side.wait_ms is not None:
                        key = "ADL" if side.bit == 1 else "AD2"
                        V[key] = w(V[key], ts2d + jnp.int64(side.wait_ms))
        return V

    def _start_capture_scopes(self, V: dict, mask2d, j: int, ts2d):
        """Scopes whose start step j captured its first event now.

        A scope starting at a capture-LESS absent step does NOT start at
        arrival or arming — the reference measures `within` across
        captured events only (a head-absent StateEvent has no events, so
        its timestamp stays -1 and isExpired can't fire:
        AbsentPatternTestCase q42, `not A for 1 sec -> e2 within 2 sec`
        matches however long the quiet stretch was). Such scopes anchor
        at their first capturing successor via the `ST > a` branch below."""
        plan = self.plan
        for g, (a, b, t) in enumerate(plan.scopes):
            # a capture AT the scope's start step anchors it — including
            # captures on the present side of a MIXED waitish logical head
            # (`not A for t and e2=B`): within counts from e2's capture
            starts_here = a == j
            # first capture AFTER a capture-less waitish scope head (the
            # `started` guard keeps only the earliest capture's timestamp)
            enters_here = (a < j <= b and plan.steps[a].waitish
                           and all(s.capture is None
                                   for s in plan.steps[a].sides))
            if starts_here or enters_here:
                started = (V["CD"] & plan.scope_bit(g)) != 0
                m = mask2d & ~started
                V["SC"][g] = jnp.where(m, ts2d, V["SC"][g])
                V["CD"] = jnp.where(m, V["CD"] | plan.scope_bit(g), V["CD"])
        return V

    # .......................................................... expiry pass

    def _expire(self, V: dict, ts2d):
        """Kill partial matches past the whole-pattern `within` or past a
        started scope's bound (reference expireEvents)."""
        plan = self.plan
        A = V["A"]
        if plan.within is not None:
            A = A & ~(ts2d > V["T0"] + jnp.int64(plan.within))
        for g, (a, b, t) in enumerate(plan.scopes):
            if a == 0 and b == plan.last_step:
                # scope == whole pattern: same as plan.within on T0
                A = A & ~(((V["CD"] & plan.scope_bit(g)) != 0)
                          & (ts2d > V["SC"][g] + jnp.int64(t)))
                continue
            started = (V["CD"] & plan.scope_bit(g)) != 0
            in_scope = (V["ST"] > a) & (V["ST"] <= b)
            if plan.steps[a].waitish:
                in_scope = in_scope | (V["ST"] == a)
            A = A & ~(started & in_scope & (ts2d > V["SC"][g] + jnp.int64(t)))
        V["A"] = A
        return V

    # ...................................................... deadline engine

    def _cascade(self, V: dict, ts2d, emit, ets, fork_reqs: List):
        """Advance waiting slots whose absent deadlines have passed; one
        ascending pass chains consecutive waits. ``fork_reqs`` collects
        (mask2d, target_step, arm_ts2d) for sticky re-arms needing a forked
        child (allocated by the caller)."""
        plan = self.plan
        L = plan.last_step
        for st in plan.steps:
            j = st.index
            if st.kind == "absent":
                at = V["A"] & (V["ST"] == j)
                due = at & (ts2d >= V["ADL"])
                if st.sticky:
                    if j == L:
                        emit = emit | due
                        ets = jnp.where(due, V["ADL"], ets)
                    elif j == 0:
                        # head every-absent: pending successors carry no
                        # captures, so keep at most ONE per key (the
                        # reference replaces rather than stacks them)
                        pending = jnp.any(V["A"] & (V["ST"] == j + 1),
                                          axis=1)[:, None]
                        fork_reqs.append((due & ~pending, j + 1, V["ADL"]))
                    else:
                        fork_reqs.append((due, j + 1, V["ADL"]))
                    V["ADL"] = jnp.where(due, V["ADL"] + jnp.int64(st.wait_ms),
                                         V["ADL"])
                else:
                    if j == L:
                        emit = emit | due
                        ets = jnp.where(due, V["ADL"], ets)
                        V["A"] = V["A"] & ~due
                    else:
                        adl = V["ADL"]
                        V = self._enter(V, due, j + 1, adl)
            elif st.kind in ("and", "or"):
                # completion timestamp: 'and' completes when the LAST due
                # side fires (max over due-now deadlines); 'or' when the
                # FIRST does (min) — only deadlines firing now count
                is_and = st.kind == "and"
                comp_ts = None
                init = -FAR_FUTURE if is_and else FAR_FUTURE
                fired = jnp.zeros_like(V["A"])
                for side in st.sides:
                    if not (side.absent and side.wait_ms is not None):
                        continue
                    adlx = V["ADL"] if side.bit == 1 else V["AD2"]
                    due_s = (
                        V["A"] & (V["ST"] == j) & (ts2d >= adlx)
                        & ((V["BT"] & side.bit) == 0)
                        & ((V["VB"] & side.bit) == 0)
                    )
                    V["BT"] = jnp.where(due_s, V["BT"] | side.bit, V["BT"])
                    fired = fired | due_s
                    cand = jnp.where(due_s, adlx, init)
                    if comp_ts is None:
                        comp_ts = cand
                    else:
                        comp_ts = (jnp.maximum if is_and else jnp.minimum)(
                            comp_ts, cand)
                if comp_ts is None:
                    continue
                comp_ts = jnp.where(fired, comp_ts, ts2d)
                if st.kind == "and":
                    nb = st.need_bits
                    comp = fired & ((V["BT"] & nb) == nb)
                else:
                    comp = fired
                if st.sticky:
                    if j == L:
                        emit = emit | comp
                        ets = jnp.where(comp, comp_ts, ets)
                    elif j == 0 and all(s.absent for s in st.sides):
                        # head every-absent logical: capture-less pending
                        # successors dedupe per key (see the absent branch)
                        pending = jnp.any(V["A"] & (V["ST"] == j + 1),
                                          axis=1)[:, None]
                        fork_reqs.append((comp & ~pending, j + 1, comp_ts))
                    else:
                        fork_reqs.append((comp, j + 1, comp_ts))
                    # re-arm the parent's deadlines for the next interval
                    for side in st.sides:
                        if side.absent and side.wait_ms is not None:
                            key = "ADL" if side.bit == 1 else "AD2"
                            V[key] = jnp.where(
                                comp, V[key] + jnp.int64(side.wait_ms), V[key])
                    V["BT"] = jnp.where(comp, 0, V["BT"])
                    V["VB"] = jnp.where(comp, 0, V["VB"])
                else:
                    if j == L:
                        emit = emit | comp
                        ets = jnp.where(comp, comp_ts, ets)
                        V["A"] = V["A"] & ~comp
                    else:
                        V = self._enter(V, comp, j + 1, comp_ts)
        return V, emit, ets

    def _next_deadline(self, state) -> jnp.ndarray:
        """Earliest pending absent deadline across all keys/slots (FAR_FUTURE
        when none) — drives scheduler wake-up."""
        plan = self.plan
        nd = FAR_FUTURE
        A, ST = state["active"], state["stepi"]
        for st in plan.steps:
            j = st.index
            if st.kind == "absent":
                wait = A & (ST == j)
                nd = jnp.minimum(nd, jnp.min(jnp.where(wait, state["adl"], FAR_FUTURE)))
            elif st.kind in ("and", "or"):
                for side in st.sides:
                    if side.absent and side.wait_ms is not None:
                        adlx = state["adl"] if side.bit == 1 else state["adl2"]
                        wait = (
                            A & (ST == j)
                            & ((state["bits"] & side.bit) == 0)
                            & ((state["vbits"] & side.bit) == 0)
                        )
                        nd = jnp.minimum(nd, jnp.min(jnp.where(wait, adlx, FAR_FUTURE)))
        return nd

    # ....................................................... fork allocator

    def _alloc_forks(self, V: dict, req2d, overflow):
        """Allocate one free slot per requesting slot and copy the source
        slot's whole per-slot state into it. Returns (V, dst_mask,
        overflow); callers then `_enter`/capture at dst_mask positions."""
        S = self.plan.slots
        A = V["A"]
        B = A.shape[0]
        free = ~A
        n_free = jnp.sum(free, axis=1)
        fs = jnp.argsort(
            jnp.where(free, jnp.arange(S)[None, :], S + jnp.arange(S)[None, :]),
            axis=1)
        rank = jnp.cumsum(req2d, axis=1, dtype=jnp.int32) - 1
        can = req2d & (rank < n_free[:, None])
        overflow = overflow + jnp.sum(req2d & ~can).astype(jnp.int32)
        dst = jnp.where(can, jnp.take_along_axis(fs, jnp.clip(rank, 0, S - 1), axis=1), S)
        src_idx = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
        ident = jnp.concatenate(
            [src_idx, jnp.zeros((B, 1), jnp.int32)], axis=1)
        src_of_dst = ident.at[jnp.arange(B)[:, None], dst].set(
            src_idx, mode="drop")[:, :S]
        dst_mask = jnp.zeros((B, S + 1), bool).at[
            jnp.arange(B)[:, None], dst].set(True, mode="drop")[:, :S]

        def copy(col):
            g = jnp.take_along_axis(col, src_of_dst, axis=1)
            return jnp.where(dst_mask, g, col)

        for key in ("ST", "BT", "VB", "T0", "ADL", "AD2", "CD"):
            V[key] = copy(V[key])
        V["CP"] = {n: copy(c) for n, c in V["CP"].items()}
        V["SC"] = [copy(c) for c in V["SC"]]
        V["A"] = A | dst_mask
        return V, dst_mask, overflow

    # .................................................. one stream's step

    def apply_stream(self, stream_id: str, state: dict, cols: dict, ctx: dict):
        """Process one batch arriving on ``stream_id``; returns
        (new_state, out_cols) where out_cols is a flattened [B*(S+1)] match
        emission (capture columns + __ts__/__type__/__valid__/__gk__).

        Dispatches to the loop-free fast kernel for simple two-step chains
        (the dominant production shape — BASELINE config #4); everything
        else takes the generic per-round ``while_loop`` engine."""
        side_kind = self._fast_side(stream_id) if self.fast_enabled else None
        if side_kind is not None:
            return self._apply_stream_fast(stream_id, state, cols, ctx,
                                           side_kind)
        return self._apply_stream_generic(stream_id, state, cols, ctx)

    def expire_to(self, state, hwm_per_key):
        """Physically clear every pending past its `within` deadline as
        of its KEY's event-time high-water mark ``hwm_per_key`` ([K]).
        The fast kernels expire LAZILY (masks, no state writes) — exact
        for monotone feeds; before a host-forced fallback to the generic
        engine (out-of-order batch), the runtime applies the clears the
        generic engine would already have made, so the fallback cannot
        resurrect an expired pending. Per key because the generic
        `_expire` only advances each row's own key's clock."""
        w = self.plan.within
        if w is None:
            return state
        state = dict(state)
        state["active"] = state["active"] & ~(
            state["sts"] + jnp.int64(w)
            < jnp.asarray(hwm_per_key)[:, None])
        return state

    def _fast_side(self, stream_id: str):
        """'head'/'tail' when ``stream_id`` feeds a fast-eligible plan:
        exactly two plain single-side stream steps on DIFFERENT streams,
        simple captures, no counts/absent/logical/sticky/scopes — the
        `e1=A -> e2=B` / `e1=A, e2=B` family (with or without `every` /
        whole-pattern `within`). For these, same-batch serial dependence
        reduces to closed forms (see _apply_stream_fast), so no round
        loop is needed."""
        plan = self.plan
        if len(plan.steps) != 2 or plan.scopes or plan.rearm_on_empty:
            return None
        # head `every (...)` groups: only the trivial per-event span {0: 0}
        # (plain `every e1`) keeps plain-every semantics
        if any(a != b or a != 0 for a, b in plan.every_groups.items()):
            return None
        for st in plan.steps:
            if st.kind != "stream" or st.sticky or len(st.sides) != 1:
                return None
            s = st.sides[0]
            if s.absent or s.wait_ms is not None or s.capture is None:
                return None
            c = s.capture
            if c.is_count or c.n_idx or c.last_offsets or c.last_ring:
                return None
        s0, s1 = plan.steps[0].sides[0], plan.steps[1].sides[0]
        if s0.stream_id == s1.stream_id:
            return None
        if stream_id == s0.stream_id:
            return "head"
        if stream_id == s1.stream_id:
            return "tail"
        return None

    def _fast_ev(self, CP, CD, B, fresh: bool):
        """Eval dict for a side condition, mirroring the generic round's
        construction for non-count captures: capture cols [B, S] (or fresh
        NULLs [B, 1]), presence synthetics, current attrs [B, 1]."""
        plan = self.plan
        ev = {}
        if fresh:
            for n in self.cap_cols:
                ev[n] = (jnp.ones((B, 1), CP[n].dtype) if n.endswith("?")
                         else jnp.zeros((B, 1), CP[n].dtype))
            for cap in plan.captures:
                ev[cap_col(cap.cid, PRESENT)] = jnp.ones((B, 1), bool)
                ev[cap_col(cap.cid, PRESENT) + "?"] = jnp.ones((B, 1), bool)
        else:
            ev.update(CP)
            for cap in plan.captures:
                ev[cap_col(cap.cid, PRESENT)] = jnp.ones_like(CD, bool)
                ev[cap_col(cap.cid, PRESENT) + "?"] = (
                    CD & (1 << cap.cid)) == 0
        return ev

    def _fast_out(self, emit, emit_caps, ts, cols, pk, B):
        """[B, S(+1)] emission tensors -> the generic flattened format."""
        S = self.plan.slots
        out_valid = jnp.zeros((B, S + 1), bool).at[:, :S].set(emit)
        out_caps = {}
        for n, dt in self.cap_cols.items():
            z = jnp.zeros((B, S + 1), dt)
            if n in emit_caps:
                z = z.at[:, :S].set(jnp.where(emit, emit_caps[n], z[:, :S]))
            out_caps[n] = z
        out_caps["__capdone__"] = jnp.zeros((B, S + 1), jnp.int32).at[
            :, :S].set(jnp.where(emit, emit_caps["__capdone__"], 0))
        out_ts = jnp.broadcast_to(ts[:, None], (B, S + 1))
        return self._flatten_out(out_valid, out_caps, out_ts, ts, cols, pk, B)

    def _apply_stream_fast(self, stream_id, state, cols, ctx, side_kind):
        """Loop-free two-step chain kernel.

        Closed forms replacing the per-round loop (each proven against the
        generic engine by tests/test_nfa_fast_differential.py):
        - tail (e2) batches never arm, so consumption is "first matching
          row per slot" — a scatter-min over row indices; SEQUENCE kills
          reduce to "the key's first row decides everything"
          (StreamPreStateProcessor.java:382-395 semantics).
        - head (e1) batches never consume, so arming is rank-allocation of
          free slots in index order; the one serial case — a `within`
          expiry boundary crossing between two same-key arming rows, which
          re-orders the free list mid-batch — is detected exactly and
          `lax.cond`s into the generic engine (rare: needs two same-key
          arms straddling an expiry inside ONE batch).
        - SEQUENCE head batches: every event kills what it cannot extend,
          so only the LAST row per key can remain pending, always at the
          lowest free slot (index 0 once everything is killed).
        """
        plan = self.plan
        S = plan.slots
        L = plan.last_step
        K = state["consumed"].shape[0]
        B = cols[VALID_KEY].shape[0]
        ts = cols[TS_KEY]
        ts2d = ts[:, None]
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols.get(PK_KEY, jnp.zeros(B, jnp.int32)).astype(jnp.int32), 0, K - 1)
        w = plan.within
        cap_names = list(self.cap_cols)
        side = (plan.steps[0] if side_kind == "head" else plan.steps[1]).sides[0]
        in_def = side.definition

        def cur_ev(ev):
            for a in in_def.attributes:
                ev[a.name] = cols[a.name][:, None]
                ev[a.name + "?"] = cols[a.name + "?"][:, None]
            ev[TS_KEY] = ts2d
            return ev

        head_cap = plan.steps[0].sides[0].capture
        tail_cap = plan.steps[1].sides[0].capture
        head_pref = f"c{head_cap.cid}__"

        if side_kind == "tail":
            cap = side.capture
            A_pk = state["active"][pk]
            at1 = A_pk & (state["stepi"][pk] == L)
            # slot state for the TAIL capture is never written by the fast
            # head (and never read): its emission value IS the current row,
            # so ev carries broadcast current values instead of gathers
            CP = {n: state[n][pk] for n in cap_names
                  if n.startswith(head_pref)}
            tdef = tail_cap.definition
            for a in tdef.attributes:
                CP[cap_col(tail_cap.cid, a.name)] = jnp.broadcast_to(
                    cols[a.name][:, None], (B, S))
                CP[cap_col(tail_cap.cid, a.name) + "?"] = jnp.broadcast_to(
                    cols[a.name + "?"][:, None], (B, S))
            CP[cap_col(tail_cap.cid, TS_KEY)] = jnp.broadcast_to(ts2d, (B, S))
            CD = state["capdone"][pk]
            ev = cur_ev(self._fast_ev(CP, CD, B, fresh=False))
            cond = (side.cond(ev, ctx) if side.cond is not None
                    else jnp.ones((B, 1), bool))
            match = at1 & jnp.broadcast_to(cond, (B, S)) & valid_cur[:, None]
            if w is not None:
                # lazy per-row expiry — exact for monotone feeds (the
                # out-of-order case lax.conds to the generic engine below)
                match = match & (ts2d <= state["sts"][pk] + jnp.int64(w))
            ridx = jnp.arange(B, dtype=jnp.int32)

            def tail_fast(state, cols):
                if plan.sequence:
                    # first VALID row per key consumes its matches and
                    # kills the rest; later rows find nothing
                    _o, _i, occv, _c, _s = _per_key_layout(pk, valid_cur, K)
                    emit = match & (valid_cur & (occv == 0))[:, None]
                    touched = jnp.zeros((K,), bool).at[
                        jnp.where(valid_cur, pk, K)].set(True, mode="drop")
                    active2 = state["active"] & ~touched[:, None]
                else:
                    first = jnp.full((K, S), B, jnp.int32).at[pk].min(
                        jnp.where(match, ridx[:, None], B), mode="drop")
                    emit = match & (ridx[:, None] == first[pk])
                    active2 = state["active"] & ~(first < B)
                emit_caps = dict(CP)  # c0 = slot state, c1 = current row
                emit_caps["__capdone__"] = CD | (1 << cap.cid)
                new_state = dict(state)
                new_state["active"] = active2
                out = self._fast_out(emit, emit_caps, ts, cols, pk, B)
                out["__overflow__"] = jnp.int32(0)
                out["__notify__"] = _notify_of(self._next_deadline(new_state))
                return new_state, out

            return tail_fast(state, cols)

        # ---- head side
        cap = side.capture
        ev = cur_ev(self._fast_ev(state, None, B, fresh=True))
        cond1 = (side.cond(ev, ctx)[:, 0] if side.cond is not None
                 else jnp.ones((B,), bool))
        arm_c = valid_cur & cond1

        def head_fast(state, cols):
            consumed0 = state["consumed"]
            if plan.every:
                arm = arm_c
                _o, _i, occ, _c, _s = _per_key_layout(pk, arm, K)
            else:
                _o, _i, occc, _c, _s = _per_key_layout(pk, arm_c, K)
                arm = arm_c & ~consumed0[pk] & (occc == 0)
                occ = jnp.zeros(B, jnp.int64)
            if plan.sequence:
                # every valid row kills all (non-waitish = all) pendings of
                # its key, then arms at the lowest free slot — only the
                # LAST row per key survives, at slot 0
                _o2, _i2, occv, cnts, _s2 = _per_key_layout(pk, valid_cur, K)
                is_last = valid_cur & (occv == cnts[pk] - 1)
                pend = arm & is_last
                touched = jnp.zeros((K,), bool).at[
                    jnp.where(valid_cur, pk, K)].set(True, mode="drop")
                slot = jnp.where(pend, jnp.int64(0), jnp.int64(S))
                flat = jnp.where(pend, pk.astype(jnp.int64) * S, jnp.int64(K * S))
                active2 = state["active"] & ~touched[:, None]
                overflow2 = state["nfa_overflow"]
            else:
                act_pk = state["active"][pk]
                free = ~act_pk
                if w is not None:
                    free = free | (ts2d > state["sts"][pk] + jnp.int64(w))
                n_free = jnp.sum(free, axis=1)
                fs = jnp.argsort(
                    jnp.where(free, jnp.arange(S)[None, :],
                              S + jnp.arange(S)[None, :]), axis=1)
                can = arm & (occ < n_free)
                overflow2 = state["nfa_overflow"] + jnp.sum(
                    arm & ~can).astype(jnp.int32)
                slot = jnp.where(
                    can,
                    jnp.take_along_axis(
                        fs, jnp.clip(occ, 0, S - 1)[:, None].astype(jnp.int32),
                        axis=1)[:, 0].astype(jnp.int64),
                    jnp.int64(S))
                pend = arm & can
                flat = jnp.where(pend, pk.astype(jnp.int64) * S + slot,
                                 jnp.int64(K * S))
                active2 = state["active"]
                touched = None

            def put2d(arr, val):
                return arr.reshape(K * S).at[flat].set(
                    val, mode="drop").reshape(K, S)

            new_state = dict(state)
            new_state["active"] = put2d(active2, True)
            new_state["stepi"] = put2d(state["stepi"], jnp.int32(L))
            new_state["bits"] = put2d(state["bits"], jnp.int32(0))
            new_state["vbits"] = put2d(state["vbits"], jnp.int32(0))
            new_state["sts"] = put2d(state["sts"], ts)
            cleared_cd = put2d(state["capdone"], jnp.int32(1 << cap.cid))
            new_state["capdone"] = cleared_cd
            for n in cap_names:
                if not n.startswith(head_pref):
                    # tail-capture slot state is never read on the fast
                    # path (emissions take the current row) — skip the
                    # clearing scatters; capdone says "not captured"
                    continue
                base = state[n]
                if n == cap_col(cap.cid, TS_KEY):
                    val = ts
                else:
                    a = n[len(head_pref):]
                    val = cols[a]
                new_state[n] = put2d(base, val)
            new_state["consumed"] = state["consumed"].at[
                jnp.where(arm, pk, K)].set(True, mode="drop")
            new_state["nfa_overflow"] = overflow2
            emit = jnp.zeros((B, S), bool)
            emit_caps = {n: jnp.zeros((B, S), dt)
                         for n, dt in self.cap_cols.items()}
            emit_caps["__capdone__"] = jnp.zeros((B, S), jnp.int32)
            out = self._fast_out(emit, emit_caps, ts, cols, pk, B)
            out["__overflow__"] = (
                overflow2 > state["nfa_overflow"]).astype(jnp.int32)
            out["__notify__"] = _notify_of(self._next_deadline(new_state))
            return new_state, out

        return head_fast(state, cols)

    def _apply_stream_generic(self, stream_id: str, state: dict, cols: dict, ctx: dict):
        """The generic engine (see the module docstring): round r takes
        every key's r-th CURRENT row of the batch, and runs as tiles of at
        most W rows (``_tile_rows``), one ``while_loop`` iteration each.

        Before the loop the batch's CURRENT rows are put in (round, key)
        order and every column is gathered into it once. An iteration
        takes the next W positions, cut at the end of their round: rows of
        distinct keys, sorted by key. It gathers their keys' state views
        ``[W, S]``, runs the round body over them, scatters the views back
        at the keys (sorted, unique: the padding lanes carry the
        out-of-range keys ``K + lane``) and writes the tile's
        ``[W, S + 1]`` emissions at its positions of an output buffer.
        Round r's tiles all run before round r + 1's, so each key meets
        its rows in arrival order; the body reads nothing across rows.
        After the loop one gather puts the emissions back in row order,
        as ``_flatten_out`` expects them."""
        plan = self.plan
        S = plan.slots
        L = plan.last_step
        K = state["consumed"].shape[0]
        B = cols[VALID_KEY].shape[0]
        W = _tile_rows(B, K)
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols.get(PK_KEY, jnp.zeros(B, jnp.int32)).astype(jnp.int32), 0, K - 1)

        order, _i, occ, _c, _s = _per_key_layout(pk, valid_cur, K)
        n_rounds = jnp.max(jnp.where(valid_cur, occ, -1)) + 1
        n_rows = jnp.sum(valid_cur, dtype=jnp.int32)
        # (round, key) order: the key order, stably sorted by round; rows
        # that take no round (not CURRENT) last
        rnd, by_round = lax.sort(
            (jnp.where(valid_cur, occ, B).astype(jnp.int32)[order],
             order.astype(jnp.int32)), num_keys=1, is_stable=True)
        pos = jnp.arange(B, dtype=jnp.int32)
        last_of_round = jnp.concatenate([rnd[1:] != rnd[:-1],
                                         jnp.ones(1, bool)])
        round_end = lax.cummin(jnp.where(last_of_round, pos + 1, B),
                               reverse=True)

        def in_tile_order(col):
            # padded by W rows, so the last tile's slice stays in bounds
            col = col[by_round]
            return jnp.concatenate([col, jnp.zeros((W,) + col.shape[1:],
                                                   col.dtype)])

        cols_sorted = {n: in_tile_order(c) for n, c in cols.items()
                       if jnp.ndim(c) >= 1 and c.shape[0] == B}
        pk_sorted = in_tile_order(pk)
        lanes = jnp.arange(W, dtype=jnp.int32)

        # ops consuming this stream, in step order (absent sides included —
        # their matches are violations, not advances)
        ops: List[Tuple[StepSpec, SideSpec]] = [
            (st, side)
            for st in plan.steps
            for side in st.sides
            if side.stream_id == stream_id
        ]
        in_def = ops[0][1].definition if ops else None
        cap_names = list(self.cap_cols)
        arm_j = plan.arm_step()

        def capture_current(CP, CD, mask2d, cap: CaptureSpec, src):
            """Write the current event (the tile's columns ``src``) into a
            capture (last + indexed slot + counter) for slots selected by
            mask2d [W, S]."""
            cid = cap.cid
            if cap.last_ring:
                # shift the ring BEFORE the new event overwrites `last`:
                # L[j] <- L[j-1], L[1] <- old last. Staleness across count
                # restarts is masked by the counter at read time.
                for j in range(cap.last_ring, 0, -1):
                    for a in cap.definition.attributes:
                        src_n = (cap_lastk_col(cid, j - 1, a.name) if j > 1
                                 else cap_col(cid, a.name))
                        dst = cap_lastk_col(cid, j, a.name)
                        CP[dst] = jnp.where(mask2d, CP[src_n], CP[dst])
                        CP[dst + "?"] = jnp.where(mask2d, CP[src_n + "?"],
                                                  CP[dst + "?"])
            for a in cap.definition.attributes:
                n = cap_col(cid, a.name)
                CP[n] = jnp.where(mask2d, src[a.name][:, None], CP[n])
                CP[n + "?"] = jnp.where(mask2d, src[a.name + "?"][:, None], CP[n + "?"])
            n = cap_col(cid, TS_KEY)
            CP[n] = jnp.where(mask2d, src[TS_KEY][:, None], CP[n])
            if cap.is_count:
                cnt_n = cap_cnt_col(cid)
                before = CP[cnt_n]
                for i in range(cap.n_idx):
                    sel = mask2d & (before == i)
                    for a in cap.definition.attributes:
                        ni = cap_idx_col(cid, i, a.name)
                        CP[ni] = jnp.where(sel, src[a.name][:, None], CP[ni])
                        CP[ni + "?"] = jnp.where(sel, src[a.name + "?"][:, None],
                                                 CP[ni + "?"])
                CP[cnt_n] = jnp.where(mask2d, before + 1, CP[cnt_n])
            CD = jnp.where(mask2d, CD | (1 << cid), CD)
            return CP, CD

        def tile_body(carry):
            (s, tiles, active, stepi, bits, vbits, sts, adl, adl2, capdone,
             consumed, armed, caps, scs, out_valid, out_caps, out_ts,
             overflow) = carry
            # positions [s, e): the next W rows of s's round
            e = jnp.minimum(s + W, round_end[s])
            m = lanes < e - s
            tc = {n: lax.dynamic_slice_in_dim(c, s, W)
                  for n, c in cols_sorted.items()}
            ts = tc[TS_KEY]
            keys = lax.dynamic_slice_in_dim(pk_sorted, s, W)
            # where the views go back: sorted and unique, padding past K
            rows_pk = jnp.where(m, keys, K + lanes)

            V = {
                "A": active[keys],
                "ST": stepi[keys],
                "BT": bits[keys],
                "VB": vbits[keys],
                "T0": sts[keys],
                "ADL": adl[keys],
                "AD2": adl2[keys],
                "CD": capdone[keys],
                "CP": {n: caps[n][keys] for n in cap_names},
                "SC": [scs[g][keys] for g in range(len(self.scope_cols))],
            }
            CONS = consumed[keys]
            ARMD = armed[keys]
            ts2d = ts[:, None]

            # ---- arming: a key's very first row arms the head wait.
            # A capture-LESS armed head (pure absent) starts `within` from
            # its FIRST CAPTURE, not from arming — T0 arms at a far-future
            # sentinel that every later capture min()s down to its ts
            # (AbsentPatternTestCase q42: the quiet stretch does not count)
            arm_capless = arm_j is not None and all(
                sd.capture is None for sd in plan.steps[arm_j].sides)
            if arm_j is not None:
                need = m & ~ARMD
                onehot0 = need[:, None] & (jnp.arange(S)[None, :] == 0)
                V["A"] = V["A"] | onehot0
                V["T0"] = jnp.where(
                    onehot0, _T0_FAR if arm_capless else ts2d, V["T0"])
                V = self._enter(V, onehot0, arm_j, ts2d)
                ARMD = ARMD | need

            # ---- expiry + deadline cascade (before matching: a row at
            # ts past a deadline sees the advanced state)
            V = self._expire(V, ts2d)
            emit = jnp.zeros((W, S), bool)
            ets = jnp.broadcast_to(ts2d, (W, S))
            fork_reqs: List = []
            V, emit, ets = self._cascade(V, ts2d, emit, ets, fork_reqs)

            A, ST, BT, VB, T0, CD = (V["A"], V["ST"], V["BT"], V["VB"],
                                     V["T0"], V["CD"])
            CP = V["CP"]

            # eval dict: current attrs [W,1], captures [W,S]
            ev = dict(CP)
            # a count capture with no occurrences yet reads NULL (the
            # reference's empty StateEvent chain): mask `last` by cnt==0
            # and ring slot j by cnt<=j — this also cures ring staleness
            # across count restarts. `@present` synthetics carry the bare
            # StateEvent presence checks (`e2 is null`, `e2[last-k] is
            # null`): their null mask IS absence.
            ones2d = jnp.ones((W, S), bool)
            pres_cols: List[str] = []

            def _pres(ev_d, name, absent):
                ev_d[name] = ones2d
                ev_d[name + "?"] = absent
                pres_cols.append(name)

            for cap in plan.captures:
                if not cap.is_count:
                    _pres(ev, cap_col(cap.cid, PRESENT),
                          (V["CD"] & (1 << cap.cid)) == 0)
                    continue
                cnt = CP[cap_cnt_col(cap.cid)]
                _pres(ev, cap_col(cap.cid, PRESENT), cnt == 0)
                for j in range(1, cap.last_ring + 1):
                    _pres(ev, cap_lastk_col(cap.cid, j, PRESENT), cnt <= j)
                for i in range(cap.n_idx):
                    _pres(ev, cap_idx_col(cap.cid, i, PRESENT), cnt <= i)
                for a in cap.definition.attributes:
                    n = cap_col(cap.cid, a.name) + "?"
                    ev[n] = CP[n] | (cnt == 0)
                    for j in range(1, cap.last_ring + 1):
                        nj = cap_lastk_col(cap.cid, j, a.name) + "?"
                        ev[nj] = CP[nj] | (cnt <= j)
            if in_def is not None:
                for a in in_def.attributes:
                    ev[a.name] = tc[a.name][:, None]
                    ev[a.name + "?"] = tc[a.name + "?"][:, None]
            ev[TS_KEY] = ts2d
            # fresh-start eval dict: capture references are NULL (a fresh
            # chain has captured nothing — a freed slot's stale values
            # must not leak into fresh-start conditions)
            ev_fresh = dict(ev)
            for n in cap_names:
                if n.endswith("?"):
                    ev_fresh[n] = jnp.ones((W, 1), ev[n].dtype)
                else:
                    ev_fresh[n] = jnp.zeros((W, 1), ev[n].dtype)
            for n in pres_cols:   # fresh chains have captured nothing
                ev_fresh[n + "?"] = jnp.ones((W, 1), bool)

            # ---- phase 1: match masks against pre-event state; the
            # furthest-advanced op wins a slot (no per-event forking)
            win = jnp.full((W, S), -1, jnp.int32)
            conds: List[jnp.ndarray] = []
            at_masks: List[jnp.ndarray] = []
            adv_masks: List[jnp.ndarray] = []
            # per-op [(src_step, mask)]: advances out of a sticky (`every`)
            # count source fork a child instead of moving the parent
            adv_fork_masks: List[List[Tuple[int, jnp.ndarray]]] = []
            viols: List[jnp.ndarray] = []
            for oi, (st, side) in enumerate(ops):
                j = st.index
                cond = side.cond(ev, ctx) if side.cond is not None \
                    else jnp.ones((W, 1), bool)
                cond = jnp.broadcast_to(cond, (W, S))
                conds.append(cond)
                if side.absent:
                    # a matching event on an absent side while the slot
                    # waits = violation (AbsentStreamPreStateProcessor)
                    v = A & (ST == j) & m[:, None] & cond
                    if st.kind in ("and", "or"):
                        v = v & ((BT & side.bit) == 0)
                    viols.append(v)
                    at_masks.append(jnp.zeros((W, S), bool))
                    adv_masks.append(jnp.zeros((W, S), bool))
                    adv_fork_masks.append([])
                    continue
                viols.append(jnp.zeros((W, S), bool))
                at = A & (ST == j) & m[:, None] & cond
                if st.kind == "count":
                    cnt = CP[cap_cnt_col(side.capture.cid)]
                    at = at & (cnt < st.max_count)
                elif st.kind in ("and", "or"):
                    # a side is consumed once (LogicalPreStateProcessor):
                    # an already-matched side must not re-match/overwrite
                    at = at & ((BT & side.bit) == 0)
                adv = jnp.zeros((W, S), bool)
                fork_all = jnp.zeros((W, S), bool)
                fork_srcs: List[Tuple[int, jnp.ndarray]] = []
                for p in self._advance_sources(j):
                    src = plan.steps[p]
                    src_cap = src.sides[0].capture
                    pc = CP[cap_cnt_col(src_cap.cid)]
                    am = A & (ST == p) & (pc >= src.min_count)
                    if (src.kind == "count" and src.sticky
                            and src.min_count != src.max_count):
                        # range `every` count: group = whatever is collected
                        # when consumed; parent re-arms, child advances
                        fm = am & m[:, None] & cond
                        fork_srcs.append((p, fm))
                        fork_all = fork_all | fm
                    else:
                        # exact `every` counts fork at completion instead:
                        # complete groups are waiting children that MOVE
                        # (the collecting parent has cnt < min and never
                        # qualifies as an advance source)
                        adv = adv | am
                adv = adv & m[:, None] & cond
                at_masks.append(at)
                adv_masks.append(adv)
                adv_fork_masks.append(fork_srcs)
                claim = at | adv | fork_all
                if oi > 0 and ops[oi - 1][0] is st:
                    # sides of one logical step: the FIRST side wins when
                    # an event matches both (reference LogicalPreState
                    # processes side 1's executor first — SequenceTestCase
                    # testQuery8 captures e2, not e3)
                    claim = claim & (win != oi - 1)
                win = jnp.where(claim, oi, win)

            matched = win >= 0

            # ---- violations: kill / mark / re-arm
            A2, ST2, BT2, VB2, CD2 = A, ST, BT, VB, CD
            ADL2_, AD22_ = V["ADL"], V["AD2"]
            CP2 = dict(CP)
            for oi, (st, side) in enumerate(ops):
                if not side.absent:
                    continue
                v = viols[oi]
                j = st.index
                if st.kind == "absent":
                    if st.sticky or st.index == arm_j:
                        # every-not restarts its interval; a HEAD wait
                        # (armed start state) re-inits after violation
                        # even without `every` — reference start states
                        # re-initialize per chunk, so the quiet window
                        # re-anchors at the violating event
                        # (AbsentPatternTestCase q6/q18)
                        ADL2_ = jnp.where(v, ts2d + jnp.int64(st.wait_ms), ADL2_)
                    else:
                        A2 = A2 & ~v
                elif st.kind == "and":
                    if st.sticky:
                        BT2 = jnp.where(v, 0, BT2)
                        VB2 = jnp.where(v, 0, VB2)
                        if side.wait_ms is not None:
                            key_arr = ADL2_ if side.bit == 1 else AD22_
                            key_arr = jnp.where(v, ts2d + jnp.int64(side.wait_ms), key_arr)
                            if side.bit == 1:
                                ADL2_ = key_arr
                            else:
                                AD22_ = key_arr
                    else:
                        A2 = A2 & ~v       # `and` with a violated absent side is dead
                else:  # or
                    VB2 = jnp.where(v, VB2 | side.bit, VB2)
                    if all(s.absent for s in st.sides):
                        dead = (VB2 & st.need_bits) == st.need_bits
                        A2 = A2 & ~(v & dead)

            # ---- phase 2: apply the winning transition per slot
            emit2 = jnp.zeros((W, S), bool)
            kill = jnp.zeros((W, S), bool)
            sticky_emit_ops: List[Tuple[jnp.ndarray, StepSpec, SideSpec]] = []
            phase2_forks: List[Tuple[jnp.ndarray, int, SideSpec]] = []
            # (mask, src_step): sticky count parents to re-arm (zero their
            # collection) after the emission snapshot + fork copies
            count_resets: List[Tuple[jnp.ndarray, StepSpec]] = []
            for oi, (st, side) in enumerate(ops):
                if side.absent:
                    continue
                j = st.index
                eff_at = at_masks[oi] & (win == oi)
                eff_adv = adv_masks[oi] & (win == oi)
                if (st.kind == "and" and oi > 0 and ops[oi - 1][0] is st
                        and not ops[oi - 1][1].absent):
                    # ONE event matching BOTH `and` sides fills both
                    # captures in the same round (each side is its own
                    # pre-state processor in the reference and both consume
                    # the event — LogicalPatternTestCase testQuery5); side 1
                    # won the claim arbitration, side 2 still consumes
                    both = (win == oi) | (win == oi - 1)
                    eff_at = at_masks[oi] & both
                    eff_adv = adv_masks[oi] & both
                eff = eff_at | eff_adv
                cap = side.capture
                # advances out of a sticky (`every`) count source: the
                # parent stays collecting (reset below); a forked child
                # takes this op's transition (plan validation guarantees
                # st.kind == "stream" here)
                for p, fmask in adv_fork_masks[oi]:
                    fm = fmask & (win == oi)
                    count_resets.append((fm, plan.steps[p]))
                    if j == L:
                        sticky_emit_ops.append((fm, st, side))
                    else:
                        phase2_forks.append((fm, j + 1, side))
                if st.sticky and st.kind == "stream":
                    # sticky step: parent stays; fork an advanced child.
                    # For a mid-chain `every (...)` GROUP, fork only while
                    # no earlier child is still INSIDE the group span —
                    # iterations are sequential, not overlapping
                    # (EveryPatternTestCase:351 grouping)
                    gend = plan.every_groups.get(j)
                    if gend is not None and gend > j:
                        busy = jnp.any(A & (ST > j) & (ST <= gend), axis=1)
                        eff = eff & ~busy[:, None]
                    if j == L:
                        sticky_emit_ops.append((eff, st, side))
                    else:
                        phase2_forks.append((eff, j + 1, side))
                    continue
                if st.kind == "count":
                    # entering resets the counter; absorbing continues it
                    CP2, CD2 = capture_current(CP2, CD2, eff, cap, tc)
                    if arm_capless:
                        T0 = jnp.where(eff, jnp.minimum(T0, ts2d), T0)
                    ST2 = jnp.where(eff, j, ST2)
                    if (j < L and not st.sticky
                            and st.min_count == st.max_count):
                        # a FULL exact count advances into the next step
                        # immediately (it can absorb nothing more) — the
                        # reference adds the shared state event to the next
                        # pre-state at min-reach (processMinCountReached),
                        # so an absent successor can be violated while the
                        # chain "rests" (CountPatternTestCase:886)
                        cnt_after = CP2[cap_cnt_col(cap.cid)]
                        done = eff & (cnt_after >= st.max_count)
                        tmp = {"ST": ST2, "BT": BT2, "VB": VB2,
                               "ADL": ADL2_, "AD2": AD22_, "CD": CD2,
                               "SC": list(V["SC"])}
                        tmp = self._enter(tmp, done, j + 1, ts2d)
                        ST2, BT2, VB2 = tmp["ST"], tmp["BT"], tmp["VB"]
                        ADL2_, AD22_, CD2 = (tmp["ADL"], tmp["AD2"],
                                             tmp["CD"])
                        V["SC"] = tmp["SC"]
                    if j == L:
                        cnt_after = CP2[cap_cnt_col(cap.cid)]
                        done = eff & (cnt_after >= st.min_count)
                        emit2 = emit2 | done
                        if st.sticky:
                            # `every` count tail: emit each completed group
                            # and re-arm a fresh collection
                            count_resets.append((done, st))
                    elif st.sticky and st.min_count == st.max_count:
                        # exact `every` count mid-chain: a completed group
                        # forks a waiting child (it advances on the next
                        # step's event); the parent restarts collecting
                        # (CountPatternTestCase.testQuery20 grouping)
                        cnt_after = CP2[cap_cnt_col(cap.cid)]
                        done = eff & (cnt_after >= st.max_count)
                        phase2_forks.append((done, j, None))
                        count_resets.append((done, st))
                elif st.kind == "stream":
                    CP2, CD2 = capture_current(CP2, CD2, eff, cap, tc)
                    if arm_capless:
                        T0 = jnp.where(eff, jnp.minimum(T0, ts2d), T0)
                    if j == L:
                        emit2 = emit2 | eff
                        kill = kill | eff
                    else:
                        tmp = {"ST": ST2, "BT": BT2, "VB": VB2,
                               "ADL": ADL2_, "AD2": AD22_, "CD": CD2,
                               "SC": V["SC"]}
                        tmp = self._enter(tmp, eff, j + 1, ts2d)
                        ST2, BT2, VB2 = tmp["ST"], tmp["BT"], tmp["VB"]
                        ADL2_, AD22_, CD2 = tmp["ADL"], tmp["AD2"], tmp["CD"]
                        if j + 1 >= plan.eager_tail_start:
                            # the rest of the chain is all min-0 counts:
                            # already complete — emit now, keep absorbing
                            emit2 = emit2 | eff
                else:  # and / or
                    CP2, CD2 = capture_current(CP2, CD2, eff, cap, tc)
                    if arm_capless:
                        T0 = jnp.where(eff, jnp.minimum(T0, ts2d), T0)
                    bt2 = BT2 | jnp.where(eff, side.bit, 0)
                    nb = st.need_bits
                    if st.kind == "and":
                        full = (bt2 & nb) == nb
                    else:
                        full = jnp.ones((W, S), bool)
                    done = eff & full
                    if st.sticky:
                        # re-arm the logical parent on completion
                        if j == L:
                            emit2 = emit2 | done
                        else:
                            phase2_forks.append((done, j + 1, None))
                        BT2 = jnp.where(eff & ~done, bt2,
                                        jnp.where(done, 0, BT2))
                        VB2 = jnp.where(done, 0, VB2)
                        for s2 in st.sides:
                            if s2.absent and s2.wait_ms is not None:
                                arr = ADL2_ if s2.bit == 1 else AD22_
                                arr = jnp.where(done, ts2d + jnp.int64(s2.wait_ms), arr)
                                if s2.bit == 1:
                                    ADL2_ = arr
                                else:
                                    AD22_ = arr
                        continue
                    if j == L:
                        emit2 = emit2 | done
                        kill = kill | done
                    else:
                        tmp = {"ST": ST2, "BT": BT2, "VB": VB2,
                               "ADL": ADL2_, "AD2": AD22_, "CD": CD2,
                               "SC": V["SC"]}
                        tmp = self._enter(tmp, done, j + 1, ts2d)
                        ST2, BT2, VB2 = tmp["ST"], tmp["BT"], tmp["VB"]
                        ADL2_, AD22_, CD2 = tmp["ADL"], tmp["AD2"], tmp["CD"]
                    BT2 = jnp.where(eff & ~done, bt2, BT2)
                    ST2 = jnp.where(eff & ~full, j, ST2)

            # scope starts for plain capture steps
            scV = {"CD": CD2, "SC": V["SC"]}
            for oi, (st, side) in enumerate(ops):
                if side.absent or st.sticky:
                    continue
                eff = (at_masks[oi] | adv_masks[oi]) & (win == oi)
                scV = self._start_capture_scopes(scV, eff, st.index, ts2d)
            CD2, V["SC"] = scV["CD"], scV["SC"]

            if plan.sequence:
                # strict continuity kills unmatched partials — but not
                # slots WAITING at an absent-ish step: their lifecycle is
                # time-driven, non-matching events pass them by
                # (AbsentSequenceTestCase: a non-violating event during
                # `not X for t` does not break the sequence)
                at_waitish = jnp.zeros_like(A)
                for wst in plan.steps:
                    if wst.waitish:
                        at_waitish = at_waitish | (ST == wst.index)
                kill = kill | (m[:, None] & A & ~matched & ~at_waitish)
            A2 = A2 & ~kill

            emit_all = (emit | emit2) & m[:, None]
            ets = jnp.where(emit2, ts2d, ets)

            # ---- sticky emissions at the last step: emit parent captures
            # + the current event, parent survives
            CPe = None
            semit = jnp.zeros((W, S), bool)
            for eff, st, side in sticky_emit_ops:
                if CPe is None:
                    CPe = dict(CP2)
                    CDe = CD2
                CPe, CDe = capture_current(CPe, CDe, eff, side.capture, tc)
                semit = semit | eff
            emit_all = emit_all | (semit & m[:, None])

            # ---- emission snapshot BEFORE fork allocation: forks may
            # reuse slots freed by emitting matches and would clobber the
            # capture columns the emission reads
            out_cd = jnp.where(emit_all, CD2, 0)
            if CPe is not None:
                ov2 = {n: jnp.where(semit, CPe[n],
                                    jnp.where(emit_all, CP2[n], _zero(CP2[n])))
                       for n in cap_names}
                out_cd = jnp.where(semit, CDe, out_cd)
            else:
                ov2 = {n: jnp.where(emit_all, CP2[n], _zero(CP2[n]))
                       for n in cap_names}

            # ---- allocate forked children (sticky advances)
            V2 = {"A": A2, "ST": ST2, "BT": BT2, "VB": VB2, "T0": T0,
                  "ADL": ADL2_, "AD2": AD22_, "CD": CD2, "CP": CP2,
                  "SC": V["SC"]}
            for req, target, arm_ts in fork_reqs:
                V2, dstm, overflow = self._alloc_forks(V2, req & m[:, None], overflow)
                V2 = self._enter(V2, dstm, target, _gather_like(arm_ts, req, dstm))
            for req, target, side in phase2_forks:
                V2, dstm, overflow = self._alloc_forks(V2, req & m[:, None], overflow)
                if side is not None and side.capture is not None:
                    V2["CP"], V2["CD"] = capture_current(
                        V2["CP"], V2["CD"], dstm, side.capture, tc)
                V2 = self._enter(V2, dstm, target, ts2d)
                V2 = self._start_capture_scopes(V2, dstm, target - 1, ts2d)
            A2, ST2, BT2, VB2 = V2["A"], V2["ST"], V2["BT"], V2["VB"]
            T0, ADL2_, AD22_, CD2 = V2["T0"], V2["ADL"], V2["AD2"], V2["CD"]
            CP2, SC2 = V2["CP"], V2["SC"]

            # ---- re-arm sticky (`every`) count parents: zero the counter,
            # the collected capture arrays, and any capture scope anchored
            # at the count step, so the next group starts fresh (applied
            # after the emission snapshot and fork copies, which must see
            # the completed collection)
            for fm, src_st in count_resets:
                scap = src_st.sides[0].capture
                cnt_col = cap_cnt_col(scap.cid)
                pref, prefi = f"c{scap.cid}__", f"c{scap.cid}i"
                for n in cap_names:
                    if n == cnt_col or n.startswith(pref) or n.startswith(prefi):
                        clear = (jnp.ones((), CP2[n].dtype) if n.endswith("?")
                                 else jnp.zeros((), CP2[n].dtype))
                        CP2[n] = jnp.where(fm, clear, CP2[n])
                for g, (a, b, t) in enumerate(plan.scopes):
                    if a == src_st.index and not plan.steps[a].waitish:
                        CD2 = jnp.where(fm, CD2 & ~plan.scope_bit(g), CD2)

            # ---- fresh starts
            every_ok = plan.every | ~CONS
            if plan.rearm_on_empty:
                # count-head non-every: the start state re-arms once no
                # chain is live (post-match / post-expiry) — see NFAPlan
                no_live = ~jnp.any(A, axis=1)
                every_ok = every_ok | no_live
            # head `every (...)` GROUP: the next iteration arms only after
            # the previous one exits the group span (pre-advance occupancy;
            # the completing event itself does not seed the new iteration —
            # reference addEveryState lands after the current chunk)
            head_gend = plan.every_groups.get(0)
            # a (0, 0) span gates only LOGICAL heads — `every (e1 and e2)`
            # is ONE step whose half-filled pair parks AT step 0, and the
            # next iteration must not arm beside it (LogicalPatternTestCase
            # testQuery15); count heads (`every e1?`) also park at 0 but
            # re-arm per event by design (SequenceTestCase testQuery7)
            if plan.every and head_gend is not None and (
                    head_gend > 0 or plan.steps[0].kind in ("and", "or")):
                in_head_group = jnp.any(A & (ST <= head_gend), axis=1)
            else:
                in_head_group = None
            # SEQUENCE: an event absorbed into an ONGOING (pre-completion)
            # count collection belongs to that chain alone — it must not
            # also seed a fresh `every` iteration. Collections at/after
            # eager_tail_start are already complete (their chain emitted),
            # so absorbs there DO let the event seed the next iteration
            # (SequencePartitionTestCase q11 vs q3: the rising-run absorb
            # suppresses, a trailing-star absorb does not).
            seq_absorbing = None
            if plan.sequence and plan.every:
                terms = [jnp.any(at_masks[oi2] & (win == oi2), axis=1)
                         for oi2, (st2, side2) in enumerate(ops)
                         if st2.kind == "count" and not side2.absent
                         and st2.index < plan.eager_tail_start]
                if terms:
                    seq_absorbing = terms[0]
                    for t in terms[1:]:
                        seq_absorbing = seq_absorbing | t
            fresh_any = jnp.zeros((W,), bool)
            direct = jnp.zeros((W,), bool)
            direct_op = jnp.full((W,), -1, jnp.int32)
            fresh_reqs: List[Tuple[jnp.ndarray, int, int, SideSpec]] = []
            for oi, (st, side) in enumerate(ops):
                if side.absent:
                    continue
                j = st.index
                if not self._fresh_ok(j):
                    continue
                fcond = (side.cond(ev_fresh, ctx)[:, 0]
                         if side.cond is not None else jnp.ones((W,), bool))
                f = m & every_ok & fcond
                if seq_absorbing is not None:
                    f = f & ~seq_absorbing
                if in_head_group is not None and j <= head_gend:
                    f = f & ~in_head_group
                if st.kind == "count":
                    # non-overlapping `every` collections: an event some
                    # slot absorbed into its collection does not also seed
                    # a fresh instance — the next instance begins with the
                    # first event a full collection cannot take
                    # (CountPatternTestCase testQuery18/20 grouping)
                    absorbed = jnp.any(at_masks[oi] & (win == oi), axis=1)
                    f = f & ~absorbed
                    if j == L and 1 >= st.min_count:
                        direct = direct | f
                        direct_op = jnp.where(f & (direct_op < 0), oi, direct_op)
                    if j < L or 1 < st.max_count:
                        fresh_reqs.append((f, j, 0, side))       # park at j
                elif st.kind == "stream":
                    if j == L and not st.sticky:
                        direct = direct | f
                        direct_op = jnp.where(f & (direct_op < 0), oi, direct_op)
                    elif st.sticky:
                        # a sticky head is plan.every — fresh slots park AT it
                        fresh_reqs.append((f, j, 0, side))
                    else:
                        fresh_reqs.append((f, j + 1, 0, side))   # rest past j
                        if j + 1 >= plan.eager_tail_start:
                            # everything after j is a min-0 count: this
                            # fresh chain is already complete — emit now
                            # AND park the slot to keep absorbing
                            direct = direct | f
                            direct_op = jnp.where(f & (direct_op < 0), oi,
                                                  direct_op)
                else:  # logical
                    full0 = st.kind == "or"
                    if full0 and j == L:
                        direct = direct | f
                        direct_op = jnp.where(f & (direct_op < 0), oi, direct_op)
                    elif full0:
                        fresh_reqs.append((f, j + 1, 0, side))
                    else:
                        fresh_reqs.append((f, j, side.bit, side))
                fresh_any = fresh_any | f

            # ---- allocate fresh slots
            NF = len(fresh_reqs)
            if NF:
                req = jnp.stack([fr[0] for fr in fresh_reqs], axis=1)  # [W,NF]
                free = ~A2
                n_free = jnp.sum(free, axis=1)
                fs = jnp.argsort(
                    jnp.where(free, jnp.arange(S)[None, :],
                              S + jnp.arange(S)[None, :]), axis=1)
                rank = jnp.cumsum(req.astype(jnp.int32), axis=1) - 1
                can = req & (rank < n_free[:, None])
                overflow = overflow + jnp.sum(req & ~can).astype(jnp.int32)
                slot_of = jnp.where(
                    can, jnp.take_along_axis(fs, jnp.clip(rank, 0, S - 1), axis=1), S)
                bidx = jnp.arange(W)
                for k, (f, step_val, bits_val, side) in enumerate(fresh_reqs):
                    slot = slot_of[:, k]
                    cap = side.capture
                    onehot = jnp.zeros((W, S + 1), bool).at[bidx, slot].set(
                        True)[:, :S]
                    A2 = A2 | onehot
                    T0 = jnp.where(onehot, ts2d, T0)
                    # clear the new slot's captures (masks to NULL),
                    # then capture the event
                    for n in cap_names:
                        clear = (jnp.ones((), CP2[n].dtype) if n.endswith("?")
                                 else jnp.zeros((), CP2[n].dtype))
                        CP2[n] = jnp.where(onehot, clear, CP2[n])
                    CD2 = jnp.where(onehot, 0, CD2)
                    tmp = {"ST": ST2, "BT": BT2, "VB": VB2,
                           "ADL": ADL2_, "AD2": AD22_, "CD": CD2, "SC": SC2}
                    tmp = self._enter(tmp, onehot, step_val, ts2d)
                    ST2, BT2, VB2 = tmp["ST"], tmp["BT"], tmp["VB"]
                    ADL2_, AD22_, CD2, SC2 = (tmp["ADL"], tmp["AD2"],
                                              tmp["CD"], tmp["SC"])
                    BT2 = jnp.where(onehot, bits_val, BT2)
                    if cap is not None:
                        CP2, CD2 = capture_current(CP2, CD2, onehot, cap, tc)
                        scV2 = self._start_capture_scopes(
                            {"CD": CD2, "SC": SC2}, onehot,
                            fresh_cap_step(self.plan, step_val, bits_val), ts2d)
                        CD2, SC2 = scV2["CD"], scV2["SC"]

            # ---- scatter views back at the tile's keys
            def put(dst, view):
                return dst.at[rows_pk].set(view, mode="drop",
                                           indices_are_sorted=True,
                                           unique_indices=True)

            consumed2 = put(consumed, CONS | fresh_any | direct)
            armed2 = put(armed, ARMD) if arm_j is not None else armed

            # ---- direct-emission column (fresh match completing instantly)
            ov3 = {}
            for n in cap_names:
                col_S = jnp.zeros((W,), self.cap_cols[n])
                for oi, (st, side) in enumerate(ops):
                    cap = side.capture
                    if cap is None:
                        continue
                    dm = direct & (direct_op == oi)
                    base = None
                    if n == cap_col(cap.cid, TS_KEY):
                        col_S = jnp.where(dm, ts, col_S)
                    elif n == cap_cnt_col(cap.cid) if cap.is_count else False:
                        col_S = jnp.where(dm, 1, col_S)
                    elif n.startswith(f"c{cap.cid}__"):
                        base = n[len(f"c{cap.cid}__"):]
                    elif n.startswith(f"c{cap.cid}i0__"):
                        base = n[len(f"c{cap.cid}i0__"):]
                    if base is not None and base in tc:
                        col_S = jnp.where(dm, tc[base], col_S)
                ov3[n] = jnp.concatenate([ov2[n], col_S[:, None]], axis=1)
            direct_cd = jnp.zeros((W,), jnp.int32)
            for oi, (st, side) in enumerate(ops):
                if side.capture is None:
                    continue
                dm = direct & (direct_op == oi)
                direct_cd = jnp.where(dm, jnp.int32(1 << side.capture.cid), direct_cd)
            ov3["__capdone__"] = jnp.concatenate([out_cd, direct_cd[:, None]], axis=1)

            # ---- the tile's emissions, once, at its positions [s, s + W)
            # (lanes past the tile emit nothing; a later tile writes over
            # them, or they are rows that take no round)
            def place(buf, rows):
                return lax.dynamic_update_slice_in_dim(buf, rows, s, axis=0)

            t_valid = jnp.concatenate([emit_all, direct[:, None]], axis=1)
            t_ts = jnp.concatenate([jnp.where(emit_all, ets, ts2d), ts2d],
                                   axis=1)
            return (e, tiles + 1, put(active, A2), put(stepi, ST2),
                    put(bits, BT2), put(vbits, VB2), put(sts, T0),
                    put(adl, ADL2_), put(adl2, AD22_), put(capdone, CD2),
                    consumed2, armed2,
                    {n: put(caps[n], CP2[n]) for n in cap_names},
                    [put(scs[g], SC2[g]) for g in range(len(self.scope_cols))],
                    place(out_valid, t_valid),
                    {n: place(out_caps[n], v) for n, v in ov3.items()},
                    place(out_ts, t_ts), overflow)

        # the output buffers, in tile order, W rows longer than the batch
        out_valid0 = jnp.zeros((B + W, S + 1), bool)
        out_caps0 = {n: jnp.zeros((B + W, S + 1), dt)
                     for n, dt in self.cap_cols.items()}
        out_caps0["__capdone__"] = jnp.zeros((B + W, S + 1), jnp.int32)
        out_ts0 = jnp.broadcast_to(cols_sorted[TS_KEY][:, None], (B + W, S + 1))

        carry0 = (jnp.int32(0), jnp.int32(0), state["active"], state["stepi"],
                  state["bits"], state["vbits"], state["sts"], state["adl"],
                  state["adl2"], state["capdone"], state["consumed"],
                  state["armed"],
                  {n: state[n] for n in cap_names},
                  [state[g] for g in self.scope_cols],
                  out_valid0, out_caps0, out_ts0, state["nfa_overflow"])

        with jax.named_scope(ROUNDS_SCOPE):
            res = lax.while_loop(lambda c: c[0] < n_rows, tile_body, carry0)
        (_s, tiles, active2, stepi2, bits2, vbits2, sts2, adl_2, adl2_2,
         capdone2, consumed2, armed2, caps2, scs2, out_valid, out_caps,
         out_ts, overflow2) = res
        # back to row order: row by_round[p] sits at position p
        at = jnp.zeros(B, jnp.int32).at[by_round].set(pos,
                                                        unique_indices=True)
        out_valid = out_valid[at]
        out_caps = {n: c[at] for n, c in out_caps.items()}
        out_ts = out_ts[at]

        new_state = dict(state)
        new_state.update(active=active2, stepi=stepi2, bits=bits2,
                         vbits=vbits2, sts=sts2, adl=adl_2, adl2=adl2_2,
                         capdone=capdone2, consumed=consumed2, armed=armed2,
                         nfa_overflow=overflow2)
        for g, name in enumerate(self.scope_cols):
            new_state[name] = scs2[g]
        for n in cap_names:
            new_state[n] = caps2[n]

        out = self._flatten_out(out_valid, out_caps, out_ts, cols[TS_KEY],
                                cols, pk, B)
        out["__overflow__"] = (overflow2 > state["nfa_overflow"]).astype(jnp.int32)
        out["__notify__"] = _notify_of(self._next_deadline(new_state))
        out["__rounds__"] = n_rounds.astype(jnp.int32)
        out["__tiles__"] = tiles
        return new_state, out

    # ................................................ scheduler TIMER sweep

    def apply_timer(self, state: dict, now, ctx: dict):
        """Advance every key's waiting slots whose deadlines have passed
        (the role of the reference scheduler posting TIMER events through
        AbsentStreamPreStateProcessor). Emissions flatten to [K*S]."""
        plan = self.plan
        S = plan.slots
        K = state["consumed"].shape[0]
        cap_names = list(self.cap_cols)
        ts2d = jnp.broadcast_to(jnp.int64(now), (K, S))

        V = {
            "A": state["active"],
            "ST": state["stepi"],
            "BT": state["bits"],
            "VB": state["vbits"],
            "T0": state["sts"],
            "ADL": state["adl"],
            "AD2": state["adl2"],
            "CD": state["capdone"],
            "CP": {n: state[n] for n in cap_names},
            "SC": [state[g] for g in self.scope_cols],
        }
        V = self._expire(V, ts2d)
        emit = jnp.zeros((K, S), bool)
        ets = ts2d
        fork_reqs: List = []
        V, emit, ets = self._cascade(V, ts2d, emit, ets, fork_reqs)
        # emission snapshot before forks (forks may reuse freed slots)
        emit_CP = dict(V["CP"])
        emit_CD = V["CD"]
        overflow = state["nfa_overflow"]
        for req, target, arm_ts in fork_reqs:
            V, dstm, overflow = self._alloc_forks(V, req, overflow)
            V = self._enter(V, dstm, target, _gather_like(arm_ts, req, dstm))

        new_state = dict(state)
        new_state.update(active=V["A"], stepi=V["ST"], bits=V["BT"],
                         vbits=V["VB"], sts=V["T0"], adl=V["ADL"],
                         adl2=V["AD2"], capdone=V["CD"],
                         nfa_overflow=overflow)
        for g, name in enumerate(self.scope_cols):
            new_state[name] = V["SC"][g]
        for n in cap_names:
            new_state[n] = V["CP"][n]

        # flatten [K, S] emissions
        N = K * S
        out: Dict[str, jnp.ndarray] = {}
        cd_flat = jnp.where(emit, emit_CD, 0).reshape(N)
        for cap in plan.captures:
            got = (cd_flat & (1 << cap.cid)) != 0
            cnt_flat = emit_CP[cap_cnt_col(cap.cid)].reshape(N) if cap.is_count else None
            for a in cap.definition.attributes:
                n = cap_col(cap.cid, a.name)
                out[n] = emit_CP[n].reshape(N)
                out[n + "?"] = emit_CP[n + "?"].reshape(N) | ~got
                for i in range(cap.n_idx):
                    ni = cap_idx_col(cap.cid, i, a.name)
                    out[ni] = emit_CP[ni].reshape(N)
                    out[ni + "?"] = (emit_CP[ni + "?"].reshape(N) | ~got
                                     | (cnt_flat <= i))
                for j in range(1, cap.last_ring + 1):
                    nj = cap_lastk_col(cap.cid, j, a.name)
                    out[nj] = emit_CP[nj].reshape(N)
                    out[nj + "?"] = (emit_CP[nj + "?"].reshape(N) | ~got
                                     | (cnt_flat <= j))
            n = cap_col(cap.cid, TS_KEY)
            out[n] = emit_CP[n].reshape(N)
            if cap.is_count:
                out[cap_cnt_col(cap.cid)] = cnt_flat
            _emit_last_cols(out, cap,
                            lambda nm: emit_CP[nm].reshape(N), got, cnt_flat)
            _emit_present_cols(out, cap, got, cnt_flat, N)
        out[VALID_KEY] = emit.reshape(N)
        out[TS_KEY] = ets.reshape(N)
        out[TYPE_KEY] = jnp.zeros(N, jnp.int8)
        pk_flat = jnp.repeat(jnp.arange(K, dtype=jnp.int32), S)
        out["__gk__"] = pk_flat
        out[PK_KEY] = pk_flat
        out["__overflow__"] = (overflow > state["nfa_overflow"]).astype(jnp.int32)
        out["__notify__"] = _notify_of(self._next_deadline(new_state))
        return new_state, out

    # ......................................................... output shape

    def _flatten_out(self, out_valid, out_caps, out_ts, ts, cols, pk, B):
        """Flatten [B, S+1] emissions row-major (event order, slot order)."""
        S = self.plan.slots
        N = B * (S + 1)
        out: Dict[str, jnp.ndarray] = {}
        capdone_flat = out_caps["__capdone__"].reshape(N)
        for cap in self.plan.captures:
            got = (capdone_flat & (1 << cap.cid)) != 0
            cnt_flat = out_caps[cap_cnt_col(cap.cid)].reshape(N) if cap.is_count else None
            for a in cap.definition.attributes:
                n = cap_col(cap.cid, a.name)
                out[n] = out_caps[n].reshape(N)
                out[n + "?"] = out_caps[n + "?"].reshape(N) | ~got
                for i in range(cap.n_idx):
                    ni = cap_idx_col(cap.cid, i, a.name)
                    out[ni] = out_caps[ni].reshape(N)
                    out[ni + "?"] = (out_caps[ni + "?"].reshape(N) | ~got
                                     | (cnt_flat <= i))
                for j in range(1, cap.last_ring + 1):
                    nj = cap_lastk_col(cap.cid, j, a.name)
                    out[nj] = out_caps[nj].reshape(N)
                    out[nj + "?"] = (out_caps[nj + "?"].reshape(N) | ~got
                                     | (cnt_flat <= j))
            n = cap_col(cap.cid, TS_KEY)
            out[n] = out_caps[n].reshape(N)
            if cap.is_count:
                out[cap_cnt_col(cap.cid)] = cnt_flat
            _emit_last_cols(out, cap,
                            lambda nm: out_caps[nm].reshape(N), got, cnt_flat)
            _emit_present_cols(out, cap, got, cnt_flat, N)
        out[VALID_KEY] = out_valid.reshape(N)
        out[TS_KEY] = out_ts.reshape(N)
        out[TYPE_KEY] = jnp.zeros(N, jnp.int8)  # matches emit as CURRENT
        out["__gk__"] = jnp.repeat(cols.get("__gk__", pk), S + 1)
        if PK_KEY in cols:
            out[PK_KEY] = jnp.repeat(cols[PK_KEY], S + 1)
        return out


def _emit_last_cols(out: Dict, cap: CaptureSpec, flat_of, got, cnt_flat):
    """Materialize ``e[last - k]`` derived columns: the value at runtime
    position cnt-1-k selected across the capture's indexed slots. Open
    counts (cap.last_ring) emit from ring columns instead — never here."""
    if not cap.last_offsets or cnt_flat is None or cap.last_ring:
        return
    for k in sorted(cap.last_offsets):
        pos = cnt_flat - 1 - k
        for a in cap.definition.attributes:
            acc = None
            mk = None
            for i in range(cap.n_idx):
                sel = pos == i
                v = flat_of(cap_idx_col(cap.cid, i, a.name))
                m = flat_of(cap_idx_col(cap.cid, i, a.name) + "?")
                # rows whose pos matches no slot keep slot 0's value but
                # are nulled by the pos<0 / ~got mask below
                acc = v if acc is None else jnp.where(sel, v, acc)
                mk = m if mk is None else jnp.where(sel, m, mk)
            if acc is None:
                continue
            out[cap_last_col(cap.cid, k, a.name)] = acc
            out[cap_last_col(cap.cid, k, a.name) + "?"] = (
                mk | ~got | (pos < 0))


def _emit_present_cols(out: Dict, cap: CaptureSpec, got, cnt_flat, N: int):
    """`@present` synthetics on emitted rows: null mask = StateEvent
    absence (bare `e2 is null` / `e2[last-k] is null` in selectors)."""
    ones = jnp.ones(N, bool)
    out[cap_col(cap.cid, PRESENT)] = ones
    out[cap_col(cap.cid, PRESENT) + "?"] = (
        ~got if cnt_flat is None else ~got | (cnt_flat == 0))
    if cnt_flat is None:
        return
    for i in range(cap.n_idx):
        out[cap_idx_col(cap.cid, i, PRESENT)] = ones
        out[cap_idx_col(cap.cid, i, PRESENT) + "?"] = ~got | (cnt_flat <= i)
    for j in range(1, cap.last_ring + 1):
        out[cap_lastk_col(cap.cid, j, PRESENT)] = ones
        out[cap_lastk_col(cap.cid, j, PRESENT) + "?"] = ~got | (cnt_flat <= j)
    if not cap.last_ring:
        for k in sorted(cap.last_offsets):
            out[cap_last_col(cap.cid, k, PRESENT)] = ones
            out[cap_last_col(cap.cid, k, PRESENT) + "?"] = (
                ~got | (cnt_flat - 1 - k < 0))


def fresh_cap_step(plan: NFAPlan, rest_step: int, bits_val: int) -> int:
    """The step whose event a fresh slot captured: rest-past slots captured
    step rest-1; park-at slots (counts, logical sides) captured rest."""
    if bits_val != 0:
        return rest_step
    if rest_step > 0 and plan.steps[rest_step - 1].kind == "stream":
        return rest_step - 1
    return rest_step


def _zero(x):
    return jnp.zeros((), x.dtype)


def _gather_like(arm_ts, req, dst_mask):
    """Move per-source-slot arm timestamps to their allocated destination
    slots: within a row, sources and destinations pair in slot-rank order,
    and `_alloc_forks` preserves rank, so a rank-aligned sort suffices."""
    S = req.shape[1]
    idx = jnp.arange(S)[None, :]
    src_key = jnp.where(req, idx, S + idx)
    src_sorted = jnp.take_along_axis(arm_ts, jnp.argsort(src_key, axis=1), axis=1)
    rank_dst = jnp.cumsum(dst_mask, axis=1, dtype=jnp.int32) - 1
    vals = jnp.take_along_axis(src_sorted, jnp.clip(rank_dst, 0, S - 1), axis=1)
    return jnp.where(dst_mask, vals, 0)


def _notify_of(next_dl):
    return jnp.where(next_dl >= FAR_FUTURE, jnp.int64(-1), next_dl)
