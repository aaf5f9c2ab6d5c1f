"""Event model: user-facing Event rows and columnar batches.

Replaces the reference's pooled row objects and linked-list chunks
(``core/event/Event.java``, ``event/stream/StreamEvent.java:37-57``,
``event/ComplexEventChunk.java:62-232``) with a struct-of-arrays design:
each stream batch is one numpy (host) / jax (device) array per attribute
plus timestamp, event-type and validity columns. The linked-list surgery of
``ComplexEventChunk`` becomes mask updates; the CURRENT/EXPIRED/TIMER/RESET
event types (``ComplexEvent.Type``) become an i8 column.
"""

from __future__ import annotations

import ctypes
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

from siddhi_tpu.observability import journey
from siddhi_tpu.observability.tracing import span, spans_on
from siddhi_tpu.ops.expressions import (PADDED_KEY, TS_KEY, TYPE_KEY,
                                        VALID_KEY)
from siddhi_tpu.ops.types import dtype_of
from siddhi_tpu.query_api.definitions import AbstractDefinition, AttrType

# ComplexEvent.Type (reference event/ComplexEvent.java)
CURRENT = 0
EXPIRED = 1
TIMER = 2
RESET = 3

TYPE_NAMES = {CURRENT: "CURRENT", EXPIRED: "EXPIRED", TIMER: "TIMER", RESET: "RESET"}


@dataclass
class Event:
    """User-facing event (reference ``core/event/Event.java``)."""

    timestamp: int = -1
    data: Sequence = field(default_factory=list)
    is_expired: bool = False  # kept for API parity with the reference
    # partition-key id for events flowing through inner '#streams' (the
    # analog of the reference's ThreadLocal partition flow id,
    # SiddhiAppContext.java:55). None outside partitions.
    pk: Optional[int] = None
    # dense group-key id (GroupedComplexEvent.getGroupKey analog) — attached
    # only when a grouped rate limiter needs a key that isn't projected
    gk: Optional[int] = None

    def __repr__(self):
        return f"Event{{timestamp={self.timestamp}, data={list(self.data)}, isExpired={self.is_expired}}}"


class StringDictionary:
    """App-global string <-> int32 id dictionary.

    Strings never reach the device: group keys, symbols etc. travel as dense
    ids (the TPU answer to per-event string group-key building in reference
    ``GroupByKeyGenerator.java:37``). The dictionary only grows, so encoded
    ids (including ones baked into compiled constants) stay stable.
    """

    NULL_ID = -1

    def __init__(self):
        self._to_id: Dict[str, int] = {}
        self._to_str: List[str] = []
        # insert guard: id assignment is check-then-append, and the wire
        # front door (ThreadingHTTPServer threads in decode_frame) plus
        # multiple @Async producers can insert concurrently — without
        # this, the same NEW string can win two different ids and split
        # one group key in two. Reads stay lock-free (GIL-atomic dict
        # probe); only the rare miss pays the lock.
        self._insert_lock = threading.Lock()
        # native accelerator (strdict.cpp): a C++ mirror of _to_id probed
        # once per string by encode_array. Python stays authoritative for
        # the id space — the mirror only ever holds (string, id) pairs
        # that already exist in _to_id. Lazily created on first bulk
        # encode; None when the native lib is unavailable.
        self._native = None
        self._native_lib = None

    def encode(self, s: Optional[str]) -> int:
        if s is None:
            return self.NULL_ID
        i = self._to_id.get(s)
        if i is None:
            with self._insert_lock:
                i = self._to_id.get(s)     # double-check under the lock
                if i is None:
                    i = len(self._to_str)
                    self._to_str.append(s)
                    if self._native is not None:
                        self._mirror_insert(s, i)
                    # publish the id LAST: a lock-free reader that sees
                    # the dict entry must find _to_str[i] present
                    self._to_id[s] = i
        return i

    def _mirror_insert(self, s: str, i: int):
        try:
            b = s.encode("utf-8")
        except UnicodeEncodeError:
            # lone surrogates (surrogateescape-decoded transport bytes)
            # can't round-trip utf-8; they stay on the Python slow path
            # (strdict_encode marks them misses anyway)
            return
        self._native_lib.strdict_insert(self._native, b, len(b), i)

    def restore_strings(self, strings: List[str]):
        """Replace the id space wholesale (snapshot restore) — rebuilds the
        native mirror, which would otherwise serve ids from the discarded
        space."""
        with self._insert_lock:
            self._to_str = list(strings)
            self._to_id = {s: i for i, s in enumerate(strings)}
            if self._native is not None:
                self._native_lib.strdict_clear(self._native)
                for i, s in enumerate(strings):
                    self._mirror_insert(s, i)

    def __del__(self):
        try:
            if self._native is not None:
                self._native_lib.strdict_free(self._native)
        except Exception:
            pass

    def decode(self, i: int) -> Optional[str]:
        if i < 0:
            return None
        return self._to_str[i]

    def rank_table(self, min_capacity: int = 16) -> np.ndarray:
        """Lexicographic rank per id, padded to a pow2 capacity so growth
        rarely changes the array SHAPE (ids are assigned in arrival order,
        so `order by` on a string column must sort by rank, not id —
        OrderByLimitTestCase limitTest2). Cached per dictionary size."""
        n = len(self._to_str)
        cap = max(min_capacity, 16)
        while cap < n + 1:   # keep >= one pad slot: id -1 wraps to table[-1]
            cap *= 2
        cached = getattr(self, "_rank_cache", None)
        if cached is not None and cached[0] == n and len(cached[1]) == cap:
            return cached[1]
        # padding (including the wrapped null id -1) ranks AFTER every
        # real string, so nulls sort last
        table = np.full(cap, n, np.int32)
        if n:
            order = sorted(range(n), key=lambda i: self._to_str[i])
            for r, i in enumerate(order):
                table[i] = r
        self._rank_cache = (n, table)
        return table

    _MISS = -2

    def _ensure_native(self):
        """Lazy native-mirror build, guarded so concurrent first probes
        (ingest pack-pool workers) build it exactly once."""
        if self._native is not None or self._native_lib is not None:
            return
        with _NATIVE_INIT_LOCK:
            if self._native is not None or self._native_lib is not None:
                return
            from siddhi_tpu.native import strdict_lib

            lib = strdict_lib()
            if lib is None:
                self._native_lib = False   # failed: never re-probe the lib
            else:
                self._native_lib = lib
                self._native = ctypes.c_void_p(lib.strdict_new())
                # backfill from a SNAPSHOT (a concurrent encode() insert
                # would otherwise mutate the dict mid-iteration); a pair
                # inserted twice — here and by that racing encode — is
                # idempotent, and a pair the snapshot missed at worst
                # probes as an extra _MISS, resolved correctly by the
                # serial phase; never a wrong id
                with self._insert_lock:
                    items = list(self._to_id.items())
                for s, i in items:
                    self._mirror_insert(s, i)

    def probe_array(self, values: np.ndarray) -> np.ndarray:
        """Read-only bulk probe: ids for known strings, ``_MISS`` markers
        for everything else (new strings, Nones, non-str values) —
        NOTHING is inserted, so concurrent probes from ingest pack-pool
        workers are safe. Callers resolve the markers serially (in row
        order) via :meth:`resolve_missing` so the id ASSIGNMENT order —
        which snapshots and rank tables observe — stays identical to the
        single-threaded encode."""
        arr = np.asarray(values, object)
        if not arr.flags.c_contiguous:
            arr = np.ascontiguousarray(arr)
        out = np.empty(len(arr), np.int64)
        self._ensure_native()
        if self._native is not None:
            self._native_lib.strdict_encode(
                self._native, arr.ctypes.data_as(ctypes.c_void_p), len(arr),
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                self.NULL_ID, self._MISS)
        else:
            get = self._to_id.get
            out = np.fromiter((get(v, self._MISS) for v in arr),
                              np.int64, len(arr))
        return out

    def resolve_missing(self, ids: np.ndarray, value_of) -> None:
        """Serial second phase of a bulk encode: replace every ``_MISS``
        marker in ``ids`` (in index order) by encoding ``value_of(i)`` —
        the ONLY place a bulk path inserts new strings, so parallel
        probes stay deterministic."""
        miss_idx = np.nonzero(ids == self._MISS)[0]
        for i in miss_idx:
            v = value_of(int(i))
            ids[i] = (self.NULL_ID if v is None
                      else self.encode(v if type(v) is str else str(v)))

    def encode_array(self, values: np.ndarray) -> np.ndarray:
        """Bulk dictionary encoding — the batched answer to per-event
        string keys (``GroupByKeyGenerator.java:37``). Fast path: ONE call
        into the native open-addressing map (strdict.cpp; ~10x the Python
        dict loop at 65k-row batches); only misses (NEW strings, Nones,
        non-str values) take the per-element Python path
        (``resolve_missing``), which also inserts new pairs into the
        native mirror via ``encode``. Falls back to a per-string Python
        dict probe when the native lib can't build. Nones encode to
        NULL_ID."""
        arr = np.asarray(values, object)
        if not arr.flags.c_contiguous:
            arr = np.ascontiguousarray(arr)
        out = self.probe_array(arr)
        self.resolve_missing(out, lambda i: arr[i])
        return out

    def __len__(self):
        return len(self._to_str)


def encode_key_tuples(arrays, rows: np.ndarray, id_of) -> np.ndarray:
    """Dense ids for key tuples taken row-wise from ``arrays`` at ``rows``:
    structured-array ``np.unique`` preserves each column's dtype, and the
    Python dictionary (``id_of``) is probed once per *unique* tuple — the
    shared batched keying used by GroupKeyer and ValuePartitionKeyer."""
    B = arrays[0].shape[0]
    rec = np.empty(B, dtype=[(f"k{i}", a.dtype) for i, a in enumerate(arrays)])
    for i, a in enumerate(arrays):
        rec[f"k{i}"] = a
    uniq, inv = np.unique(rec[rows], return_inverse=True)
    lut = np.empty(len(uniq), np.int32)
    for u_i in range(len(uniq)):
        lut[u_i] = id_of(tuple(x.item() for x in uniq[u_i]))
    return lut[inv]


# vectorized None-scan over object columns (HostBatch.from_events): one
# ufunc sweep instead of a per-row `is None` list comprehension
_NONE_MASK = np.frompyfunc(lambda v: v is None, 1, 1)

# one-shot native strdict bootstrap guard (StringDictionary._ensure_native):
# plain Lock, not make_lock — held only around the ctypes constructor, no
# ranked lock is ever taken under it
_NATIVE_INIT_LOCK = threading.Lock()


def pack_pool_of(app_context):
    """The app's ingest pack pool, or None (pool size 0 / no context) —
    the one accessor every pack call site uses, so the inline path stays
    a single getattr (``core/stream/input/pack_pool.py``)."""
    if app_context is None:
        return None
    return getattr(app_context, "ingest_pack_pool", None)


def _pad_len(n: int, minimum: int = 8) -> int:
    """Pad batch length to a power of two to bound jit recompiles."""
    b = minimum
    while b < n:
        b *= 2
    return b


def _pull(refs: list, rows: bool) -> list:
    """The output's device->host pull (``LazyColumns``): one
    ``jax.device_get`` of ``refs``, under a ``siddhi.pull`` span while
    spans are on, and charged to the journey whose emit stage is open on
    this thread. ``rows``: the pull is of output columns, whose padded
    length counts as ``rows_padded`` (a popped control scalar's does
    not)."""
    import jax

    if not spans_on():
        return jax.device_get(refs)
    nbytes = sum(int(getattr(r, "nbytes", 0)) for r in refs)
    jr = journey.emitting_journey()
    length = max((r.shape[0] for r in refs if getattr(r, "ndim", 0)),
                 default=0) if rows else 0
    with span("pull", bytes=nbytes, arrays=len(refs), rows=length,
              batch=jr.batch if jr is not None else None) as sp:
        out = jax.device_get(refs)
    if jr is not None:
        jr.pulled(sp.ms or 0.0, length)
    return out


def launch_step(step, state, *args, query: str, jr=None):
    """The host->device side of a batch: ``step(state, *args)``, the call
    of a jitted step and nothing else, under a ``siddhi.launch`` span
    while spans are on, and charged to the batch's journey ``jr``. There
    is no ``device_put`` on the hot path: the batch's numpy columns go up
    inside this call, so ``h2d_bytes`` / ``h2d_arrays`` are the ``nbytes``
    and count of the argument leaves that are numpy (a leaf already on the
    device crosses nothing); ``state_leaves`` is what the call flattens
    beside them. A step that binds further arguments itself (a join side's
    probe surface) names them in ``step.bound``."""
    if not spans_on():
        return step(state, *args)
    import jax

    leaves = jax.tree_util.tree_leaves(state)
    n_state = len(leaves)
    leaves += jax.tree_util.tree_leaves((args, getattr(step, "bound", ())))
    up = [x for x in leaves if isinstance(x, (np.ndarray, np.generic))]
    nbytes = sum(int(x.nbytes) for x in up)
    with span("launch", query=query, h2d_bytes=nbytes, h2d_arrays=len(up),
              state_leaves=n_state,
              batch=jr.batch if jr is not None else None) as sp:
        out = step(state, *args)
    if jr is not None:
        jr.launched(sp.ms, nbytes)
    return out


class LazyColumns(dict):
    """Column dict whose device-array values materialize to numpy on first
    access. A device->host pull is a synchronization that costs a fixed
    part and the bytes it moves (on the v5e 1.3 ms a pull plus 1.9 ms per
    65,536 rows of a cell's columns: PERF_LEDGER.jsonl, PR 29), and
    ``jax.device_get`` batches arbitrarily many arrays into one round
    trip — so the first touched device column pulls every remaining
    device column in one transfer, and
    consumers that never read data columns (output counters served by the
    ``__meta__`` size hint) pull nothing.

    An NFA step's columns are its valid rows compacted to a narrower
    static width (``ops/compact.py``), and the same columns at their padded
    width ride beside them under ``PADDED_KEY``. Those are held aside, out
    of reach of a pull, until ``choose`` is told the meta's count: it
    throws them away where the count fits the compacted columns and swaps
    them in where it does not. Whoever builds a ``HostBatch`` from a
    step's output calls it first (``QueryRuntime._host_batch``); a pull of
    an output nobody chose for moves the padded columns, which hold every
    row whatever the count."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._padded = dict.pop(self, PADDED_KEY, None)
        # after a fall-back, the compacted width it fell back from
        self.fell_back_from = None

    def choose(self, count: Optional[int]):
        """Settle what a pull will move, before anything touches a column:
        the compacted columns if the output's ``count`` valid rows fit them
        (True), else the padded ones (False; ``count`` None: not known, so
        they do not). None, and nothing done, for an output that carries
        one set of columns only."""
        padded, self._padded = self._padded, None
        if padded is None:
            return None
        width = dict.__getitem__(self, VALID_KEY).shape[0]
        if count is not None and count <= width:
            return True
        dict.update(self, padded)
        self.fell_back_from = width
        return False

    def __getitem__(self, k):
        v = super().__getitem__(k)
        if not isinstance(v, np.ndarray):
            self._materialize_all()
            v = super().__getitem__(k)
        return v

    def _materialize_all(self):
        if self._padded is not None:
            self.choose(None)
        pending = [(key, val) for key, val in super().items()
                   if not isinstance(val, np.ndarray)]
        if not pending:
            return
        pulled = _pull([v for _k, v in pending], rows=True)
        for (key, _v), arr in zip(pending, pulled):
            super().__setitem__(key, np.asarray(arr))

    def get(self, k, default=None):
        if k in self:
            return self[k]
        return default

    def pop(self, k, *default):
        # pops materialize ONLY the popped value (control scalars like
        # __meta__ must not drag every data column across the link);
        # explicit device_get — this IS a sanctioned pull point, and the
        # SIDDHI_TPU_SANITIZE transfer guard rejects implicit transfers
        if k in self:
            v = super().__getitem__(k)
            dict.pop(self, k)
            if not isinstance(v, np.ndarray):
                v = np.asarray(_pull([v], rows=False)[0])
            return v
        if default:
            return default[0]
        raise KeyError(k)


class HostBatch:
    """Columnar batch on host (numpy), convertible to device cols dict.

    Column keys: attribute names (optionally prefixed by the planner), plus
    reserved ``__ts__`` (i64), ``__type__`` (i8), ``__valid__`` (bool) and
    per-attribute null masks under ``<key>?``. Columns may be lazily-held
    device arrays (``LazyColumns``) that pull on first read.
    """

    def __init__(self, cols: Dict[str, np.ndarray], size: Optional[int] = None):
        self.cols = cols
        self._size = size        # known valid-row count (avoids a pull)

    @property
    def size(self) -> int:
        if self._size is None:
            self._size = int(np.asarray(self.cols[VALID_KEY]).sum())
        return self._size

    @property
    def capacity(self) -> int:
        return self.cols[VALID_KEY].shape[0]

    # per-batch journey trace context (observability/journey.py): stamped
    # at pack when journey tracing is on, forked per receiving query
    journey = None

    @staticmethod
    def from_events(
        events: Sequence[Event],
        definition: AbstractDefinition,
        dictionary: StringDictionary,
        pad_to: Optional[int] = None,
        event_type: int = CURRENT,
        pool=None,
    ) -> "HostBatch":
        # the pack stage: one span, and the journey's stamp from it
        with journey.pack_span() as sp:
            batch, pack_ms = HostBatch._pack_events(
                events, definition, dictionary, pad_to, event_type, pool)
        journey.stamp_pack(batch, sp, pack_ms)
        return batch

    @staticmethod
    def _pack_events(events, definition, dictionary, pad_to, event_type,
                     pool):
        """The batch, and the pack service time where it is not the
        caller's span (the parallel pack's max-not-sum), else None."""
        if pool is not None:
            chunks = pool.plan_events(len(events), definition)
            if chunks is not None:
                # multicore ingest (core/stream/input/pack_pool.py): the
                # encode work runs as sequence-numbered sub-batch tasks
                # on the pool; the ordered merge keeps outputs AND
                # dictionary id assignment bit-identical to this inline
                # path. The plan is computed ONCE and threaded through —
                # a pool state flip between two plan calls must not
                # strand the batch between paths.
                return _parallel_from_events(pool, chunks, events,
                                             definition, dictionary,
                                             pad_to, event_type)
        if journey.enabled():
            journey.maybe_delay("pack")   # tests' planted pack bottleneck
        n = len(events)
        b = pad_to if pad_to is not None else _pad_len(n)
        cols: Dict[str, np.ndarray] = {
            TS_KEY: np.zeros(b, np.int64),
            TYPE_KEY: np.full(b, event_type, np.int8),
            VALID_KEY: np.zeros(b, bool),
        }
        cols[VALID_KEY][:n] = True
        if n:
            cols[TS_KEY][:n] = np.fromiter(
                (ev.timestamp for ev in events), np.int64, n)
            expired = np.fromiter((ev.is_expired for ev in events), bool, n)
            if expired.any():
                cols[TYPE_KEY][:n][expired] = EXPIRED
        rows = [ev.data for ev in events]
        for pos, attr in enumerate(definition.attributes):
            dtype = dtype_of(attr.type)
            arr = np.zeros(b, dtype)
            # null masks are always present so device column sets (and jit
            # shapes) stay static whether or not a batch contains nulls
            mask = np.zeros(b, bool)
            if n:
                if attr.type == AttrType.OBJECT:
                    # set ingestion. Element codes follow the stream's
                    # recorded element type (see encode_set_value); the
                    # representation follows its multi/singleton register:
                    # a MULTI attr (unionSet output) re-encodes as live
                    # count + '#set'/'#setm' companions, a singleton as its
                    # element code.
                    from siddhi_tpu.ops.expressions import encode_set_value

                    elem_t = (getattr(definition, "object_elem_types", None)
                              or {}).get(attr.name)
                    multi = attr.name in (getattr(
                        definition, "object_multi_attrs", None) or set())
                    as_sets = []
                    nulls = []
                    for i, r in enumerate(rows):
                        val = r[pos]
                        if val is None:
                            nulls.append(i)
                            as_sets.append(frozenset())
                        elif isinstance(val, (set, frozenset)):
                            as_sets.append(val)
                        else:
                            as_sets.append(frozenset([val]))
                    if multi:
                        H = max(1, max((len(s) for s in as_sets), default=1))
                        snap = np.zeros((b, H), np.int64)
                        snapm = np.zeros((b, H), bool)
                        for i, s in enumerate(as_sets):
                            for j, el in enumerate(s):
                                snap[i, j] = encode_set_value(
                                    el, elem_t, dictionary)
                                snapm[i, j] = True
                            arr[i] = len(s)
                        cols[attr.name + "#set"] = snap
                        cols[attr.name + "#setm"] = snapm
                    else:
                        for i, s in enumerate(as_sets):
                            if len(s) > 1:
                                raise ValueError(
                                    f"attribute '{attr.name}' carries "
                                    "singleton sets (createSet transport); "
                                    "got a multi-element set")
                            if s:
                                arr[i] = encode_set_value(
                                    next(iter(s)), elem_t, dictionary)
                    if nulls:
                        mask[nulls] = True
                elif attr.type == AttrType.STRING:
                    # ONE bulk dictionary pass over the column (native
                    # strdict fast path; Nones encode to NULL_ID there)
                    # instead of a per-row Python encode() probe
                    col = np.fromiter((r[pos] for r in rows), object, n)
                    ids = dictionary.encode_array(col)
                    mask[:n] = ids == StringDictionary.NULL_ID
                    arr[:n] = np.where(mask[:n], 0, ids)
                else:
                    zero = False if attr.type == AttrType.BOOL else 0
                    col = np.fromiter((r[pos] for r in rows), object, n)
                    nulls = _NONE_MASK(col).astype(bool)
                    if nulls.any():
                        mask[:n] = nulls
                        arr[:n] = np.where(nulls, zero, col)
                    else:
                        arr[:n] = col
            cols[attr.name] = arr
            cols[attr.name + "?"] = mask
        return HostBatch(cols), None

    @staticmethod
    def from_columns(
        data: Dict[str, np.ndarray],
        definition: AbstractDefinition,
        dictionary: StringDictionary,
        timestamps: Optional[np.ndarray] = None,
        default_ts: int = 0,
        pad_to: Optional[int] = None,
        pool=None,
    ) -> "HostBatch":
        """Zero-copy-ish columnar ingestion — the TPU-native fast path that
        skips per-event objects entirely. ``data`` maps attribute names to
        arrays (strings may be numpy object/str arrays, encoded here, or
        pre-encoded int ids). ``<name>?`` null-mask arrays are optional."""
        with journey.pack_span() as sp:
            batch, pack_ms = HostBatch._pack_columns(
                data, definition, dictionary, timestamps, default_ts,
                pad_to, pool)
        journey.stamp_pack(batch, sp, pack_ms)
        return batch

    @staticmethod
    def _pack_columns(data, definition, dictionary, timestamps, default_ts,
                      pad_to, pool):
        if pool is not None:
            chunks = pool.plan_columns(data, definition)
            if chunks is not None:
                return _parallel_from_columns(pool, chunks, data,
                                              definition, dictionary,
                                              timestamps, default_ts,
                                              pad_to)
        if journey.enabled():
            journey.maybe_delay("pack")
        first = next(iter(data.values()))
        n = len(first)
        b = pad_to if pad_to is not None else _pad_len(n)
        cols: Dict[str, np.ndarray] = {
            TYPE_KEY: np.full(b, CURRENT, np.int8),
            VALID_KEY: np.zeros(b, bool),
        }
        cols[VALID_KEY][:n] = True
        ts = np.zeros(b, np.int64)
        if timestamps is not None:
            ts[:n] = np.asarray(timestamps, np.int64)[:n]
        else:
            ts[:n] = default_ts
        cols[TS_KEY] = ts
        for attr in definition.attributes:
            if attr.name not in data:
                raise KeyError(f"column '{attr.name}' missing from batch")
            src = np.asarray(data[attr.name])
            dtype = dtype_of(attr.type)
            arr = np.zeros(b, dtype)
            mask = np.zeros(b, bool)
            if attr.type == AttrType.STRING and not np.issubdtype(src.dtype, np.integer):
                ids = dictionary.encode_array(src)[:n]
                mask[:n] = ids == StringDictionary.NULL_ID
                arr[:n] = np.where(mask[:n], 0, ids)
            elif attr.type == AttrType.STRING:
                ids = np.asarray(src[:n], np.int64)
                mask[:n] = ids < 0  # pre-encoded: negative = null
                arr[:n] = np.where(mask[:n], 0, ids)
            else:
                arr[:n] = src[:n]
            user_mask = data.get(attr.name + "?")
            if user_mask is not None:
                mask[:n] |= np.asarray(user_mask, bool)[:n]
            cols[attr.name] = arr
            cols[attr.name + "?"] = mask
        return HostBatch(cols), None

    def to_events(
        self,
        attr_order: Sequence[tuple],  # [(key, AttrType), ...]
        dictionary: StringDictionary,
        types_wanted: Optional[Sequence[int]] = None,
        pk_key: Optional[str] = None,
        gk_key: Optional[str] = None,
        object_meta: Optional[Dict[str, object]] = None,
        object_multi: Optional[set] = None,
    ) -> List[Event]:
        """Decode valid rows into Events (optionally filtered by type).
        ``pk_key`` names a partition-id column to attach as Event.pk;
        ``gk_key`` a group-id column to attach as Event.gk.
        ``object_meta`` maps OBJECT (set-valued) attr names to their
        element AttrType (raw int codes without it); ``object_multi``
        names the attrs that are MULTI-element sets — decoding one whose
        '#set' companions were dropped raises instead of emitting the
        live count as a bogus singleton."""
        valid = np.asarray(self.cols[VALID_KEY])
        types = np.asarray(self.cols[TYPE_KEY])
        ts = np.asarray(self.cols[TS_KEY])
        pk_col = self.cols.get(pk_key) if pk_key is not None else None
        gk_col = self.cols.get(gk_key) if gk_key is not None else None
        keep = valid
        if types_wanted is not None:
            keep = keep & np.isin(types, list(types_wanted))
        idx = np.nonzero(keep)[0]
        if idx.size == 0:
            return []
        # decode per column (vectorized), then zip rows — no per-cell
        # dispatch on dtype inside the row loop
        col_lists: List[list] = []
        for key, attr_type in attr_order:
            vals = np.asarray(self.cols[key])[idx]
            if attr_type == AttrType.OBJECT:
                # set values: '#set'/'#setm' companions hold the elements
                # (unionSet snapshots); a bare column is a singleton set
                # whose value IS the element code (createSet transport)
                from siddhi_tpu.ops.expressions import decode_set_element

                elem_t = (object_meta or {}).get(key)
                snap = self.cols.get(key + "#set")
                if snap is not None:
                    sv = np.asarray(snap)[idx]
                    sm = np.asarray(self.cols[key + "#setm"])[idx]
                    lst = [frozenset(decode_set_element(c, elem_t, dictionary)
                                     for c in row_v[row_m])
                           for row_v, row_m in zip(sv, sm)]
                elif object_multi and key in object_multi:
                    # the base column of a multi set is its live COUNT —
                    # decoding it as an element would be silent garbage
                    # (mirrors the unionSet arg_is_multi guard)
                    raise ValueError(
                        f"multi-element set attribute '{key}' lost its "
                        f"'#set' element snapshot (a window buffers only "
                        f"the base column); project it before windowing")
                else:
                    lst = [frozenset([decode_set_element(v, elem_t, dictionary)])
                           for v in vals]
                mask = self.cols.get(key + "?")
                if mask is not None:
                    mvals = np.asarray(mask)[idx]
                    if mvals.any():
                        lst = [None if m else v for v, m in zip(lst, mvals)]
                col_lists.append(lst)
                continue
            if attr_type == AttrType.STRING:
                lst = [dictionary.decode(int(v)) for v in vals]
            elif attr_type == AttrType.BOOL:
                lst = [bool(v) for v in vals]
            elif attr_type in (AttrType.INT, AttrType.LONG):
                lst = vals.astype(np.int64).tolist()
            else:
                lst = vals.astype(np.float64).tolist()
            mask = self.cols.get(key + "?")
            if mask is not None:
                mvals = np.asarray(mask)[idx]
                if mvals.any():
                    lst = [None if m else v for v, m in zip(lst, mvals)]
            col_lists.append(lst)
        ts_l = ts[idx].tolist()
        exp_l = (types[idx] == EXPIRED).tolist()
        rows = zip(*col_lists) if col_lists else ([] for _ in idx)
        out = [
            Event(timestamp=t, data=list(r), is_expired=e)
            for t, e, r in zip(ts_l, exp_l, rows)
        ]
        if pk_col is not None:
            pks = np.asarray(pk_col)[idx].tolist()
            for ev, p in zip(out, pks):
                ev.pk = int(p)
        if gk_col is not None:
            gks = np.asarray(gk_col)[idx].tolist()
            for ev, g in zip(out, gks):
                ev.gk = int(g)
        return out


# ------------------------------------------------------ parallel ordered pack
#
# The multicore half of HostBatch.from_events / from_columns ("Scaling
# Ordered Stream Processing on Shared-Memory Multicores", PAPERS.md): the
# encode work of ONE batch is split into sequence-numbered row-range
# sub-batches that run on the app's IngestPackPool workers, each writing a
# disjoint slice of the pre-allocated output columns. The ordered merge —
# waiting the sub-batches out in sequence order, then resolving every NEW
# string serially in attribute-major row order — keeps the produced arrays
# AND the dictionary's id-assignment order bit-identical to the inline
# path, so emission order, WAL records, snapshots and rank tables cannot
# tell the paths apart. Journey pack attribution follows the PR-11
# max-not-sum rule: concurrent sub-batch service counts once (the slowest
# packer), plus the serial merge.

def _parallel_from_events(pool, chunks, events, definition, dictionary,
                          pad_to, event_type):
    jt = journey.enabled()
    n = len(events)
    b = pad_to if pad_to is not None else _pad_len(n)
    cols: Dict[str, np.ndarray] = {
        TS_KEY: np.zeros(b, np.int64),
        TYPE_KEY: np.full(b, event_type, np.int8),
        VALID_KEY: np.zeros(b, bool),
    }
    cols[VALID_KEY][:n] = True
    attrs = definition.attributes
    arrs: Dict[str, np.ndarray] = {}
    masks: Dict[str, np.ndarray] = {}
    scratch: Dict[str, np.ndarray] = {}   # string probe ids (_MISS marked)
    positions = {}
    for pos, attr in enumerate(attrs):
        arrs[attr.name] = np.zeros(b, dtype_of(attr.type))
        masks[attr.name] = np.zeros(b, bool)
        positions[attr.name] = pos
        if attr.type == AttrType.STRING:
            scratch[attr.name] = np.empty(n, np.int64)

    def pack_chunk(lo: int, hi: int) -> None:
        if jt:
            journey.maybe_delay("pack")   # planted-bottleneck injection
        m = hi - lo
        sub = events[lo:hi]
        cols[TS_KEY][lo:hi] = np.fromiter(
            (ev.timestamp for ev in sub), np.int64, m)
        expired = np.fromiter((ev.is_expired for ev in sub), bool, m)
        if expired.any():
            cols[TYPE_KEY][lo:hi][expired] = EXPIRED
        rows = [ev.data for ev in sub]
        for pos, attr in enumerate(attrs):
            if attr.type == AttrType.STRING:
                col = np.fromiter((r[pos] for r in rows), object, m)
                # probe only — new strings stay _MISS markers for the
                # serial merge (deterministic id assignment)
                scratch[attr.name][lo:hi] = dictionary.probe_array(col)
            else:
                zero = False if attr.type == AttrType.BOOL else 0
                col = np.fromiter((r[pos] for r in rows), object, m)
                nulls = _NONE_MASK(col).astype(bool)
                if nulls.any():
                    masks[attr.name][lo:hi] = nulls
                    arrs[attr.name][lo:hi] = np.where(nulls, zero, col)
                else:
                    arrs[attr.name][lo:hi] = col

    chunk_ms = pool.run_ordered(chunks, pack_chunk)
    t_merge = time.perf_counter()
    for attr in attrs:
        if attr.type == AttrType.STRING:
            ids = scratch[attr.name]
            pos = positions[attr.name]
            # serial miss resolution in row order, attributes in
            # declaration order — the exact insertion order the inline
            # per-attribute encode_array produces
            dictionary.resolve_missing(
                ids, lambda i, _p=pos: events[i].data[_p])
            mask = ids == StringDictionary.NULL_ID
            masks[attr.name][:n] = mask
            arrs[attr.name][:n] = np.where(mask, 0, ids)
        cols[attr.name] = arrs[attr.name]
        cols[attr.name + "?"] = masks[attr.name]
    batch = HostBatch(cols)
    merge_ms = (time.perf_counter() - t_merge) * 1000.0
    pool.record_merge(merge_ms)
    # max-not-sum: sub-batches packed concurrently — the pack stage's
    # service is the slowest packer plus the serial merge
    return batch, max(chunk_ms, default=0.0) + merge_ms


def _parallel_from_columns(pool, chunks, data, definition, dictionary,
                           timestamps, default_ts, pad_to):
    jt = journey.enabled()
    first = next(iter(data.values()))
    n = len(first)
    b = pad_to if pad_to is not None else _pad_len(n)
    cols: Dict[str, np.ndarray] = {
        TYPE_KEY: np.full(b, CURRENT, np.int8),
        VALID_KEY: np.zeros(b, bool),
    }
    cols[VALID_KEY][:n] = True
    ts = np.zeros(b, np.int64)
    if timestamps is not None:
        ts_src = np.asarray(timestamps, np.int64)
    else:
        ts_src = None
        ts[:n] = default_ts
    cols[TS_KEY] = ts
    attrs = definition.attributes
    for attr in attrs:
        if attr.name not in data:
            raise KeyError(f"column '{attr.name}' missing from batch")
    arrs: Dict[str, np.ndarray] = {}
    masks: Dict[str, np.ndarray] = {}
    scratch: Dict[str, np.ndarray] = {}
    srcs = {attr.name: np.asarray(data[attr.name]) for attr in attrs}
    str_obj = {attr.name: (attr.type == AttrType.STRING
                           and not np.issubdtype(srcs[attr.name].dtype,
                                                 np.integer))
               for attr in attrs}
    for attr in attrs:
        arrs[attr.name] = np.zeros(b, dtype_of(attr.type))
        masks[attr.name] = np.zeros(b, bool)
        if str_obj[attr.name]:
            scratch[attr.name] = np.empty(n, np.int64)

    def pack_chunk(lo: int, hi: int) -> None:
        if jt:
            journey.maybe_delay("pack")
        if ts_src is not None:
            ts[lo:hi] = ts_src[lo:hi]
        for attr in attrs:
            src = srcs[attr.name]
            if str_obj[attr.name]:
                scratch[attr.name][lo:hi] = dictionary.probe_array(
                    src[lo:hi])
            elif attr.type == AttrType.STRING:
                ids = np.asarray(src[lo:hi], np.int64)
                m = ids < 0           # pre-encoded: negative = null
                masks[attr.name][lo:hi] = m
                arrs[attr.name][lo:hi] = np.where(m, 0, ids)
            else:
                arrs[attr.name][lo:hi] = src[lo:hi]

    chunk_ms = pool.run_ordered(chunks, pack_chunk)
    t_merge = time.perf_counter()
    for attr in attrs:
        if str_obj[attr.name]:
            ids = scratch[attr.name]
            src = srcs[attr.name]
            dictionary.resolve_missing(ids, lambda i, _s=src: _s[i])
            mask = ids == StringDictionary.NULL_ID
            masks[attr.name][:n] = mask
            arrs[attr.name][:n] = np.where(mask, 0, ids)
        user_mask = data.get(attr.name + "?")
        if user_mask is not None:
            masks[attr.name][:n] |= np.asarray(user_mask, bool)[:n]
        cols[attr.name] = arrs[attr.name]
        cols[attr.name + "?"] = masks[attr.name]
    batch = HostBatch(cols)
    merge_ms = (time.perf_counter() - t_merge) * 1000.0
    pool.record_merge(merge_ms)
    return batch, max(chunk_ms, default=0.0) + merge_ms
