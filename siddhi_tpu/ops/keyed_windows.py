"""Per-partition-key window stages: dense ``[K, W]`` ring-buffer tensors.

Inside ``partition with (...)`` each partition instance owns an independent
window in the reference (one processor object per key, created lazily by
``PartitionRuntimeImpl.initPartition``, ``partition/PartitionRuntimeImpl.java:346-365``).
Here all keys share one state tensor: buffers are flattened ``[K*W]`` arrays
(key ``k`` owns slots ``[k*W, (k+1)*W)``) so capacity growth along the key
axis is a prefix copy, and one batch updates every key's window with
gather/scatter — no per-key loop, no vmap over K.

Semantics match the unkeyed stages in ``ops/windows.py`` applied per key:
- keyed length: sliding; when key k's window is full, each arrival on k
  emits [EXPIRED(oldest of k, ts=now), CURRENT] (``LengthWindowProcessor``).
- keyed time: sliding; each key's FIFO drains entries older than t before
  the batch; TIMER chunks drain all keys (``TimeWindowProcessor``).

The partition key id column is ``PK_KEY`` (host-computed, dense ids).

A 64-bit integer is two 32-bit planes on the TPU, and a scatter of both
planes at once (one two-operand scatter) misses the compiler's sorted
path: 146 ns an update into a ``[16,384,000]`` ring against 5-8 for a
32-bit column (PERF.md section 5). So the keyed length window HOLDS an
int64 ring column as its two words: two ``uint32[K*W]`` leaves ``(low,
high)`` under ``buf[name]``, key-major like every ring leaf, each written
by a one-operand 32-bit scatter in place. Nothing in a step reads or
writes such a ring whole: the expired lane re-joins the 64 bits of the
rows it gathers (``int64_from_words`` on a batch's worth of elements),
and only ``contents()``, the partitioned join's probe surface, re-joins a
ring. (Held as ``int64[K*W]`` and split / re-joined around the write, the
layout cost 33 of a 152 ms step at 131,072,000 slots: the compiler's
``X64Split*`` / ``X64Combine`` are timed copies on the chip, PR 33.) The
routed exchange's buckets in ``parallel/mesh.py`` scatter an int64 column
the same way (``int64_words`` / ``int64_from_words``). A ``double`` has no
bits to take on that chip (a pair of float32 there, and the compiler
refuses to bitcast it), so a ``double`` column stays ONE float64 leaf and
ONE two-plane write. The other keyed stages keep their int64 rings whole.

How a ring write is LOWERED on that chip goes by the ring's size: staged
whole in fast memory while small, swept through it in windows behind a
sort of the compiler's own up to 108 M slots, and from 114.7 M slots up
one update after another (85-93 ns each: 6.0 ms for 65,536 rows into
``[131,072,000]``, the same for a 1-byte mask). Handed slots that ARE
sorted and unique, and told so, it takes the windowed path at every size
(1.9 ms there; PERF.md section 7). So ``KeyedLengthWindowStage`` gives
every row a slot of its own (a row that is not written gets an
out-of-range one, dropped) and ``_ring_write`` sorts each leaf's
``(slot, word)`` pairs itself, one two-operand sort a leaf, as the
compiler does where it sorts: sorts that share a key are MERGED by the
compiler into one sort with every column as payload, which compiles in
minutes (PR 28), so each sort's operands sit behind a barrier of their
own. The writes are traced in ``siddhi.ring_write`` inside
``siddhi.state``; the benchmark's ``step_ring_write_ms`` reads it.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from siddhi_tpu.ops.expressions import (
    OKEY_KEY, PK_KEY, RIDX_KEY, TS_KEY, TYPE_KEY, VALID_KEY, CompileError)
from siddhi_tpu.ops.windows import (
    CURRENT,
    EXPIRED,
    FLUSH_KEY,
    NOTIFY_KEY,
    OVERFLOW_KEY,
    RESET,
    WindowStage,
    _BIG,
    _data_keys,
    _order_emit,
    _row_order_base,
)



def int64_words(v):
    """An int64 array as its ``(low, high)`` uint32 words, bit for bit.
    Unsigned, so that the TPU compiler takes the low plane as it is (a
    convert to int32 is one more pass over a ring)."""
    return v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32)


def int64_from_words(low, high):
    """``int64_words``' inverse."""
    return (high.astype(jnp.int64) << 32) | low.astype(jnp.int64)


def _new_ring(slots: int, dtype):
    """A zeroed ring column of ``slots`` slots: one leaf, or for an int64
    column its ``(low, high)`` uint32 word leaves (never ``[slots, 2]``: a
    minor dimension of 2 is padded to a tile on the chip)."""
    if np.dtype(dtype) == np.int64:
        return (jnp.zeros((slots,), jnp.uint32), jnp.zeros((slots,), jnp.uint32))
    return jnp.zeros((slots,), dtype)


def _ring_read(ring, at):
    """The ring column's values at the flat slots ``at``; a column held
    as words is re-joined there, over ``at``'s elements and not the ring."""
    if isinstance(ring, tuple):
        low, high = ring
        return int64_from_words(low[at], high[at])
    return ring[at]


RING_WRITE_SCOPE = "siddhi.ring_write"
_INT32_MAX = np.iinfo(np.int32).max


def _plane_write(plane, slot, word):
    """One ring leaf written at ``slot``. int32 slots are the caller's
    word that they are unique (``KeyedLengthWindowStage.apply``): they and
    their words are sorted here, behind a barrier that keeps this sort
    apart from the other leaves' (module docstring), and the scatter is
    told both. int64 slots (a ring beyond 31 bits of slots) are written
    as they come."""
    if slot.dtype != jnp.int32:
        return plane.at[slot].set(word, mode="drop")
    slot, word = lax.sort(lax.optimization_barrier((slot, word)),
                          num_keys=1, is_stable=False)
    return plane.at[slot].set(word, mode="drop", indices_are_sorted=True,
                              unique_indices=True)


def _ring_write(ring, slot, col):
    """``ring.at[slot].set(col, mode="drop")`` leaf by leaf; a column held
    as words is two one-operand 32-bit scatters at the same slots, each
    in place."""
    with jax.named_scope(RING_WRITE_SCOPE):
        if isinstance(ring, tuple):
            return tuple(_plane_write(plane, slot, word) for plane, word
                         in zip(ring, int64_words(col.astype(jnp.int64))))
        return _plane_write(ring, slot, col)


def _per_key_layout(pk, valid_cur, num_keys: int):
    """Group batch rows by key: returns (order, inv_order, occ, counts,
    start_pos) where occ[i] is row i's arrival rank within its key this
    batch, counts is [K] per-key insert count, and start_pos[i] is the
    sorted-array position of the first row of row i's key.

    counts is read off the sorted batch: a key's count is its last row's
    rank + 1, written by one 32-bit scatter from the segment ends (unique
    keys) and widened after. A histogram ``zeros(int64).at[pk].add(1)`` is
    a two-plane scatter-add on the chip: 5.55 ms for 65,536 rows against
    0.33 (TPU v5e, PR 26)."""
    B = pk.shape[0]
    safe_pk = jnp.where(valid_cur, pk, num_keys).astype(jnp.int32)
    order = jnp.argsort(safe_pk, stable=True)
    inv_order = jnp.argsort(order, stable=True)
    pk_sorted = safe_pk[order]
    sidx = jnp.arange(B, dtype=jnp.int64)
    seg_start = jnp.concatenate([jnp.ones(1, bool), pk_sorted[1:] != pk_sorted[:-1]])
    start_pos_sorted = lax.cummax(jnp.where(seg_start, sidx, jnp.int64(-1)))
    occ_sorted = sidx - start_pos_sorted
    occ = occ_sorted[inv_order]
    start_pos = start_pos_sorted[inv_order]
    seg_end = jnp.concatenate([seg_start[1:], jnp.ones(1, bool)])
    counts = jnp.zeros(num_keys + 1, jnp.int32).at[
        jnp.where(seg_end, pk_sorted, num_keys)].set(
            occ_sorted.astype(jnp.int32) + 1)[:num_keys].astype(jnp.int64)
    return order, inv_order, occ, counts, start_pos


class KeyedLengthWindowStage(WindowStage):
    """Sliding length window per partition key."""

    keyed = True

    def __init__(self, length: int, col_specs: Dict[str, np.dtype]):
        if length <= 0:
            raise CompileError("length window needs a positive length")
        self.length = length
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        W = self.length
        buf = {k: _new_ring(num_keys * W, dt) for k, dt in self.col_specs.items()}
        return {"buf": buf, "total": jnp.zeros((num_keys,), jnp.int64)}

    @property
    def ring_capacity(self) -> int:
        return self.length

    def live_fill(self, state):
        """Hottest key's live row count — ``win_fill`` instrument slot
        (max, not sum: the saturation signal is the fullest per-key
        ring, which is what capacity overflow is a function of)."""
        return jnp.max(jnp.minimum(state["total"], jnp.int64(self.length)))

    def apply(self, state, cols, ctx):
        W = self.length
        K = state["total"].shape[0]
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)

        order, _inv, occ, counts, start_pos = _per_key_layout(pk, valid_cur, K)

        total0 = state["total"][pk]            # per-row prior count of its key
        seq = total0 + occ                     # per-key arrival sequence
        evicts = valid_cur & (seq >= W)
        evict_seq = seq - W

        # evictee inserted earlier in this same batch?
        from_batch = evict_seq >= total0
        batch_sorted_pos = jnp.clip(start_pos + (evict_seq - total0), 0, B - 1)
        batch_row = order[batch_sorted_pos]
        flat = jnp.clip(pk * W + evict_seq % W, 0, K * W - 1)

        expired = {}
        for k in keys:
            ring_v = _ring_read(state["buf"][k], flat)
            expired[k] = jnp.where(from_batch, cols[k][batch_row], ring_v)
        expired[TS_KEY] = jnp.broadcast_to(now, (B,))  # LengthWindowProcessor:120

        # write the last min(W, n_key) arrivals of each key (unique slots);
        # a row that is not written gets an out-of-range slot of its own
        # (dropped), so that every slot is unique and ``_ring_write`` may
        # say so. That needs K*W + B slots in an int32: beyond it, the one
        # shared out-of-range slot and the plain write.
        write = valid_cur & (occ >= counts[pk] - W)
        at = pk * W + seq % W
        if K * W + B <= _INT32_MAX:
            slot = jnp.where(write, at, K * W + jnp.arange(B, dtype=jnp.int64)
                             ).astype(jnp.int32)
        else:
            slot = jnp.where(write, at, jnp.int64(K * W))
        new_buf = {k: _ring_write(state["buf"][k], slot, cols[k]) for k in state["buf"]}

        # order base: original batch position (global under device routing,
        # so a shard's 2*i/2*i+1 keys interleave correctly with its peers')
        idx = _row_order_base(cols, B)
        parts = [
            (expired, jnp.full((B,), EXPIRED, jnp.int8), evicts, 2 * idx),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, 2 * idx + 1),
        ]
        out, okey = _order_emit(parts)
        if RIDX_KEY in cols:
            out[OKEY_KEY] = okey   # route wrapper merges shards by this
        return {"buf": new_buf, "total": state["total"] + counts}, out

    def contents(self, state):
        """Per-key probe surface for partitioned joins: ([K, W] cols,
        [K, W] valid); a column held as words comes back int64."""
        W = self.length
        K = state["total"].shape[0]
        cols = {k: (int64_from_words(*v) if isinstance(v, tuple) else v
                    ).reshape(K, W) for k, v in state["buf"].items()}
        j = jnp.arange(W, dtype=jnp.int64)[None, :]
        valid = j < jnp.minimum(state["total"], W)[:, None]
        return cols, valid

    def arrival_start(self, state):
        """[K] slot of each key's oldest row in ``contents``: read from
        there on, a key's ring is in arrival order."""
        total = state["total"]
        return jnp.where(total >= self.length, total % self.length, 0)

    def reset_keys(self, state, ids):
        """@purge: restart purged keys' windows (rows become unreachable
        as soon as total is zeroed)."""
        return {"buf": state["buf"],
                "total": state["total"].at[ids].set(0)}


class KeyedTimeWindowStage(WindowStage):
    """Sliding time window per partition key (live clock driven). Each key
    keeps a FIFO ring of capacity ``Wc``; expiry scans the ``[K, Wc]`` ring
    (arrival order per key is timestamp-monotone, so the expired set is a
    FIFO prefix per key).

    ``external=True`` is the keyed externalTime variant: each key's cutoff
    clock advances only with that key's own events (the reference gives
    every partition key its own ExternalTimeWindowProcessor instance), and
    expired rows keep their original timestamps.

    ``max_len`` is the keyed timeLength variant: on top of time expiry,
    each insert beyond ``max_len`` live rows evicts its key's oldest row
    (emitted EXPIRED just before the displacing insert —
    TimeLengthWindowProcessor per key)."""

    keyed = True

    def __init__(self, time_ms: int, col_specs: Dict[str, np.dtype], capacity: int,
                 external: bool = False, max_len: int = None,
                 ts_key: str = TS_KEY):
        if external and max_len is not None:
            raise CompileError("externalTime cannot combine with a length cap")
        self.time_ms = time_ms
        self.capacity = max(capacity, max_len) if max_len is not None else capacity
        self.col_specs = col_specs
        self.external = external
        self.max_len = max_len
        self.ts_key = ts_key    # externalTime clock column (attribute)
        self.needs_scheduler = not external

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        buf = {k: jnp.zeros((num_keys * Wc,), dt) for k, dt in self.col_specs.items()}
        return {
            "buf": buf,
            "total": jnp.zeros((num_keys,), jnp.int64),
            "expired_upto": jnp.zeros((num_keys,), jnp.int64),
        }

    @property
    def ring_capacity(self) -> int:
        return self.capacity

    def live_fill(self, state):
        """Hottest key's live (unexpired) row count — ``win_fill``
        instrument slot (see KeyedLengthWindowStage.live_fill)."""
        return jnp.max(jnp.maximum(
            state["total"] - state["expired_upto"], jnp.int64(0)))

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        K = state["total"].shape[0]
        t = jnp.int64(self.time_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        ts = cols[TS_KEY]
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)
        # order keys: all ring expirees (0..K*Wc-1) drain before the batch;
        # then per batch row r: same-key in-batch expirees at BASE+r*STRIDE+i,
        # r's own CURRENT at BASE+r*STRIDE+B+1.
        STRIDE = jnp.int64(B + 2)
        BASE = jnp.int64(K * Wc)

        total0 = state["total"]          # [K]
        exp0 = state["expired_upto"]     # [K]

        # [K, Wc] FIFO view of every key's ring
        j = jnp.arange(Wc, dtype=jnp.int64)
        fifo_seq = exp0[:, None] + j[None, :]
        occupied = fifo_seq < total0[:, None]
        fifo_flat = (jnp.arange(K, dtype=jnp.int64)[:, None] * Wc + fifo_seq % Wc)
        ring_ts = state["buf"][TS_KEY][fifo_flat]

        order, inv, occ, counts, start_pos = _per_key_layout(pk, valid_cur, K)
        B_idx = jnp.arange(B, dtype=jnp.int64)

        if self.external:
            # keyed externalTime: key k's clock advances only with key k's
            # events. An item (ring or earlier batch row) expires just
            # before the first same-key batch row whose ts covers it —
            # found by a composite (key, ts) searchsorted over the
            # key-grouped batch layout.
            M = jnp.int64(1) << 42      # > any ms epoch until ~2109
            ck = cols[self.ts_key]
            ring_ck = state["buf"][self.ts_key][fifo_flat]
            ts_c = jnp.clip(ck, 0, M - 1)
            safe_pk = jnp.where(valid_cur, pk, jnp.int64(K))
            # a backwards external clock would leave the composite keys
            # unsorted and searchsorted arbitrary; cummax over the grouped
            # composite is a per-key running max (the key occupies the high
            # bits and groups are contiguous ascending, so the running max
            # never leaks across keys) — mirroring the unkeyed stage's
            # lax.cummax guard (ExternalTimeWindowProcessor degrades the
            # same way under a non-monotone clock)
            comp_sorted = lax.cummax(
                (safe_pk[order] * M + ts_c[order]).astype(jnp.int64))

            def first_covering(keys_of, item_ts):
                tgt = keys_of * M + jnp.clip(item_ts + t, 0, M - 1)
                pos = jnp.searchsorted(comp_sorted, tgt, side="left")
                posc = jnp.clip(pos, 0, B - 1)
                ok = (pos < B) & (safe_pk[order][posc] == keys_of)
                return ok, jnp.where(ok, order[posc], B)

            ring_keys = jnp.broadcast_to(
                jnp.arange(K, dtype=jnp.int64)[:, None], (K, Wc)).reshape(-1)
            ring_cov, ring_anchor = first_covering(ring_keys, ring_ck.reshape(-1))
            expire_ring = occupied & ring_cov.reshape(K, Wc)
            n_exp_per_key = jnp.sum(expire_ring.astype(jnp.int64), axis=1)

            batch_cov, batch_anchor = first_covering(
                jnp.where(valid_cur, pk, jnp.int64(K)), ck)
            batch_exp = valid_cur & batch_cov
            nxt = batch_anchor

            ring_rows = {k: state["buf"][k][fifo_flat.reshape(-1)] for k in state["buf"]}
            batch_exp_rows = {k: cols[k] for k in keys}  # original timestamps

            # anchor-major order: everything anchored before batch row a
            # sorts between rows a-1 and a
            STRIDE2 = jnp.int64(K * Wc + B + 2)
            ring_okey = ring_anchor * STRIDE2 + jnp.arange(K * Wc, dtype=jnp.int64)
            batch_okey = nxt * STRIDE2 + jnp.int64(K * Wc) + B_idx
            cur_okey = B_idx * STRIDE2 + jnp.int64(K * Wc) + B + 1
            extra_parts = []
            len_cursor = None
        else:
            expire_ring = occupied & (ring_ts + t <= now)
            n_exp_per_key = jnp.sum(expire_ring.astype(jnp.int64), axis=1)

            # within-batch expiry: a row whose ts is already older than the
            # cutoff expires before the next CURRENT row of the same key
            nxt_sorted_pos = start_pos + occ + 1
            has_next = (occ + 1) < counts[pk]
            nxt = jnp.where(has_next, order[jnp.clip(nxt_sorted_pos, 0, B - 1)], B)
            batch_exp = valid_cur & (ts + t <= now) & (nxt < B)

            ring_rows = {k: state["buf"][k][fifo_flat.reshape(-1)] for k in state["buf"]}
            batch_exp_rows = {k: cols[k] for k in keys}
            batch_exp_rows[TS_KEY] = jnp.broadcast_to(now, (B,))

            # anchor-major order: item anchored at batch row a sorts
            # between rows a-1 and a; time ring expirees drain first
            STRIDE_A = jnp.int64(K * Wc + B + 2)
            KWc = jnp.int64(K * Wc)
            ring_okey = jnp.arange(K * Wc, dtype=jnp.int64)
            batch_okey = (nxt + 1) * STRIDE_A + KWc + B_idx
            cur_okey = (B_idx + 1) * STRIDE_A + KWc + B + 1

            if self.max_len is not None:
                # timeLength: drain oldest rows so each key's live count
                # stays <= L, each evictee anchored before its displacer
                # (the insert L sequence numbers later)
                L = jnp.int64(self.max_len)
                n_be = jnp.zeros(K + 1, jnp.int64).at[
                    jnp.where(batch_exp, pk, K)].add(1)[:K]
                E = exp0 + n_exp_per_key + n_be      # cursor after time drain
                n_len = jnp.maximum(total0 + counts - L - E, 0)
                start_key = jnp.full((K + 1,), B, jnp.int64).at[
                    jnp.where(valid_cur, pk, jnp.int64(K))].min(start_pos)[:K]

                len_ring = occupied & (fifo_seq >= E[:, None]) & (
                    fifo_seq < (E + n_len)[:, None])
                disp_pos_r = start_key[:, None] + (fifo_seq + L - total0[:, None])
                anchor_r = order[jnp.clip(disp_pos_r, 0, B - 1)]

                seq_b = total0[pk] + occ
                len_batch = valid_cur & (seq_b >= E[pk]) & (seq_b < (E + n_len)[pk])
                disp_pos_b = start_pos + occ + L
                anchor_b = order[jnp.clip(disp_pos_b, 0, B - 1)]

                len_ring_rows = dict(ring_rows)
                len_ring_rows[TS_KEY] = jnp.broadcast_to(now, (K * Wc,))
                extra_parts = [
                    (len_ring_rows, jnp.full((K * Wc,), EXPIRED, jnp.int8),
                     len_ring.reshape(-1),
                     (anchor_r.reshape(-1) + 1) * STRIDE_A + ring_okey),
                    (batch_exp_rows, jnp.full((B,), EXPIRED, jnp.int8),
                     len_batch, (anchor_b + 1) * STRIDE_A + KWc + B_idx),
                ]
                len_cursor = E + n_len
            else:
                extra_parts = []
                len_cursor = None
            ring_rows = dict(ring_rows)
            ring_rows[TS_KEY] = jnp.where(expire_ring.reshape(-1), now,
                                          ring_rows[TS_KEY])

        parts = [
            (ring_rows, jnp.full((K * Wc,), EXPIRED, jnp.int8), expire_ring.reshape(-1), ring_okey),
            (batch_exp_rows, jnp.full((B,), EXPIRED, jnp.int8), batch_exp, batch_okey),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, cur_okey),
        ] + extra_parts
        out, _ = _order_emit(parts)

        # append inserts per key
        seq = total0[pk] + occ
        write = valid_cur & (occ >= counts[pk] - Wc)
        slot = jnp.where(write, pk * Wc + seq % Wc, jnp.int64(K * Wc))
        new_buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop") for k in state["buf"]}
        n_batch_exp_per_key = jnp.zeros(K + 1, jnp.int64).at[
            jnp.where(batch_exp, pk, K)
        ].add(1)[:K]
        new_total = total0 + counts
        if len_cursor is not None:
            new_exp = len_cursor       # includes time drain + length evictions
        else:
            new_exp = exp0 + n_exp_per_key + n_batch_exp_per_key

        live = new_total - new_exp
        out[OVERFLOW_KEY] = jnp.any(live > Wc).astype(jnp.int32)

        if self.external:
            out[NOTIFY_KEY] = jnp.int64(-1)   # expiry rides event arrivals
        else:
            fifo2 = new_exp[:, None] + j[None, :]
            occ2 = fifo2 < new_total[:, None]
            flat2 = jnp.arange(K, dtype=jnp.int64)[:, None] * Wc + fifo2 % Wc
            ts2 = new_buf[TS_KEY][flat2]
            nxt_notify = jnp.min(jnp.where(occ2, ts2 + t, _BIG))
            out[NOTIFY_KEY] = jnp.where(jnp.any(occ2), nxt_notify, jnp.int64(-1))
        return {"buf": new_buf, "total": new_total, "expired_upto": new_exp}, out

    def contents(self, state):
        """Per-key probe surface: slot j of key k is live iff some sequence
        s in [expired_upto, total) lands on it (s % Wc == j)."""
        Wc = self.capacity
        K = state["total"].shape[0]
        cols = {k: v.reshape(K, Wc) for k, v in state["buf"].items()}
        j = jnp.arange(Wc, dtype=jnp.int64)[None, :]
        exp0 = state["expired_upto"][:, None]
        live = state["total"][:, None] - exp0
        valid = ((j - exp0 % Wc) % Wc) < live
        return cols, valid

    def arrival_start(self, state):
        """[K] slot of each key's oldest live row in ``contents``: read
        from there on, a key's ring is in arrival order."""
        return state["expired_upto"] % self.capacity

    def reset_keys(self, state, ids):
        return {"buf": state["buf"],
                "total": state["total"].at[ids].set(0),
                "expired_upto": state["expired_upto"].at[ids].set(0)}


class KeyedLengthBatchWindowStage(WindowStage):
    """Tumbling count batches per partition key (reference
    LengthBatchWindowProcessor applied per key): key k's Nth arrival
    flushes [EXPIRED(previous batch), RESET, CURRENT(batch)]. A chunk can
    complete several batches for one key — emission rows gather from the
    stored partial ring, the stored previous batch, or earlier rows of
    the same chunk by absolute per-key sequence number."""

    keyed = True
    batch_mode = True

    def __init__(self, length: int, col_specs: Dict[str, np.dtype]):
        if length <= 0:
            raise CompileError("lengthBatch window needs a positive length")
        self.length = length
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        N = self.length
        K = num_keys
        zero = lambda: {k: jnp.zeros((K, N), dt)                  # noqa: E731
                        for k, dt in self.col_specs.items()}
        return {"cur": zero(), "prev": zero(),
                "cnt": jnp.zeros((K,), jnp.int64),      # total arrivals ever
                "prev_full": jnp.zeros((K,), bool)}     # prev batch exists

    def apply(self, state, cols, ctx):
        N = self.length
        K = state["cnt"].shape[0]
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)
        jN = jnp.arange(N, dtype=jnp.int64)

        order, _inv, occ, counts, start_pos = _per_key_layout(pk, valid_cur, K)
        cnt0 = state["cnt"][pk]                  # [B] prior arrivals of row's key
        seq = cnt0 + occ                         # absolute per-key sequence
        flush = valid_cur & ((seq + 1) % N == 0)

        def gather(q):
            """[B, N] rows at absolute positions q[b, j] of row b's key:
            from this chunk, the stored partial ring, or the stored
            previous batch (negative q = invalid)."""
            from_chunk = q >= cnt0[:, None]
            chunk_pos = jnp.clip(start_pos[:, None] + (q - cnt0[:, None]), 0, B - 1)
            chunk_row = order[chunk_pos]
            part_start = cnt0 - cnt0 % N         # partial batch's first seq
            in_ring = (~from_chunk) & (q >= part_start[:, None])
            slot = (q % N).astype(jnp.int32)
            outr = {}
            for k in keys:
                ring_v = state["cur"][k][pk[:, None], slot]
                prev_v = state["prev"][k][pk[:, None], slot]
                v = jnp.where(from_chunk, cols[k][chunk_row],
                              jnp.where(in_ring, ring_v, prev_v))
                outr[k] = v
            return outr

        # batch being completed by a flush row at seq s: positions s+1-N..s
        cur_q = (seq[:, None] - (N - 1)) + jN[None, :]
        cur_rows = gather(cur_q)
        # the batch before it: positions s+1-2N..s-N (may be the stored prev)
        prev_q = cur_q - N
        prev_rows = gather(prev_q)
        # a previous batch exists if those positions are >= 0 AND (they come
        # from this chunk/ring, or the stored prev batch exists)
        prev_from_store = prev_q[:, 0] < (cnt0 - cnt0 % N)
        has_prev = flush & (prev_q[:, 0] >= 0) & (
            ~prev_from_store | state["prev_full"][pk])

        # ordering: per flush row r: N expired, 1 reset, N current
        idx = jnp.arange(B, dtype=jnp.int64)
        STRIDE = jnp.int64(2 * N + 1)
        exp_okey = (idx[:, None] * STRIDE + jN[None, :]).reshape(B * N)
        reset_okey = idx * STRIDE + N
        cur_okey = (idx[:, None] * STRIDE + N + 1 + jN[None, :]).reshape(B * N)

        exp_emit = {k: v.reshape(B * N) for k, v in prev_rows.items()}
        exp_emit[TS_KEY] = jnp.where(
            (has_prev[:, None] & jnp.ones((B, N), bool)).reshape(B * N),
            now, exp_emit[TS_KEY])
        cur_emit = {k: v.reshape(B * N) for k, v in cur_rows.items()}
        reset_rows = {k: jnp.zeros((B,), v.dtype) for k, v in cols.items()
                      if k in keys}
        reset_rows[TS_KEY] = jnp.broadcast_to(now, (B,))

        parts = [
            (exp_emit, jnp.full((B * N,), EXPIRED, jnp.int8),
             (has_prev[:, None] & jnp.ones((B, N), bool)).reshape(B * N), exp_okey),
            (reset_rows, jnp.full((B,), RESET, jnp.int8), has_prev, reset_okey),
            (cur_emit, jnp.full((B * N,), CURRENT, jnp.int8),
             (flush[:, None] & jnp.ones((B, N), bool)).reshape(B * N), cur_okey),
        ]
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        # ---- state update
        new_cnt = state["cnt"] + counts
        # cur ring: rows with seq >= floorN(new_cnt) of their key
        part_start_new = (new_cnt - new_cnt % N)[pk]
        keep = valid_cur & (seq >= part_start_new)
        kslot = jnp.where(keep, (seq % N).astype(jnp.int64), jnp.int64(N))
        kpk = jnp.where(keep, pk, K)
        new_cur = {k: state["cur"][k].at[kpk, kslot].set(cols[k], mode="drop")
                   for k in state["cur"]}
        # prev batch: the last completed batch — rows with seq in
        # [floorN(new_cnt)-N, floorN(new_cnt)) that arrived this chunk;
        # keys that flushed at least once get a full new prev
        flushed_key = jnp.zeros((K + 1,), bool).at[
            jnp.where(flush, pk, K)].set(True, mode="drop")[:K]
        pstart = part_start_new - N
        in_prev = valid_cur & (seq >= pstart) & (seq < part_start_new)
        ppk = jnp.where(in_prev, pk, K)
        pslot = jnp.where(in_prev, (seq % N).astype(jnp.int64), jnp.int64(N))
        new_prev = {}
        for k in state["prev"]:
            # keys that flushed: batch rows may ALSO come from the old cur
            # ring (batch started before this chunk)
            base = jnp.where(flushed_key[:, None], state["cur"][k],
                             state["prev"][k])
            new_prev[k] = base.at[ppk, pslot].set(cols[k], mode="drop")
        new_prev_full = state["prev_full"] | flushed_key
        return {"cur": new_cur, "prev": new_prev, "cnt": new_cnt,
                "prev_full": new_prev_full}, out

    def contents(self, state):
        """Join/find probes see the last COMPLETED batch per key — the
        reference's ``expiredEventQueue``
        (LengthBatchWindowProcessor.java:288-299), matching the unkeyed
        stage."""
        N = self.length
        K = state["prev_full"].shape[0]
        valid = jnp.broadcast_to(state["prev_full"][:, None], (K, N))
        return dict(state["prev"]), valid

    def reset_keys(self, state, ids):
        return {"cur": state["cur"], "prev": state["prev"],
                "cnt": state["cnt"].at[ids].set(0),
                "prev_full": state["prev_full"].at[ids].set(False)}


class KeyedTimeBatchWindowStage(WindowStage):
    """Tumbling time batches per partition key (reference
    TimeBatchWindowProcessor per partition instance): a key's first event
    starts its boundary clock; at each elapsed boundary the key's
    collected batch flushes [EXPIRED(prev), RESET, CURRENT(batch)].
    Flushes are checked once per chunk against the chunk clock (arriving
    rows join the flushing batch) and drained COMPACTED: at most D due
    keys per tick, leftovers re-armed immediately."""

    keyed = True
    batch_mode = True
    needs_scheduler = True

    def __init__(self, time_ms: int, col_specs: Dict[str, np.dtype], capacity: int,
                 expired_needed: bool = True):
        if time_ms <= 0:
            raise CompileError("timeBatch window needs a positive time")
        self.time_ms = time_ms
        self.capacity = capacity
        self.col_specs = col_specs
        # outputExpectsExpiredEvents=False (insert-into join sides): a key
        # whose batch is empty never flushes, so the findable prev batch is
        # retained for probes instead of drained (matches the unkeyed
        # TimeBatchWindowStage and the reference's undrained
        # expiredEventQueue)
        self.expired_needed = expired_needed

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        K = num_keys
        zero = lambda: {k: jnp.zeros((K, Wc), dt)                 # noqa: E731
                        for k, dt in self.col_specs.items()}
        return {"buf": zero(), "prev": zero(),
                "cnt": jnp.zeros((K,), jnp.int32),
                "prev_cnt": jnp.zeros((K,), jnp.int32),
                "next_emit": jnp.zeros((K,), jnp.int64)}   # 0 = unstarted

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        K = state["cnt"].shape[0]
        t = jnp.int64(self.time_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)
        jW = jnp.arange(Wc, dtype=jnp.int32)

        # ---- collect arrivals (rows join the possibly-flushing batch)
        _o, _i, occ, counts, _s = _per_key_layout(pk, valid_cur, K)
        slot = jnp.where(valid_cur,
                         jnp.minimum(state["cnt"][pk] + occ.astype(jnp.int32),
                                     Wc - 1),
                         Wc).astype(jnp.int32)
        kpk = jnp.where(valid_cur, pk, K)
        buf = {k: state["buf"][k].at[kpk, slot].set(cols[k], mode="drop")
               for k in state["buf"]}
        overflow_now = state["cnt"] + counts.astype(jnp.int32)
        cnt = jnp.minimum(overflow_now, Wc)
        # first arrival starts the key's boundary clock
        started0 = state["next_emit"] > 0
        has_arrival = counts > 0
        next_emit = jnp.where(~started0 & has_arrival, now + t,
                              state["next_emit"])

        # ---- compacted flush of due keys
        D = min(64, K)
        exp_need = jnp.bool_(self.expired_needed)
        due = (next_emit > 0) & (now >= next_emit) \
            & ((cnt > 0) | (exp_need & (state["prev_cnt"] > 0)))
        korder = jnp.argsort(~due)
        kids = korder[:D]
        ksel = due[kids]
        jD = jnp.arange(D, dtype=jnp.int64)
        cur_sel = ksel[:, None] & (jW[None, :] < cnt[kids][:, None])
        prev_sel = ksel[:, None] & (jW[None, :] < state["prev_cnt"][kids][:, None])
        leftover = jnp.sum(due.astype(jnp.int32)) > D

        STRIDE = jnp.int64(2 * Wc + 1)
        prev_rows = {k: state["prev"][k][kids].reshape(D * Wc)
                     for k in state["prev"]}
        prev_rows[TS_KEY] = jnp.where(prev_sel.reshape(D * Wc), now,
                                      prev_rows[TS_KEY])
        cur_rows = {k: buf[k][kids].reshape(D * Wc) for k in buf}
        reset_rows = {k: jnp.zeros((D,), v.dtype)
                      for k, v in cur_rows.items()}
        reset_rows[TS_KEY] = jnp.broadcast_to(now, (D,))
        jwl = jnp.broadcast_to(jW.astype(jnp.int64)[None, :], (D, Wc))
        parts = [
            (prev_rows, jnp.full((D * Wc,), EXPIRED, jnp.int8),
             prev_sel.reshape(D * Wc),
             (jD[:, None] * STRIDE + jwl).reshape(D * Wc)),
            (reset_rows, jnp.full((D,), RESET, jnp.int8),
             ksel & (cnt[kids] > 0) & (state["prev_cnt"][kids] > 0),
             jD * STRIDE + Wc),
            (cur_rows, jnp.full((D * Wc,), CURRENT, jnp.int8),
             cur_sel.reshape(D * Wc),
             (jD[:, None] * STRIDE + Wc + 1 + jwl).reshape(D * Wc)),
        ]
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        # flushed keys: cur -> prev, roll the boundary past `now`
        fsel = jnp.zeros((K,), bool).at[jnp.where(ksel, kids, K)].set(
            True, mode="drop")
        new_prev = {k: jnp.where(fsel[:, None], buf[k], state["prev"][k])
                    for k in state["prev"]}
        new_prev_cnt = jnp.where(fsel, cnt, state["prev_cnt"])
        new_cnt = jnp.where(fsel, 0, cnt)
        rolled = now - ((now - next_emit) % t) + t
        new_next = jnp.where(fsel, rolled, next_emit)

        out[OVERFLOW_KEY] = jnp.any(overflow_now > Wc).astype(jnp.int32)
        started = new_next > 0
        sched_need = (new_cnt > 0) | (exp_need & (new_prev_cnt > 0))
        nxt = jnp.min(jnp.where(started & sched_need, new_next, _BIG))
        nxt = jnp.where(leftover, now, nxt)
        out[NOTIFY_KEY] = jnp.where(
            jnp.any(started & sched_need) | leftover, nxt, jnp.int64(-1))
        return {"buf": buf, "prev": new_prev, "cnt": new_cnt,
                "prev_cnt": new_prev_cnt, "next_emit": new_next}, out

    def contents(self, state):
        """Join/find probes see the last flushed batch per key — the
        reference's ``expiredEventQueue``
        (TimeBatchWindowProcessor.java:368-380), matching the unkeyed
        stage."""
        valid = (jnp.arange(self.capacity, dtype=jnp.int32)[None, :]
                 < state["prev_cnt"][:, None])
        return dict(state["prev"]), valid

    def reset_keys(self, state, ids):
        return {"buf": state["buf"], "prev": state["prev"],
                "cnt": state["cnt"].at[ids].set(0),
                "prev_cnt": state["prev_cnt"].at[ids].set(0),
                "next_emit": state["next_emit"].at[ids].set(0)}


class KeyedSessionWindowStage(WindowStage):
    """``session(gap)`` over dense per-key state — the shape the host
    SessionWindowStage keeps in a Python dict, inverted to ``[K, W]``
    tensors: per-key row buffer + last-event timestamp + row count. Events
    pass through as CURRENT; a key idle past ``gap`` emits its buffered
    session as one EXPIRED chunk (reference ``SessionWindowProcessor``
    without allowedLatency). In-batch gaps are handled with one round per
    same-key occurrence (``lax.while_loop``); end-of-batch idle keys are
    swept vectorized across all K."""

    keyed = True
    needs_scheduler = True

    def __init__(self, gap_ms: int, col_specs: Dict[str, np.dtype], capacity: int):
        if gap_ms <= 0:
            raise CompileError("session window needs a positive gap")
        self.gap_ms = gap_ms
        self.capacity = capacity
        self.col_specs = col_specs

    def init_state(self, num_keys: int = 1) -> dict:
        W = self.capacity
        K = num_keys
        return {
            "buf": {k: jnp.zeros((K, W), dt) for k, dt in self.col_specs.items()},
            "cnt": jnp.zeros((K,), jnp.int32),
            "last": jnp.zeros((K,), jnp.int64),
            "sess_overflow": jnp.int32(0),
        }

    def apply(self, state, cols, ctx):
        W = self.capacity
        K = state["cnt"].shape[0]
        gap = jnp.int64(self.gap_ms)
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        ts = cols[TS_KEY]
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int32), 0, K - 1)
        jW = jnp.arange(W, dtype=jnp.int32)

        _o, _i, occ, _c, _s = _per_key_layout(pk, valid_cur, K)
        n_rounds = jnp.max(jnp.where(valid_cur, occ, -1)) + 1

        buf_names = list(self.col_specs)
        out_exp0 = {n: jnp.zeros((B, W), self.col_specs[n]) for n in buf_names}
        exp_mask0 = jnp.zeros((B, W), bool)

        def round_body(carry):
            r, buf, cnt, last, out_exp, exp_mask, overflow = carry
            m = valid_cur & (occ == r)
            rows_pk = jnp.where(m, pk, K)
            cnt_k = cnt[pk]                      # [B]
            last_k = last[pk]
            brk = m & (cnt_k > 0) & (ts > last_k + gap)
            # emit the broken session's rows (this row's private lane)
            sel = brk[:, None] & (jW[None, :] < cnt_k[:, None])
            out_exp = {n: jnp.where(sel, buf[n][pk], out_exp[n]) for n in buf_names}
            exp_mask = exp_mask | sel
            cnt2 = jnp.where(brk, 0, cnt_k)
            # append the current row to its key's session
            overflow = overflow + jnp.sum(m & (cnt2 >= W)).astype(jnp.int32)
            slot = jnp.where(m, jnp.minimum(cnt2, W - 1), 0)
            buf = {n: buf[n].at[rows_pk, slot].set(cols[n], mode="drop")
                   for n in buf_names}
            cnt = cnt.at[rows_pk].set(jnp.where(m, cnt2 + 1, cnt_k), mode="drop")
            last = last.at[rows_pk].set(jnp.where(m, ts, last_k), mode="drop")
            return r + 1, buf, cnt, last, out_exp, exp_mask, overflow

        carry0 = (jnp.int32(0), state["buf"], state["cnt"], state["last"],
                  out_exp0, exp_mask0, state["sess_overflow"])
        (_r, buf, cnt, last, out_exp, exp_mask, overflow) = lax.while_loop(
            lambda c: c[0] < n_rounds, round_body, carry0)

        # end-of-batch idle sweep, COMPACTED: at most D due keys drain per
        # tick (emitting [K, W] every batch would materialize K*W rows at
        # 10k+ keys); leftovers re-arm an immediate timer and drain on the
        # next sweep
        D = min(128, K)
        due = (cnt > 0) & (last + gap <= now)
        korder = jnp.argsort(~due)              # due keys first, stable
        kids = korder[:D]                       # [D] candidate key ids
        ksel = due[kids]                        # which candidates are due
        jD = jnp.arange(D, dtype=jnp.int64)
        sweep_sel = ksel[:, None] & (jW[None, :] < cnt[kids][:, None])  # [D, W]
        cnt = cnt.at[jnp.where(ksel, kids, K)].set(0, mode="drop")
        leftover = jnp.sum(due.astype(jnp.int32)) > D

        # ordering: per-row [expired lane..., current], then the sweep
        idx = jnp.arange(B, dtype=jnp.int64)
        STRIDE = jnp.int64(W + 1)
        exp_rows = {n: out_exp[n].reshape(B * W) for n in buf_names}
        exp_rows[TS_KEY] = jnp.where(exp_mask.reshape(B * W), now,
                                     exp_rows[TS_KEY])
        exp_okey = (idx[:, None] * STRIDE + jW[None, :]).reshape(B * W)
        cur_okey = idx * STRIDE + W
        BASE = jnp.int64(B) * STRIDE
        sweep_rows = {n: buf[n][kids].reshape(D * W) for n in buf_names}
        sweep_rows[TS_KEY] = jnp.where(sweep_sel.reshape(D * W), now,
                                       sweep_rows[TS_KEY])
        sweep_okey = BASE + (jD[:, None] * W + jW[None, :]).reshape(D * W)

        parts = [
            (exp_rows, jnp.full((B * W,), EXPIRED, jnp.int8),
             exp_mask.reshape(B * W), exp_okey),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, cur_okey),
            (sweep_rows, jnp.full((D * W,), EXPIRED, jnp.int8),
             sweep_sel.reshape(D * W), sweep_okey),
        ]
        out, _ = _order_emit(parts)
        nxt = jnp.min(jnp.where(cnt > 0, last + gap, _BIG))
        nxt = jnp.where(leftover, now, nxt)     # drain the backlog next tick
        out[NOTIFY_KEY] = jnp.where(jnp.any(cnt > 0) | leftover,
                                    nxt, jnp.int64(-1))
        out[OVERFLOW_KEY] = (overflow > state["sess_overflow"]).astype(jnp.int32)
        return {"buf": buf, "cnt": cnt, "last": last,
                "sess_overflow": overflow}, out

    def contents(self, state):
        jW = jnp.arange(self.capacity, dtype=jnp.int32)
        valid = jW[None, :] < state["cnt"][:, None]
        return dict(state["buf"]), valid

    def reset_keys(self, state, ids):
        return {"buf": state["buf"],
                "cnt": state["cnt"].at[ids].set(0),
                "last": state["last"].at[ids].set(0),
                "sess_overflow": state["sess_overflow"]}


class KeyedHoppingWindowStage(WindowStage):
    """``hopping(windowTime, hopTime)`` per partition key: each key hops on
    its own phase (the reference gives every key its own HopingWindowProcessor
    whose first event arms the schedule); every hop emits the key's trailing
    windowTime of events as a batch [EXPIRED(prev snapshot), RESET,
    CURRENT(snapshot)]."""

    keyed = True
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
        zero = lambda: {k: jnp.zeros((num_keys * Wc,), dt)  # noqa: E731
                        for k, dt in self.col_specs.items()}
        return {"buf": zero(), "prev": zero(),
                "total": jnp.zeros((num_keys,), jnp.int64),
                "expired_upto": jnp.zeros((num_keys,), jnp.int64),
                "prev_count": jnp.zeros((num_keys,), jnp.int64),
                "next_emit": jnp.full((num_keys,), -1, jnp.int64)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        K = state["total"].shape[0]
        w = jnp.int64(self.window_ms)
        hop = jnp.int64(self.hop_ms)
        keys = _data_keys(cols)
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)

        order, _inv, occ_r, counts, _start = _per_key_layout(pk, valid_cur, K)

        # append arrivals to each key's ts-monotone FIFO ring
        total0 = state["total"]
        exp0 = state["expired_upto"]
        seq = total0[pk] + occ_r
        write = valid_cur & (occ_r >= counts[pk] - Wc)
        slot = jnp.where(write, pk * Wc + seq % Wc, jnp.int64(K * Wc))
        buf = {k: state["buf"][k].at[slot].set(cols[k], mode="drop")
               for k in state["buf"]}
        total = total0 + counts

        # per-key hop schedule: a key's first event arms it
        ne0 = state["next_emit"]
        ne = jnp.where((ne0 < 0) & (total > 0), now + hop, ne0)
        send = (ne >= 0) & (now >= ne)
        ne2 = jnp.where(send, ne + hop, ne)

        # stale rows (older than the trailing window) leave the live range
        j = jnp.arange(Wc, dtype=jnp.int64)[None, :]
        grid_k = jnp.arange(K, dtype=jnp.int64)[:, None]
        fifo_seq = exp0[:, None] + j
        occ = fifo_seq < total[:, None]
        flat = (grid_k * Wc + fifo_seq % Wc).reshape(-1)
        ring_ts = buf[TS_KEY][flat].reshape(K, Wc)
        stale = occ & (ring_ts <= now - w)
        new_exp = exp0 + jnp.sum(stale.astype(jnp.int64), axis=1)

        in_window = occ & ~stale & send[:, None]
        cur_rows = {k: buf[k][flat] for k in buf}
        n_emit = jnp.sum(in_window.astype(jnp.int64), axis=1)

        # key-major emission order: [EXPIRED prev, RESET, CURRENT snapshot]
        STRIDE = jnp.int64(2 * Wc + 2)
        kflat = jnp.broadcast_to(grid_k, (K, Wc)).reshape(-1)
        prev_valid = ((j < state["prev_count"][:, None]) & send[:, None]).reshape(-1)
        prev_rows = dict(state["prev"])
        prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
        jflat = jnp.broadcast_to(j, (K, Wc)).reshape(-1)
        reset_valid = send & (state["prev_count"] > 0)
        reset_rows = {k: jnp.zeros((K,), v.dtype) for k, v in buf.items()}
        reset_rows[TS_KEY] = jnp.where(reset_valid, now, jnp.int64(0))

        parts = [
            (prev_rows, jnp.full((K * Wc,), EXPIRED, jnp.int8), prev_valid,
             kflat * STRIDE + jflat),
            (reset_rows, jnp.full((K,), RESET, jnp.int8), reset_valid,
             jnp.arange(K, dtype=jnp.int64) * STRIDE + Wc),
            (cur_rows, jnp.full((K * Wc,), CURRENT, jnp.int8),
             in_window.reshape(-1), kflat * STRIDE + Wc + 1 + jflat),
        ]
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        # emitted snapshot becomes each flushing key's next expiry batch
        emit_rank = jnp.cumsum(in_window.astype(jnp.int64), axis=1) - 1
        pslot = jnp.where(in_window, grid_k * Wc + emit_rank,
                          jnp.int64(K * Wc)).reshape(-1)
        clear = send[kflat]
        new_prev = {}
        for k in state["prev"]:
            base = jnp.where(clear, jnp.zeros((), state["prev"][k].dtype),
                             state["prev"][k])
            new_prev[k] = base.at[pslot].set(cur_rows[k], mode="drop")
        new_state = {
            "buf": buf,
            "prev": new_prev,
            "total": total,
            "expired_upto": new_exp,
            "prev_count": jnp.where(send, n_emit, state["prev_count"]),
            "next_emit": ne2,
        }
        pending = ne2 >= 0
        out[NOTIFY_KEY] = jnp.where(jnp.any(pending),
                                    jnp.min(jnp.where(pending, ne2, _BIG)),
                                    jnp.int64(-1))
        out[OVERFLOW_KEY] = jnp.any((total - new_exp) > Wc).astype(jnp.int32)
        return new_state, out

    def contents(self, state):
        Wc = self.capacity
        K = state["total"].shape[0]
        j = jnp.arange(Wc, dtype=jnp.int64)[None, :]
        fifo_seq = state["expired_upto"][:, None] + j
        occ = fifo_seq < state["total"][:, None]
        grid_k = jnp.arange(K, dtype=jnp.int64)[:, None]
        flat = (grid_k * Wc + fifo_seq % Wc).reshape(-1)
        cols = {k: v[flat].reshape(K, Wc) for k, v in state["buf"].items()}
        return cols, occ

    def reset_keys(self, state, ids):
        return {"buf": state["buf"], "prev": state["prev"],
                "total": state["total"].at[ids].set(0),
                "expired_upto": state["expired_upto"].at[ids].set(0),
                "prev_count": state["prev_count"].at[ids].set(0),
                "next_emit": state["next_emit"].at[ids].set(-1)}


class KeyedBatchWindowStage(WindowStage):
    """``#window.batch()`` per partition key: key k's window is its rows
    from the latest chunk containing k; those rows expire when k's next
    chunk arrives (each key has its own BatchWindowProcessor instance in
    the reference partition runtime). Per key-in-chunk emission:
    [EXPIRED(prev batch), RESET, CURRENT rows], keys ordered by first
    appearance in the chunk."""

    keyed = True
    batch_mode = True

    def __init__(self, col_specs: Dict[str, np.dtype], capacity: int):
        self.col_specs = col_specs
        self.capacity = capacity

    def init_state(self, num_keys: int = 1) -> dict:
        Wc = self.capacity
        prev = {k: jnp.zeros((num_keys * Wc,), dt) for k, dt in self.col_specs.items()}
        return {"prev": prev, "prev_count": jnp.zeros((num_keys,), jnp.int64)}

    def apply(self, state, cols, ctx):
        Wc = self.capacity
        K = state["prev_count"].shape[0]
        keys = _data_keys(cols)
        B = cols[VALID_KEY].shape[0]
        now = jnp.int64(ctx["current_time"])
        valid_cur = cols[VALID_KEY] & (cols[TYPE_KEY] == CURRENT)
        pk = jnp.clip(cols[PK_KEY].astype(jnp.int64), 0, K - 1)
        safe_pk = jnp.where(valid_cur, pk, jnp.int64(K))
        B_idx = jnp.arange(B, dtype=jnp.int64)

        order, _inv, occ, counts, _start = _per_key_layout(pk, valid_cur, K)
        in_chunk = counts > 0                                    # [K]
        # anchor: each key's first row index this chunk
        first_row = jnp.full((K + 1,), B, jnp.int64).at[safe_pk].min(B_idx)[:K]

        STRIDE = jnp.int64(Wc + B + 2)
        grid_k = jnp.broadcast_to(
            jnp.arange(K, dtype=jnp.int64)[:, None], (K, Wc))
        widx = jnp.broadcast_to(jnp.arange(Wc, dtype=jnp.int64)[None, :], (K, Wc))
        flat = (grid_k * Wc + widx).reshape(-1)

        prev_valid = ((widx < state["prev_count"][:, None])
                      & in_chunk[:, None]).reshape(-1)
        prev_rows = {k: state["prev"][k][flat] for k in state["prev"]}
        prev_rows[TS_KEY] = jnp.where(prev_valid, now, prev_rows[TS_KEY])
        prev_okey = (first_row[grid_k.reshape(-1)] * STRIDE + widx.reshape(-1))

        reset_valid = in_chunk & (state["prev_count"] > 0)
        reset_rows = {k: jnp.zeros((K,), state["prev"][k].dtype)
                      for k in state["prev"]}
        reset_rows[TS_KEY] = jnp.where(reset_valid, now, jnp.int64(0))
        reset_okey = first_row * STRIDE + Wc

        cur_okey = first_row[pk] * STRIDE + Wc + 1 + B_idx

        parts = [
            (prev_rows, jnp.full((K * Wc,), EXPIRED, jnp.int8), prev_valid, prev_okey),
            (reset_rows, jnp.full((K,), RESET, jnp.int8), reset_valid, reset_okey),
            ({k: cols[k] for k in keys}, cols[TYPE_KEY], valid_cur, cur_okey),
        ]
        out, _ = _order_emit(parts)
        out[FLUSH_KEY] = jnp.zeros_like(out[TS_KEY], dtype=jnp.int32)

        slot = jnp.where(valid_cur & (occ < Wc), pk * Wc + occ, jnp.int64(K * Wc))
        new_prev = {}
        clear = in_chunk[grid_k.reshape(-1)]   # wipe only keys in this chunk
        for k in state["prev"]:
            base = jnp.where(clear, jnp.zeros((), state["prev"][k].dtype),
                             state["prev"][k])
            new_prev[k] = base.at[slot].set(cols[k], mode="drop")
        new_count = jnp.where(in_chunk, counts, state["prev_count"])
        out[OVERFLOW_KEY] = jnp.any(counts > Wc).astype(jnp.int32)
        return {"prev": new_prev, "prev_count": new_count}, out

    def contents(self, state):
        Wc = self.capacity
        K = state["prev_count"].shape[0]
        cols = {k: v.reshape(K, Wc) for k, v in state["prev"].items()}
        j = jnp.arange(Wc, dtype=jnp.int64)[None, :]
        valid = j < jnp.minimum(state["prev_count"], Wc)[:, None]
        return cols, valid

    def reset_keys(self, state, ids):
        return {"prev": state["prev"],
                "prev_count": state["prev_count"].at[ids].set(0)}


def create_keyed_window_stage(window, input_def, resolver, app_context,
                              expired_needed: bool = True) -> WindowStage:
    """Keyed (partitioned) window factory. Capacity per key comes from
    ``app_context.partition_window_capacity``."""
    from siddhi_tpu.ops.windows import (_const_param, _expect_arity,
                                        _int_const_param, window_col_specs)

    name = window.name.lower()
    col_specs = window_col_specs(input_def, extra=(PK_KEY,))

    capacity = getattr(app_context, "partition_window_capacity", 256)

    if name == "length":
        _expect_arity(window, 1, 1)
        return KeyedLengthWindowStage(_int_const_param(window, 0, "length"), col_specs)
    if name == "time":
        _expect_arity(window, 1, 1)
        return KeyedTimeWindowStage(_int_const_param(window, 0, "time"), col_specs, capacity)
    if name == "externaltime":
        # externalTime(tsAttr, time) — per-key cutoff clock from the named
        # timestamp attribute
        from siddhi_tpu.ops.windows import _external_ts_key

        _expect_arity(window, 2, 2)
        return KeyedTimeWindowStage(_int_const_param(window, 1, "time"),
                                    col_specs, capacity, external=True,
                                    ts_key=_external_ts_key(window, input_def))
    if name == "timelength":
        _expect_arity(window, 2, 2)
        return KeyedTimeWindowStage(_int_const_param(window, 0, "time"),
                                    col_specs, capacity,
                                    max_len=_int_const_param(window, 1, "length"))
    if name == "delay":
        # delay is key-independent: the unkeyed stage (its ring carries the
        # pk column) behaves identically per key and shards per device
        from siddhi_tpu.ops.windows import DelayWindowStage

        _expect_arity(window, 1, 1)
        return DelayWindowStage(_int_const_param(window, 0, "delay"),
                                col_specs,
                                getattr(app_context, "window_capacity", 4096))
    if name == "lengthbatch":
        if len(window.parameters) > 1:
            raise CompileError(
                "lengthBatch streamCurrentEvents is not supported inside a "
                "partition yet")
        _expect_arity(window, 1, 1)
        length = _int_const_param(window, 0, "length")
        if length == 0:
            raise CompileError(
                "lengthBatch(0) is not supported inside a partition yet")
        return KeyedLengthBatchWindowStage(length, col_specs)
    if name == "timebatch":
        if len(window.parameters) > 1:
            raise CompileError(
                "timeBatch startTime/streamCurrentEvents are not supported "
                "inside a partition yet")
        _expect_arity(window, 1, 1)
        return KeyedTimeBatchWindowStage(
            _int_const_param(window, 0, "time"), col_specs, capacity,
            expired_needed=expired_needed)
    if name == "batch":
        if window.parameters:
            raise CompileError(
                "batch chunkLength is not supported inside a partition yet")
        return KeyedBatchWindowStage(col_specs, capacity)
    if name == "hopping":
        _expect_arity(window, 2, 2)
        return KeyedHoppingWindowStage(
            _int_const_param(window, 0, "windowTime"),
            _int_const_param(window, 1, "hopTime"), col_specs, capacity)
    if name == "session":
        if len(window.parameters) >= 2:
            # session with its own key attribute and/or allowedLatency:
            # per-key host stage instances (the session key may differ
            # from the partition key). The dense keyed stage covers the
            # plain session(gap) fast path, keyed by the partition.
            from siddhi_tpu.ops.host_windows import (
                PartitionedHostWindow,
                create_host_window_stage,
            )

            return PartitionedHostWindow(
                lambda: create_host_window_stage(window, input_def, resolver,
                                                 app_context))
        return KeyedSessionWindowStage(int(_const_param(window, 0, "gap")),
                                       col_specs, capacity)
    if name in ("sort", "frequent", "lossyfrequent", "cron",
                "expression", "expressionbatch"):
        # host-mode windows inside a partition: one stage instance per key
        from siddhi_tpu.ops.host_windows import (
            PartitionedHostWindow,
            create_host_window_stage,
        )

        return PartitionedHostWindow(
            lambda: create_host_window_stage(window, input_def, resolver,
                                             app_context))
    raise CompileError(
        f"window '{window.name}' inside a partition is not implemented yet "
        f"(keyed variants exist for: length, lengthBatch, time, timeBatch, "
        f"externalTime, timeLength, delay, session)"
    )
