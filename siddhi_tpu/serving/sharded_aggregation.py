"""Mesh-sharded incremental aggregation: the serving tier's write side.

The reference's only multi-node aggregation story shards through a shared
database — every node writes per-``shardId`` rows into common tables and
readers stitch them back (``AggregationParser.java:171-197``, mirrored
here by ``IncrementalAggregationRuntime.publish_shard/stitch_shards``).
This module replaces that DB round trip with in-process mesh sharding:

- **One rollup program, N shards.** ``ShardedIncrementalAggregation``
  compiles the aggregation's selector/base/output specs exactly once (the
  base-class constructor) and key-partitions only the *state*: each
  ``AggregationShard`` owns the sec/min/hour/day bucket stores for its
  slice of the group-key space ("On the Semantic Overlap of Operators in
  Stream Processing Engines" — share the program, split the data).
- **Routing.** A group tuple's owner is ``crc32(key) % n_shards`` — the
  same owner-by-modulus convention as the keyed-query sharding
  (``parallel/mesh.device_route_query_step``). Ingest prepares a batch once
  (``_prepare_batch``) and folds each shard's row subset under that
  shard's own lock, so two shards never contend.
- **Snapshot reads, no stop-the-world.** Queries read per-shard
  *partials* — an epoch-pinned, immutable copy of the shard's buckets
  built under the shard lock and cached until the next fold bumps the
  epoch. A query storm therefore costs each shard at most one copy per
  ingest epoch, and ingest never waits on a reader. Each shard also
  materializes its partials as device-resident columnar arrays on its
  assigned mesh device (``shard_device_contents``).
- **Ordered merge.** ``rows()`` scatter-gathers the shards' partials and
  stitches them with a deterministic k-way ordered merge ("Scaling
  Ordered Stream Processing on Shared-Memory Multicores" — merge by
  (bucket, group), fold duplicates with ``_BaseSpec.fold``, the same
  shard-stitch rule the DB mode uses). Output rows are computed by the
  base class's ``_rows_from_items`` — one code path, so sharded and
  unsharded results are bit-identical.
- **Per-shard WALs + rebuild.** Each shard records its routed row subset
  in its own bounded ``IngestWAL``; ``checkpoint_shards`` cuts/trims
  them, and ``rebuild_shard`` restores a lost shard from its last blob
  plus the WAL suffix — effectively-once, shard-scoped, without touching
  the siblings. A blob whose cut predates the WAL's last checkpoint trims
  is restored WITHOUT replay (the suffix follows a newer base — the PR-1
  stale-revision rule).

Enable with the ``siddhi_tpu.agg_shards`` config key (>1) or construct
directly; ``@PartitionById`` DB-stitch mode still works and keeps the
legacy runtime (MIGRATION.md).
"""

from __future__ import annotations

import heapq
import logging
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple

from siddhi_tpu.analysis.locks import make_lock
from siddhi_tpu.core.aggregation.incremental import (
    IncrementalAggregationRuntime,
    parse_duration_name,
)
from siddhi_tpu.core.event import Event
from siddhi_tpu.query_api.definitions import Duration

_LOG = logging.getLogger("siddhi_tpu.serving")


def _merge_key(item):
    return item[0], item[1]


class AggregationShard:
    """One key-range's multi-granularity bucket stores.

    Owns the same ``{Duration: {bucket: {group: [bases]}}}`` layout as the
    single-shard runtime plus a monotonically increasing ``epoch`` (bumped
    on every fold/purge/restore) that pins snapshot reads: ``partials()``
    and the device view are cached per (duration, epoch), so a repeated
    dashboard read between two ingest folds touches no locks beyond one
    epoch check."""

    def __init__(self, index: int, durations: List[Duration], device=None,
                 wal=None):
        self.index = index
        self.device = device
        self.durations = durations
        self.store: Dict[Duration, Dict[int, Dict[tuple, list]]] = {
            d: {} for d in durations}
        self._dirty: set = set()
        self._deleted: set = set()
        self._lock = make_lock("shard")
        self.epoch = 0
        self.wal = wal
        # duration -> (epoch, sorted [(bucket, group, [bases copy])])
        self._partials_cache: Dict[Duration, Tuple[int, list]] = {}
        # duration -> (epoch, (definition, device cols, device valid))
        self._device_cache: Dict[Duration, Tuple[int, tuple]] = {}

    def bump(self) -> None:
        """Invalidate snapshot views; call under ``_lock`` after any
        store mutation."""
        self.epoch += 1

    def partials(self, duration: Duration) -> list:
        """Epoch-pinned snapshot of this shard's buckets for one duration:
        a sorted, immutable list of (bucket, group, base-values-copy).
        Readers share the cached copy; a concurrent fold builds new slots
        but never mutates a handed-out copy."""
        with self._lock:
            cached = self._partials_cache.get(duration)
            if cached is not None and cached[0] == self.epoch:
                return cached[1]
            # .get: after a cross-layout restore a shard re-creates a
            # declared duration only when ingest first touches it
            items = [(b, g, list(vals))
                     for b, groups in self.store.get(duration, {}).items()
                     for g, vals in groups.items()]
            items.sort(key=_merge_key)
            self._partials_cache[duration] = (self.epoch, items)
            return items

    def wipe(self) -> None:
        """Fault injection: lose this shard's state (the in-process analog
        of a died aggregation node). ``rebuild_shard`` recovers it."""
        with self._lock:
            self.store = {d: {} for d in self.durations}
            self._dirty.clear()
            self._deleted.clear()
            self._partials_cache.clear()
            self._device_cache.clear()
            self.bump()


class ShardedIncrementalAggregation(IncrementalAggregationRuntime):
    def __init__(self, definition, app_context, dictionary,
                 stream_definitions, n_shards: int,
                 wal_batches: Optional[int] = 1024):
        super().__init__(definition, app_context, dictionary,
                         stream_definitions)
        if self.shard_mode:
            raise ValueError(
                f"aggregation '{definition.id}': @PartitionById DB-stitch "
                f"mode and in-process mesh sharding are mutually exclusive "
                f"(MIGRATION.md)")
        if n_shards < 1:
            raise ValueError("agg_shards must be >= 1")
        self.n_shards = int(n_shards)

        # shard i answers from device i (round-robin over the mesh): the
        # device view caches live where the shard's keyed state would be
        # placed by parallel/mesh key-axis sharding
        try:
            import jax

            devs = jax.devices()
        except Exception:  # noqa: BLE001 — serving works host-only too
            devs = [None]

        from siddhi_tpu.resilience.replay import IngestWAL

        self.shards: List[AggregationShard] = []
        for i in range(self.n_shards):
            wal = (IngestWAL(max_batches=wal_batches,
                             app_context=app_context)
                   if wal_batches else None)
            self.shards.append(AggregationShard(
                i, self.durations, device=devs[i % len(devs)], wal=wal))
        self._last_cuts: List[int] = [0] * self.n_shards

        tel = getattr(app_context, "telemetry", None)
        self._fanout_hist = self._merge_hist = None
        self._query_hists: Dict[Duration, object] = {}
        if tel is not None and hasattr(tel, "histogram"):
            aid = definition.id
            tel.gauge(f"aggregation.{aid}.shards", lambda: self.n_shards)
            for s in self.shards:
                if s.wal is not None:
                    tel.gauge(f"aggregation.{aid}.shard{s.index}"
                              f".wal_batches", s.wal.__len__)
            self._fanout_hist = tel.histogram("serving.fanout_ms")
            self._merge_hist = tel.histogram("serving.merge_ms")
            self._query_hists = {
                d: tel.histogram(f"serving.query.{d.value}_ms")
                for d in self.durations}

    # ------------------------------------------------------------- routing

    def _owner_of(self, g: tuple) -> int:
        """Deterministic shard owner of one group tuple. Group components
        are numeric (strings travel as dictionary ids), so ``repr`` is a
        stable byte key within a runtime; WAL/snapshot recovery re-routes
        through this same function, so ownership survives restarts even
        if the hash landed differently before."""
        if self.n_shards == 1:
            return 0
        return zlib.crc32(repr(g).encode()) % self.n_shards

    # -------------------------------------------------------------- ingest

    def receive(self, events: List[Event]):
        prep = self._prepare_batch(events)
        if prep is None:
            return
        t0 = time.perf_counter()
        # base-class parity: ingest re-creates declared granularities a
        # shrinking restore removed (self.store is the sharded runtime's
        # queryable-duration marker; buckets live in the shards)
        for d in self.durations:
            self.store.setdefault(d, {})
        owned: Dict[int, list] = {}
        for i in prep["idx"]:
            owned.setdefault(
                self._owner_of(prep["group_tuples"][int(i)]), []).append(i)
        for s_idx, rows in owned.items():
            shard = self.shards[s_idx]
            with shard._lock:
                self._fold_rows(shard, prep, rows)
                shard.bump()
                if shard.wal is not None:
                    # the shard's routed sub-batch, in arrival order — the
                    # replay source for a shard-scoped rebuild. Recorded
                    # INSIDE the shard lock: a concurrent rebuild then
                    # sees this batch either folded+recorded or neither —
                    # fold-then-record outside the lock would let the
                    # rebuild's store swap discard the fold while the
                    # replay misses the not-yet-appended record
                    shard.wal.record_events(
                        self.input_stream_id,
                        [events[int(i)] for i in rows])
        if self._flush_hist is not None:
            self._flush_hist.record((time.perf_counter() - t0) * 1000.0)

    # --------------------------------------------------------------- query

    def _scatter(self, fn) -> list:
        """Run ``fn(shard)`` over all shards concurrently on the shared
        serving pool; falls back to inline reads when the executor
        refuses new work (interpreter teardown) so a late query never
        fails just because scatter cannot."""
        if self.n_shards == 1:
            return [fn(self.shards[0])]
        from siddhi_tpu.serving.query_tier import scatter_pool

        try:
            futures = [scatter_pool().submit(fn, s) for s in self.shards]
        except RuntimeError:
            return [fn(s) for s in self.shards]
        return [f.result() for f in futures]

    def rows(self, duration: Duration,
             within: Optional[Tuple[int, int]] = None) -> List[list]:
        within = self._resolve_within(duration, within)
        t0 = time.perf_counter()
        parts = self._scatter(lambda s: s.partials(duration))
        t1 = time.perf_counter()
        merged = self._ordered_merge(parts, within)
        t2 = time.perf_counter()
        if self._fanout_hist is not None:
            self._fanout_hist.record((t1 - t0) * 1000.0)
            self._merge_hist.record((t2 - t1) * 1000.0)
        out = self._rows_from_items(merged)
        h = self._query_hists.get(duration)
        if h is not None:
            h.record((time.perf_counter() - t0) * 1000.0)
        return out

    def _ordered_merge(self, parts: List[list],
                       within: Optional[Tuple[int, int]]) -> list:
        """Deterministic k-way merge of per-shard partials, ordered by
        (bucket, group). Ownership is disjoint in steady state, but a
        rebuild-in-progress or a cross-layout restore can surface the same
        (bucket, group) on two shards — duplicates fold by base
        (``_BaseSpec.fold``), the reference's shard-stitch rule."""
        base_specs = list(self.bases.values())
        merged: list = []
        for item in heapq.merge(*parts, key=_merge_key):
            if within is not None and not (within[0] <= item[0] < within[1]):
                continue
            if merged and _merge_key(merged[-1]) == _merge_key(item):
                prev = merged[-1][2]
                merged[-1] = (item[0], item[1], [
                    spec.fold(a, b)
                    for spec, a, b in zip(base_specs, prev, item[2])])
            else:
                merged.append(item)
        return merged

    def shard_device_contents(self, index: int, duration: Duration):
        """One shard's stitched rollup rows as device-resident columnar
        arrays on the shard's mesh device, cached per ingest epoch —
        repeated on-demand reads between folds are served from the device
        without re-walking the host cube. Returns (output_definition,
        {col: jax.Array}, valid)."""
        import jax

        shard = self.shards[index]
        with shard._lock:
            epoch = shard.epoch
            cached = shard._device_cache.get(duration)
        if cached is not None and cached[0] == epoch:
            return cached[1]
        definition, cols, valid = self._columnize(
            self._rows_from_items(shard.partials(duration)))
        dev = shard.device
        if dev is not None:
            cols = {k: jax.device_put(v, dev) for k, v in cols.items()}
            valid = jax.device_put(valid, dev)
        view = (definition, cols, valid)
        shard._device_cache[duration] = (epoch, view)
        return view

    def _bucket_count(self, duration: Duration) -> int:
        return sum(len(s.store.get(duration, ())) for s in self.shards)

    # --------------------------------------------------------------- purge

    def purge(self, now: Optional[int] = None) -> int:
        if now is None:
            now = int(self.app_context.timestamp_generator.current_time())
        purged = 0
        for shard in self.shards:
            with shard._lock:
                touched = False
                for d, dstore in shard.store.items():
                    keep_ms = self.retention.get(d)
                    if keep_ms is None:
                        continue
                    cutoff = now - keep_ms
                    drop = [b for b in dstore if b < cutoff]
                    for b in drop:
                        del dstore[b]
                        shard._deleted.add((d, b))
                        shard._dirty.discard((d, b))
                        touched = True
                    purged += len(drop)
                if touched:
                    shard.bump()
        return purged

    # ----------------------------------------------- checkpoint + rebuild

    def _ser_store(self, store) -> dict:
        return {d.value: {b: {g: list(v) for g, v in groups.items()}
                          for b, groups in dstore.items()}
                for d, dstore in store.items()}

    def _deser_store(self, ser) -> dict:
        out = {d: {} for d in self.durations}
        for dv, dstore in ser.items():
            d = parse_duration_name(dv)
            if d not in out:
                continue
            out[d] = {
                int(b): {(tuple(g) if isinstance(g, (list, tuple))
                          else (g,)): list(v)
                         for g, v in groups.items()}
                for b, groups in dstore.items()}
        return out

    def checkpoint_shards(self) -> List[dict]:
        """Per-shard checkpoint blobs ({"store", "cut"}) for the rebuild
        protocol. The WAL is trimmed at each shard's cut — the blob now
        covers that prefix — so the retained suffix is exactly what a
        later ``rebuild_shard`` must replay."""
        blobs = []
        for shard in self.shards:
            with shard._lock:
                cut = shard.wal.cut() if shard.wal is not None else 0
                blobs.append({"shard": shard.index,
                              "store": self._ser_store(shard.store),
                              "cut": cut})
            if shard.wal is not None:
                shard.wal.trim(cut)
        return blobs

    def kill_shard(self, index: int) -> None:
        """Fault injection: wipe one shard's state (its WAL survives, as a
        live process's log would)."""
        self.shards[index].wipe()

    def rebuild_shard(self, index: int, blob: dict) -> int:
        """Supervisor rebuild protocol for one lost shard: restore the
        shard's last checkpoint blob, then re-fold its WAL suffix (records
        newer than the blob's cut) — effectively-once, without touching
        sibling shards or blocking their ingest. A blob whose cut predates
        the WAL's last checkpoint trim skips the replay: the retained
        suffix follows a NEWER base, and grafting it onto this older one
        would silently lose the gap (the PR-1 stale-revision rule).
        Returns the number of replayed records."""
        from siddhi_tpu.resilience import stat_count

        shard = self.shards[index]
        cut = int(blob.get("cut", 0))
        replayed = 0
        with shard._lock:
            shard.store = self._deser_store(blob.get("store", {}))
            shard._dirty = {(d, b) for d, dstore in shard.store.items()
                            for b in dstore}
            shard._deleted.clear()
            shard._partials_cache.clear()
            shard._device_cache.clear()
            if shard.wal is not None:
                if cut < shard.wal.checkpoint_seq:
                    _LOG.warning(
                        "aggregation '%s' shard %d: checkpoint cut %d "
                        "predates the WAL's last trim %d — skipping the "
                        "replay (suffix follows a newer base)",
                        self.definition.id, index, cut,
                        shard.wal.checkpoint_seq)
                    stat_count(self.app_context,
                               "resilience.shard_replay_skips")
                else:
                    recs = shard.wal.records_after(cut)
                    # the bounded log drops OLDEST records on overflow:
                    # if appends happened past the cut but the retained
                    # suffix no longer starts at cut+1, the gap was
                    # dropped — the rebuild is incomplete and must say so
                    # (sequence numbers are contiguous, so a hole in the
                    # range is detectable exactly)
                    newest = shard.wal.cut()
                    first = recs[0].seq if recs else newest + 1
                    if newest > cut and first > cut + 1:
                        _LOG.error(
                            "aggregation '%s' shard %d: WAL overflow "
                            "dropped records %d..%d of the replay suffix "
                            "(bound too small / checkpoints too sparse) — "
                            "rebuilt state is missing those batches",
                            self.definition.id, index, cut + 1, first - 1)
                        stat_count(self.app_context,
                                   "resilience.shard_replay_gaps")
                        tel = getattr(self.app_context, "telemetry", None)
                        if tel is not None:
                            tel.count("serving.shard_replay_gaps")
                    for rec in recs:
                        prep = self._prepare_batch(
                            rec.payload if rec.kind == "events" else [])
                        if prep is None:
                            continue
                        rows = [i for i in prep["idx"]
                                if self._owner_of(
                                    prep["group_tuples"][int(i)]) == index]
                        self._fold_rows(shard, prep, rows)
                        replayed += 1
            shard.bump()
        stat_count(self.app_context, "resilience.shard_rebuilds")
        tel = getattr(self.app_context, "telemetry", None)
        if tel is not None:
            tel.count("serving.shard_rebuilds")
        return replayed

    # ---------------------------------------------------------- snapshots

    def snapshot(self) -> dict:
        shards = []
        self._last_cuts = []
        for shard in self.shards:
            with shard._lock:
                shards.append({"shard": shard.index,
                               "store": self._ser_store(shard.store)})
                self._last_cuts.append(
                    shard.wal.cut() if shard.wal is not None else 0)
        return {"sharded": True, "n_shards": self.n_shards,
                "base_keys": list(self.bases), "shards": shards}

    def restore(self, snap: dict):
        # merge to one flat store, realign base keys through the shared
        # helper, then re-route every (bucket, group) to its owner — an
        # UNSHARDED revision or a different shard count cross-restores
        # transparently
        if snap.get("sharded"):
            merged = self._merge_sharded_snapshot(snap)
        else:
            merged = snap
        # reuse the base realignment (snap base_keys -> current layout)
        holder = _RestoreTarget()
        _base_restore(self, holder, merged)
        # mirror the base class's wholesale-replace semantics: the
        # queryable granularity set follows the RESTORED state (fewer or
        # more durations than declared both work — _resolve_within checks
        # the store, and ingest re-creates declared durations on demand)
        for shard in self.shards:
            with shard._lock:
                shard.store = {d: {} for d in holder.store}
                shard._dirty.clear()
                shard._deleted.clear()
                shard._partials_cache.clear()
                shard._device_cache.clear()
        for d, dstore in holder.store.items():
            for b, groups in dstore.items():
                for g, vals in groups.items():
                    shard = self.shards[self._owner_of(g)]
                    shard.store[d].setdefault(b, {})[g] = vals
        self.store = {d: {} for d in holder.store}
        for shard in self.shards:
            with shard._lock:
                shard.bump()
                # the restored state supersedes any retained suffix
                if shard.wal is not None:
                    shard.wal.mark_checkpoint()

    # --------------------------------------------- incremental snapshots

    def incremental_snapshot(self) -> dict:
        shards = []
        for shard in self.shards:
            with shard._lock:
                out = {"buckets": {}, "deleted": []}
                for d, b in shard._dirty:
                    groups = shard.store.get(d, {}).get(b)
                    if groups is None:
                        continue
                    out["buckets"].setdefault(d.value, {})[b] = {
                        g: list(v) for g, v in groups.items()}
                out["deleted"] = [(d.value, b) for d, b in shard._deleted]
                shards.append(out)
        return {"sharded": True, "shards": shards}

    def clear_oplog(self):
        for i, shard in enumerate(self.shards):
            with shard._lock:
                shard._dirty.clear()
                shard._deleted.clear()
            if shard.wal is not None and i < len(self._last_cuts):
                # the revision covering _last_cuts is now durable: the
                # retained suffix follows it
                shard.wal.trim(self._last_cuts[i])

    def apply_increment(self, snap: dict):
        if snap.get("sharded") and len(snap.get("shards", [])) == self.n_shards:
            for shard, sub in zip(self.shards, snap["shards"]):
                with shard._lock:
                    for dv, b in sub.get("deleted", []):
                        shard.store.get(Duration(dv), {}).pop(b, None)
                    for dv, buckets in sub.get("buckets", {}).items():
                        d = Duration(dv)
                        dstore = shard.store.setdefault(d, {})
                        for b, groups in buckets.items():
                            dstore[b] = {g: list(v)
                                         for g, v in groups.items()}
                    shard.bump()
            return
        # foreign layout (unsharded, or a different shard count): buckets
        # REPLACE wholesale, split by ownership
        subs = (snap.get("shards", [snap])
                if snap.get("sharded") else [snap])
        for sub in subs:
            for dv, b in sub.get("deleted", []):
                d = Duration(dv)
                for shard in self.shards:
                    with shard._lock:
                        if shard.store.get(d, {}).pop(b, None) is not None:
                            shard.bump()
            for dv, buckets in sub.get("buckets", {}).items():
                d = Duration(dv)
                for b, groups in buckets.items():
                    owned: Dict[int, dict] = {}
                    for g, v in groups.items():
                        g = tuple(g) if isinstance(g, (list, tuple)) else (g,)
                        owned.setdefault(self._owner_of(g), {})[g] = list(v)
                    for shard in self.shards:
                        mine = owned.get(shard.index)
                        with shard._lock:
                            dstore = shard.store.setdefault(d, {})
                            if mine:
                                dstore[b] = mine
                            else:
                                dstore.pop(b, None)
                            shard.bump()

    # ------------------------------------------------- DB shard-stitch API

    def publish_shard(self):  # pragma: no cover — guarded at construction
        raise RuntimeError(
            "in-process mesh sharding replaces @PartitionById DB-stitch "
            "publishing (MIGRATION.md)")

    def stitch_shards(self) -> int:  # pragma: no cover
        raise RuntimeError(
            "in-process mesh sharding replaces @PartitionById DB-stitch "
            "reads (MIGRATION.md)")


class _RestoreTarget:
    """Bare store holder the base restore writes into."""

    def __init__(self):
        self.store: dict = {}


def _base_restore(runtime: ShardedIncrementalAggregation,
                  holder: _RestoreTarget, snap: dict) -> None:
    """Base-key realignment of a flat snapshot into ``holder.store`` —
    the body of ``IncrementalAggregationRuntime.restore`` minus the
    self-mutation, reused so sharded restore realigns identically."""
    snap_keys = snap.get("base_keys")
    cur_keys = list(runtime.bases)
    if snap_keys is None or snap_keys == cur_keys:
        remap = None
    else:
        remap = [snap_keys.index(k) if k in snap_keys else -1
                 for k in cur_keys]

    def realign(v):
        if remap is None:
            return list(v)
        return [v[j] if j >= 0 else None for j in remap]

    holder.store = {
        parse_duration_name(dv): {
            int(b): {(tuple(g) if isinstance(g, (list, tuple))
                      else (g,)): realign(v)
                     for g, v in groups.items()}
            for b, groups in dstore.items()
        }
        for dv, dstore in snap["store"].items()
    }
