"""SnapshotService: checkpoint/restore of a whole app's state.

Mirror of reference ``util/snapshot/SnapshotService.java:51-800`` + the
``persist()/restoreRevision/restoreLastRevision`` lifecycle
(``SiddhiAppRuntimeImpl.java:677-755``), redesigned for dense state: the
hierarchical map-of-State-objects walk becomes one pytree per query
(device arrays -> numpy), plus the host-side key dictionaries (string
dictionary, group keyers, partition key spaces) and the shared stores
(tables, named windows). The app barrier quiesces input during both
operations (the ThreadBarrier role, ``util/ThreadBarrier.java``).

The wire format is a versioned pickle of numpy arrays — intentionally not
the reference's JDK serialization (impl-private there too, SURVEY.md §7).
"""

from __future__ import annotations

import itertools
import logging
import pickle
import time
from typing import List, Optional

import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

# v2: named-window entries became {'host','data'} wrappers, queries gained
# 'host_window'
# v3: aggregation snapshots carry base_keys (avg gained per-output cnt@
# bases; positional slot lists would misalign against v2 snapshots)
# v4: GroupKeyer key tuples gained null-mask elements (general path) and
# the single-string LUT moved to shifted dict ids — older keyer_map
# snapshots would silently orphan their aggregate rows
# v5: the keyed length window holds an int64 ring column as two uint32
# word leaves (low, high) under buf[name]; a v4 state has one int64 leaf
# there and would not fit the step
FORMAT_VERSION = 5


# one jitted identity per replicated sharding: jax.jit caches by wrapped
# function identity, so a fresh lambda per leaf per persist would pay a
# full recompile of the allgather at every checkpoint
_REPLICATE_JIT: dict = {}


def _telemetry():
    # process-global registry: _to_host is a module function with no app
    # context in scope, and the replicate-jit cache is process-wide too
    from siddhi_tpu.observability.telemetry import global_registry

    return global_registry()


def _to_host(tree):
    import jax

    def pull(x):
        if getattr(x, "is_fully_addressable", True) is False:
            # multi-process mesh: this host cannot read the peer shards
            # directly — replicate through one allgather so the snapshot
            # is WHOLE on every host and any survivor can restore
            # (requires every process to capture at the same point, the
            # SPMD contract persist() already runs under). jit identity
            # with a replicated out_sharding compiles to that allgather.
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(x.sharding.mesh, PartitionSpec())
            fn = _REPLICATE_JIT.get(rep)
            _telemetry().record_jit("snapshot.replicate_allgather",
                                    hit=fn is not None)
            if fn is None:
                fn = jax.jit(lambda a: a, out_shardings=rep)
                _REPLICATE_JIT[rep] = fn
            x = fn(x)
        return np.asarray(x)

    return jax.tree_util.tree_map(pull, tree)


def _to_device(tree):
    import jax

    return jax.tree_util.tree_map(lambda x: jnp.asarray(x), tree)


class SnapshotService:
    def __init__(self, app_runtime):
        self.app_runtime = app_runtime

    # ------------------------------------------------------------ capture

    def _capture_common(self) -> dict:
        rt = self.app_runtime
        dictionary = rt.app_context.string_dictionary
        pump = getattr(rt.app_context, "completion_pump", None)
        if pump is not None and pump.has_pending:
            # batches riding the dispatch pipeline drain INSIDE the
            # barrier: their state updates are already in the pytrees the
            # capture reads, so their outputs must emit before the cut —
            # a restore must neither lose nor re-emit them
            pump.flush()
        for q in rt.query_runtimes.values():
            if getattr(q, "_deferred", None):
                q.flush_deferred()   # un-emitted outputs must not be lost
        queries = {}
        for name, q in rt.query_runtimes.items():
            with q._lock:
                rl = getattr(q, "_route_layout", None)
                if rl is not None and q._state is not None:
                    # device-routed runtimes snapshot CANONICAL (unsharded)
                    # state at GLOBAL capacities, so revisions cross-restore
                    # between any shard counts and the unsharded runtime
                    from siddhi_tpu.parallel.mesh import canonical_route_state

                    state = canonical_route_state(q)
                    sel_keys = rl.n * rl.localK
                    win_keys = (rl.n * rl.local_win
                                if rl.local_win > 1 else q._win_keys)
                else:
                    state = q._state
                    sel_keys = q.selector_plan.num_keys
                    win_keys = q._win_keys
                strip = getattr(q, "strip_engine_state", None)
                if strip is not None and state is not None:
                    # join engine (core/join/): the partition directories
                    # and cross-stream sequence are derived state — the
                    # snapshot stores the canonical [W] ring layout only,
                    # so revisions cross-restore engine<->legacy and
                    # across join_partitions values
                    state = strip(state)
                queries[name] = {
                    "state": _to_host(state) if state is not None else None,
                    "sel_keys": sel_keys,
                    "win_keys": win_keys,
                    "keyer_map": dict(q.keyer._map) if q.keyer is not None else None,
                    "host_window": (q.host_window.snapshot()
                                    if q.host_window is not None else None),
                    "nfa_hwm": (np.array(q._nfa_hwm_arr)
                                if getattr(q, "_nfa_hwm_arr", None)
                                is not None else None),
                }
        windows = {}
        for wid, w in rt.named_windows.items():
            with w._lock:
                if w.host_mode:
                    windows[wid] = {"host": True, "data": w.stage.snapshot()}
                else:
                    windows[wid] = {"host": False, "data": _to_host(w.state)}
        return {
            "version": FORMAT_VERSION,
            "app": rt.name,
            "strings": list(dictionary._to_str),
            "queries": queries,
            "windows": windows,
            "partitions": [p.keyspace.snapshot() for p in rt.partition_contexts],
            # playback event clock: restoring mid-trace must resume event
            # time, or re-armed timers land at WALL-clock timestamps and
            # held windows never expire (reference persists via the
            # element snapshot map; the clock travels with it)
            "clock": rt.app_context.timestamp_generator._last_event_ts,
        }

    def full_snapshot(self) -> bytes:
        """Pure capture — op logs are untouched; PersistenceManager calls
        ``mark_checkpoint`` only after the revision is durably saved."""
        rt = self.app_runtime
        obj = self._capture_common()
        tables = {}
        for tid, t in rt.tables.items():
            if not hasattr(t, "state"):
                continue    # @store record tables own their durability
            with t._lock:
                tables[tid] = {"state": _to_host(t.state), "capacity": t.capacity}
        obj["tables"] = tables
        obj["aggregations"] = {aid: a.snapshot() for aid, a in rt.aggregations.items()}
        return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)

    def mark_checkpoint(self):
        """Clear the incremental op logs after a checkpoint is durably
        stored (clear-before-save would lose deltas on a failed save)."""
        rt = self.app_runtime
        for t in rt.tables.values():
            if hasattr(t, "clear_oplog"):
                t.clear_oplog()
        for a in rt.aggregations.values():
            a.clear_oplog()

    def incremental_snapshot(self, base_revision: str) -> bytes:
        """Checkpoint with op-log deltas for the heavy history holders
        (aggregation buckets, table inserts) and full state for the light
        components — the reference's incremental SnapshotService split
        (``SnapshotService.java:189`` IncrementalSnapshotable)."""
        rt = self.app_runtime
        obj = self._capture_common()
        obj["incremental"] = True
        obj["base"] = base_revision
        obj["tables_inc"] = {
            tid: t.incremental_snapshot()
            for tid, t in rt.tables.items() if hasattr(t, "incremental_snapshot")
        }
        obj["aggregations_inc"] = {
            aid: a.incremental_snapshot() for aid, a in rt.aggregations.items()
        }
        return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)

    # ------------------------------------------------------------ restore

    def restore(self, data: bytes):
        obj = pickle.loads(data)
        if obj.get("incremental"):
            raise ValueError(
                "incremental snapshot cannot be restored standalone — "
                "restore its base chain via PersistenceManager")
        self._restore_obj(obj)
        self.mark_checkpoint()   # restored state must not re-enter op logs

    def apply_incremental(self, data: bytes, rearm: bool = True):
        """Apply one incremental checkpoint on top of already-restored
        state: light components overwrite, heavy ones apply op logs."""
        obj = pickle.loads(data) if isinstance(data, (bytes, bytearray)) else data
        self._restore_obj(obj, incremental=True)
        rt = self.app_runtime
        for tid, snap in obj.get("tables_inc", {}).items():
            t = rt.tables.get(tid)
            if t is not None and hasattr(t, "apply_increment"):
                t.apply_increment(snap)
        for aid, snap in obj.get("aggregations_inc", {}).items():
            a = rt.aggregations.get(aid)
            if a is not None:
                a.apply_increment(snap)
        if rearm:
            self._rearm_schedulers()

    def _restore_obj(self, obj, incremental: bool = False):
        if obj.get("version") != FORMAT_VERSION:
            raise ValueError(
                f"snapshot format {obj.get('version')} is not supported "
                f"(expected {FORMAT_VERSION})"
            )
        rt = self.app_runtime
        if obj.get("app") != rt.name:
            raise ValueError(
                f"snapshot belongs to app '{obj.get('app')}', not '{rt.name}' — "
                f"name apps with @app:name for stable restore identities"
            )
        dictionary = rt.app_context.string_dictionary
        # the fresh runtime's compile-time dictionary entries are a prefix of
        # the snapshot's (same app text parses in the same order)
        strings = obj["strings"]
        if strings[: len(dictionary._to_str)] != dictionary._to_str[:len(strings)]:
            raise ValueError(
                "snapshot belongs to a different app (string dictionaries diverge)"
            )
        dictionary.restore_strings(strings)

        # resume the event clock: re-armed timers and window deadlines
        # must anchor to restored EVENT time, not wall time. Forced (not
        # monotone) — restoring an EARLIER revision in-place rolls the
        # clock back with the state (reference restoreRevision replay)
        clock = obj.get("clock", -1)
        if clock is not None and clock >= 0:
            rt.app_context.timestamp_generator.reset_timestamp(int(clock))

        for snap, pctx in zip(obj["partitions"], rt.partition_contexts):
            pctx.keyspace.restore(snap)

        pump = getattr(rt.app_context, "completion_pump", None)
        if pump is not None:
            # in-flight pipelined outputs belong to the rolled-back
            # timeline — discard without emitting (like q._deferred below)
            pump.discard_all()

        for name, qsnap in obj["queries"].items():
            q = rt.query_runtimes.get(name)
            if q is None:
                raise ValueError(f"snapshot has unknown query '{name}'")
            with q._lock:
                q._deferred = []   # pre-restore outputs belong to the
                #                    rolled-back timeline — discard
                if q.rate_limiter is not None:
                    # likewise: buffered/counted limiter state would flush
                    # phantom pre-restore events after the rollback
                    q.rate_limiter.reset()
                q.selector_plan.num_keys = qsnap["sel_keys"]
                q._win_keys = qsnap["win_keys"]
                if getattr(q, "_route_layout", None) is not None:
                    # device-routed runtimes relayout host-side and upload
                    # shard-major below (adopt_canonical) — a _to_device
                    # here would round-trip the whole canonical state
                    # through the device for nothing
                    q._state = qsnap["state"]
                else:
                    q._state = _to_device(qsnap["state"]) if qsnap["state"] is not None else None
                if q.keyer is not None and qsnap["keyer_map"] is not None:
                    # write into the member's OWN keyer: a fused fan-out
                    # group may have aliased q.keyer to a sibling's
                    # (identical-computation dedup), and a restored
                    # snapshot can carry divergent per-member maps — the
                    # group re-derives sharing below (on_restore)
                    keyer = getattr(q, "_own_keyer", None)
                    if keyer is None:   # explicit: an empty keyer is falsy
                        keyer = q.keyer
                    keyer._map = dict(qsnap["keyer_map"])
                    keyer._next = max(keyer._map.values(), default=-1) + 1
                    keyer._lut = np.full(64, -1, np.int32)  # lazily rebuilt
                    if keyer is not q.keyer:
                        q.keyer = keyer
                if getattr(q, "_route_layout", None) is not None:
                    # snapshots store canonical layout/capacities; re-derive
                    # THIS runtime's shard-major layout (the snapshot may
                    # come from a different shard count, or be unsharded)
                    from siddhi_tpu.parallel.mesh import adopt_canonical

                    adopt_canonical(q, qsnap["sel_keys"], qsnap["win_keys"])
                if q.host_window is not None and qsnap.get("host_window") is not None:
                    q.host_window.restore(qsnap["host_window"])
                if hasattr(q, "_nfa_hwm_arr"):
                    # no nfa_hwm in the snapshot -> the mirror must RESET:
                    # keeping post-snapshot high-water marks after a
                    # rollback would permanently classify every later
                    # batch as hard (fast kernel never used) and feed
                    # expire_to clocks from the abandoned timeline
                    hwm = qsnap.get("nfa_hwm")
                    q._nfa_hwm_arr = (np.array(hwm, np.int64)
                                      if hwm is not None else None)
                q._step = None
                if hasattr(q, "_steps"):
                    q._steps.clear()
                adopt = getattr(q, "adopt_restored_state", None)
                if adopt is not None:
                    # join engine: rebuild the partition directories from
                    # the restored canonical rings (and reset the drain-
                    # sequence expectation)
                    adopt()

        # fused fan-out groups: re-derive keyer sharing from the restored
        # maps and drop the compiled fused step (key capacities changed)
        for g in getattr(rt, "fused_fanout_groups", ()) or ():
            g.on_restore()

        for tid, tsnap in obj.get("tables", {}).items():
            t = rt.tables.get(tid)
            if t is None:
                raise ValueError(f"snapshot has unknown table '{tid}'")
            with t._lock:
                t.state = _to_device(tsnap["state"])
                t.capacity = tsnap["capacity"]
                t._pk_dirty = True

        for aid, asnap in obj.get("aggregations", {}).items():
            a = rt.aggregations.get(aid)
            if a is None:
                raise ValueError(f"snapshot has unknown aggregation '{aid}'")
            a.restore(asnap)

        for wid, wsnap in obj["windows"].items():
            w = rt.named_windows.get(wid)
            if w is None:
                raise ValueError(f"snapshot has unknown window '{wid}'")
            with w._lock:
                if wsnap.get("host"):
                    w.stage.restore(wsnap["data"])
                else:
                    w.state = _to_device(wsnap["data"])
                    w._step = None

        if not incremental:
            self._rearm_schedulers()

    def _rearm_schedulers(self):
        """Re-arm expiry timers on restored time-driven stages (the
        reference re-schedules on restore; without this, in live mode
        restored held events would wait for the next arrival to expire).
        One immediate TIMER step per stage drains anything already due and
        re-requests the stage's next wake time via ``__notify__``."""
        rt = self.app_runtime
        scheduler = rt.app_context.scheduler
        if scheduler is None:
            return
        # timers of the pre-restore timeline are void (esp. on rollback,
        # where they'd sit in the FUTURE of the restored clock)
        scheduler.clear_pending()
        now = int(rt.app_context.timestamp_generator.current_time())
        for q in rt.query_runtimes.values():
            if getattr(q, "_state", None) is None:
                continue
            sides = getattr(q, "sides", None)
            if sides is not None:  # join runtime: per-side timer callbacks
                for sk, side in sides.items():
                    if side.window_stage is not None and side.window_stage.needs_scheduler:
                        scheduler.notify_at(now, q._timer_cbs[sk])
                continue
            win = getattr(q, "window_stage", None)
            host = getattr(q, "host_window", None)
            needs = (win is not None and win.needs_scheduler) or (
                host is not None and getattr(host, "needs_scheduler", False))
            if needs:
                scheduler.notify_at(now, q.process_timer)
        for w in rt.named_windows.values():
            stage_needs = getattr(w.stage, "needs_scheduler", False)
            if stage_needs:
                scheduler.notify_at(now, w.process_timer)


class PersistenceManager:
    """persist/restore lifecycle against the configured store (reference
    SiddhiAppRuntimeImpl.persist:677 / restoreRevision:719)."""

    def __init__(self, app_runtime):
        self.app_runtime = app_runtime
        self.snapshot_service = SnapshotService(app_runtime)
        self._last_revision: Optional[str] = None
        # persistence is in use: start journaling table inserts so
        # incremental checkpoints have an op log to draw from
        for t in app_runtime.tables.values():
            if hasattr(t, "journal_enabled"):
                t.journal_enabled = True

    def _store(self):
        store = self.app_runtime.app_context.siddhi_context.persistence_store
        if store is None:
            raise RuntimeError(
                "no persistence store configured — call "
                "SiddhiManager.set_persistence_store(...) first"
            )
        return store

    _seq = itertools.count()  # ms collisions must not overwrite snapshots

    def _drain_async_junctions(self, timeout_s: float = 5.0) -> bool:
        """Wait (holding the barrier) until every @Async junction's queue
        and in-flight unit have been APPLIED. The WAL records at the
        InputHandler boundary — BEFORE the async queue — so a cut taken
        while batches are still queued would trim events whose effects are
        not in the snapshot, and a restore would silently lose them. The
        barrier stops new sends; the workers keep draining. Returns False
        if a (wedged) worker did not drain in time."""
        from siddhi_tpu.core.stream.junction import _NOTHING

        rt = self.app_runtime
        deadline = time.monotonic() + timeout_s
        while True:
            busy = [j for j in rt.junctions.values()
                    if getattr(j, "_async", False) and j._running
                    and (not j._queue.empty()
                         or j._inflight is not _NOTHING)]
            if not busy:
                return True
            if time.monotonic() > deadline:
                log.warning(
                    "persist: async junction(s) %s did not drain in %.1fs "
                    "— the ingest WAL will not be trimmed for this "
                    "checkpoint (replay may overlap the snapshot)",
                    [j.definition.id for j in busy], timeout_s)
                return False
            time.sleep(0.001)

    def persist(self, incremental: bool = False) -> str:
        """Full checkpoint, or (``incremental=True``, after at least one
        full) an op-log delta chained to the previous revision (reference
        incremental SnapshotService + IncrementalPersistenceStore)."""
        from siddhi_tpu.observability.tracing import span

        t_start = time.perf_counter()
        rt = self.app_runtime
        store = self._store()
        wal = getattr(rt.app_context, "ingest_wal", None)
        with span("persist", app=rt.name, incremental=incremental):
            with rt._barrier:  # quiesce inputs (ThreadBarrier)
                # accepted-but-queued async batches must be applied before
                # the capture, or the WAL cut below would cover them
                # unapplied
                drained = self._drain_async_junctions() if wal is not None \
                    else True
                if incremental and self._last_revision is not None:
                    data = self.snapshot_service.incremental_snapshot(
                        self._last_revision)
                else:
                    data = self.snapshot_service.full_snapshot()
                # the WAL cut marks what this snapshot covers; the trim
                # waits for the durable save — a batch accepted after the
                # barrier releases must survive in the log
                # (resilience/replay.py)
                wal_cut = wal.cut() if (wal is not None and drained) else None
            # sortable: ms prefix, then a process-monotonic counter
            revision = (f"{int(time.time() * 1000):020d}_"
                        f"{next(self._seq):06d}_{rt.name}")
            store.save(rt.name, revision, data)
            # only after the save is durable: clear the op logs
            self.snapshot_service.mark_checkpoint()
            if wal_cut is not None:
                wal.trim(wal_cut)
                wal.checkpoint_revision = revision
            self._last_revision = revision
        sm = rt.app_context.statistics_manager
        if sm is not None and sm.level >= 1:
            # checkpoint stalls ingest for its whole barrier'd capture —
            # its tail belongs on the same percentile surface as queries
            sm.latency_tracker("snapshot.persist").record(
                (time.perf_counter() - t_start) * 1000.0)
        return revision

    def persist_incremental(self) -> str:
        return self.persist(incremental=True)

    def restore_revision(self, revision: str):
        rt = self.app_runtime
        store = self._store()
        # walk the base chain: a stack of increments over one full snapshot
        chain: List[dict] = []
        rev: Optional[str] = revision
        while rev is not None:
            data = store.load(rt.name, rev)
            if data is None:
                raise KeyError(f"revision '{rev}' not found for app '{rt.name}'")
            obj = pickle.loads(data)
            chain.append(obj)
            rev = obj.get("base") if obj.get("incremental") else None
        with rt._barrier:
            self.snapshot_service._restore_obj(chain[-1])
            for obj in reversed(chain[:-1]):
                self.snapshot_service.apply_incremental(obj, rearm=False)
            self.snapshot_service._rearm_schedulers()
            # replayed state must not re-enter the next delta's op log
            self.snapshot_service.mark_checkpoint()
        self._last_revision = revision
        # effectively-once: re-feed the post-checkpoint ingest suffix in
        # arrival order (outside the barrier — replay sends re-enter it).
        # The suffix FOLLOWS wal.checkpoint_revision; replaying it onto an
        # OLDER restored revision would graft it onto a base it never
        # followed (with the middle missing), so that case skips the
        # replay and leaves the log intact. Revisions sort by their ms
        # prefix; a NEWER revision (an SPMD peer's simultaneous
        # checkpoint, cluster recovery) is a valid base for the suffix.
        wal = getattr(rt.app_context, "ingest_wal", None)
        if wal is not None and len(wal):
            if (wal.checkpoint_revision is None
                    or revision >= wal.checkpoint_revision):
                wal.replay(rt)
            else:
                log.warning(
                    "ingest-WAL replay skipped: restored revision %s "
                    "precedes the WAL's checkpoint %s — the retained "
                    "suffix does not follow this base",
                    revision, wal.checkpoint_revision)

    def restore_last_revision(self) -> Optional[str]:
        rt = self.app_runtime
        store = self._store()
        rev = store.get_last_revision(rt.name)
        if rev is not None:
            self.restore_revision(rev)
        return rev

    def clear_all_revisions(self):
        self._store().clear_all_revisions(self.app_runtime.name)
        # the next incremental must not chain to a wiped revision
        self._last_revision = None
