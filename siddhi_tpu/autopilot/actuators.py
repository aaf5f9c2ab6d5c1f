"""The declared actuator registry — every live knob the autopilot may
touch, with its typed-knob name and hard bounds.

graftlint R7 (``analysis/rules_actuators.py``) holds this registry to
the same bidirectional parity discipline as metric families (R3) and
device instruments (R6): every ``Actuator(...)`` must name a typed knob
declared in ``core/util/knobs.py``, every ``PolicyRule(...)`` must name
a declared actuator, and an actuator no policy rule can ever reach is a
dead declaration — all three are lint findings.

Every ``apply`` preserves WHAT the engine emits by construction — it
may only change when/where work runs:

- ``pipeline_depth``  plain attr write; the CompletionPump reads
                      ``app_context.pipeline_depth`` at every submit.
- ``ingest_pool``     ``IngestPackPool.resize`` (ordered merge keeps
                      sub-batch sequence numbers authoritative).
- ``join_partitions`` Wp shrink through the same rebuild path the
                      PanJoin growth side uses (``_rebuild_side``).
- ``route_shards``    blue/green re-install via the canonical-snapshot
                      cross-restore path (``device_route_query_step``
                      on an already-routed runtime).
- ``admission_cap``   mutates the live ``OverloadConfig`` quotas.
- ``fuse_fanout``     dissolve/re-form fused fan-out groups, deferred
                      to a batch boundary on the delivering thread.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

# direction spellings used across policy, decision log and telemetry
UP, DOWN = "up", "down"


@dataclass(frozen=True)
class Actuator:
    """One declared actuation path.

    ``knob`` is the governing typed-knob key in ``core/util/knobs.py``
    (graftlint R7 checks the reference). ``lo``/``hi`` are hard value
    bounds the policy may never push past. ``apply(rt, direction)``
    returns ``(old, new)`` when it changed something, None when the
    actuation does not apply to this runtime (nothing to log)."""

    name: str
    knob: str
    lo: int
    hi: int
    doc: str
    apply: Optional[Callable] = None


def _clamp(v: int, lo: int, hi: int) -> int:
    return max(lo, min(hi, v))


def _apply_pipeline_depth(rt, direction) -> Optional[Tuple[int, int]]:
    ctx = rt.app_context
    old = int(getattr(ctx, "pipeline_depth", 1) or 1)
    new = _clamp(old + (1 if direction == UP else -1), 1, 8)
    if new == old:
        return None
    # the pump reads app_context.pipeline_depth live at every submit —
    # in-flight batches drain at the old depth, the next submit sees new
    ctx.pipeline_depth = new
    return old, new


def _apply_ingest_pool(rt, direction) -> Optional[Tuple[int, int]]:
    ctx = rt.app_context
    pool = getattr(ctx, "ingest_pack_pool", None)
    old = int(pool.workers) if pool is not None else 0
    new = _clamp(old + (1 if direction == UP else -1), 0, 8)
    if new == old:
        return None
    if pool is None:
        # pool-from-zero: same construction start() performs lazily
        from siddhi_tpu.core.stream.input.pack_pool import IngestPackPool

        ctx.ingest_pack_pool = IngestPackPool(
            ctx, workers=new, split_rows=ctx.ingest_split)
    elif new == 0:
        # pool-to-zero: graceful drain; in-flight run_ordered calls
        # detect the shutdown race and re-pack inline (bit-identical)
        pool.shutdown()
        ctx.ingest_pack_pool = None
    else:
        pool.resize(new)
    ctx.ingest_pool = new
    return old, new


def _apply_join_partitions(rt, direction) -> Optional[Tuple[int, int]]:
    """Shrink-only: Wp GROWTH stays where it always was (the engine
    grows pre-dispatch inside ``prepare_batch`` the moment occupancy
    demands it); the autopilot's contribution is the reverse path —
    releasing over-provisioned sub-windows after a skew burst passes."""
    if direction != DOWN:
        return None
    changed = None
    for qr in rt.query_runtimes.values():
        eng = getattr(qr, "engine", None)
        if eng is None or not hasattr(eng, "shrink_partitions"):
            continue
        with qr._lock:   # no batch mid-step while the directory rebuilds
            shrunk = eng.shrink_partitions()
        for _side, (old_wp, new_wp) in (shrunk or {}).items():
            changed = (old_wp, new_wp) if changed is None else \
                (max(changed[0], old_wp), max(changed[1], new_wp))
    return changed


def _apply_route_shards(rt, direction) -> Optional[Tuple[int, int]]:
    from siddhi_tpu.parallel.mesh import (
        device_route_query_step,
        make_mesh,
        route_ineligibility,
    )
    import jax

    n_dev = len(jax.devices())
    cap = int(getattr(rt.app_context, "route_shards", 0) or 0) or n_dev
    changed = None
    for qr in rt.query_runtimes.values():
        layout = getattr(qr, "_route_layout", None)
        if layout is None or route_ineligibility(qr) is not None:
            continue   # never routes an UNrouted query — install is a
            # deployment decision; the autopilot only re-sizes
        old = int(layout.n)
        new = old * 2 if direction == UP else old // 2
        if new < 2 or new > min(cap, n_dev) or new == old:
            continue
        with qr._lock:
            # drain this owner's pipelined batches so the canonical
            # snapshot captures a settled state (owner -> pump order)
            rt.app_context.completion_pump.flush_owner(qr)
            device_route_query_step(
                qr, make_mesh(new), rows_per_shard=layout.rows_per_shard)
        changed = (old, new)
    return changed


def _apply_admission_cap(rt, direction) -> Optional[Tuple[int, int]]:
    ctl = getattr(rt.app_context, "overload", None)
    if ctl is None or ctl.config.queue_quota is None:
        return None   # no quotas armed: nothing to cap
    old = int(ctl.config.queue_quota)
    new = _clamp(old * 2 if direction == UP else old // 2, 16, 1 << 20)
    if new == old:
        return None
    # live config mutation — admit() reads the config per call, and the
    # quota gauges divide by it, so /metrics tracks the new cap at once
    ctl.config.queue_quota = new
    return old, new


def _apply_fuse_fanout(rt, direction) -> Optional[Tuple[int, int]]:
    from siddhi_tpu.core.plan.fanout_plan import plan_junction_groups

    ctx = rt.app_context
    target = direction == UP
    old_n = len(rt.fused_fanout_groups)
    if target and old_n > 0:
        return None          # already fused
    if not target and old_n == 0 and not ctx.fuse_fanout:
        return None          # already dissolved
    ctx.fuse_fanout = target

    def _refit(junction):
        # runs ON the delivering thread at a batch boundary (the
        # junction drains deferred mutations before fanning a batch
        # out), so the receiver list is never rewired mid-delivery
        for g in [g for g in list(rt.fused_fanout_groups)
                  if g.junction is junction]:
            g.dissolve()
            try:
                rt.fused_fanout_groups.remove(g)
            except ValueError:
                pass
        if target:
            rt.fused_fanout_groups.extend(plan_junction_groups(junction))

    junctions = {g.junction for g in rt.fused_fanout_groups} if not target \
        else set(rt.junctions.values())
    for j in junctions:
        j.defer_mutation(lambda jn=j: _refit(jn))
    return (old_n, 0) if not target else (0, 1)


def _declare(*actuators: Actuator) -> Dict[str, Actuator]:
    return {a.name: a for a in actuators}


ACTUATORS: Dict[str, Actuator] = _declare(
    Actuator(name="pipeline_depth", knob="pipeline_depth", lo=1, hi=8,
             doc="CompletionPump overlap depth (live attr read)",
             apply=_apply_pipeline_depth),
    Actuator(name="ingest_pool", knob="ingest_pool", lo=0, hi=8,
             doc="IngestPackPool worker count (ordered-merge resize)",
             apply=_apply_ingest_pool),
    Actuator(name="join_partitions", knob="join_partition_slack", lo=1,
             hi=64,
             doc="device-join Wp shrink (growth stays in prepare_batch)",
             apply=_apply_join_partitions),
    Actuator(name="route_shards", knob="route_shards", lo=2, hi=64,
             doc="routed shard count (canonical blue/green re-install)",
             apply=_apply_route_shards),
    Actuator(name="admission_cap", knob="quota_queue_depth", lo=16,
             hi=1 << 20,
             doc="live OverloadConfig queue quota",
             apply=_apply_admission_cap),
    Actuator(name="fuse_fanout", knob="fuse_fanout", lo=0, hi=1,
             doc="fan-out fusion dissolve/re-form at a batch boundary",
             apply=_apply_fuse_fanout),
)
