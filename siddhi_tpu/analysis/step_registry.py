"""Declarative registry of every jitted step BUILDER in the engine.

``tools/hlo_audit.py`` used to audit a hand-kept list of step kinds;
a new builder (the device join engine, the sharded-agg selector) only
got audited when somebody remembered. This registry is the contract:
every entry here names a production code path that compiles a step
with ``jax.jit``, and hlo_audit asserts its decorated audit set covers
ALL of them — adding a builder without an audit fails the quick tier
by construction.

Entries are (dotted module path, attribute) so the registry is
importable without jax and verifiable by a plain resolve.
"""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

# audit name -> (module, attr) of the builder that jits the step
JIT_STEP_BUILDERS: Dict[str, Tuple[str, str]] = {
    # per-query single-stream step (QueryRuntime._make_step -> jax.jit)
    "query_step": ("siddhi_tpu.core.query.runtime", "QueryRuntime"),
    # fused sibling queries: one jitted step per junction group
    "fused_fanout": ("siddhi_tpu.core.query.fused_fanout",
                     "FusedFanoutRuntime"),
    # GSPMD keyed sharding: what the device router cannot take
    "gspmd_replicated_batch": ("siddhi_tpu.parallel.mesh",
                               "shard_query_step"),
    # device-side repartitioning: routing inside the step
    "device_routed": ("siddhi_tpu.parallel.mesh",
                      "device_route_query_step"),
    # device join engine: fused insert+probe side step
    "device_join": ("siddhi_tpu.core.join.engine", "DeviceJoinEngine"),
    # serving tier: sharded incremental aggregation's on-demand
    # selector steps over per-shard device views
    "sharded_agg": ("siddhi_tpu.serving.sharded_aggregation",
                    "ShardedIncrementalAggregation"),
}


# Builders whose steps carry a device-instrument meta suffix
# (observability/instruments.py): their hlo_audit functions must ALSO
# assert the packed meta matches the runtime's declared
# instrument_slots() spec — one module, zero extra transfers, lanes
# accounted for. A builder gaining a suffix without joining this tuple
# (or vice versa) fails the audit's coverage check.
INSTRUMENTED_STEP_BUILDERS = (
    "query_step",      # win_fill / groups lanes
    "device_routed",   # route slots + aggregated inner lanes
    "device_join",     # seq + per-partition fill lanes
)


# Program-cache participation (core/util/program_cache.py, round 15):
# audit name -> the ``family=`` tag(s) its builder passes to
# ``instrument_jit``. The tag is part of the cache key — wrapper
# shardings (``in_shardings=...``) are invisible in the traced jaxpr,
# so two builders jitting the same function under different shardings
# must never alias; tests/test_program_cache.py asserts each declared
# tag still appears at a call site in the named module (a builder
# gaining/renaming a tag without updating this inventory fails there).
# ``sharded_agg`` is absent by design: its on-demand selectors fold
# host-side — there is no production jit to cache (hlo_audit builds
# its probe program ad hoc).
PROGRAM_CACHE_FAMILIES: Dict[str, Tuple[str, ...]] = {
    "query_step": ("query_step", "selector"),
    "fused_fanout": ("fused_fanout",),
    "gspmd_replicated_batch": ("gspmd_replicated_batch",),
    "device_routed": ("device_routed",),
    # NFA steps ride QueryRuntime's module (pattern/sequence queries)
    "nfa_step": ("nfa_step", "nfa_timer"),
    # join sides tag per side at the call site: device_join.left/right
    "device_join": ("device_join",),
}

# family tags above that are PREFIXES of the call-site tag (the call
# site appends a dynamic suffix, e.g. ``device_join.left``)
PROGRAM_CACHE_PREFIX_FAMILIES = ("device_join", "device_routed")

# module that carries each family's instrument_jit call site (may
# differ from the builder's own module — NFA steps live in
# core/query/nfa_runtime, join sides in core/query/join_runtime)
PROGRAM_CACHE_FAMILY_SITES: Dict[str, str] = {
    "query_step": "siddhi_tpu.core.query.runtime",
    "selector": "siddhi_tpu.core.query.runtime",
    "fused_fanout": "siddhi_tpu.core.query.fused_fanout",
    "gspmd_replicated_batch": "siddhi_tpu.parallel.mesh",
    "device_routed": "siddhi_tpu.parallel.mesh",
    "nfa_step": "siddhi_tpu.core.query.nfa_runtime",
    "nfa_timer": "siddhi_tpu.core.query.nfa_runtime",
    "device_join": "siddhi_tpu.core.query.join_runtime",
}


def resolve(name: str):
    """Import and return the registered builder (audit-time sanity:
    a renamed/moved builder fails loudly, not silently unaudited)."""
    module, attr = JIT_STEP_BUILDERS[name]
    return getattr(importlib.import_module(module), attr)
