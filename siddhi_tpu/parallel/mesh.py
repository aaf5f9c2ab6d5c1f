"""Key-space sharding of query state over a TPU device mesh.

The reference scales by partitioning *state* across threads in one JVM
(``partition/PartitionStreamReceiver.java:96-135``, per-key state maps in
``util/snapshot/state/PartitionStateHolder.java:43-48``). The TPU-native
equivalent: keyed state lives in dense ``[..., K, ...]`` arrays, and K is
sharded across chips over a 1-D ``Mesh`` axis (ICI). Event batches are
sharded along the batch axis; XLA inserts the all-to-all/psum collectives
needed to scatter rows into the owning shard — there is no hand-written
NCCL/MPI analog (SURVEY.md §2.13, §5.8).

Multi-host: the same code runs under ``jax.distributed`` with a mesh that
spans hosts; shardings are expressed only via ``NamedSharding``, so the
DCN/ICI split is the compiler's job.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from siddhi_tpu.observability.instruments import (
    MERGE_SCOPE, ROUTE_SCOPE, named_step)
from siddhi_tpu.observability.tracing import span

KEY_AXIS = "keys"


def force_host_devices(n: int) -> None:
    """Force an ``n``-device virtual CPU platform (sharding tests and CPU
    tools; never on the chip path).

    Sets the platform through the config API as well as the environment
    (the latter for subprocesses) and re-initializes backends with the
    host-device count applied, so it also works after a backend was
    already initialized.
    """
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"  # for subprocesses
    jax.config.update("jax_platforms", "cpu")
    from jax.extend.backend import clear_backends

    clear_backends()  # must precede the device-count update (guarded)
    jax.config.update("jax_num_cpu_devices", n)


def make_mesh(n_devices: Optional[int] = None, axis_name: str = KEY_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis_name,))


def key_axis_sharding(mesh: Mesh, arr_ndim: int, key_axis_index: int) -> NamedSharding:
    """Shard one array along its key axis, replicate the rest."""
    spec = [None] * arr_ndim
    spec[key_axis_index] = KEY_AXIS
    return NamedSharding(mesh, P(*spec))


def _key_axis_of(path, leaf, num_keys: int, win_keys: int) -> int:
    """Key-axis index of a query-state leaf, or -1 if unkeyed.

    Keyed state: selector/aggregator arrays (under the ``"sel"`` subtree,
    shape ``[slots, K]``), partitioned window state (under ``"win"``:
    per-key rows ``[Kw]`` or flat ring buffers ``[Kw*W]`` — key-contiguous
    layout, so an even split along axis 0 is a split along keys), and NFA
    slot tensors (``"nfa"``: key-major ``[K, S]`` / per-key ``[K]``)."""
    if not hasattr(leaf, "shape") or leaf.ndim == 0:
        return -1
    top = path[0].key if path and hasattr(path[0], "key") else None
    if top == "sel":
        for i, s in enumerate(leaf.shape):
            if s == num_keys:
                return i
    if (top in ("win", "lwin", "rwin") and win_keys > 1
            and leaf.shape[0] % win_keys == 0):
        # "lwin"/"rwin": a partitioned join's per-side keyed rings share
        # the single-stream keyed-window layout (key-contiguous flat)
        return 0
    if top == "nfa" and win_keys > 1 and leaf.shape[0] == win_keys:
        return 0
    return -1


def state_shardings(state, mesh: Mesh, num_keys: int, win_keys: int = 1):
    """Pytree of shardings for a query-state pytree.

    Only keyed state is sharded (see ``_key_axis_of``). Global (unkeyed,
    ``win_keys`` == 1) window ring buffers and scalars are replicated —
    sharding a global ring along its ring axis would put every window
    write on a collective."""
    replicated = NamedSharding(mesh, P())
    n_dev = mesh.devices.size

    def one(path, leaf):
        ax = _key_axis_of(path, leaf, num_keys, win_keys)
        if ax < 0:
            return replicated
        top = path[0].key if path and hasattr(path[0], "key") else None
        if top in ("win", "nfa") and (
            leaf.shape[0] % n_dev != 0 or win_keys % n_dev != 0
        ):
            return replicated
        return key_axis_sharding(mesh, leaf.ndim, ax)

    return jax.tree_util.tree_map_with_path(one, state)


def batch_shardings(cols, mesh: Mesh):
    """Shard every [B, ...] column along the batch axis."""

    def one(leaf):
        return NamedSharding(mesh, P(KEY_AXIS, *([None] * (leaf.ndim - 1)))) if leaf.ndim else NamedSharding(mesh, P())

    return jax.tree_util.tree_map(one, cols)


def _release_from_fanout(runtime):
    """A sharded step owns the runtime's dispatch: a fused fan-out group
    (core/query/fused_fanout.py) would keep stepping the member through
    its pre-sharding fused computation, so hand the member back its own
    junction subscription before wiring the sharded jit."""
    group = getattr(runtime, "_fanout_group", None)
    if group is not None:
        group.release(runtime)


def shard_query_step(runtime, mesh: Mesh, donate: bool = True):
    """Jit a QueryRuntime's step with its keyed state sharded over ``mesh``.

    Returns ``(jitted_step, sharded_state)``. The batch stays replicated in
    this wrapper (scatter-heavy segment reductions into K-sharded state are
    the collective-bound part; replicating the small event batch keeps the
    all-to-all off the hot path). For B-sharded ingestion use
    ``batch_shardings`` explicitly.
    """
    _release_from_fanout(runtime)
    num_keys = runtime.selector_plan.num_keys
    if runtime._state is None:
        runtime._state = runtime._init_state()
    step = runtime.build_step_fn()
    st_sh = state_shardings(runtime._state, mesh, num_keys,
                            win_keys=getattr(runtime, "_win_keys", 1))
    state = jax.device_put(runtime._state, st_sh)
    out_sh = _out_shardings(mesh, st_sh)
    jitted = jax.jit(
        named_step(step, "gspmd_replicated_batch"),
        in_shardings=(st_sh, None, None),
        out_shardings=out_sh,
        donate_argnums=(0,) if donate else (),
    )
    # telemetry: a sharded (re-)jit is a compile event — capacity growth
    # re-invokes this function, and those recompiles must be visible on
    # /metrics (siddhi_jit_compiles_total) before they show up as p99
    tel = getattr(runtime.app_context, "telemetry", None)
    if tel is not None:
        # cache_extra: in_shardings/out_shardings live on the jit
        # wrapper, invisible in the traced program — the mesh string is
        # the witness that keeps distinct layouts from aliasing
        jitted = tel.instrument_jit(
            jitted, f"query.{runtime.name}.sharded_step",
            family="gspmd_replicated_batch", cache_extra=str(mesh))
    # hand the runtime the sharded timeline so junction-fed batches
    # (QueryRuntime.process_batch) and direct jitted() callers share state;
    # remember the mesh so capacity growth re-establishes the sharding
    # (QueryRuntime._ensure_capacity re-invokes this function)
    runtime._state = state
    runtime._step = jitted
    runtime._shard_mesh = mesh
    if hasattr(runtime, "_steps"):
        # NFA runtimes jit one step per input stream (plus a TIMER sweep);
        # clear them so they re-jit with the sharded in_shardings
        runtime._steps.clear()
        runtime._timer_step = None
    return jitted, state


def _out_shardings(mesh: Mesh, st_sh):
    """(state', out) output shardings for a sharded query step: state keeps
    its key-axis sharding; the OUT batch is forced replicated. On one host
    this is what the host pull does anyway; on a multi-process mesh it is
    required — ``jax.device_get`` can only read fully-addressable arrays,
    so a partially-sharded output would strand rows on the other host.
    ``None`` (let XLA choose) when the mesh is single-process: forcing a
    replicate there costs a gather with no benefit."""
    if all(d.process_index == jax.process_index() for d in mesh.devices.flat):
        return None
    return (st_sh, NamedSharding(mesh, P()))


def sharded_jit_for(runtime, fn, n_state_args: int = 1, n_plain_args: int = 2):
    """Jit ``fn(state, *plain)`` with the runtime's recorded mesh shardings
    (used by NFAQueryRuntime for per-stream and timer steps)."""
    mesh = runtime._shard_mesh
    st_sh = state_shardings(runtime._state, mesh, runtime.selector_plan.num_keys,
                            win_keys=getattr(runtime, "_win_keys", 1))
    return jax.jit(
        fn,
        in_shardings=(st_sh,) + (None,) * n_plain_args,
        out_shardings=_out_shardings(mesh, st_sh),
        donate_argnums=(0,),
    )


# ---------------------------------------------------------------------------
# Two sharded paths, each the only one for its inputs. Above: GSPMD
# (``shard_query_step``), for whatever ``route_ineligibility`` refuses
# (patterns, time-driven and unkeyed windows), for a multi-process mesh and
# for the survivor-mesh recovery. Below: the device router for keyed
# queries (``device_route_query_step``). The unrouted batch enters
# B-sharded, each shard computes owners on device, rows exchange
# shard-to-shard with one dense all_to_all, and emitted rows re-merge into
# the exact unsharded emission order on the way out ("Scaling Ordered
# Stream Processing on Shared-Memory Multicores": ordered re-merge over
# out-of-order parallel execution). Two dense id spaces ride each row —
# the partition key (owner = pk % n, local = pk // n) and the group-by key
# (owned by its pk's shard, local ids assigned per shard in allocation
# order via a host-maintained LUT) — so GK need not equal PK.
# ---------------------------------------------------------------------------

# plain numpy scalar: a module-level jnp constant would initialize the
# jax backend AT IMPORT TIME and silently break force_host_devices
_ROUTE_BIG = np.int64(2 ** 62)
# registry -> {scope: np[n] last routed rows}. Weak keys: a dead app's
# registry must not pin its arrays forever, and a NEW registry allocated
# at a recycled address must not inherit the old one's "already
# registered" state (id()-keyed caching would do exactly that)
import weakref as _weakref

_ROUTE_ROWS: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()


def _record_route_telemetry(telemetry, scope: str, rows, exchange_ms):
    """siddhi_shard_rows{shard} gauges + siddhi_shard_exchange_ms histogram
    of the device-routed path, so key skew is visible (``scope`` = query
    name; the process-global registry where the app has no telemetry)."""
    if telemetry is None:
        from siddhi_tpu.observability.telemetry import global_registry

        telemetry = global_registry()
    telemetry.histogram(f"shard.exchange_ms.{scope}").record(exchange_ms)
    store = _ROUTE_ROWS.setdefault(telemetry, {})
    prev = store.get(scope)
    known = 0 if prev is None else prev.shape[0]
    store[scope] = np.asarray(rows, np.int64)
    # register gauges for any shard indices not seen before — a
    # re-install onto a LARGER mesh must grow the gauge set, not keep
    # reporting only the original shards' skew
    for i in range(known, len(store[scope])):
        telemetry.gauge(
            f"shard.rows.{scope}.{i}",
            lambda s=scope, j=i, st=store: (
                float(st[s][j]) if j < st[s].shape[0] else 0.0))


class RouteLayout:
    """Host-side bookkeeping of one device-routed query: shard count,
    receive capacity, and the group-key local-id LUT that carries a
    distinct GK through the exchange. ``localK``/``local_win`` mirror the
    runtime's (now per-shard) capacity fields; ``n * localK`` is the
    global dense-id capacity the keyer allocates into."""

    def __init__(self, mesh: Mesh, rows_per_shard: int,
                 partitioned: bool, use_lut: bool):
        self.mesh = mesh
        self.n = int(mesh.devices.size)
        self.rows_per_shard = int(rows_per_shard)
        self.quota = max(1, self.rows_per_shard // self.n)
        self.partitioned = partitioned
        self.use_lut = use_lut
        self.localK = 1
        self.local_win = 1
        # group-key space: global gk id -> (owner shard, per-shard local id)
        self.gk_owner = np.full(0, -1, np.int32)
        self.gk_local = np.full(0, -1, np.int32)
        self.gk_counts = np.zeros(self.n, np.int64)
        self.gk_known = 0
        self._lut_dev = None      # (lut [Kg], inv [n, localK]) device pair
        self._lut_dirty = True

    # ------------------------------------------------------------- lut sync

    def _resize_gk(self, cap: int):
        if self.gk_owner.shape[0] >= cap:
            return
        grown_o = np.full(cap, -1, np.int32)
        grown_l = np.full(cap, -1, np.int32)
        grown_o[: self.gk_owner.shape[0]] = self.gk_owner
        grown_l[: self.gk_local.shape[0]] = self.gk_local
        self.gk_owner, self.gk_local = grown_o, grown_l

    def sync_gk(self, keyer) -> bool:
        """Assign per-shard local ids to group keys allocated since the
        last sync (allocation order per shard — deterministic given the
        keyer map). Returns True while every shard still fits localK;
        False means a shard overflowed and capacity must grow."""
        if not self.use_lut or keyer is None:
            return True
        total = len(keyer)
        if total <= self.gk_known and not self._lut_dirty:
            return int(self.gk_counts.max(initial=0)) <= self.localK
        self._resize_gk(max(total, self.n * self.localK))
        if total > self.gk_known:
            fresh = sorted(
                ((gid, key) for key, gid in keyer._map.items()
                 if gid >= self.gk_known))
            for gid, key in fresh:
                owner = int(key[0]) % self.n   # composite keys lead with pk
                self.gk_owner[gid] = owner
                self.gk_local[gid] = self.gk_counts[owner]
                self.gk_counts[owner] += 1
            self.gk_known = total
            self._lut_dirty = True
        return int(self.gk_counts.max(initial=0)) <= self.localK

    def rebuild_gk(self, keyer):
        """Full LUT rebuild (restore / capacity growth): local ids are a
        pure function of the keyer map, so rebuilding is always safe."""
        self.gk_owner = np.full(0, -1, np.int32)
        self.gk_local = np.full(0, -1, np.int32)
        self.gk_counts = np.zeros(self.n, np.int64)
        self.gk_known = 0
        self._lut_dirty = True
        return self.sync_gk(keyer)

    def device_luts(self):
        """(lut, inv) device pair, replicated over the mesh; refreshed
        only when the host LUT changed (steady state: zero transfers)."""
        if self._lut_dev is not None and not self._lut_dirty:
            return self._lut_dev
        Kg = self.n * self.localK
        if self.use_lut:
            self._resize_gk(Kg)
            lut = np.where(self.gk_local[:Kg] >= 0,
                           self.gk_local[:Kg], 0).astype(np.int32)
            inv = np.zeros((self.n, self.localK), np.int32)
            alloc = np.nonzero(self.gk_local[:Kg] >= 0)[0]
            inv[self.gk_owner[alloc], self.gk_local[alloc]] = alloc
        else:
            lut = np.zeros(1, np.int32)
            inv = np.zeros((self.n, 1), np.int32)
        rep = NamedSharding(self.mesh, P())
        self._lut_dev = (jax.device_put(lut, rep), jax.device_put(inv, rep))
        self._lut_dirty = False
        return self._lut_dev

    # --------------------------------------------------------- permutations

    def pk_positions(self, local: int) -> np.ndarray:
        """Routed row of global pk id g in a [n * local] key space."""
        g = np.arange(self.n * local, dtype=np.int64)
        return (g % self.n) * local + g // self.n

    def gk_positions(self) -> np.ndarray:
        """Routed row of global gk id g (bijective over [n * localK]):
        allocated ids sit at (owner, local); unallocated ids — and ids
        whose per-shard local slot exceeds localK (allocated this batch,
        about to trigger growth; they never owned a state row yet) — fill
        the remaining all-init rows in order."""
        Kg = self.n * self.localK
        if not self.use_lut:
            return self.pk_positions(self.localK)
        self._resize_gk(Kg)
        pos = np.full(Kg, -1, np.int64)
        placed = np.nonzero(
            (self.gk_local[:Kg] >= 0) & (self.gk_local[:Kg] < self.localK))[0]
        pos[placed] = (self.gk_owner[placed].astype(np.int64) * self.localK
                       + self.gk_local[placed])
        free = np.setdiff1d(np.arange(Kg), pos[placed], assume_unique=False)
        pos[pos < 0] = free
        return pos

    def gk_inverse_values(self) -> np.ndarray:
        """[n, localK] local gk id -> global gk id (0 where unallocated;
        ids allocated past localK — pending growth, no state row yet —
        are simply not placed)."""
        inv = np.zeros((self.n, self.localK), np.int64)
        Kg = self.n * self.localK
        self._resize_gk(Kg)
        placed = np.nonzero(
            (self.gk_local[:Kg] >= 0) & (self.gk_local[:Kg] < self.localK))[0]
        inv[self.gk_owner[placed], self.gk_local[placed]] = placed
        return inv


def route_ineligibility(runtime) -> Optional[str]:
    """Why this runtime cannot take the device-routed path (None = it
    can, else a ``core.eligibility.Reason`` — free text with a stable
    machine-readable ``.code``). v1 scope: single-stream partitioned
    queries over device keyed length windows (or no window at all), and
    non-partitioned grouped aggregations without a window. Time-driven
    windows keep the legacy paths until their emission-order keys are
    made global-aware."""
    from siddhi_tpu.core.eligibility import ReasonCode as RC
    from siddhi_tpu.core.eligibility import reason
    from siddhi_tpu.ops.keyed_windows import KeyedLengthWindowStage

    if getattr(runtime, "sides", None) is not None:
        return _join_route_ineligibility(runtime)
    if hasattr(runtime, "_steps"):
        return reason(RC.NFA_QUERY, "pattern/sequence (NFA) queries")
    if runtime.host_window is not None:
        return reason(RC.HOST_WINDOW, "host-mode windows")
    sp = runtime.selector_plan
    if sp.order_by or sp.limit is not None or sp.offset is not None:
        return reason(RC.ORDER_LIMIT,
                      "order by / limit (batch-global ordering)")
    win = runtime.window_stage
    if win is not None and not isinstance(win, KeyedLengthWindowStage):
        return reason(RC.WINDOW_NOT_GLOBAL_AWARE,
                      f"window stage {type(win).__name__} (emission-order "
                      f"keys not global-aware yet)")
    if win is not None and runtime.partition_ctx is None:
        return reason(RC.GLOBAL_WINDOW, "global (non-partitioned) windows")
    if runtime.partition_ctx is None and runtime.keyer is None:
        return reason(RC.UNKEYED, "unkeyed queries (nothing to route by)")
    if runtime.carried_pk:
        return reason(RC.INNER_PARTITION_STREAM,
                      "inner partition '#stream' inputs")
    return None


def _join_route_ineligibility(runtime) -> Optional[str]:
    """Why a JOIN runtime cannot take the device-routed path (None = it
    can). v1 scope: partitioned keyed-length-window stream-stream joins —
    both sides' keyed rings route by the partition key through the same
    exchange, probes stay partition-local by construction (a key's whole
    ring lives on its owner shard), and the join step's emission-order
    keys (trigger okey stridden by the probe width) re-merge exactly."""
    from siddhi_tpu.core.eligibility import ReasonCode as RC
    from siddhi_tpu.core.eligibility import reason
    from siddhi_tpu.ops.keyed_windows import KeyedLengthWindowStage

    if runtime.partition_ctx is None:
        return reason(RC.JOIN_UNPARTITIONED,
                      "non-partitioned joins (nothing to route by)")
    if runtime.keyer is not None:
        return reason(RC.GROUPED_SELECT,
                      "grouped join selectors (host keyed select between "
                      "stages)")
    sp = runtime.selector_plan
    if sp.order_by or sp.limit is not None or sp.offset is not None:
        return reason(RC.ORDER_LIMIT,
                      "join order by / limit (batch-global ordering)")
    if runtime.index_probe is not None:
        return reason(RC.INDEXED_PROBE, "indexed join probes")
    for side in runtime.sides.values():
        if side.store is not None or side.host_window is not None:
            return reason(RC.STORE_SIDE,
                          f"shared-store/host-window join side "
                          f"'{side.stream_id}'")
        if side.global_side:
            return reason(RC.GLOBAL_SIDE,
                          "global (non-partitioned) join sides")
        if not isinstance(side.window_stage, KeyedLengthWindowStage):
            return reason(RC.WINDOW_NOT_GLOBAL_AWARE,
                          f"join window stage "
                          f"{type(side.window_stage).__name__} "
                          f"(emission-order keys not global-aware yet)")
    return None


def device_route_query_step(runtime, mesh: Mesh, rows_per_shard: int = 4096,
                            exchange: Optional[str] = None):
    """Install on-device repartitioning for a keyed query: the runtime's
    step becomes a ``shard_map`` whose body (1) computes each row's owner
    shard from its key on device, (2) exchanges rows shard-to-shard with a
    dense ``jax.lax.all_to_all``, (3) rewrites the partition- and
    group-key columns into their per-shard local id spaces (distinct
    spaces — GK == PK is not required), (4) steps the shard's local
    state, and (5) re-merges emitted rows across shards by their global
    emission-order keys, so sharded output is bit-identical to unsharded.

    Raises ``CompileError`` for a query ``route_ineligibility`` refuses
    (``shard_query_step`` is the path for those). ``exchange`` is kept for
    the configuration files that still pass it: ``None`` and
    ``"all_to_all"`` mean the one exchange there is, any other value
    raises ``CompileError``.

    ``rows_per_shard`` bounds each shard's per-batch receive capacity;
    the host pre-checks per-pair quotas and SPLITS oversized batches
    (``prepare_routed_batches``) instead of dying, and the device-side
    overflow flag (rows beyond quota) surfaces as ``FatalQueryError``
    naming ``rows_per_shard``.

    Returns ``(step3, state)`` where ``step3(state, cols, now)`` is also
    installed as ``runtime._step`` so junction-fed batches take the
    routed path (CompletionPump-eligible: the merged meta keeps the
    ``[overflow, notify, count]`` prefix)."""
    from siddhi_tpu.ops.expressions import CompileError

    why = route_ineligibility(runtime)
    if why is not None:
        raise CompileError(
            f"query '{runtime.name}': device routing does not support "
            f"{why} — use shard_query_step for those")
    if exchange not in (None, "all_to_all"):
        raise CompileError(
            f"query '{runtime.name}': exchange = {exchange!r} is not "
            f"supported — rows are exchanged by 'all_to_all' only")
    _release_from_fanout(runtime)
    partitioned = runtime.partition_ctx is not None
    use_lut = partitioned and runtime.keyer is not None

    # current (global/canonical) capacities and state
    if runtime._route_layout is not None:
        canonical = canonical_route_state(runtime)
        old = runtime._route_layout
        Kg = old.n * old.localK
        Wg = old.n * old.local_win if old.local_win > 1 else runtime._win_keys
    else:
        Kg = runtime.selector_plan.num_keys
        Wg = runtime._win_keys
        canonical = None
        if runtime._state is not None:
            canonical = jax.tree_util.tree_map(
                np.asarray, jax.device_get(runtime._state))

    layout = RouteLayout(mesh, rows_per_shard, partitioned, use_lut)
    _install_routed(runtime, layout, canonical, Kg, Wg)
    return runtime._step, runtime._state


def _install_routed(runtime, layout: RouteLayout, canonical, Kg: int, Wg: int):
    """Shared tail of install / capacity growth / snapshot adoption: size
    the per-shard capacities, (re)build the GK LUT, lay the canonical
    state out shard-major, and jit the routed step."""
    n = layout.n
    Kg = max(int(Kg), n)
    # floor 16 (the engine's minimum key capacity): a tiny localK would
    # collide with aggregator slot counts in _key_axis_of's size-match
    # heuristic ([slots, K] with slots == K is ambiguous)
    layout.localK = max(16, _pow2_div(Kg, n))
    if layout.partitioned:
        Wg = max(int(Wg), n)
        layout.local_win = max(16, _pow2_div(Wg, n))
    else:
        layout.local_win = 1
    # per-shard GK pressure can exceed localK under key skew even when the
    # global count fits — grow until the worst shard fits
    layout.rebuild_gk(runtime.keyer)
    while int(layout.gk_counts.max(initial=0)) > layout.localK:
        layout.localK *= 2
        layout._lut_dirty = True
    runtime.selector_plan.num_keys = layout.localK
    runtime._win_keys = layout.local_win
    runtime._route_layout = layout
    runtime._shard_mesh = layout.mesh
    # meta layout changed: drop the cached drain-side instrument spec
    runtime._instr_spec = None

    state = _canonical_to_routed(runtime, layout, canonical)
    if n > 1:
        axes = _routed_axes(runtime, layout, state)
        st_specs = jax.tree_util.tree_map(
            lambda ax: P(KEY_AXIS) if ax <= 0 else P(*([None] * ax), KEY_AXIS),
            axes)
        state = jax.device_put(state, jax.tree_util.tree_map(
            lambda spec: NamedSharding(layout.mesh, spec), st_specs))
    else:
        state = jax.device_put(state)
    runtime._state = state
    if getattr(runtime, "sides", None) is not None:
        # joins jit one routed step PER SIDE, lazily — the side steps are
        # rebuilt on demand by process_side_batch (routed_step_for with
        # side_key); a stale _steps cache would run the old capacities
        runtime._step = None
        runtime._steps.clear()
    else:
        runtime._step = routed_step_for(runtime)


def _pow2_div(total: int, n: int) -> int:
    """total/n rounded up to the next power of two (total, n both pow2 in
    practice; stays exact then)."""
    k = 1
    need = (total + n - 1) // n
    while k < need:
        k *= 2
    return k


def _routed_axes(runtime, layout: RouteLayout, state):
    """Key-axis index per leaf of the GLOBAL routed state (shard-major
    layout, leaf sizes n*localK / n*local_win*W); -1 = unkeyed (stacked
    with a leading device axis)."""
    Kg = layout.n * layout.localK
    Wgk = layout.n * layout.local_win if layout.local_win > 1 else 1
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _key_axis_of(path, leaf, Kg, Wgk), state)


# -------------------------------------------------------- state relayout

def _leaf_space(path) -> str:
    top = path[0].key if path and hasattr(path[0], "key") else None
    return "gk" if top == "sel" else "pk"


def _buffered_id_col(path) -> Optional[str]:
    """'__gk__'/'__pk__' when this window-buffer leaf stores key ids whose
    VALUES must translate between local and global spaces."""
    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY

    top = path[0].key if path and hasattr(path[0], "key") else None
    tail = path[-1].key if path and hasattr(path[-1], "key") else None
    if top in ("win", "lwin", "rwin") and tail in (GK_KEY, PK_KEY):
        return "gk" if tail == GK_KEY else "pk"
    return None


def canonical_route_state(runtime):
    """Routed (shard-major) state -> canonical unsharded layout, host-side
    numpy. Snapshots store THIS, so revisions cross-restore between any
    routed layouts (2/4/8 shards) and the unsharded runtime."""
    layout = runtime._route_layout
    state = jax.tree_util.tree_map(np.asarray, jax.device_get(runtime._state))
    n, Kl, Wl = layout.n, layout.localK, layout.local_win
    pos_gk = layout.gk_positions()
    inv_gk_vals = layout.gk_inverse_values() if layout.use_lut else None

    def one(path, leaf):
        axes = _key_axis_of(path, leaf, n * Kl, n * Wl if Wl > 1 else 1)
        if axes < 0:
            return leaf[0] if leaf.ndim else leaf   # stacked unkeyed copy
        leaf = np.asarray(leaf)
        idcol = _buffered_id_col(path)
        if idcol is not None:
            # translate buffered LOCAL key ids to global before the rows
            # move: ring rows of shard s live in block s of the flat ring.
            # Without a LUT (no distinct group-by) the gk space IS the pk
            # space, so both translate by the round-robin formula.
            per_shard = leaf.shape[0] // n
            out = leaf.copy()
            for s in range(n):
                blk = out[s * per_shard:(s + 1) * per_shard]
                if idcol == "pk" or inv_gk_vals is None:
                    out[s * per_shard:(s + 1) * per_shard] = blk * n + s
                else:
                    safe = np.clip(blk.astype(np.int64), 0, Kl - 1)
                    out[s * per_shard:(s + 1) * per_shard] = (
                        inv_gk_vals[s][safe].astype(leaf.dtype))
            leaf = out
        if _leaf_space(path) == "gk":
            return np.take(leaf, pos_gk, axis=axes)
        keys = n * Wl
        W = leaf.shape[0] // keys
        pos = layout.pk_positions(Wl)
        rows = (pos[:, None] * W + np.arange(W)[None, :]).reshape(-1)
        return leaf[rows]

    return jax.tree_util.tree_map_with_path(one, state)


def _canonical_to_routed(runtime, layout: RouteLayout, canonical):
    """Canonical state (possibly smaller-capacity) -> routed shard-major
    layout at the layout's capacities; missing key rows come from init."""
    n, Kl, Wl = layout.n, layout.localK, layout.local_win
    # routed init: per-shard local inits concatenated shard-major
    local_init = jax.tree_util.tree_map(np.asarray, runtime._init_state())
    axes_local = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _key_axis_of(path, leaf, Kl,
                                        Wl if Wl > 1 else 1), local_init)

    def stack(leaf, ax):
        arr = np.asarray(leaf)
        if ax < 0:
            return np.stack([arr] * n, axis=0)
        return np.concatenate([arr] * n, axis=ax)

    routed = jax.tree_util.tree_map(stack, local_init, axes_local)
    if canonical is None:
        return routed
    pos_gk = layout.gk_positions()
    if layout.use_lut:
        layout._resize_gk(n * Kl)

    def one(path, routed_leaf, canon_leaf):
        ax = _key_axis_of(path, routed_leaf, n * Kl, n * Wl if Wl > 1 else 1)
        if ax < 0:
            base = np.asarray(canon_leaf)
            return np.stack([base] * n, axis=0)
        canon_leaf = np.asarray(canon_leaf)
        out = np.asarray(routed_leaf).copy()
        if _leaf_space(path) == "gk":
            # source capacity comes from the canonical leaf itself (it may
            # be a smaller snapshot/pre-growth layout)
            g = np.arange(min(canon_leaf.shape[ax], n * Kl))
            if layout.use_lut:
                # only groups ALIVE in the (rebuilt-from-keyer) LUT carry
                # their canonical rows over. Purged gids are absent from
                # the keyer map, so the rebuild compacts local ids — and
                # the freed slots are exactly what new groups allocate
                # next; copying a purged group's stale aggregates there
                # would seed new groups with dead state (verified bug).
                # Dropped rows fall back to init, like the unsharded
                # engine's "purged rows become unreachable" rule.
                g = g[layout.gk_local[g] >= 0]
            sl_dst = [slice(None)] * out.ndim
            sl_src = [slice(None)] * out.ndim
            sl_dst[ax] = pos_gk[g]
            sl_src[ax] = g
            out[tuple(sl_dst)] = canon_leaf[tuple(sl_src)]
            return out
        keys = n * Wl
        W = out.shape[0] // keys
        pos = layout.pk_positions(Wl)
        g = np.arange(min(canon_leaf.shape[0] // max(W, 1), keys))
        rows_dst = (pos[g][:, None] * W + np.arange(W)[None, :]).reshape(-1)
        rows_src = (g[:, None] * W + np.arange(W)[None, :]).reshape(-1)
        out[rows_dst] = canon_leaf[rows_src]
        idcol = _buffered_id_col(path)
        if idcol is not None:
            # translate buffered GLOBAL key ids to this layout's locals
            # (without a LUT the gk space IS the pk space — formula)
            per_shard = out.shape[0] // n
            for s in range(n):
                blk = out[s * per_shard:(s + 1) * per_shard]
                if idcol == "pk" or not layout.use_lut:
                    out[s * per_shard:(s + 1) * per_shard] = (
                        blk.astype(np.int64) // n).astype(out.dtype)
                else:
                    lut_g = np.where(
                        layout.gk_local[: n * Kl] >= 0,
                        layout.gk_local[: n * Kl], 0).astype(np.int64)
                    safe = np.clip(blk.astype(np.int64), 0, len(lut_g) - 1)
                    out[s * per_shard:(s + 1) * per_shard] = (
                        lut_g[safe].astype(out.dtype))
        return out

    return jax.tree_util.tree_map_with_path(one, routed, canonical)


# ----------------------------------------------------------- routed step

def _int32_words(v):
    """Column ``v`` (``[rows, ...]``, any dtype but float64) as ``(words,
    back)``: ``words`` is int32 ``[rows, p]`` and holds the column's bits
    (a 64-bit integer as two words, a float as its bits, anything
    narrower than a word widened to one); ``back`` turns such words into
    the column again."""
    dt = v.dtype
    if dt.itemsize == 8:
        bits = jax.lax.bitcast_convert_type(v, jnp.int32)       # [.., 2]
        unbits = lambda b: jax.lax.bitcast_convert_type(b, dt)  # noqa: E731
    elif jnp.issubdtype(dt, jnp.floating):
        as_int = jnp.dtype(f"int{8 * dt.itemsize}")
        bits = jax.lax.bitcast_convert_type(v, as_int).astype(jnp.int32)
        unbits = lambda b: jax.lax.bitcast_convert_type(  # noqa: E731
            b.astype(as_int), dt)
    else:
        bits = v.astype(jnp.int32)
        unbits = lambda b: b.astype(dt)  # noqa: E731
    inner = bits.shape[1:]

    def back(words):
        return unbits(words.reshape((words.shape[0],) + inner))

    return bits.reshape(v.shape[0], -1), back


def _ordered_merge(okey, out, n: int):
    """The routed egress's ordered re-merge (call under ``shard_map`` over
    ``KEY_AXIS``): ``okey`` holds THIS shard's ``L`` order keys and ``out``
    its ``L`` emitted rows per column. Returns every column over all
    ``n * L`` rows in the stable order of the gathered keys, replicated:
    what ``all_gather(v)[argsort(all_gather(okey), stable=True)]`` gives,
    bit for bit and in every slot, invalid rows included. Nothing here
    computes on a value: columns are only moved.

    The order keys are gathered and sorted ONCE. Every column but the
    doubles is then placed by the shard that owns the rows: its bits go,
    as 1-D int32 words, into zeroed ``[n * L]`` buffers at this shard's
    rows' positions in the merged order (``rank``, the inverse of the
    sort's permutation); the shards' buffers hold disjoint slots, so one
    integer ``psum`` (exact) combines them. A one-operand 32-bit scatter
    gets the TPU compiler's sorted path; a 64-bit value scattered as one
    two-plane operand does not (PERF.md sections 5, 6), so an int64 goes
    as two words, and floats ride as their bits (``-0.0 + 0.0`` and NaN
    payloads would not survive a float sum). A float64 has no bits to
    take on the TPU (it is a pair of float32 there and the compiler
    refuses to bitcast it; splitting it arithmetically flushes a
    subnormal low half), so the doubles are gathered and ride the sort
    as its payload instead."""
    def gathered(v):
        return jax.lax.all_gather(v, KEY_AXIS, axis=0, tiled=True)

    L = okey.shape[0]
    nL = n * L
    slots = jnp.arange(nL, dtype=jnp.int32)
    doubles = {k: v.reshape(L, -1) for k, v in out.items()
               if v.dtype == jnp.float64}
    keys = gathered(okey)
    payload = [gathered(d[:, j]) for d in doubles.values()
               for j in range(d.shape[1])]
    _, order, *riders = jax.lax.sort(
        [keys, slots] + payload, num_keys=1, is_stable=True)
    rank = jnp.zeros(nL, jnp.int32).at[order].set(
        slots, unique_indices=True)
    my_rank = jax.lax.dynamic_slice_in_dim(
        rank, jax.lax.axis_index(KEY_AXIS) * L, L)
    columns = {k: _int32_words(v) for k, v in out.items()
               if k not in doubles}
    words = jax.lax.psum([
        jnp.zeros(nL, jnp.int32).at[my_rank].set(
            w[:, j], unique_indices=True)
        for w, _back in columns.values() for j in range(w.shape[1])],
        KEY_AXIS)
    merged, riders, words = {}, iter(riders), iter(words)
    for k, v in out.items():
        if k in doubles:
            merged[k] = jnp.stack(
                [next(riders) for _ in range(doubles[k].shape[1])],
                axis=1).reshape((nL,) + v.shape[1:])
        else:
            w, back = columns[k]
            merged[k] = back(jnp.stack(
                [next(words) for _ in range(w.shape[1])], axis=1))
    return merged


def routed_step_for(runtime, side_key: Optional[str] = None):
    """Build (and return) the device-routed ``step3(state, cols, now)``
    for a runtime whose ``_route_layout`` is installed. ``side_key``
    selects one side of a JOIN runtime (the side's fused insert+probe
    step routes like any keyed step: both sides' rings are sharded by the
    partition key, so a routed row's probe surface — the other side's
    ring rows of ITS OWN key — is already local to its owner shard).
    The heavy lifting happens in one jitted ``shard_map``:

    ingress   rows enter B-sharded; each shard computes ``owner = key % n``
              for its slice, buckets rows per destination (per-pair quota
              ``rows_per_shard // n``; over-quota rows are counted, not
              silently dropped), and one dense ``all_to_all`` moves every
              bucket to its owner. Received rows arrive source-major, i.e.
              in original batch order.
    local     PK/GK columns are rewritten to per-shard local ids (PK by
              ``// n``; GK through the replicated LUT — distinct id
              spaces, so GK != PK is fine) and the shard steps its local
              ``[.., K/n]`` state.
    egress    the window/selector's emission-order key (``__okey__``,
              derived from the pre-exchange global row index) rides out.
              Shards ``all_gather`` the order keys and sort them once
              (stable; invalid rows last); each shard then places its
              OWN emitted rows at their positions in that order (one
              32-bit scatter a word) and the disjoint partial columns
              are summed across shards as integers; float64 columns,
              which the TPU cannot hand over as words, are gathered and
              ride that one sort as payload (``_ordered_merge``: columns
              are moved, never computed on). This ordered re-merge makes
              sharded output bit-identical to the unsharded run and
              leaves it replicated on every shard, with ``1/n`` of the
              rows moved a shard. The packed meta becomes
              ``[overflow, notify, count, route_overflow, rows_0..n-1]``
              (prefix-compatible with the unsharded ``[3]`` contract)."""

    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import (
        OKEY_KEY, PK_KEY, RIDX_KEY, VALID_KEY)
    from siddhi_tpu.ops.keyed_windows import int64_from_words, int64_words

    layout = runtime._route_layout
    n, Q = layout.n, layout.quota
    # the program's name on the device: its instrument_jit family and the
    # mesh's width. The name is part of JAX's compile-cache key and the
    # scopes below are not (debug info is stripped from the key), so a
    # program cached before it was traced in ``siddhi.route`` /
    # ``siddhi.merge`` would come back without them in a profiler trace
    routed_family = ("device_routed" + (f".{side_key}" if side_key else "")
                     + f"_x{n}")
    localK = layout.localK
    partitioned, use_lut = layout.partitioned, layout.use_lut
    # device instruments (observability/instruments.py): the inner step
    # appends its own slot lanes; the route wrapper adds the exchange
    # residual and aggregates the inner lanes across shards per each
    # slot's declared reduction. Captured at BUILD so the compiled meta
    # layout matches runtime.instrument_slots() exactly.
    ins_on = runtime._instruments_on()
    inner_slots = runtime._step_instrument_slots()
    if side_key is not None:
        side_step = runtime.build_side_step_fn(side_key)
        _ph = jnp.zeros((1,), bool)

        def step(state, cols, now):
            # probe placeholders are inert: both probe surfaces live
            # inside the sharded state (keyed rings)
            return side_step(state, {}, _ph, cols, now)
    else:
        step = runtime.build_step_fn()
    key_name = PK_KEY if partitioned else GK_KEY

    if n == 1:
        def one_dev(state, cols, luts, now):
            cols = dict(cols)
            with jax.named_scope(ROUTE_SCOPE):
                B = cols[VALID_KEY].shape[0]
                cols[RIDX_KEY] = jnp.arange(B, dtype=jnp.int64)
                rows = jnp.sum(cols[VALID_KEY], dtype=jnp.int64)
            st, out = step(state, cols, now)
            out = dict(out)
            meta = out.pop("__meta__")
            out.pop(OKEY_KEY, None)   # single shard: already in order
            with jax.named_scope(MERGE_SCOPE):
                parts = [meta[:3], jnp.zeros(1, jnp.int64), rows[None]]
                if ins_on:
                    parts.append(
                        jnp.full((1,), n * Q, jnp.int64) - rows[None])
                parts.append(meta[3:])    # inner step's instrument lanes
                out["__meta__"] = jnp.concatenate(parts)
            return st, out

        jitted = jax.jit(named_step(one_dev, routed_family),
                         donate_argnums=(0,))
        return _finish_routed_install(runtime, layout, jitted, side_key)

    axes = _routed_axes(runtime, layout, runtime._state)
    st_specs = jax.tree_util.tree_map(
        lambda ax: P(KEY_AXIS) if ax <= 0 else P(*([None] * ax), KEY_AXIS),
        axes)
    def wrapped(state, cols, luts, now):
        state = jax.tree_util.tree_map(
            lambda leaf, ax: leaf[0] if ax < 0 else leaf, state, axes)
        with jax.named_scope(ROUTE_SCOPE):
            me = jax.lax.axis_index(KEY_AXIS)
            valid = cols[VALID_KEY]
            Bl = valid.shape[0]
            ridx = (me.astype(jnp.int64) * Bl
                    + jnp.arange(Bl, dtype=jnp.int64))
            # owner shard per local row (invalid rows route nowhere)
            owner = jnp.where(valid, cols[key_name].astype(jnp.int64) % n,
                              jnp.int64(n))
            dest = jnp.arange(n, dtype=jnp.int64)[:, None]
            maskd = owner[None, :] == dest                    # [n, Bl]
            pos = jnp.cumsum(maskd.astype(jnp.int64), axis=1) - 1
            # per-ROW slot: each row has exactly one destination, so
            # every column scatters once at [Bl] cost (an [n*Bl]
            # broadcast-scatter here would n-fold the hot loop's scatter
            # bandwidth)
            owner_c = jnp.clip(owner, 0, n - 1).astype(jnp.int32)
            pos_row = jnp.take_along_axis(pos, owner_c[None, :], axis=0)[0]
            sendable = owner < n                              # valid rows
            sent_row = sendable & (pos_row < Q)
            route_ov = jnp.sum((sendable & ~sent_row).astype(jnp.int64))
            slot_row = jnp.where(sent_row, owner * Q + pos_row,
                                 jnp.int64(n * Q))

            def exch(col):
                # an int64 goes as its two 32-bit words, each through its
                # own bucket scatter and all_to_all: one two-plane scatter
                # gets no sorted path on the TPU (ops/keyed_windows.py).
                # A double cannot be split there and goes as it is
                if col.dtype == jnp.int64:
                    return int64_from_words(*map(exch, int64_words(col)))
                buf = jnp.zeros((n * Q,) + col.shape[1:], col.dtype)
                buf = buf.at[slot_row].set(col, mode="drop")
                return jax.lax.all_to_all(
                    buf, KEY_AXIS, split_axis=0, concat_axis=0, tiled=True)

            rcols = {k: exch(v) for k, v in cols.items()}
            rcols[RIDX_KEY] = exch(ridx)
            rows_here = jnp.sum(rcols[VALID_KEY], dtype=jnp.int64)
            # global -> per-shard local ids (two separate dense spaces)
            if partitioned:
                pk = rcols[PK_KEY]
                rcols[PK_KEY] = (
                    pk.astype(jnp.int64) // n).astype(pk.dtype)
            gk = rcols[GK_KEY]
            if use_lut:
                lut = luts[0]
                gl = lut[jnp.clip(gk.astype(jnp.int64), 0,
                                  lut.shape[0] - 1)]
                gl = jnp.clip(gl, 0, localK - 1)
            else:
                gl = gk.astype(jnp.int64) // n
            rcols[GK_KEY] = gl.astype(gk.dtype)

        st, out = step(state, rcols, now)
        out = dict(out)
        meta = out.pop("__meta__")
        with jax.named_scope(MERGE_SCOPE):
            okey = jnp.asarray(out.pop(OKEY_KEY), jnp.int64)
            valid_o = out[VALID_KEY]
            okey = jnp.where(valid_o, okey, _ROUTE_BIG)
            # local -> global ids on the emitted rows
            if partitioned and PK_KEY in out:
                pko = out[PK_KEY]
                out[PK_KEY] = (pko.astype(jnp.int64) * n
                               + me.astype(jnp.int64)).astype(pko.dtype)
            if GK_KEY in out:
                gko = out[GK_KEY]
                if use_lut:
                    inv = luts[1]
                    gg = inv[me, jnp.clip(gko.astype(jnp.int64), 0,
                                          localK - 1)]
                else:
                    gg = gko.astype(jnp.int64) * n + me.astype(jnp.int64)
                out[GK_KEY] = gg.astype(gko.dtype)
            # ordered re-merge by the global emission-order key (invalid
            # rows sort last, exactly like _order_emit does within one
            # step)
            merged = _ordered_merge(okey, out, n)
            ov = jax.lax.psum(meta[0], KEY_AXIS)
            ntb = jnp.where(meta[1] < 0, _ROUTE_BIG, meta[1])
            # 64-bit min/max across shards go through all_gather: the
            # TPU lowers an s64 all-reduce only for Sum ("UNIMPLEMENTED:
            # Supported lowering only of Sum all reduce" on pmin/pmax)
            nt = jnp.min(jax.lax.all_gather(ntb, KEY_AXIS))
            nt = jnp.where(nt >= _ROUTE_BIG, jnp.int64(-1), nt)
            cnt = jax.lax.psum(meta[2], KEY_AXIS)
            rov = jax.lax.psum(route_ov, KEY_AXIS)
            rows = jax.lax.all_gather(rows_here, KEY_AXIS)
            parts = [jnp.stack([ov, nt, cnt, rov]),
                     rows.astype(jnp.int64)]
            if ins_on:
                # exchange residual: receive capacity left on the FULLEST
                # shard this batch (0 = one more skewed batch overflows)
                parts.append(jnp.full((1,), n * Q, jnp.int64)
                             - jnp.max(rows).astype(jnp.int64)[None])
            # inner step's instrument lanes, aggregated per declared
            # reduce
            # (sum for shard-owned counts, max for fill levels)
            lane = 3
            for slot in inner_slots:
                v = meta[lane:lane + slot.width]
                lane += slot.width
                parts.append(
                    jnp.max(jax.lax.all_gather(v, KEY_AXIS), axis=0)
                    if slot.reduce == "max"
                    else jax.lax.psum(v, KEY_AXIS))
            merged["__meta__"] = jnp.concatenate(parts)
        st = jax.tree_util.tree_map(
            lambda leaf, ax: jnp.asarray(leaf)[None] if ax < 0 else leaf,
            st, axes)
        return st, merged

    sharded = jax.shard_map(
        wrapped, mesh=layout.mesh,
        in_specs=(st_specs, P(KEY_AXIS), P(), P()),
        out_specs=(st_specs, P()),
        check_vma=False,
    )
    jitted = jax.jit(named_step(sharded, routed_family),
                     donate_argnums=(0,))
    return _finish_routed_install(runtime, layout, jitted, side_key)


def _finish_routed_install(runtime, layout: RouteLayout, jitted,
                           side_key: Optional[str] = None):
    key = f"query.{runtime.name}.routed_step" + (
        f".{side_key}" if side_key else "")
    tel = getattr(runtime.app_context, "telemetry", None)
    if tel is not None:
        jitted = tel.instrument_jit(
            jitted, key,
            family="device_routed" + (f".{side_key}" if side_key else ""),
            cache_extra=str(layout.mesh))

    def step3(state, cols, now):
        return jitted(state, cols, layout.device_luts(), now)

    step3._key = key
    step3._routed_raw = jitted    # hlo_audit lowers through this
    step3._layout = layout
    return step3


def prepare_routed_batches(runtime, cols):
    """Host side of the device-routed dispatch: pad the batch to a
    multiple of the shard count, pre-check the per-(src, dst) exchange
    quotas, and SPLIT oversized batches in half until every piece fits —
    feasible splitting replaces the old router's hard ``shard overflow``
    death. Also records the shard-skew gauges and the (now tiny)
    host-side exchange-prep histogram. Returns a list of column dicts to
    dispatch in order."""
    import time as _time

    from siddhi_tpu.core.plan.selector_plan import GK_KEY
    from siddhi_tpu.ops.expressions import PK_KEY, VALID_KEY

    layout = runtime._route_layout
    t0 = _time.perf_counter()
    n, quota = layout.n, layout.quota
    cols = {k: np.asarray(v) for k, v in dict(cols).items()}
    key_name = PK_KEY if layout.partitioned else GK_KEY

    def pad_to_mult(c):
        B = c[VALID_KEY].shape[0]
        if B % n == 0:
            return c
        pad = n - B % n
        return {k: np.concatenate(
            [v, np.zeros((pad,) + v.shape[1:], v.dtype)]) for k, v in c.items()}

    pieces = []

    def emit(c):
        c = pad_to_mult(c)
        B = c[VALID_KEY].shape[0]
        Bl = B // n
        valid = c[VALID_KEY].astype(bool)
        key = c[key_name].astype(np.int64)
        src = np.arange(B) // Bl
        pair = (src * n + key % n)[valid]
        counts = np.bincount(pair, minlength=n * n)
        if int(counts.max(initial=0)) <= quota or B <= n:
            pieces.append(c)
            return
        half = max((B // 2 // n) * n, n)
        emit({k: v[:half] for k, v in c.items()})
        emit({k: v[half:] for k, v in c.items()})

    emit(cols)
    dest_rows = np.bincount(
        (np.asarray(cols[key_name], np.int64) % n)[
            np.asarray(cols[VALID_KEY], bool)], minlength=n)
    _record_route_telemetry(
        getattr(runtime.app_context, "telemetry", None), runtime.name,
        dest_rows, (_time.perf_counter() - t0) * 1000.0)
    return pieces


def ensure_routed_capacity(runtime) -> None:
    """Routed analog of ``QueryRuntime._ensure_capacity``: grow per-shard
    capacities when the GLOBAL key population outgrows ``n * localK`` /
    ``n * local_win`` — or when key skew overfills one shard's slice of
    the group-key space — re-laying the live state out via its canonical
    form."""
    layout = runtime._route_layout
    n = layout.n
    needed_sel = runtime._needed_sel_keys()
    needed_win = (runtime.partition_ctx.num_keys()
                  if runtime.partition_ctx is not None else 1)
    fits = layout.sync_gk(runtime.keyer)
    grow_sel = needed_sel > n * layout.localK or not fits
    grow_win = layout.partitioned and needed_win > n * layout.local_win
    if not (grow_sel or grow_win):
        return
    canonical = (canonical_route_state(runtime)
                 if runtime._state is not None else None)
    Kg = n * layout.localK
    while needed_sel > Kg:
        Kg *= 2
    Wg = n * layout.local_win if layout.partitioned else 1
    while layout.partitioned and needed_win > Wg:
        Wg *= 2
    overloaded = getattr(runtime.app_context, "overload", None) is not None
    if overloaded and canonical is not None:
        # device-memory budget gate (resilience/overload.py): routed
        # growth re-lays the whole state out at the grown global
        # capacity — deny BEFORE allocating n shards' worth of it
        from siddhi_tpu.core.util.statistics import pytree_nbytes
        from siddhi_tpu.resilience.overload import ensure_memory_budget

        ratio = max(Kg / max(n * layout.localK, 1),
                    (Wg / max(n * layout.local_win, 1)
                     if layout.partitioned else 1.0))
        ensure_memory_budget(
            runtime.app_context, f"query.{runtime.name}",
            int(pytree_nbytes(canonical) * ratio),
            what=f"query '{runtime.name}' routed key-capacity growth "
                 f"({n * layout.localK}->{Kg} global keys)")
    if canonical is None:
        _install_routed(runtime, layout, canonical, Kg, Wg)
    else:
        # the plain path's span and counter (_ensure_capacity); the
        # re-layout goes through the host, so the device holds the old
        # state and the new one, and ``bytes_after`` is not known before
        with span("grow", query=runtime.name,
                  from_keys=runtime.key_capacity(), to_keys=max(Kg, Wg),
                  bytes_before=runtime.state_bytes()) as sp:
            _install_routed(runtime, layout, canonical, Kg, Wg)
        runtime.note_growth(sp.ms)
    if overloaded:
        from siddhi_tpu.core.util.statistics import pytree_nbytes
        from siddhi_tpu.resilience.overload import charge_memory

        charge_memory(runtime.app_context, f"query.{runtime.name}",
                      pytree_nbytes(runtime._state))


def adopt_canonical(runtime, sel_keys_g: int, win_keys_g: int) -> None:
    """Snapshot-restore hook: ``runtime._state`` currently holds CANONICAL
    state at the snapshot's global capacities (snapshots of routed
    runtimes are captured canonical — see ``canonical_route_state``);
    re-derive this runtime's shard-major layout from it. Works for any
    source layout: unsharded, or routed at a different shard count."""
    layout = runtime._route_layout
    canonical = None
    if runtime._state is not None:
        canonical = jax.tree_util.tree_map(
            np.asarray, jax.device_get(runtime._state))
    _install_routed(runtime, layout, canonical, sel_keys_g, win_keys_g)
