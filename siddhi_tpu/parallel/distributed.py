"""Multi-host initialization: the DCN-facing half of the comm backend.

The reference scales out with NCCL/MPI-style transports; the TPU-native
equivalent is ``jax.distributed``: every host runs the same program,
``initialize_cluster`` joins them into one JAX process group, and
``global_mesh`` spans EVERY host's devices in one 1-D key mesh. The same
``NamedSharding``s used single-host (``parallel/mesh.py``) then shard key
state across hosts — XLA routes collectives over ICI within a slice and
DCN across slices; nothing else in the framework changes.

Usage (identical program on each host)::

    from siddhi_tpu.parallel.distributed import initialize_cluster, global_mesh
    initialize_cluster(coordinator_address="host0:8476",
                       num_processes=4, process_id=HOST_RANK)
    mesh = global_mesh()
    shard_query_step(runtime, mesh)
"""

from __future__ import annotations

from typing import Optional

from siddhi_tpu.parallel.mesh import KEY_AXIS


def initialize_cluster(coordinator_address: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None,
                       max_missing_heartbeats: Optional[int] = None) -> None:
    """Join this process into the cluster (``jax.distributed.initialize``);
    with no arguments, cluster-environment auto-detection applies.

    ``max_missing_heartbeats`` bounds how long the coordination service
    waits before declaring a silent peer dead — at which point it
    propagates an error that TERMINATES every healthy task. It counts
    missed 10 s heartbeats (jax's default, 10, is its 100 s
    ``heartbeat_timeout_seconds``). A supervised deployment
    (``resilience/supervisor.py``) that wants to recover in place rather
    than be torn down should raise it; the supervisor's own peer monitor
    and the bounded device pull provide the (much faster) failure
    detection instead."""
    import jax

    kwargs = {}
    if max_missing_heartbeats is not None:
        kwargs["heartbeat_timeout_seconds"] = 10 * int(max_missing_heartbeats)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kwargs,
    )


def global_mesh(axis_name: str = KEY_AXIS):
    """1-D mesh over every device of every process (DCN+ICI spanning)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (axis_name,))


def process_info() -> dict:
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


class ClusterPeerError(RuntimeError):
    """A multi-process device pull did not complete within the configured
    timeout — a peer process is presumed dead or unreachable.

    The reference surfaces transport failures through source retry /
    OnError hooks (``stream/input/source/Source.java:155-185``); the
    TPU-native failure mode is different: a peer dying mid-collective
    leaves every other host BLOCKED inside XLA, so the detection has to
    be a bounded wait around the device pull. Raised inside the
    junction's delivery path, this error rides the same ``@OnError`` /
    fault-stream machinery as any other processing failure.

    TERMINAL for the runtime: the timed-out pull leaves a leaked thread
    parked on the device stream, so retrying (or stepping the runtime
    again) only stacks more leaked threads — ``guarded_pull`` counts
    them (``cluster.outstanding_pulls`` gauge) and fails fast at its
    cap. Recovery story: tear the runtime down, restart the cluster with
    the surviving hosts (new ``jax.distributed`` incarnation), and
    ``restore_last_revision()`` from the persistence store — snapshots
    are host-side and replicated, so any surviving host can restore."""


def local_survivor_mesh(axis_name: str = KEY_AXIS):
    """1-D mesh over THIS process's devices only — the shape a survivor
    rebuilds on after a peer death, when re-forming the full cluster is
    not (yet) possible. State restored from the replicated snapshot store
    re-shards onto it transparently (same NamedSharding specs, smaller
    device set)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.local_devices()), (axis_name,))


# Fault-injection slot (resilience/faults.py): when set, every
# guarded_pull consults it BEFORE waiting — ``FaultInjector.drop_peer``
# installs a hook that raises ClusterPeerError immediately, simulating a
# dead peer without waiting out the pull timeout. Never set in production.
_fault_hook = None

# Leaked-pull accounting: every timeout abandons a daemon thread parked
# in an un-cancellable XLA host wait. The count of still-outstanding
# pulls is exported as a process gauge (``cluster.outstanding_pulls`` on
# GET /metrics), and bounded by ``_MAX_OUTSTANDING_PULLS`` — reaching
# the cap means the caller kept stepping a runtime that ClusterPeerError
# already declared dead (see guarded_pull's docstring: the error is
# TERMINAL), and further pulls fail fast instead of stacking threads.
_MAX_OUTSTANDING_PULLS = 32
_outstanding_pulls = 0
_pull_lock = None    # created lazily (threading import stays function-local)


def outstanding_pulls() -> int:
    """Device pulls currently in flight or abandoned-but-parked (leaked
    native waits from timed-out guarded_pull calls)."""
    return _outstanding_pulls


def _register_pull_gauge():
    from siddhi_tpu.observability.telemetry import global_registry

    global_registry().gauge("cluster.outstanding_pulls", outstanding_pulls)


_register_pull_gauge()


def guarded_pull(value, timeout_s: float, what: str = "cluster step"):
    """``np.asarray(value)`` bounded by ``timeout_s``.

    The wait runs in a daemon thread; on timeout the caller gets a
    labeled ``ClusterPeerError`` immediately (the stuck native wait stays
    parked in the abandoned thread — XLA host calls are not cancellable,
    but the PROGRAM regains control, which is the part that matters for
    failure detection).

    ``ClusterPeerError`` is TERMINAL for the runtime that raised it: the
    abandoned thread still owns the device stream, so retrying the pull
    (or stepping the same runtime again) can only stack more leaked
    threads behind a dead collective. The supported recovery is the
    supervisor's peer protocol — abandon the runtime, rebuild on
    ``local_survivor_mesh()``, restore the last revision, replay the WAL
    (``resilience/supervisor.py``). Outstanding pulls are counted on the
    ``cluster.outstanding_pulls`` gauge and capped at
    ``_MAX_OUTSTANDING_PULLS``; at the cap, guarded_pull fails fast."""
    import threading

    import numpy as np

    global _pull_lock, _outstanding_pulls
    if _pull_lock is None:
        _pull_lock = threading.Lock()

    if _fault_hook is not None:
        _fault_hook(what)

    with _pull_lock:
        if _outstanding_pulls >= _MAX_OUTSTANDING_PULLS:
            raise ClusterPeerError(
                f"{what}: {_outstanding_pulls} device pulls already "
                f"outstanding (cap {_MAX_OUTSTANDING_PULLS}) — earlier "
                f"ClusterPeerErrors were terminal; abandon this runtime "
                f"and run the peer-recovery protocol instead of retrying")
        _outstanding_pulls += 1

    box = {}
    done = threading.Event()

    def wait():
        global _outstanding_pulls
        try:
            # explicit device_get: guarded_pull is a sanctioned pull
            # point (the sanitizer transfer guard allows explicit only)
            import jax

            box["v"] = np.asarray(jax.device_get(value))
        except Exception as ex:  # surfaced to the caller below
            box["e"] = ex
        finally:
            with _pull_lock:
                _outstanding_pulls -= 1
            done.set()

    t = threading.Thread(target=wait, daemon=True,
                         name="siddhi-cluster-pull")
    t.start()
    if not done.wait(timeout_s):
        raise ClusterPeerError(
            f"{what} did not complete within {timeout_s:.1f}s — a cluster "
            f"peer process is presumed dead; this error is terminal for "
            f"the runtime: abandon it, restart the cluster and restore "
            f"from the last snapshot revision")
    if "e" in box:
        raise box["e"]
    return box["v"]
