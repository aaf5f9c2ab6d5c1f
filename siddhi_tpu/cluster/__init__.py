"""Cluster fabric: the wire-speed multi-process distributed tier.

One **router process** owns ingest sequencing, key partitioning and
ordered egress; N **worker processes** each run a full single-process
engine over their key range; a **supervisor** respawns dead workers and
drives the PR-1 recovery protocol (restore last revision + replay the
router-side WAL suffix). See ``router.py`` for the architecture notes
and README "Cluster fabric" for the topology diagram.

Not ``jax.distributed``: plain-CPU XLA refuses multiprocess
computations (see tests/test_multihost.py skips), so the fabric is
plain sockets carrying the PR-13 zero-copy columnar wire format —
which also means it exercises REAL multicore parallelism on hosts
with no chip. A cluster worker runs its engine on the CPU backend
(``supervisor.py`` forces ``JAX_PLATFORMS=cpu``): it does not use the
chip today.
"""

from siddhi_tpu.cluster.egress import OrderedEgress
from siddhi_tpu.cluster.router import ClusterRuntime
from siddhi_tpu.cluster.supervisor import WorkerSupervisor

__all__ = ["ClusterRuntime", "OrderedEgress", "WorkerSupervisor"]
