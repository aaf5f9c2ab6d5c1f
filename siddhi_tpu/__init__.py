"""siddhi_tpu — a TPU-native streaming & Complex Event Processing framework.

A from-scratch re-design (NOT a port) of the capabilities of the reference
Siddhi engine (/root/reference, Java): SiddhiQL compiles to a columnar,
batched dataflow whose hot path is a fused JAX/XLA step function per query,
with per-key state held in dense ``[num_keys, ...]`` device arrays instead of
per-key heap objects behind thread-locals.

Public API surface mirrors the reference's (``SiddhiManager``
-> ``SiddhiAppRuntime`` -> ``InputHandler`` / ``StreamCallback`` /
``QueryCallback``; reference: siddhi-core ``SiddhiManager.java:49``,
``SiddhiAppRuntime.java``, ``stream/input/InputHandler.java``).
"""

# The window/NFA hot path swaps ring-buffer slots in place (gather old
# value, scatter new one into the SAME donated [K*W] buffer). XLA:CPU's
# default copy-insertion cannot prove the gather-before-scatter ordering
# and materializes two full-buffer copies per column per step (O(K*W)
# bytes — 33x slower at the bench shape); region analysis proves it.
# CPU-only flag, inert on TPU. Must be set before backend init.
import os as _os
import sys as _sys


def _jax_backend_initialized() -> bool:
    """True when the embedding application already initialized a JAX
    backend before importing siddhi_tpu — XLA_FLAGS set below are then
    inert (XLA parsed them at backend init)."""
    xb = _sys.modules.get("jax._src.xla_bridge")
    return xb is not None and bool(xb.backends_are_initialized())


_FLAG = "--xla_cpu_copy_insertion_use_region_analysis"
if _FLAG not in _os.environ.get("XLA_FLAGS", ""):
    # name-only check: an explicit user setting (either value) wins
    _os.environ["XLA_FLAGS"] = (
        _os.environ.get("XLA_FLAGS", "") + " " + _FLAG + "=true").strip()
    if _jax_backend_initialized():
        # the mutation came too late: the CPU backend already parsed its
        # flags, so the ring-swap fix (two full-buffer copies per window
        # column per step, 33x at the bench shape — see the comment
        # above) is silently OFF. Warn once so the regression cannot be
        # reintroduced unnoticed; see README "Observability" for the fix
        # (import siddhi_tpu before any jax computation, or set the flag
        # in the environment).
        import warnings as _warnings

        _warnings.warn(
            "siddhi_tpu: a JAX backend was initialized before importing "
            f"siddhi_tpu, so '{_FLAG}=true' cannot take effect — the "
            "XLA:CPU window/NFA ring-swap path will run up to 33x slower. "
            "Import siddhi_tpu before running any jax computation, or set "
            f"XLA_FLAGS={_FLAG}=true in the environment.",
            RuntimeWarning, stacklevel=2)

# Millisecond epoch timestamps need int64; enable x64 before any jax use.
import jax

jax.config.update("jax_enable_x64", True)

# SIDDHI_TPU_SANITIZE=1 arms the runtime sanitizers (transfer-guard
# host-pull detection, post-warmup recompile watchdog, lock-order
# assertions — siddhi_tpu/analysis/sanitize.py). Config-only: the
# backend is NOT initialized here (that being the R1 bug class).
from siddhi_tpu.analysis import sanitize as _sanitize

if _sanitize.enabled():
    _sanitize.enable()

__version__ = "0.1.0"

__all__ = [
    "SiddhiManager",
    "StreamCallback",
    "QueryCallback",
    "Event",
    "__version__",
]


def __getattr__(name):
    # Lazy to keep `import siddhi_tpu.compiler` light and cycle-free.
    if name == "SiddhiManager":
        from siddhi_tpu.core.manager import SiddhiManager
        return SiddhiManager
    if name == "StreamCallback":
        from siddhi_tpu.core.stream.output.stream_callback import StreamCallback
        return StreamCallback
    if name == "QueryCallback":
        from siddhi_tpu.core.query.callback import QueryCallback
        return QueryCallback
    if name == "Event":
        from siddhi_tpu.core.event import Event
        return Event
    raise AttributeError(name)
