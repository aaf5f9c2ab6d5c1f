"""Runtime sanitizers, gated by ``SIDDHI_TPU_SANITIZE=1``.

Four detectors for the bug classes graftlint checks statically, armed
at runtime so CI and quick checks catch what escapes the AST:

1. **Host-transfer detection.** ``jax.transfer_guard`` is set to
   ``disallow`` for implicit device->host transfers (explicit
   ``jax.device_get`` — the engine's sanctioned batched pull — stays
   allowed). On the CPU backend jax's guard is inert (arrays alias host
   memory), so a portable shim additionally patches the device array's
   scalar coercions (``float()``/``int()``/``bool()``/``.item()`` — the
   exact R5 pattern set) to raise ``HostPullError`` outside an
   ``allowed_pull()`` scope.

2. **Post-warmup recompile watchdog.** ``InstrumentedJit``
   (observability/telemetry.py) tracks the wrapped jitted callable's
   compile-cache size per call; once a key exceeds its compile budget
   (``SIDDHI_TPU_SANITIZE_MAX_COMPILES``, default 8 — pow2 padding
   means a healthy step sees a handful of shapes), or ANY cache miss
   lands after ``freeze_compiles()``, a ``RecompileError`` names the
   jit key. Compile storms (a recompile per batch) fail loudly instead
   of showing up as p99.

3. **Lock-order assertions.** ``analysis.locks.make_lock`` returns
   ``CheckedRLock``s that enforce the partial order declared in
   ``analysis/lockorder.py`` per thread, per acquisition.

4. **Lock-coverage (guarded-by) assertions.** ``analysis.guards``
   installs a data descriptor per field a class declares in its
   ``GUARDED_BY`` map (the static half is graftlint R8): every
   read/write asserts via the ``CheckedRLock`` per-thread holdings that
   a lock of the guarding rank is held, raising ``GuardViolation``
   otherwise. Plain attributes when off — zero cost.

Enable with ``SIDDHI_TPU_SANITIZE=1`` in the environment BEFORE
importing siddhi_tpu (the lock factory and jit proxies read it at
construction). ``tools/quick_all.py sanitize`` runs the quick-check
tier under it.
"""

from __future__ import annotations

import os
import threading

_ENV = "SIDDHI_TPU_SANITIZE"
_ENV_MAX_COMPILES = "SIDDHI_TPU_SANITIZE_MAX_COMPILES"


class HostPullError(RuntimeError):
    """A device value was coerced to a host scalar outside a sanctioned
    pull site (the R5 no-host-pull-in-hot-path bug class)."""


class RecompileError(RuntimeError):
    """A jitted step recompiled past its warmup budget."""


def enabled() -> bool:
    return os.environ.get(_ENV, "").strip().lower() in ("1", "true", "on",
                                                        "yes")


def max_compiles() -> int:
    # typed read: a junk spelling raises naming the variable instead of
    # silently falling back to the default (the R2 discipline)
    from siddhi_tpu.core.util.knobs import env_knob

    return env_knob(_ENV_MAX_COMPILES, "int", 8)


# ----------------------------------------------------------- pull guard

_TLS = threading.local()
_PATCHED = [False]


class allowed_pull:
    """Scope marker for sanctioned host pulls (snapshot capture, test
    assertions): scalar coercions inside do not raise."""

    def __enter__(self):
        _TLS.depth = getattr(_TLS, "depth", 0) + 1
        return self

    def __exit__(self, *exc):
        _TLS.depth -= 1
        return False


def _pull_allowed() -> bool:
    return getattr(_TLS, "depth", 0) > 0


def _install_pull_guard() -> None:
    """Patch the concrete jax array type's scalar coercions to raise
    outside ``allowed_pull()``. ``np.asarray``/``jax.device_get`` (the
    sanctioned batched pulls) are untouched; on non-CPU backends the
    jax transfer guard additionally covers implicit ``np.asarray``."""
    if _PATCHED[0]:
        return
    # class import only — materializing an array here would initialize
    # the backend at siddhi_tpu import (the R1 bug class)
    from jax._src.array import ArrayImpl as cls

    for name in ("__float__", "__int__", "__bool__", "item"):
        orig = getattr(cls, name)

        def guard(self, *args, __orig=orig, __name=name, **kw):
            # enabled() re-checked per call: the patch is process-wide
            # and must go inert when a test unsets the env var
            if enabled() and not _pull_allowed():
                raise HostPullError(
                    f"sanitizer: host pull via {__name}() on a device "
                    f"array outside a sanctioned pull site — batch the "
                    f"transfer through jax.device_get (or wrap a cold-"
                    f"path read in analysis.sanitize.allowed_pull())")
            return __orig(self, *args, **kw)

        setattr(cls, name, guard)
    _PATCHED[0] = True


# ------------------------------------------------------ recompile guard

_FROZEN = [False]


def freeze_compiles() -> None:
    """Declare warmup over: from now on ANY jit cache miss raises
    ``RecompileError`` naming the key (tests pin this around a planted
    recompile; long-running soaks call it after their warm phase)."""
    _FROZEN[0] = True


def thaw_compiles() -> None:
    _FROZEN[0] = False


def compiles_frozen() -> bool:
    return _FROZEN[0]


def check_recompile(key: str, compiles: int) -> None:
    """Called by ``InstrumentedJit`` when the wrapped callable's compile
    cache grew. Raises past the per-key budget or after a freeze."""
    if not enabled():
        # an InstrumentedJit built while sanitize was on caches its slow
        # path, but after disable()/env-unset the watchdog must go inert
        # like the pull guard does
        return
    if _FROZEN[0]:
        raise RecompileError(
            f"sanitizer: jit key '{key}' recompiled after warmup "
            f"(freeze_compiles() active; compile #{compiles})")
    budget = max_compiles()
    if compiles > budget:
        raise RecompileError(
            f"sanitizer: jit key '{key}' compiled {compiles} times — "
            f"past the {_ENV_MAX_COMPILES}={budget} budget; a compile "
            f"per batch means a shape or dtype is not stabilizing "
            f"(check pow2 padding and weak types)")


# --------------------------------------------------------------- enable

def enable() -> None:
    """Arm every sanitizer this process supports. Idempotent; called at
    ``siddhi_tpu`` import when ``SIDDHI_TPU_SANITIZE=1``. Only
    configures jax (no backend init)."""
    import jax

    # implicit device->host transfers raise on accelerator backends;
    # explicit jax.device_get / device_put remain allowed
    jax.config.update("jax_transfer_guard_device_to_host", "disallow")
    _install_pull_guard()


def disable() -> None:
    """Disarm the jax-config side (tests that enable() mid-process call
    this in teardown; the pull-guard patch needs no undo — it re-checks
    ``enabled()`` per call and goes inert with the env var)."""
    import jax

    jax.config.update("jax_transfer_guard_device_to_host", "allow")
    thaw_compiles()
