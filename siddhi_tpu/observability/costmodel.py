"""Compiled-program cost registry: what did XLA actually build?

ROADMAP item 2 (a process-wide compiled-program cache across tenant
apps) needs a BEFORE picture: how many programs does a fleet compile,
how many are duplicates, and what does each cost? ROADMAP item 3's
probe daemon needs a machine-readable device-cost capture wherever a
chip is attached. This registry is both: when enabled, the first
compile of every jit key (``telemetry.InstrumentedJit``) also captures

- ``compiled.cost_analysis()``  — flops + bytes accessed per execution,
- ``compiled.memory_analysis()``— argument/output/temp/code bytes
  (the XLA buffer-assignment peak picture),
- a **jaxpr fingerprint** — sha1 over the traced jaxpr text; two keys
  with equal fingerprints are structurally identical programs, i.e.
  candidates for the semantic-overlap dedup of "On the Semantic Overlap
  of Operators in Stream Processing Engines" (PAPERS.md). The fused
  fan-out dedup (PR 3) additionally proves constants/state equal before
  sharing — the fingerprint is the cheap superset estimate, so the
  duplicate clusters here bound the cross-app cache win from above.

Exported as ``jitcost.<key>.<metric>`` process gauges (rendered as the
``siddhi_jit_cost_*{key}`` families on ``GET /metrics``) and as JSON at
``GET /programs`` with fingerprint-duplicate clusters.

Cost of capture: tracing + ONE extra ahead-of-time XLA compile per
(key, first shape) — jax's jit cache and the AOT path do not share
executables, so profiling mode roughly doubles first-call compile
time. Steady-state throughput is untouched (capture runs once, before
the first execution, never on the hot path), but the default is OFF:
enable per app with ``siddhi_tpu.profile_costs: true``, process-wide
with ``SIDDHI_TPU_PROFILE_COSTS=1`` or ``POST /profile/costs/start``.
Capture happens BEFORE the first real call on purpose: the step jits
donate their state argument, and a post-call trace would read deleted
buffers.
"""

from __future__ import annotations

import hashlib
import logging
import threading
import time
from dataclasses import asdict, dataclass, field
from typing import Dict, List, Optional

log = logging.getLogger(__name__)

_enable_count = 0
_lock = threading.RLock()


def enabled() -> bool:
    if _enable_count > 0:
        return True
    # typed env read (knob discipline: junk spellings raise naming the
    # variable); re-checked per call so tests can flip it mid-process —
    # called once per first-compile, never on the steady hot path
    from siddhi_tpu.core.util.knobs import env_knob

    return bool(env_knob("SIDDHI_TPU_PROFILE_COSTS", "bool", False))


def enable() -> None:
    """Refcounted process-wide enable (one ``disable()`` per
    ``enable()``); the env spelling is an independent override."""
    global _enable_count
    with _lock:
        _enable_count += 1


def disable(force: bool = False) -> None:
    global _enable_count
    with _lock:
        _enable_count = 0 if force else max(0, _enable_count - 1)


@dataclass
class ProgramRecord:
    """One compiled program (per jit key; re-jits on capacity growth
    overwrite their key with the fresh shape's capture)."""

    key: str
    fingerprint: str            # sha1[:16] of the traced jaxpr text
    platform: str = ""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    code_bytes: int = 0
    compile_ms: float = 0.0     # the AOT capture compile (not the jit's)
    captures: int = 1           # how many times this key re-captured
    shared: bool = False        # analysis reused from an equal program
    error: Optional[str] = None
    extra: Dict[str, float] = field(default_factory=dict)


class CostRegistry:
    """Process-global program registry (``registry()``); the capture is
    fed by ``InstrumentedJit`` and read by ``GET /programs`` plus the
    ``siddhi_jit_cost_*`` exposition."""

    _GAUGE_METRICS = ("flops", "bytes_accessed", "arg_bytes", "out_bytes",
                      "temp_bytes", "code_bytes", "compile_ms")

    def __init__(self):
        self._lock = threading.RLock()
        self._programs: Dict[str, ProgramRecord] = {}

    # ------------------------------------------------------------ capture

    def capture(self, key: str, jitted, args, traced=None,
                shared: bool = False) -> Optional[ProgramRecord]:
        """Fingerprint + cost/memory analysis for one jitted callable
        about to run its first call. Never raises: a capture failure
        (non-jit callable, backend without analysis support) records the
        error and the engine runs on.

        ``traced`` reuses an AOT trace the program cache already made
        (one trace per first call, not two). ``shared=True`` means the
        callable is a program-cache HIT: the analysis is copied from an
        already-captured equal-fingerprint record instead of paying —
        and being double-counted as — a second AOT compile; only when
        no donor record exists (the donor app compiled with profiling
        off) does the capture fall through to a real AOT compile."""
        rec: Optional[ProgramRecord] = None
        try:
            if traced is None:
                trace = getattr(jitted, "trace", None)
                if trace is None:
                    return None     # not a jax.jit callable
                traced = trace(*args)
            fp = hashlib.sha1(
                str(traced.jaxpr).encode()).hexdigest()[:16]
            rec = ProgramRecord(key=key, fingerprint=fp)
            if shared:
                donor = self._donor(fp, key)
                if donor is not None:
                    for metric in self._GAUGE_METRICS:
                        setattr(rec, metric, getattr(donor, metric))
                    rec.platform = donor.platform
                    rec.compile_ms = 0.0    # no AOT compile happened
                    rec.shared = True
                    self._store(rec)
                    return rec
            t0 = time.perf_counter()
            compiled = traced.lower().compile()
            rec.compile_ms = (time.perf_counter() - t0) * 1000.0
            try:
                import jax

                rec.platform = jax.devices()[0].platform
            except Exception:  # noqa: BLE001 — label only
                pass
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                rec.flops = float(ca.get("flops", 0.0))
                rec.bytes_accessed = float(ca.get("bytes accessed", 0.0))
            ma = compiled.memory_analysis()
            if ma is not None:
                rec.arg_bytes = int(
                    getattr(ma, "argument_size_in_bytes", 0))
                rec.out_bytes = int(getattr(ma, "output_size_in_bytes", 0))
                rec.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0))
                rec.code_bytes = int(
                    getattr(ma, "generated_code_size_in_bytes", 0))
        except Exception as e:  # noqa: BLE001 — profiling must not break
            log.debug("cost capture failed for jit key '%s': %r", key, e)
            if rec is None:
                return None
            rec.error = repr(e)
        self._store(rec)
        return rec

    def _donor(self, fp: str, key: str) -> Optional[ProgramRecord]:
        """A clean already-captured record of the same fingerprint under
        a DIFFERENT key — the analysis source for a shared capture."""
        with self._lock:
            for rec in self._programs.values():
                if (rec.fingerprint == fp and rec.key != key
                        and rec.error is None):
                    return rec
        return None

    def _store(self, rec: ProgramRecord) -> None:
        with self._lock:
            prev = self._programs.get(rec.key)
            if prev is not None:
                rec.captures = prev.captures + 1
            self._programs[rec.key] = rec
        self._register_gauges(rec)

    def _register_gauges(self, rec: ProgramRecord) -> None:
        from siddhi_tpu.observability.telemetry import global_registry

        tel = global_registry()
        for metric in self._GAUGE_METRICS:
            # closure over the registry + key, not the record: a re-jit's
            # re-capture must be what the next scrape reads
            tel.gauge(f"jitcost.{rec.key}.{metric}",
                      lambda k=rec.key, m=metric: getattr(
                          self._programs.get(k), m, 0.0) or 0.0)

    # ------------------------------------------------------------ reading

    def programs(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._programs.values())

    def clusters(self) -> List[dict]:
        """Programs grouped by fingerprint, largest first — a cluster
        with more than one key is compiled more than once for (at least
        structurally) the same computation."""
        by_fp: Dict[str, List[str]] = {}
        for rec in self.programs():
            by_fp.setdefault(rec.fingerprint, []).append(rec.key)
        return [{"fingerprint": fp, "keys": sorted(keys),
                 "size": len(keys), "duplicates": len(keys) - 1}
                for fp, keys in sorted(by_fp.items(),
                                       key=lambda kv: (-len(kv[1]), kv[0]))]

    def snapshot(self) -> dict:
        """The ``GET /programs`` payload."""
        programs = sorted(self.programs(), key=lambda r: r.key)
        clusters = self.clusters()
        return {
            "enabled": enabled(),
            "programs": [asdict(r) for r in programs],
            "clusters": clusters,
            "unique_fingerprints": len(clusters),
            "duplicate_clusters": sum(1 for c in clusters if c["size"] > 1),
            "duplicate_programs": sum(c["duplicates"] for c in clusters),
        }

    def reset(self) -> None:
        with self._lock:
            self._programs.clear()


_REGISTRY = CostRegistry()


def registry() -> CostRegistry:
    return _REGISTRY
