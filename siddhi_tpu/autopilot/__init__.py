"""Autopilot: a closed-loop controller over the engine's live knobs.

The engine measures everything — critical-path verdicts naming the
bottleneck stage (``observability/journey.py``), device-instrument
fills and shard skew, quota-utilization gauges, per-program jit-compile
counts — but every performance knob was still set by hand. This package closes the observe→decide→actuate loop
(ROADMAP item 4), generalizing PanJoin's adaptive repartitioning
across the whole engine while every re-merge it touches keeps the
ordered-emission discipline:

- ``signals.py``   read-only snapshot of what the engine already
                   exports (no new device pulls — scrape discipline);
- ``policy.py``    rule/hysteresis layer: cooldowns, per-knob bounds,
                   oscillation damping, compile-storm backoff;
- ``actuators.py`` the declared ``ACTUATORS`` registry (graftlint R7:
                   every actuator names a typed knob from
                   ``core/util/knobs.py``, bidirectionally);
- ``controller.py`` the per-process controller thread, bounded
                   decision log, ``GET /autopilot`` report and the
                   ``siddhi_autopilot_*`` telemetry.

Gated by the typed knob ``siddhi_tpu.autopilot`` — ``off`` (default)
is bit-identical to an engine without this package; ``dry_run``
decides and logs but never actuates. Actuation may change *when*
things run, never *what* is emitted.
"""

from siddhi_tpu.autopilot.actuators import ACTUATORS  # noqa: F401
from siddhi_tpu.autopilot.controller import (  # noqa: F401
    AutopilotController,
)
from siddhi_tpu.autopilot.policy import Policy, PolicyRule  # noqa: F401
from siddhi_tpu.autopilot.signals import SignalSnapshot, collect  # noqa: F401
