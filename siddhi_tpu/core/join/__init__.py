"""Device-resident join engine (see ``engine.py``).

``attach_join_engine`` is the single planner hook: it classifies a
freshly-built ``JoinQueryRuntime`` (engine-eligible / pipeline-eligible /
legacy), instantiates the engine for eligible shapes, and registers the
join observability surface (each mirrored side's
``siddhi_join_window_rows`` / ``siddhi_join_hot_key_rows`` gauges, the
``join.<query>.out_width`` and ``window.<query>.capacity`` gauges +
``siddhi_join_probe_ms`` / ``siddhi_join_insert_ms`` histograms,
exported by ``observability/export.py``)."""

from __future__ import annotations

from siddhi_tpu.core.join.engine import (  # noqa: F401 — public surface
    ENGINE_STATE_KEYS,
    SEQ_KEY,
    DeviceJoinEngine,
    engine_ineligibility,
    extract_join_keys,
    pipeline_ineligibility,
)


def attach_join_engine(rt, on_expr) -> None:
    """Classify ``rt`` and attach the device engine when eligible.
    Called by the planner right after the runtime is built; respects the
    ``siddhi_tpu.join_engine`` opt-out (``legacy`` keeps the synchronous
    reference path wholesale, including pipeline ineligibility — the
    bit-identity baseline ``tools/quick_join_check.py`` compares
    against)."""
    from siddhi_tpu.core.eligibility import ReasonCode as RC
    from siddhi_tpu.core.eligibility import reason

    rt.engine = None
    rt.engine_reason = engine_ineligibility(rt)
    rt.pipeline_reason = pipeline_ineligibility(rt)
    mode = str(getattr(rt.app_context, "join_engine", "device") or "device")
    if mode != "device":
        rt.engine_reason = rt.engine_reason or \
            reason(RC.DISABLED, "disabled (siddhi_tpu.join_engine=legacy)")
        rt.pipeline_reason = reason(RC.DISABLED,
                                    "siddhi_tpu.join_engine=legacy")
        return
    if rt.engine_reason is not None:
        return
    pspec = extract_join_keys(
        on_expr, rt.sides["left"], rt.sides["right"], rt.dictionary) \
        if on_expr is not None else None
    rt.engine = DeviceJoinEngine(rt, pspec)
    rt._instr_spec = None   # engine suffix (seq + matches) joins the spec
    _register_metrics(rt)


def _register_metrics(rt) -> None:
    tel = getattr(rt.app_context, "telemetry", None)
    if tel is None:
        return
    # pre-declare the per-query probe/insert histograms so the
    # siddhi_join_probe_ms / siddhi_join_insert_ms families exist on
    # /metrics from app start (export.py renders them as summaries)
    tel.histogram(f"join.probe_ms.{rt.name}")
    tel.histogram(f"join.insert_ms.{rt.name}")
    eng = rt.engine
    # the keyed probe's output width (the last step's program) and the
    # slots of a growing time-window ring
    tel.gauge(f"join.{rt.name}.out_width", lambda e=eng: float(e.last_width))
    if any(p.grows for p in eng.plans.values()):
        tel.gauge(f"window.{rt.name}.capacity",
                  lambda e=eng: float(e.ring_capacity))
    for side_key in eng.rows:
        # the live rows of each mirrored side, and of its most frequent
        # key (skew), from the host mirrors: a scrape never touches
        # device state
        tel.gauge(f"join.window_rows.{rt.name}.{side_key}",
                  lambda e=eng, s=side_key: float(e.window_rows(s)))
        tel.gauge(f"join.hot_key_rows.{rt.name}.{side_key}",
                  lambda e=eng, s=side_key: float(e.hot_key_rows(s)))
