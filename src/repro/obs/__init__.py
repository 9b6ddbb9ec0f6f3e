"""Observability: stage-attributed span tracing + telemetry export.

See :mod:`repro.obs.tracer` for the SpanTracer / stage tree /
Chrome-trace export and :mod:`repro.obs.prom` for Prometheus text
exposition, and :func:`map_clock` puts spans on a profiler's clock.
Enabled per-system via ``TaijiConfig.obs`` (``ObsConfig(enabled=True)``;
the elastic KV cache's ``make_kv_taiji_config`` turns it on); disabled
(``ObsConfig``'s default) costs one ``is not None`` branch per
instrumented call site.
"""
from .prom import render_prom
from .tracer import (
    SpanTracer,
    STAGES,
    STAGE_NAMES,
    aggregate,
    export_chrome,
    map_clock,
    stage_tree,
)

__all__ = [
    "SpanTracer", "STAGES", "STAGE_NAMES",
    "aggregate", "export_chrome", "map_clock", "stage_tree", "render_prom",
]
