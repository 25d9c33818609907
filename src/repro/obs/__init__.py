"""Observability: CCT attribution, fabric tracing, structured logging.

Three answers to "where did the time go":

* `repro.obs.attribution` -- per-(instance, step, plane) CCT
  decomposition (transmit / bypass / exposed vs. hidden reconfiguration /
  idle) with a bitwise conservation guarantee, from both the vectorized
  engine (``batch_evaluate(..., attribution=True)``) and an object-walk
  oracle (``attribute(schedule)``); the derived *overlap efficiency*
  metric measures the paper's headline directly.
* `repro.obs.trace` -- span/counter instrumentation for the multi-tenant
  runtime behind a no-op default, exported as Chrome trace-event JSON
  (Perfetto-loadable; pid ``fabric``, one thread row per plane).
* `repro.obs.log` -- the structured logger the examples and benchmark
  drivers use (``REPRO_LOG=`` plain | json | debug | quiet).
* `repro.obs.metrics` -- the live metrics substrate: typed Counter /
  Gauge / log-bucketed Histogram instruments with exact associative
  ``merge()``, a ``MetricsRegistry`` with Prometheus-text and JSON
  exporters, and the ``NULL_REGISTRY`` no-op default the runtime hot
  paths are instrumented against.
* `repro.obs.slo` -- per-tenant SLO monitors (deadline targets, windowed
  response-time quantiles via histogram merge, miss counters) layered on
  the metrics substrate.

See DESIGN.md sections 16 and 20.

Names are imported from their submodule on first use, so that importing
``repro.obs.trace`` (which `repro.core` does, for ``host_span``) imports
nothing of `repro.core`.
"""

from __future__ import annotations

import importlib

_EXPORTS = {
    "attribution": (
        "Attribution",
        "attribute",
        "build_attribution",
        "closing_idle",
        "component_sum",
        "step_barriers",
    ),
    "log": ("ENV_LOG", "ObsLogger", "get_logger"),
    "metrics": (
        "NULL_REGISTRY",
        "Counter",
        "Gauge",
        "Histogram",
        "MetricsRegistry",
        "NullRegistry",
        "validate_prometheus_text",
    ),
    "slo": ("SLOMonitor", "SLOTarget", "TenantSLO"),
    "trace": (
        "JOBS_LANE",
        "NULL_TRACER",
        "ChromeTracer",
        "NullTracer",
        "Tracer",
        "host_span",
        "request_scope",
        "trace_schedule",
        "validate_trace",
        "validate_trace_file",
    ),
}
_MODULE_OF = {name: mod for mod, names in _EXPORTS.items() for name in names}

__all__ = sorted(_MODULE_OF)


def __getattr__(name: str):
    if name not in _MODULE_OF:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(
        importlib.import_module(f"{__name__}.{_MODULE_OF[name]}"), name
    )
    globals()[name] = value
    return value


def __dir__() -> list[str]:
    return sorted(set(globals()) | set(__all__))
