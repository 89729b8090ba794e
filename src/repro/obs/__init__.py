"""repro.obs — observability: metrics pytrees, span tracing, JSONL sink.

Layering rule: this package (and everything imported here) is jax-free,
so ``repro.obs`` can be imported before jax is configured —
``launch/dryrun.py`` must set ``XLA_FLAGS`` before the first jax import.
The one jax-adjacent piece, ``repro.obs.metrics``, holds the pytree
definitions (itself jax-free; the arrays come from the caller).

Quick start::

    REPRO_OBS=basic  python ...   # JSONL events -> $REPRO_OBS_PATH
    REPRO_OBS=trace  python ...   # + host latency spans as JSONL events

    from repro import obs
    with obs.span("my.region", tag="x") as sp:
        ...
    obs.emit("metric", name="elbo", value=-1.23)

Spans are ``jax.profiler`` annotations at every level: under a profiler
trace (``jax.profiler.start_trace``) they sit on the host plane beside
the device ops, on the same clock.

See ``obs/sink.py`` for the event schema and README "Observability".
"""

from repro.obs.agg import (REGISTRY, MetricsRegistry, merge_snapshots,
                           quantile_from_snapshot)
from repro.obs.sink import (BASIC, EVENT_SCHEMA, OFF, TRACE, configure,
                            emit, emit_stream_events, enabled, level, log,
                            validate_obs_events)
from repro.obs.trace import current_span, span
from repro.obs.export import prometheus_text
from repro.obs.health import HealthTracker
from repro.obs.metrics import (DvmpMetrics, LocalStepMetrics,
                               StreamBatchMetrics, TemporalFitMetrics,
                               UpdateCounters)

__all__ = [
    "OFF", "BASIC", "TRACE", "EVENT_SCHEMA",
    "configure", "enabled", "level",
    "emit", "log", "span", "current_span",
    "emit_stream_events",
    "validate_obs_events",
    "REGISTRY", "MetricsRegistry", "merge_snapshots",
    "quantile_from_snapshot",
    "prometheus_text",
    "HealthTracker",
    "StreamBatchMetrics", "TemporalFitMetrics", "LocalStepMetrics",
    "DvmpMetrics", "UpdateCounters",
]
