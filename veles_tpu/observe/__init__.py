"""Unified telemetry layer: span tracing, metrics, profiler hooks.

One measurement substrate for the whole system (docs/observability.md),
replacing the three ad-hoc timer systems that grew organically: the
workflow's method/unit wall timers, pipeline_input's per-stage
perf_counter deltas, and the health watchdog's decision-unit-only lazy
device counters.  Three pieces:

- :mod:`veles_tpu.observe.trace` — a thread-safe span tracer with a
  context-manager + decorator API emitting Chrome trace-event JSON
  (loadable in Perfetto / chrome://tracing) with per-thread tracks and
  zero overhead when disabled;
- :mod:`veles_tpu.observe.metrics` — a registry of counters, gauges
  and windowed histograms (step-time percentiles, throughput, health
  counts, queue depths).  Device scalars enter the registry only at
  the EXISTING lazy-metric sync points (decision class end,
  snapshotter rollback, server quarantine) — the registry never adds a
  host sync to the hot path;
- :mod:`veles_tpu.observe.profile` — ``jax.profiler`` start/stop
  around a configurable step window (``VELES_PROFILE=dir`` /
  ``VELES_PROFILE_WINDOW=start:stop``) and the periodic JSONL
  heartbeat (``--metrics-interval N``) consumed by web_status.py
  dashboards and offline tooling.

Cluster scope (PR 5) adds four more:

- :mod:`veles_tpu.observe.flight` — the always-on black-box ring of
  recent events, dumped on divergence/rollback/quarantine/crash;
- :mod:`veles_tpu.observe.cluster` — NTP-style clock-offset
  estimation and the master-side collector for slave trace chunks;
- :mod:`veles_tpu.observe.merge` — per-process traces -> one
  offset-corrected Perfetto timeline (also ``python -m
  veles_tpu.observe merge``);
- :mod:`veles_tpu.observe.xla_introspect` — recompile counting,
  device-memory gauges, and the live ``mfu_pct`` from the compiled
  step's cost analysis (jax imported lazily, off the hot path).

The serve tier (PR 19) adds:

- :mod:`veles_tpu.observe.requests` — request-scoped serve tracing:
  trace ids, per-segment timelines, the tail-exemplar ring dumped on
  SLO violations, and the ``python -m veles_tpu.observe requests``
  critical-path analyzer.

The fleet telemetry plane (PR 20) adds the decisions layer:

- :mod:`veles_tpu.observe.timeseries` — fixed-interval bucket rings
  fed from the registry (counter->rate, gauge->last, histogram->
  mergeable digest), shipped as bounded chunks over the trace-chunk
  links, merged fleet-side with the PR 5 clock offsets
  (``FleetTelemetry``; ``python -m veles_tpu.observe fleet``);
- :mod:`veles_tpu.observe.alerts` — declarative multi-window
  burn-rate + EMA-spike alert rules over those series,
  edge-triggered with flight + exemplar evidence dumps.

Everything here is stdlib-only and import-light, so hot modules
(units, pipeline_input, compiler-adjacent code) can import it without
dragging in jax.
"""

from veles_tpu.observe.alerts import (ALERTS_SCHEMA_VERSION,
                                      AlertManager, BurnRateRule,
                                      EmaSpikeRule, alerts,
                                      default_rules)
from veles_tpu.observe.cluster import (TraceCollector, estimate_offset,
                                       probe_sample)
from veles_tpu.observe.flight import (FLIGHT_SCHEMA_VERSION,
                                      FlightRecorder, flight,
                                      validate_flight)
from veles_tpu.observe.metrics import (Counter, Gauge, Histogram,
                                       MetricsRegistry, health_snapshot,
                                       percentiles, registry)
from veles_tpu.observe.profile import (HEARTBEAT_SCHEMA_VERSION, Heartbeat,
                                       ProfilerHook, install_profiler,
                                       profiler_step, uninstall_profiler,
                                       validate_heartbeat)
from veles_tpu.observe.requests import (ExemplarRing, analyze_files,
                                        exemplars, mint_trace_id,
                                        normalize_trace_id,
                                        render_requests)
from veles_tpu.observe.timeseries import (SERIES_SCHEMA_VERSION,
                                          FleetTelemetry, SeriesRing,
                                          digest_percentiles,
                                          digest_values,
                                          fleet_summary,
                                          merge_digests, series)
from veles_tpu.observe.trace import (ANNOTATION_PREFIX,
                                     CHUNK_SCHEMA_VERSION, SpanTracer,
                                     instant, profiler_live, span,
                                     step_annotation, tracer,
                                     validate_trace)

__all__ = [
    "SpanTracer", "tracer", "span", "instant", "validate_trace",
    "profiler_live", "step_annotation", "ANNOTATION_PREFIX",
    "CHUNK_SCHEMA_VERSION",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
    "percentiles", "health_snapshot",
    "ProfilerHook", "install_profiler", "uninstall_profiler",
    "profiler_step", "Heartbeat", "validate_heartbeat",
    "HEARTBEAT_SCHEMA_VERSION",
    "FlightRecorder", "flight", "validate_flight",
    "FLIGHT_SCHEMA_VERSION",
    "TraceCollector", "estimate_offset", "probe_sample",
    "ExemplarRing", "exemplars", "mint_trace_id",
    "normalize_trace_id", "analyze_files", "render_requests",
    "SeriesRing", "FleetTelemetry", "series", "fleet_summary",
    "digest_values", "merge_digests", "digest_percentiles",
    "SERIES_SCHEMA_VERSION",
    "AlertManager", "BurnRateRule", "EmaSpikeRule", "alerts",
    "default_rules", "ALERTS_SCHEMA_VERSION",
]
