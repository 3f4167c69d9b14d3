"""Serving-wide observability: tracing, metrics, health verdicts, exporters.

Low-overhead instrumentation for the serving stack (gateway, slot batcher,
paged KV adapter, sharded router):

  tracer.py     per-request lifecycle spans over the virtual serving clock
                or a wall clock (arrival -> queue wait -> prefill chunks ->
                decode ticks -> migration -> completion), each completion
                carrying a stage-attributed energy breakdown that sums
                *bitwise* to the conserved telemetry ledger; scoped spans
                (the tick and its host phases) mirrored to the profiler.
  metrics.py    named counters / gauges / histograms (capped reservoir
                retention, explicit ``n_dropped``) with periodic interval
                snapshots — occupancy-over-time curves instead of end-only
                aggregates.
  slo.py        SLO policy + Google-SRE multi-window burn-rate engine:
                ok/warn/critical health state machine over the serving
                clock, trace instants at transitions, burn-rate series
                columns, and the subscribable ``PressureSignal`` the
                gateway backpressure path (and the future bit-width
                degradation controller) consumes.
  costmodel.py  per-stage roofline attribution: XLA ``cost_analysis()``
                FLOPs/bytes over the ``cost_args()`` registries, joined
                with measured span durations into achieved rates and
                compute- vs memory-bound verdicts, cross-checked against
                the energy ledger.
  export.py     Chrome trace-event (Perfetto-loadable) JSON export with
                bounded ``max_events``, an incremental JSONL span-stream
                writer, a JSONL metrics dump, an OpenMetrics text
                exposition, and structural validators for all of them.
  recompile.py  jit-cache-entry accounting per compiled executable; flags
                steady-state recompiles as a metric.
  flight.py     always-on bounded ring buffer over the trace stream
                (reservoir-sampled spans, exact instant/counter/sample
                tails) — the cheap ever-running recorder incident bundles
                snapshot.
  critpath.py   per-request critical-path attribution: the request span
                split exactly (float-equal re-fold) into queue / prefill /
                handoff / decode / migration segments, aggregated into a
                which-stage-dominates-p99 ranking (per role under a
                RolePlan).
  incident.py   trigger -> bundle forensics pipeline: SLO warn->critical,
                drop bursts, recompile leaks, energy-conservation breaks
                and explicit captures snapshot the flight ring + gateway
                debug state into schema-validated, size-bounded JSON
                bundles, inspectable offline via
                ``python -m repro.serve.obs.incident``.

The contract every instrumented hot path keeps: **disabled observability
costs zero Python-level callbacks** — call sites guard on
``tracer/slo is None`` and the module-level :func:`callback_count` (which
every obs entry point charges, SLO and costmodel included) lets tests pin
that the guards really short-circuit (tests/test_obs.py, tests/test_slo.py).
"""
from repro.serve.obs.metrics import MetricsRegistry
from repro.serve.obs.recompile import RecompileDetector
from repro.serve.obs.tracer import (ENGINE_PID, NO_SPAN, REQUESTS_PID,
                                    SimClock, Tracer, WallClock,
                                    callback_count)
from repro.serve.obs.flight import FlightRecorder
from repro.serve.obs import critpath
from repro.serve.obs.critpath import (aggregate_critical_paths,
                                      analyze_critical_paths)

# incident.py is also the CLI module (`python -m repro.serve.obs.incident`);
# importing it eagerly here would double-load it under -m (runpy warns), so
# its names resolve lazily on first attribute access (PEP 562)
_INCIDENT_NAMES = ("IncidentCapture", "load_incident_bundle",
                   "validate_incident_bundle", "write_incident_bundle")


def __getattr__(name: str):
    if name in _INCIDENT_NAMES:
        from repro.serve.obs import incident
        return getattr(incident, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
from repro.serve.obs.slo import (BurnWindow, PressureEvent, PressureSignal,
                                 SLObjective, SLOMonitor, SLOPolicy)
from repro.serve.obs.costmodel import (DEFAULT_RIDGE, analyze, attribute,
                                       span_for, stage_energy)
from repro.serve.obs.export import (SpanStreamWriter, chrome_trace,
                                    openmetrics_text, read_span_stream,
                                    validate_chrome_trace,
                                    validate_openmetrics,
                                    write_chrome_trace, write_metrics_jsonl,
                                    write_openmetrics)

__all__ = [
    "ENGINE_PID", "MetricsRegistry", "RecompileDetector", "REQUESTS_PID",
    "NO_SPAN", "SimClock", "Tracer", "WallClock", "callback_count",
    "BurnWindow", "PressureEvent", "PressureSignal", "SLObjective",
    "SLOMonitor", "SLOPolicy",
    "DEFAULT_RIDGE", "analyze", "attribute", "span_for", "stage_energy",
    "SpanStreamWriter", "chrome_trace", "openmetrics_text",
    "read_span_stream", "validate_chrome_trace", "validate_openmetrics",
    "write_chrome_trace", "write_metrics_jsonl", "write_openmetrics",
    "FlightRecorder", "critpath",
    "aggregate_critical_paths", "analyze_critical_paths",
    "IncidentCapture", "load_incident_bundle", "validate_incident_bundle",
    "write_incident_bundle",
]
