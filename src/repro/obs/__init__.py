"""Unified observability plane (docs/observability.md).

One package shared by all three runtimes (the event-loop ``Cluster``
in both sim and engine flavors, the wall-clock ``AsyncCluster``) and
the fleet harness — zero-cost when off (the default: no tracer, a
disabled registry whose probes are only evaluated on demand), bounded
and benchmarked when on (the ``obs_overhead`` scenario in
``benchmarks/paged_serving.py`` gates tracing-on wall time).

  * ``Tracer``           — structured span/instant/counter records with
    JSONL and Chrome/Perfetto ``trace_event`` exporters; a run renders
    as a real timeline (instances as tracks, one row per request).
  * ``MetricsRegistry``  — counters / gauges / exact-percentile
    histograms plus pull-probes, snapshot-able mid-run; the single
    source of truth behind ``ClusterStallError`` diagnostics.
  * ``SLOSpec``          — DistServe-style TTFT/TBT attainment targets
    threaded through ``summarize()`` and ``FleetReport`` (goodput).
  * ``EventLoopProfiler`` — per-event-kind handler profiler (promoted
    from ``repro.fleet.profile``; hangs off ``Cluster.profiler``).
  * ``PhaseRecorder``    — step-phase spans of the wall-clock runtime's
    workers, mirrored into the JAX profiler as ``TraceAnnotation``s.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               observe_request)
from repro.obs.profile import EventLoopProfiler
from repro.obs.slo import SLOSpec, attainment, good_count, meets_slo
from repro.obs.tracer import (SCHEMA_VERSION, TERMINAL_EVENTS,
                              PhaseRecorder, Tracer, read_jsonl,
                              validate_chains, validate_jsonl_records,
                              validate_perfetto)

__all__ = [
    "Counter", "EventLoopProfiler", "Gauge", "Histogram",
    "MetricsRegistry", "PhaseRecorder", "SCHEMA_VERSION", "SLOSpec",
    "TERMINAL_EVENTS", "Tracer", "attainment", "good_count", "meets_slo",
    "observe_request", "read_jsonl", "validate_chains",
    "validate_jsonl_records", "validate_perfetto",
]
