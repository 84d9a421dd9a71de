"""Structured run telemetry: metrics, provenance, progress.

The reference pipeline reads its visibility off Dask's performance
reports and worker transfer logs (reference scripts/utils.py:166-231);
this package is the TPU port's equivalent substrate, designed so every
perf artifact this repo emits is *measured, attributed and auditable*:

* ``obs.metrics`` — a near-zero-overhead metrics registry (counters,
  gauges, stage timers with min/mean/max/p99). Disabled (the default)
  every instrumentation site costs a few attribute checks and one
  ``TraceAnnotation.is_enabled()`` call; enabled, each stage times the
  host wall clock. Optional JSONL event log + dict export with
  per-stage analytic FLOPs/MFU.
* Whenever a ``jax.profiler`` session records, every stage and every
  ``obs.trace`` span is also a ``jax.profiler.TraceAnnotation`` of the
  SAME name, whether or not the registry, tracer or recorder is on —
  one gate, in ``obs.trace`` — so profiles and host metrics index by
  one stage vocabulary on the profiler's clock.
* ``obs.manifest`` — the run-provenance record (device kind, SWIFTLY_*
  env knobs, git SHA, config hash, ``baseline_source``) stamped into
  every BENCH artifact, plus the artifact schema validator the
  ``bench.py --smoke`` leg runs.
* ``obs.trace`` — the hierarchical span tracer (run → bench leg →
  pass → column group → stage; serve request journeys on per-request
  tracks; HBM watermarks at span boundaries), exporting Chrome
  trace-event JSON loadable in Perfetto. Same shared-no-op discipline
  when disabled; every ``metrics.stage`` site doubles as a
  trace site through the bridge.
* ``obs.report`` — trace analysis: span trees, critical-path/self-time
  attribution (``scripts/trace_report.py``), journey decomposition,
  and the ``trace`` artifact-block schema check.
* ``obs.heartbeat`` — progress reporting for hour-scale runs
  (units/s, ETA) and incremental partial-artifact flushing so a killed
  run still leaves its finished legs on disk.
* ``obs.recorder`` — the always-on flight recorder: a bounded
  lock-light ring of fleet events (faults, ladder steps, breaker/lease
  flips, autoscale decisions, cache rolls) kept even with tracing OFF,
  dumped as a post-mortem bundle on `WorkerKilled`/`ShardLostError`/
  forced drain/SLO breach. ``SWIFTLY_RECORDER=1`` /
  ``SWIFTLY_RECORDER_SECONDS``.
* ``obs.tower`` — the fleet control tower: named telemetry sources
  merged into one ``fleet_telemetry`` block (per-replica breakdowns +
  fleet totals), windowed signals shared by the brownout ladder and
  autoscaler, and declarative SLOs evaluated with multi-window
  burn-rate rules into an ``alerts`` block.

Enable via ``SWIFTLY_METRICS=1`` (JSONL path in
``SWIFTLY_METRICS_JSONL``) / ``SWIFTLY_TRACE=1`` (Chrome JSON in
``SWIFTLY_TRACE_PATH``) or programmatically with
``metrics.enable(...)`` / ``trace.enable(path)``. See
docs/observability.md.
"""

from . import ledger, metrics, recorder, report, tower, trace
from .heartbeat import Heartbeat, PartialArtifactWriter
from .ledger import validate_plan_accuracy_artifact
from .manifest import (
    run_manifest,
    validate_artifact,
    validate_delta_artifact,
    validate_fleet_artifact,
    validate_mesh_artifact,
    validate_plan_artifact,
    validate_procfleet_artifact,
    validate_resilience_artifact,
    validate_serve_artifact,
    validate_vis_artifact,
)
from .report import (
    by_process,
    merge_traces,
    summarize_trace,
    validate_trace_artifact,
)
from .tower import (
    SLO,
    ControlTower,
    validate_alerts_artifact,
    validate_fleet_telemetry_artifact,
)

__all__ = [
    "ControlTower",
    "Heartbeat",
    "PartialArtifactWriter",
    "SLO",
    "by_process",
    "ledger",
    "merge_traces",
    "metrics",
    "recorder",
    "report",
    "run_manifest",
    "summarize_trace",
    "tower",
    "trace",
    "validate_alerts_artifact",
    "validate_artifact",
    "validate_delta_artifact",
    "validate_fleet_artifact",
    "validate_fleet_telemetry_artifact",
    "validate_mesh_artifact",
    "validate_plan_accuracy_artifact",
    "validate_plan_artifact",
    "validate_procfleet_artifact",
    "validate_resilience_artifact",
    "validate_serve_artifact",
    "validate_trace_artifact",
    "validate_vis_artifact",
]
