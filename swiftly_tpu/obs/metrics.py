"""Per-stage metrics registry: counters, gauges, stage timers.

Design constraints, in order:

1. **Zero cost off.** The engine's hot loops call ``metrics.stage(...)``
   per dispatch; disabled (the default) that is three attribute checks,
   one ``TraceAnnotation.is_enabled()`` call and the return of a shared
   no-op context manager — no allocation, no clock read, no string
   work. A disabled run is indistinguishable from an uninstrumented one
   (< 1 us per site against multi-ms dispatches).
2. **One stage vocabulary.** Whenever a ``jax.profiler`` session
   records, each stage enters a ``jax.profiler.TraceAnnotation`` of the
   same name — with the registry, the tracer and the flight recorder on
   or off — so the host-side walls in ``export()`` and the device
   timeline of a profile (``utils.profiling.trace``) index by identical
   stage names. The stage opens its span through ``obs.trace``, the one
   place that decides whether to annotate.
3. **Honest attribution.** JAX dispatch is asynchronous: a host timer
   around a dispatch measures dispatch + backpressure, not device
   compute. The engine therefore instruments its *completion pulls* as
   their own ``*.drain`` stages; per-stage MFU (analytic FLOPs from
   ``utils.flops`` divided by host wall) is exact on synchronous
   backends (CPU tests) and a dispatch-side attribution on async
   runtimes — the run-level ``total`` block is always meaningful, and
   the trace holds the per-op device truth. docs/observability.md
   spells this out.

Stage timing keeps streaming aggregates (count/total/min/max) plus a
bounded sample ring for p99 (capacity 8192; beyond that, samples
overwrite round-robin — quantiles stay representative for the uniform
dispatch streams this engine emits). All mutation is lock-guarded:
``MemorySampler`` and heartbeat threads may record concurrently.
"""

from __future__ import annotations

import json
import os
import threading
import time

from . import recorder as _recorder
from . import trace as _trace

__all__ = [
    "MetricsRegistry",
    "enabled",
    "enable",
    "disable",
    "get_registry",
    "stage",
    "observe",
    "count",
    "gauge",
    "gauge_max",
    "event",
    "export",
    "reset",
]

_P99_RING = 8192  # per-stage sample capacity (see module docstring)


# The shared disabled-path context manager is the tracer's: attribute
# writes are swallowed, so call sites may set ``st.flops`` /
# ``st.bytes_moved`` inside the block (for values only known after the
# work) without branching on enablement.
_NULL_STAGE = _trace._NULL_SPAN


class _StageStats:
    __slots__ = (
        "count", "total_s", "min_s", "max_s", "flops", "bytes_moved",
        "samples", "_ring_i",
    )

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.max_s = 0.0
        self.flops = 0
        self.bytes_moved = 0
        self.samples = []
        self._ring_i = 0

    def add(self, wall_s, flops, bytes_moved):
        self.count += 1
        self.total_s += wall_s
        if wall_s < self.min_s:
            self.min_s = wall_s
        if wall_s > self.max_s:
            self.max_s = wall_s
        self.flops += flops
        self.bytes_moved += bytes_moved
        if len(self.samples) < _P99_RING:
            self.samples.append(wall_s)
        else:
            self.samples[self._ring_i] = wall_s
            self._ring_i = (self._ring_i + 1) % _P99_RING


def _quantile_sorted(s, q):
    if not s:
        return 0.0
    return s[min(len(s) - 1, int(q * len(s)))]


def _p99(samples):
    return _quantile_sorted(sorted(samples), 0.99)


class _Stage:
    """One enabled stage timing: host wall + a span of the same name.

    Also the metrics→trace bridge: each stage opens an ``obs.trace``
    span of the SAME name — a traced span when the span tracer is on, a
    bare profiler annotation while only a profiler session records, the
    no-op otherwise — so every instrumentation site in the engine feeds
    every system with one ``with`` block and the timelines use the
    documented stage vocabulary. A stage may run with the registry
    disabled (tracing only) — it then records no registry state."""

    __slots__ = ("_reg", "name", "flops", "bytes_moved", "_t0", "_tspan")

    def __init__(self, reg, name, flops, bytes_moved):
        self._reg = reg
        self.name = name
        self.flops = flops
        self.bytes_moved = bytes_moved

    def __enter__(self):
        self._tspan = _trace.span(self.name, cat="stage")
        self._tspan.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        wall = time.perf_counter() - self._t0
        if self.flops:
            self._tspan.set(flops=self.flops)
        if self.bytes_moved:
            self._tspan.set(bytes_moved=self.bytes_moved)
        self._tspan.__exit__(*exc)
        if self._reg.enabled:
            self._reg._record_stage(self.name, wall, self.flops,
                                    self.bytes_moved)
        rec = _recorder._RECORDER
        if rec.enabled:
            rec.record("stage", self.name, round(wall, 6))
        return False


class MetricsRegistry:
    """Counters, gauges and stage timers; a no-op unless enabled.

    One process-wide instance (``get_registry()``) serves the engine;
    independent instances are constructible for tests.
    """

    def __init__(self, enabled=False, jsonl_path=None):
        self._lock = threading.Lock()
        self._jsonl = None
        self._jsonl_path = None
        self._t_epoch = time.time()
        self._t0 = time.perf_counter()
        self.counters = {}
        self.gauges = {}
        self.gauges_max = {}
        self.stages = {}
        self.enabled = False
        if enabled:
            self.enable(jsonl_path)

    # -- lifecycle ---------------------------------------------------------

    def enable(self, jsonl_path=None):
        """Turn recording on; optionally start a JSONL event log."""
        with self._lock:
            self.enabled = True
            self._t_epoch = time.time()
            self._t0 = time.perf_counter()
            if jsonl_path:
                self._jsonl_path = str(jsonl_path)
                self._jsonl = open(self._jsonl_path, "a", buffering=1)
                self._emit({"kind": "open", "t_epoch": self._t_epoch})
        return self

    def disable(self):
        """Stop recording and close the event log (state is kept for
        export until ``reset()``)."""
        with self._lock:
            self.enabled = False
            if self._jsonl is not None:
                self._jsonl.close()
                self._jsonl = None

    def reset(self):
        """Drop all recorded state (counters, gauges, stages)."""
        with self._lock:
            self.counters = {}
            self.gauges = {}
            self.gauges_max = {}
            self.stages = {}
            self._t0 = time.perf_counter()
            self._t_epoch = time.time()

    # -- recording ---------------------------------------------------------

    def stage(self, name, flops=0, bytes_moved=0):
        """Context manager timing one stage execution.

        ``flops``/``bytes_moved`` are the dispatch's analytic compute
        and data-movement attribution (accumulated into the stage).
        Disabled this returns a shared no-op object immediately —
        unless the span tracer is on (the stage runs as a trace-only
        span, no registry state), the flight recorder is on (a
        recorder-only timer appends one ring event) or a profiler
        session records (a bare profiler annotation of the name).
        """
        if self.enabled or _trace._TRACER.enabled:
            return _Stage(self, name, flops, bytes_moved)
        if _recorder._RECORDER.enabled:
            return _recorder._RecorderStage(name)
        return _trace.span(name, cat="stage")

    def observe(self, name, wall_s, flops=0, bytes_moved=0):
        """Record an externally measured duration into a stage histogram.

        For durations the registry cannot bracket with ``stage(...)`` —
        e.g. a serving request's submit→completion latency, whose span
        crosses queueing, scheduling and dispatch. Lands in the same
        export/quantile machinery as timed stages.
        """
        if not self.enabled:
            return
        self._record_stage(name, wall_s, flops, bytes_moved)

    def count(self, name, n=1):
        if not self.enabled:
            return
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def gauge(self, name, value):
        if not self.enabled:
            return
        with self._lock:
            self.gauges[name] = value

    def gauge_max(self, name, value):
        """Peak-tracking gauge: keeps the MAX ever recorded, so
        watermark-style gauges (HBM peak, queue-depth high-water)
        survive ``export()`` on long runs instead of reporting
        whatever the last sample happened to be."""
        if not self.enabled:
            return
        with self._lock:
            cur = self.gauges_max.get(name)
            if cur is None or value > cur:
                self.gauges_max[name] = value

    def event(self, kind, **fields):
        """Append a free-form event to the JSONL log (no-op otherwise)."""
        if not self.enabled:
            return
        with self._lock:
            self._emit({"kind": kind, **fields})

    def _record_stage(self, name, wall_s, flops, bytes_moved):
        with self._lock:
            st = self.stages.get(name)
            if st is None:
                st = self.stages[name] = _StageStats()
            st.add(wall_s, flops, bytes_moved)
            self._emit(
                {
                    "kind": "stage",
                    "name": name,
                    "t_s": round(time.perf_counter() - self._t0, 6),
                    "wall_s": round(wall_s, 6),
                    "flops": flops,
                    "bytes": bytes_moved,
                }
            )

    def _emit(self, record):  # caller holds the lock
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(record) + "\n")

    # -- export ------------------------------------------------------------

    def export(self):
        """All recorded telemetry as one JSON-ready dict.

        Per stage: count, wall aggregates (total/min/mean/max/p99) and,
        where the instrumentation attributed analytic FLOPs, the derived
        ``tflops`` plus ``mfu_pct`` against the chip's peak
        (``utils.flops.peak_tflops``; absent on the CPU, and an unknown
        accelerator raises there).
        """
        peak = None
        with self._lock:
            if any(st.flops for st in self.stages.values()):
                from ..utils.flops import peak_tflops

                peak = peak_tflops()
            stages = {}
            tot_wall = 0.0
            tot_flops = 0
            tot_bytes = 0
            for name in sorted(self.stages):
                st = self.stages[name]
                samples = sorted(st.samples)
                entry = {
                    "count": st.count,
                    "total_s": round(st.total_s, 6),
                    "min_s": round(st.min_s, 6),
                    "mean_s": round(st.total_s / st.count, 6),
                    "max_s": round(st.max_s, 6),
                    "p50_s": round(_quantile_sorted(samples, 0.50), 6),
                    "p99_s": round(_quantile_sorted(samples, 0.99), 6),
                }
                if st.flops:
                    entry["flops"] = st.flops
                    if st.total_s > 0:
                        tfl = st.flops / st.total_s / 1e12
                        entry["tflops"] = round(tfl, 4)
                        if peak:
                            entry["mfu_pct"] = round(100 * tfl / peak, 2)
                if st.bytes_moved:
                    entry["bytes"] = st.bytes_moved
                    if st.total_s > 0:
                        entry["gbps"] = round(
                            st.bytes_moved / st.total_s / 1e9, 3
                        )
                stages[name] = entry
                tot_wall += st.total_s
                tot_flops += st.flops
                tot_bytes += st.bytes_moved
            total = {
                "wall_s": round(tot_wall, 6),
                "flops": tot_flops,
                "bytes": tot_bytes,
            }
            if tot_flops and tot_wall > 0:
                tfl = tot_flops / tot_wall / 1e12
                total["tflops"] = round(tfl, 4)
                if peak:
                    total["mfu_pct"] = round(100 * tfl / peak, 2)
            if peak:
                total["peak_tflops"] = peak
            out = {
                "enabled": self.enabled,
                "t_epoch": self._t_epoch,
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "gauges_max": dict(self.gauges_max),
                "stages": stages,
                "total": total,
            }
            if self._jsonl_path:
                out["jsonl_path"] = self._jsonl_path
            return out


# ---------------------------------------------------------------------------
# The process-wide registry + module-level conveniences (the engine's
# call-site API: `from ..obs import metrics` ... `metrics.stage(...)`).
# ---------------------------------------------------------------------------

_REGISTRY = MetricsRegistry(
    enabled=os.environ.get("SWIFTLY_METRICS", "0") not in ("", "0"),
    jsonl_path=os.environ.get("SWIFTLY_METRICS_JSONL") or None,
)


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def enabled() -> bool:
    return _REGISTRY.enabled


def enable(jsonl_path=None):
    return _REGISTRY.enable(jsonl_path)


def disable():
    _REGISTRY.disable()


def reset():
    _REGISTRY.reset()


def stage(name, flops=0, bytes_moved=0):
    return _REGISTRY.stage(name, flops, bytes_moved)


def observe(name, wall_s, flops=0, bytes_moved=0):
    _REGISTRY.observe(name, wall_s, flops, bytes_moved)


def count(name, n=1):
    _REGISTRY.count(name, n)


def gauge(name, value):
    _REGISTRY.gauge(name, value)


def gauge_max(name, value):
    _REGISTRY.gauge_max(name, value)


def event(kind, **fields):
    _REGISTRY.event(kind, **fields)


def export():
    return _REGISTRY.export()
