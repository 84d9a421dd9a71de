"""The span tracer, trace report, and perf regression sentinel.

Pins the tentpole contracts of the tracing layer:

* disabled-path cost: ``trace.span(...)`` returns a shared no-op (no
  allocation, no clock read) and instrumented hot paths stay at
  attribute-check cost — the `metrics` no-op discipline;
* hierarchy: contextvar parenting builds the span tree, including
  across threads via ``current()``/``adopt()`` (the serve worker pump);
* the metrics→trace bridge: every ``metrics.stage`` site doubles as a
  trace span of the SAME name, with the registry off or on;
* the profiler gate: while a ``jax.profiler`` session records, every
  stage and span is one annotation of its name in the profile;
* serve request journeys: queue/compute/transfer segments SUM to the
  measured end-to-end latency and land on per-request trace tracks;
* Chrome export structure (Perfetto-loadable), critical-path/self-time
  attribution, and ``validate_trace_artifact`` failure modes;
* ``gauge_max`` peak tracking and the HBM-watermark fallback gauge;
* ``scripts/bench_compare.py`` regression verdicts.
"""

import json
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from swiftly_tpu.obs import metrics, recorder, report, trace
from swiftly_tpu.obs.metrics import MetricsRegistry, _NULL_STAGE
from swiftly_tpu.obs.report import (
    validate_trace_artifact,
    validate_trace_events,
)
from swiftly_tpu.obs.trace import _NULL_SPAN, Tracer

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


@pytest.fixture
def global_trace():
    """The process-global tracer, enabled for the test and wiped after."""
    tr = trace.get_tracer()
    tr.reset()
    tr.enable()
    yield tr
    tr.disable()
    tr.reset()


@pytest.fixture
def global_obs_off():
    """All three global systems guaranteed off (and wiped) around the
    test — tracer, registry, and flight recorder."""
    trace.get_tracer().disable()
    trace.get_tracer().reset()
    metrics.get_registry().disable()
    metrics.get_registry().reset()
    recorder.disable()
    recorder.reset()
    yield
    trace.get_tracer().disable()
    trace.get_tracer().reset()
    metrics.get_registry().disable()
    metrics.get_registry().reset()
    recorder.disable()
    recorder.reset()


# ---------------------------------------------------------------------------
# Disabled-path discipline
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_a_no_op(global_obs_off):
    s1 = trace.span("fwd.column_group", group=3)
    s2 = trace.span("bwd.sampled_fold")
    # the shared singleton: no per-call allocation
    assert s1 is _NULL_SPAN and s2 is _NULL_SPAN
    with s1 as s:
        s.set(bytes_moved=42)
        s.args = {"x": 1}  # attribute writes swallowed
    trace.instant("fault.injected", site="x")
    n_spans, n_events = trace.get_tracer().counts()
    assert n_spans == 0 and n_events == 0
    assert trace.add_span("x", 0.0, 1.0) == 0


def test_disabled_path_overhead_is_negligible(global_obs_off):
    # one loop per disabled entry point: trace.span AND the
    # metrics.stage bridge (which must return the shared no-op with
    # every system off) stay under the same per-call budget
    assert metrics.stage("fwd.column_pass") is _NULL_STAGE
    n = 100_000
    for site in (trace.span, metrics.stage):
        t0 = time.perf_counter()
        for _ in range(n):
            with site("fwd.column_pass"):
                pass
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 5e-6, site


def test_recorder_hot_path_under_5us(global_obs_off):
    # the flight recorder's acceptance budget: with the recorder ON
    # (and registry + tracer off), both the raw record() hook and the
    # recorder-only stage bridge stay under 5 us/event — cheap enough
    # to leave on for every drill and production serve run
    recorder.enable(seconds=60.0)

    def best_per_event(body, batches=20, n=10_000):
        # the best of several short batches: a batch that a loaded
        # machine preempted does not decide the per-event cost
        best = float("inf")
        for _ in range(batches):
            t0 = time.perf_counter()
            for _ in range(n):
                body()
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    def record():
        recorder.record("stage", "fwd.column_pass", 0.001)

    def stage():
        with metrics.stage("fwd.column_pass"):
            pass

    assert best_per_event(record) < 5e-6
    assert best_per_event(stage) < 5e-6
    # the ring is bounded: 400k events through a default ring stay
    # capped at capacity, newest retained
    assert len(recorder.get_recorder()._ring) <= recorder.get_recorder().capacity


# ---------------------------------------------------------------------------
# Hierarchy / context propagation
# ---------------------------------------------------------------------------


def test_span_nesting_builds_the_tree(global_trace):
    with trace.span("run", cat="run") as root:
        with trace.span("pass") as p:
            with trace.span("stage"):
                pass
        with trace.span("stage"):
            pass
    spans = report.build_tree(trace.export())
    by_id = {s["id"]: s for s in spans.values()}
    stages = [s for s in spans.values() if s["name"] == "stage"]
    assert len(spans) == 4
    assert by_id[root.id]["parent"] == 0
    assert by_id[p.id]["parent"] == root.id
    parents = sorted(s["parent"] for s in stages)
    assert parents == sorted([p.id, root.id])
    # durations nest: parent covers child
    assert by_id[root.id]["dur_s"] >= by_id[p.id]["dur_s"]


def test_context_propagates_across_threads_only_via_adopt(global_trace):
    seen = {}

    def worker(ctx):
        if ctx is not None:
            trace.adopt(ctx)
        with trace.span("worker.op") as s:
            pass
        seen[ctx] = s.parent

    with trace.span("run") as root:
        t1 = threading.Thread(target=worker, args=(trace.current(),))
        t1.start()
        t1.join()
        t2 = threading.Thread(target=worker, args=(None,))
        t2.start()
        t2.join()
    # adopted: nests under the run; not adopted: an orphan root
    assert seen[root.id] == root.id
    assert seen[None] == 0


def test_instants_and_explicit_time_spans(global_trace):
    t0 = time.perf_counter()
    trace.instant("degrade.spill.disk_to_ram", cat="degrade", site="spill")
    root = trace.add_span("serve.journey", t0, t0 + 0.5, tid=trace.JOURNEY_TID_BASE + 7, request_id=7)
    trace.add_span("serve.journey.queue", t0, t0 + 0.2,
                   tid=trace.JOURNEY_TID_BASE + 7, parent=root)
    exported = trace.export()
    assert validate_trace_events(exported) == []
    phs = [e["ph"] for e in exported["traceEvents"]]
    assert "i" in phs and "X" in phs and "M" in phs  # journey track named
    spans = report.build_tree(exported)
    names = {s["name"]: s for s in spans.values()}
    assert names["serve.journey.queue"]["parent"] == root
    assert abs(names["serve.journey"]["dur_s"] - 0.5) < 1e-6


# ---------------------------------------------------------------------------
# The metrics→trace bridge
# ---------------------------------------------------------------------------


def test_stage_sites_emit_spans_with_registry_off(global_obs_off):
    trace.enable()
    assert not metrics.get_registry().enabled
    with trace.span("run"):
        with metrics.stage("fwd.column_pass", flops=123,
                           bytes_moved=45) as st:
            st.bytes_moved = 46
    spans = report.build_tree(trace.export())
    names = {s["name"]: s for s in spans.values()}
    assert "fwd.column_pass" in names  # same vocabulary, zero extra sites
    assert names["fwd.column_pass"]["parent"] == names["run"]["id"]
    assert names["fwd.column_pass"]["args"]["flops"] == 123
    assert names["fwd.column_pass"]["args"]["bytes_moved"] == 46
    # the registry recorded NOTHING (it was off)
    assert metrics.export()["stages"] == {}


def test_stage_sites_feed_both_when_both_enabled(global_obs_off):
    trace.enable()
    metrics.enable()
    with metrics.stage("bwd.sampled_fold", flops=10):
        pass
    assert "bwd.sampled_fold" in metrics.export()["stages"]
    spans = report.build_tree(trace.export())
    assert {s["name"] for s in spans.values()} == {"bwd.sampled_fold"}


def _profile_host_events(directory):
    """``[(name, stats)]`` of the host events of the profile written
    under ``directory``."""
    import jax

    (path,) = Path(directory).rglob("*.xplane.pb")
    return [
        (ev.name, dict(ev.stats))
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:CPU")
        for line in plane.lines
        for ev in line.events
    ]


def test_stages_and_spans_reach_a_recording_profile(global_obs_off,
                                                     tmp_path):
    """One gate: while a profiler session records, every stage and span
    is a profiler annotation of its name — with registry, tracer and
    recorder off, or any one of them on — and never two."""
    import jax

    recording = jax.profiler.TraceAnnotation.is_enabled
    assert not recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert recording()
        with metrics.stage("fwd.slab_upload", flops=3) as st:
            st.bytes_moved = 42  # the writes call sites make are taken
            st.flops = 7
        with trace.span("fwd.column_group", cat="fwd", group=3) as sp:
            sp.set(n_cols=2)
        recorder.enable(seconds=60.0)
        with metrics.stage("fwd.slab_wait"):
            pass
        recorder.disable()
        trace.enable()
        with metrics.stage("fwd.drain"):
            pass
        trace.disable()
        metrics.enable()
        with metrics.stage("fwd.slab_stage"):
            pass
        metrics.disable()
    finally:
        jax.profiler.stop_trace()
    events = _profile_host_events(tmp_path)
    names = [name for name, _ in events]
    for name in ("fwd.slab_upload", "fwd.column_group", "fwd.slab_wait",
                 "fwd.drain", "fwd.slab_stage"):
        assert names.count(name) == 1, name
    assert dict(events)["fwd.column_group"] == {"group": 3}
    # each system still kept its own record of its stage
    assert [e["name"] for e in recorder.get_recorder().events()] == [
        "fwd.slab_wait"]
    assert [s["name"] for s in trace.export()["traceEvents"]] == [
        "fwd.drain"]
    assert list(metrics.export()["stages"]) == ["fwd.slab_stage"]
    # the session is closed: the shared no-ops again, and no annotation
    # class left on the registry
    assert not recording()
    assert metrics.stage("fwd.slab_upload") is _NULL_STAGE
    assert trace.span("fwd.column_group", group=3) is _NULL_SPAN
    assert not hasattr(metrics.get_registry(), "_annotation_cls")


def test_hbm_gauge_fallback_stamps_spans(global_trace):
    # CPU runtimes expose no memory_stats: the gauge fallback is the
    # watermark source, stamped at span close
    trace.set_hbm_gauge(123456789)
    with trace.span("fwd.column_group"):
        pass
    spans = report.build_tree(trace.export())
    (s,) = spans.values()
    assert s["args"]["hbm_peak_bytes"] == 123456789
    summary = report.summarize_trace(trace.export())
    assert summary["hbm_peak_bytes"] == 123456789


# ---------------------------------------------------------------------------
# gauge_max (watermarks surviving export)
# ---------------------------------------------------------------------------


def test_gauge_max_keeps_the_peak():
    reg = MetricsRegistry(enabled=True)
    reg.gauge("serve.queue_depth", 5)
    reg.gauge_max("serve.queue_depth_peak", 5)
    reg.gauge_max("serve.queue_depth_peak", 17)
    reg.gauge_max("serve.queue_depth_peak", 3)  # later dip must not erase
    reg.gauge("serve.queue_depth", 0)
    exp = reg.export()
    assert exp["gauges"]["serve.queue_depth"] == 0
    assert exp["gauges_max"]["serve.queue_depth_peak"] == 17
    reg.reset()
    assert reg.export()["gauges_max"] == {}
    # disabled: a no-op
    off = MetricsRegistry()
    off.gauge_max("x", 9)
    assert off.export()["gauges_max"] == {}


# ---------------------------------------------------------------------------
# Export structure / report / validators
# ---------------------------------------------------------------------------


def _demo_trace():
    tr = Tracer(enabled=True)
    with tr.span("bench.leg", cat="bench", config="1k") :
        with tr.span("fwd.pass"):
            time.sleep(0.002)
            with tr.span("fwd.column_group"):
                time.sleep(0.004)
        with tr.span("bwd.pass"):
            time.sleep(0.001)
    tr.instant("fault.injected", site="spill.read")
    return tr.export()


def test_chrome_export_is_structurally_valid(tmp_path, global_trace):
    with trace.span("a"):
        pass
    path = tmp_path / "t.json"
    trace.save(path)
    loaded = report.load_trace(path)
    assert validate_trace_events(loaded) == []
    for e in loaded["traceEvents"]:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["pid"], int)
    assert loaded["otherData"]["n_spans"] == 1


def test_validate_trace_events_failure_modes():
    assert validate_trace_events([]) != []
    assert validate_trace_events({}) == ["missing traceEvents list"]
    assert "empty" in validate_trace_events({"traceEvents": []})[0]
    bad_ph = {"traceEvents": [{"ph": "?", "name": "x"}]}
    assert any("unknown ph" in p for p in validate_trace_events(bad_ph))
    no_dur = {"traceEvents": [
        {"ph": "X", "name": "x", "pid": 1, "tid": 1, "ts": 0}
    ]}
    assert any("bad dur" in p for p in validate_trace_events(no_dur))


def test_critical_path_and_self_time_partition():
    exported = _demo_trace()
    spans = report.build_tree(exported)
    summary = report.summarize_trace(exported)
    assert summary["root"] == "bench.leg"
    chain = [c["name"] for c in summary["critical_path"]]
    assert chain == ["bench.leg", "fwd.pass", "fwd.column_group"]
    # self times PARTITION the root wall (the trace_report invariant:
    # the printed attribution sums back to the leg wall)
    selfs = report.self_times(spans)
    assert sum(selfs.values()) == pytest.approx(
        summary["wall_s"], abs=1e-5  # summary fields round to 1 µs
    )
    assert summary["attributed_s"] == pytest.approx(
        summary["wall_s"], abs=1e-5
    )
    top_names = [a["name"] for a in summary["top"]]
    assert top_names[0] == "fwd.column_group"  # largest self time
    assert summary["event_count"] == 1


def test_validate_trace_artifact_failure_modes():
    good = {"trace": report.summarize_trace(_demo_trace())}
    assert validate_trace_artifact(good) == []
    assert validate_trace_artifact({}) == ["missing trace block"]
    assert validate_trace_artifact({"trace": "x"}) == [
        "missing trace block"
    ]
    empty = {"trace": dict(good["trace"], span_count=0)}
    assert any("no spans" in p for p in validate_trace_artifact(empty))
    nocp = {"trace": dict(good["trace"], critical_path=[])}
    assert any(
        "critical_path is empty" in p for p in validate_trace_artifact(nocp)
    )
    missing = {"trace": {k: v for k, v in good["trace"].items()
                         if k != "wall_s"}}
    assert any("wall_s" in p for p in validate_trace_artifact(missing))
    # attribution not covering the root wall = a torn span tree
    torn = {"trace": dict(good["trace"],
                          attributed_s=good["trace"]["wall_s"] * 0.5)}
    assert any(
        "does not cover" in p for p in validate_trace_artifact(torn)
    )
    json.dumps(report.summarize_trace(_demo_trace()))  # JSON-ready


# ---------------------------------------------------------------------------
# Serve request journeys
# ---------------------------------------------------------------------------


SERVE_PARAMS = {"W": 8.0, "fov": 1.0, "N": 256, "yB_size": 96,
                "yN_size": 128, "xA_size": 56, "xM_size": 64}


@pytest.fixture(scope="module")
def serve_cover():
    from swiftly_tpu import (
        SwiftlyConfig,
        make_facet,
        make_full_facet_cover,
        make_full_subgrid_cover,
    )

    config = SwiftlyConfig(backend="jax", **SERVE_PARAMS)
    fcs = make_full_facet_cover(config)
    sgs = make_full_subgrid_cover(config)
    tasks = [
        (fc, make_facet(config.image_size, fc, [(1.0, 3, -5)]))
        for fc in fcs
    ]
    return config, tasks, sgs


def _service(serve_cover, **kwargs):
    from swiftly_tpu import SwiftlyForward
    from swiftly_tpu.serve import SubgridService

    config, tasks, _sgs = serve_cover
    fwd = SwiftlyForward(config, tasks, lru_forward=2, queue_size=50)
    return SubgridService(fwd, **kwargs)


def test_journey_segments_sum_to_latency(serve_cover, global_obs_off):
    _config, _tasks, sgs = serve_cover
    svc = _service(serve_cover)
    reqs = svc.serve(sgs[:6] + sgs[:2])  # duplicates coalesce
    for r in reqs:
        res = r.result
        assert res is not None and res.ok
        j = res.journey
        assert j is not None, "served request missing its journey"
        assert j["queue_s"] >= 0 and j["compute_s"] >= 0
        assert j["transfer_s"] >= 0
        # contiguous timestamp diffs: EXACT decomposition of latency
        assert j["queue_s"] + j["compute_s"] + j["transfer_s"] == (
            pytest.approx(res.latency_s, abs=1e-9)
        )
    stats = svc.stats()
    jb = stats["journey"]
    assert jb["n"] == len(reqs)
    shares = [jb[seg]["share"] for seg in ("queue", "compute", "transfer")]
    assert sum(shares) == pytest.approx(1.0, abs=0.01)
    for seg in ("queue", "compute", "transfer"):
        assert jb[seg]["p50_ms"] <= jb[seg]["p99_ms"] + 1e-9
    # the serve artifact validator accepts the block
    from swiftly_tpu.obs import validate_serve_artifact

    probs = validate_serve_artifact({"journey": jb})
    assert not any("journey" in p for p in probs)


def test_journey_trace_spans_on_request_tracks(serve_cover, global_trace):
    _config, _tasks, sgs = serve_cover
    svc = _service(serve_cover)
    with trace.span("demo.serve", cat="demo"):
        reqs = svc.serve(sgs[:4])
    assert all(r.result.ok for r in reqs)
    exported = trace.export()
    assert validate_trace_events(exported) == []
    spans = report.build_tree(exported)
    journeys = [s for s in spans.values() if s["name"] == "serve.journey"]
    assert len(journeys) == 4
    for j in journeys:
        segs = {spans[c]["name"] for c in j["children"]}
        assert segs == {"serve.journey.queue", "serve.journey.compute",
                        "serve.journey.transfer"}
        # segments partition the journey span
        seg_total = sum(spans[c]["dur_s"] for c in j["children"])
        assert seg_total == pytest.approx(j["dur_s"], rel=1e-3, abs=1e-6)
        assert j["tid"] >= trace.JOURNEY_TID_BASE
    js = report.journey_stats(spans)
    assert js["n_requests"] == 4
    assert (
        js["queue_share"] + js["compute_share"] + js["transfer_share"]
        == pytest.approx(1.0, abs=0.01)
    )
    # serve.batch (a metrics stage site) arrived via the bridge and
    # nests under the pump's caller context
    batch = [s for s in spans.values() if s["name"] == "serve.batch"]
    assert batch, sorted({s["name"] for s in spans.values()})


def test_worker_pump_spans_nest_under_run(serve_cover, global_trace):
    """Context propagation across the serve worker thread: start() is
    called inside the run span, so the pump's dispatch spans must nest
    under it (not appear as orphan roots)."""
    _config, _tasks, sgs = serve_cover
    svc = _service(serve_cover)
    with trace.span("demo.serve", cat="demo") as root:
        svc.start()
        reqs = [svc.submit(sg) for sg in sgs[:4]]
        for r in reqs:
            assert r.wait(30.0) is not None
        svc.stop()
    assert all(r.result.ok for r in reqs)
    spans = report.build_tree(trace.export())

    def has_root_ancestor(s):
        while s["parent"]:
            if s["parent"] == root.id:
                return True
            s = spans[s["parent"]]
        return False

    batch = [s for s in spans.values() if s["name"] == "serve.batch"]
    assert batch
    assert all(has_root_ancestor(s) for s in batch)


# ---------------------------------------------------------------------------
# The perf regression sentinel
# ---------------------------------------------------------------------------


def _leg(config="1k", mode="streamed", platform="cpu", value=10.0,
         mfu=40.0):
    return {
        "metric": f"{config} forward facet->subgrid wall-clock "
                  f"(8 subgrids, planar f32, {mode}, {platform})",
        "value": value,
        "unit": "s",
        "mfu_pct": mfu,
        "manifest": {
            "config_params": {"config": config, "mode": mode},
            "device": {"platform": platform},
        },
    }


def test_bench_compare_verdicts():
    from scripts.bench_compare import compare

    ref = [_leg(value=10.0, mfu=40.0), _leg(value=12.0, mfu=35.0)]
    # identical numbers: no regression (self-comparison must stay green)
    rep = compare([_leg(value=10.0, mfu=40.0)], ref, threshold=0.2)
    assert rep["ok"] and not rep["regressions"]
    # within threshold: green
    rep = compare([_leg(value=11.9, mfu=33.0)], ref, threshold=0.2)
    assert not rep["regressions"]
    # wall regression past 20% vs the BEST reference
    rep = compare([_leg(value=12.5)], ref, threshold=0.2)
    assert len(rep["regressions"]) == 1
    assert "slower" in rep["regressions"][0]["problems"][0]
    # MFU collapse trips it too
    rep = compare([_leg(value=10.0, mfu=20.0)], ref, threshold=0.2)
    assert any(
        "mfu" in p for v in rep["regressions"] for p in v["problems"]
    )
    # cross-platform comparisons are refused, not false-positived
    rep = compare([_leg(platform="tpu", value=99.0)], ref, threshold=0.2)
    assert not rep["regressions"]
    assert rep["skipped"] and "platform" in rep["skipped"][0]["reason"]
    # unknown leg: skipped
    rep = compare([_leg(config="8k", value=99.0)], ref)
    assert not rep["regressions"] and rep["skipped"]


def test_bench_compare_parses_legacy_metric_strings():
    from scripts.bench_compare import leg_key, leg_platform

    legacy = {
        "metric": "64k[1]-n32k-512 forward facet->subgrid wall-clock "
                  "(21609 subgrids, planar f32, streamed, tpu)",
        "value": 54.4,
    }
    assert leg_key(legacy) == ("64k[1]-n32k-512", "streamed")
    assert leg_platform(legacy) == "tpu"


def test_bench_compare_cli_round_trip(tmp_path):
    from scripts.bench_compare import main as compare_main

    latest = tmp_path / "BENCH_latest.json"
    ref = tmp_path / "BENCH_ref.json"
    latest.write_text(json.dumps(_leg(value=10.0)))
    ref.write_text(json.dumps({"parsed": _leg(value=10.0)}))
    assert compare_main(
        [str(latest), "--against", str(ref), "--json"]
    ) == 0
    # doctored faster baseline → the sentinel must trip
    ref.write_text(json.dumps({"parsed": _leg(value=5.0)}))
    assert compare_main(
        [str(latest), "--against", str(ref), "--json"]
    ) == 1
    # a file is never its own baseline (self-glob stays green)
    assert compare_main(
        [str(latest), "--against", str(latest), "--json"]
    ) == 0
