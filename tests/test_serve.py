"""On-demand subgrid serving tests.

The serving contract, pinned:

* request/batch PARITY — a coalesced batch through `SubgridService`
  (stacked column programs, bucket padding, fused multi-column) is
  BIT-IDENTICAL to sequential `get_subgrid_task` calls for the same
  configs, including masked and ragged-column request sets;
* BACKPRESSURE — depth and projected-HBM admission both shed with
  structured results; deadlines expire at scheduling boundaries; the
  SWIFTLY_QUEUE_CHECKSUM=1 checksum-pull path serves correctly;
* FAULT ISOLATION — an injected batch failure retries singly to
  success; a poisoned request is quarantined without wedging its
  column; a force-evicted cache feed falls back to recomputation;
* SCHEDULING — urgency preempts, LRU-hot columns are preferred, and
  coalescing is visible in counters and stats.
"""

import threading
import time

import numpy as np
import pytest

from swiftly_tpu import (
    SubgridConfig,
    SwiftlyConfig,
    SwiftlyForward,
    make_facet,
    make_full_facet_cover,
    make_full_subgrid_cover,
)
from swiftly_tpu.obs import metrics
from swiftly_tpu.serve import (
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_QUARANTINED,
    STATUS_SHED,
    AdmissionQueue,
    CoalescingScheduler,
    SubgridRequest,
    SubgridService,
)

TEST_PARAMS = {
    "W": 13.5625,
    "fov": 1.0,
    "N": 1024,
    "yB_size": 416,
    "yN_size": 512,
    "xA_size": 228,
    "xM_size": 256,
}

SOURCES = [(1, 1, 0), (0.5, -30, 40)]


@pytest.fixture(scope="module")
def cover():
    config = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    return config, facet_tasks, subgrid_configs


def _forward(cover, **kwargs):
    config, facet_tasks, _ = cover
    kwargs.setdefault("lru_forward", 2)
    kwargs.setdefault("queue_size", 50)
    return SwiftlyForward(config, facet_tasks, **kwargs)


def _assert_all_ok(reqs):
    for r in reqs:
        assert r.result is not None and r.result.ok, r.result


# ---------------------------------------------------------------------------
# Request/batch parity (the acceptance pin)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_service_parity_randomized(cover, seed):
    """Property-style pin: random request multisets (duplicates, random
    masks, ragged column subsets, random priorities/order) served
    through the coalescing batcher are BIT-IDENTICAL to sequential
    per-request `get_subgrid_task` on a fresh forward."""
    config, _tasks, sgs = cover
    rng = np.random.default_rng(seed)
    workload = []
    for _ in range(30):
        sg = sgs[rng.integers(len(sgs))]
        if rng.random() < 0.3:
            # masked variant: random 0/1 ownership masks
            sg = SubgridConfig(
                sg.off0, sg.off1, sg.size,
                (rng.random(sg.size) < 0.7).astype(float),
                (rng.random(sg.size) < 0.7).astype(float),
            )
        workload.append(sg)
    svc = SubgridService(
        _forward(cover),
        # power-of-two caps: the bucket shapes stay shared with the
        # other tests' batches (one in-process compile per shape)
        scheduler=CoalescingScheduler(max_batch=4 if seed % 2 else 8),
    )
    reqs = [
        svc.submit(sg, priority=int(rng.integers(0, 3)))
        for sg in workload
    ]
    while svc.pump_once():
        pass
    _assert_all_ok(reqs)
    fwd_ref = _forward(cover)
    for sg, req in zip(workload, reqs):
        ref = np.asarray(fwd_ref.get_subgrid_task(sg))
        np.testing.assert_array_equal(np.asarray(req.result.data), ref)


def test_fused_multicolumn_parity(cover):
    """fuse_columns > 1 (the `_group_columns` + `_pad_ragged_columns`
    fused-program path, ragged across columns) stays bit-identical."""
    config, _tasks, sgs = cover
    cols = sorted({sg.off0 for sg in sgs})
    # ragged on purpose: whole first column + part of the second
    workload = [sg for sg in sgs if sg.off0 == cols[0]] + [
        sg for sg in sgs if sg.off0 == cols[1]
    ][:2]
    svc = SubgridService(
        _forward(cover), fuse_columns=2,
        scheduler=CoalescingScheduler(max_batch=16),
    )
    reqs = svc.serve(workload)
    _assert_all_ok(reqs)
    fwd_ref = _forward(cover)
    for sg, req in zip(workload, reqs):
        np.testing.assert_array_equal(
            np.asarray(req.result.data),
            np.asarray(fwd_ref.get_subgrid_task(sg)),
        )


def test_checksum_queue_backpressure_serves(cover, monkeypatch):
    """SWIFTLY_QUEUE_CHECKSUM=1 (the element-pull backpressure the
    FlightQueue documents): the service's dispatches run through
    genuine element pulls and results stay bit-identical."""
    monkeypatch.setenv("SWIFTLY_QUEUE_CHECKSUM", "1")
    config, _tasks, sgs = cover
    fwd = _forward(cover, queue_size=2)  # tight bound: pull constantly
    assert fwd.queue._checksum
    svc = SubgridService(fwd, scheduler=CoalescingScheduler(max_batch=4))
    workload = list(sgs[:10])
    reqs = svc.serve(workload)
    _assert_all_ok(reqs)
    monkeypatch.delenv("SWIFTLY_QUEUE_CHECKSUM")
    fwd_ref = _forward(cover)
    for sg, req in zip(workload, reqs):
        np.testing.assert_array_equal(
            np.asarray(req.result.data),
            np.asarray(fwd_ref.get_subgrid_task(sg)),
        )


# ---------------------------------------------------------------------------
# Coalescing + scheduling
# ---------------------------------------------------------------------------


def test_one_column_coalesces_to_one_batch(cover):
    config, _tasks, sgs = cover
    col0 = [sg for sg in sgs if sg.off0 == sgs[0].off0]
    svc = SubgridService(
        _forward(cover), scheduler=CoalescingScheduler(max_batch=16)
    )
    reqs = svc.serve(col0)
    _assert_all_ok(reqs)
    st = svc.stats()
    assert st["n_batches"] == 1
    assert st["coalesce_hit_rate"] == 1.0
    assert all(r.result.batch_size == len(col0) for r in reqs)


def test_scheduler_prefers_hot_column(cover):
    """After serving column A, new requests for A and B schedule A
    first (its intermediates are LRU-resident)."""
    config, _tasks, sgs = cover
    cols = sorted({sg.off0 for sg in sgs})
    a = [sg for sg in sgs if sg.off0 == cols[0]]
    b = [sg for sg in sgs if sg.off0 == cols[1]]
    fwd = _forward(cover)
    svc = SubgridService(fwd, scheduler=CoalescingScheduler(max_batch=8))
    svc.serve(a[:2])  # column A is now LRU-hot
    # B has MORE pending than A — locality must still win
    ra = svc.submit(a[0])
    rbs = [svc.submit(sg) for sg in b]
    svc.pump_once()
    assert ra.result is not None and ra.result.ok
    assert all(r.result is None for r in rbs)
    while svc.pump_once():
        pass
    _assert_all_ok(rbs)


def test_scheduler_urgency_preempts(cover):
    """A column holding a near-deadline request preempts a hotter,
    denser column."""
    config, _tasks, sgs = cover
    cols = sorted({sg.off0 for sg in sgs})
    a = [sg for sg in sgs if sg.off0 == cols[0]]
    b = [sg for sg in sgs if sg.off0 == cols[1]]
    svc = SubgridService(
        _forward(cover),
        scheduler=CoalescingScheduler(max_batch=8, urgency_s=3600.0),
    )
    ras = [svc.submit(sg) for sg in a]           # dense, no deadline
    rb = svc.submit(b[0], deadline_s=1800.0)     # sparse but urgent
    svc.pump_once()
    assert rb.result is not None and rb.result.ok
    assert all(r.result is None for r in ras)
    while svc.pump_once():
        pass
    _assert_all_ok(ras)


def test_bucket_padding_bounds_shapes():
    sched = CoalescingScheduler(max_batch=8, bucket_pad=True)
    reqs = [
        SubgridRequest(SubgridConfig(0, i, 16)) for i in range(5)
    ]
    configs, n_pad = sched.plan_batch(reqs)
    assert len(configs) == 8 and n_pad == 3
    assert all(c is reqs[0].config for c in configs[5:])
    # cap: never pad past max_batch
    sched2 = CoalescingScheduler(max_batch=6, bucket_pad=True)
    configs2, n_pad2 = sched2.plan_batch(reqs)
    assert len(configs2) == 6 and n_pad2 == 1


def test_scheduler_consumes_compiled_plan_buckets():
    """A compiled plan's ``serve.bucket_sizes`` drives the batch shapes
    (the scheduler's power-of-two fork now lives in `plan.model`), and
    the default path is provably the plan's own bucket table."""
    from swiftly_tpu.plan import PlanInputs, bucket_sizes, compile_plan

    plan = compile_plan(
        PlanInputs.from_config("4k[1]-n2k-512", max_batch=8),
        mode="streamed",
    )
    assert plan.serve.bucket_sizes == bucket_sizes(8) == [1, 2, 4, 8]
    sched = CoalescingScheduler(
        max_batch=plan.serve.max_batch,
        bucket_sizes=plan.serve.bucket_sizes,
    )
    reqs = [SubgridRequest(SubgridConfig(0, i, 16)) for i in range(5)]
    configs, n_pad = sched.plan_batch(reqs)
    assert len(configs) == 8 and n_pad == 3
    # identical to the default power-of-two padding at every count —
    # migrating the fork changed nothing
    default = CoalescingScheduler(max_batch=8)
    for n in range(1, 9):
        sub = reqs[:1] * n
        assert sched.plan_batch(sub)[1] == default.plan_batch(sub)[1]


def test_fused_serve_batch_lowers_without_unusable_donations(cover):
    """ROADMAP item 2's "unusable donation" warnings: PR 2 fixed the
    `_column_group_finish_j` instance, and a sweep found no survivors
    in the fused serve batch path — this guard keeps it that way by
    lowering a fused multi-column batch under warning capture (the
    shared `conftest.unusable_donation_warnings` guard; its backward-
    path twin lives in tests/test_spill.py). A reappearing `Some
    donated buffers were not usable` means a new dangling donation (a
    silent HBM copy on every dispatch)."""
    from conftest import unusable_donation_warnings

    config, _tasks, sgs = cover
    cols = sorted({sg.off0 for sg in sgs})
    workload = [sg for sg in sgs if sg.off0 in cols[:2]]
    svc = SubgridService(
        _forward(cover), fuse_columns=2,
        scheduler=CoalescingScheduler(max_batch=16),
    )
    reqs = []
    donation = unusable_donation_warnings(
        lambda: reqs.extend(svc.serve(workload))
    )
    _assert_all_ok(reqs)
    assert not donation, [str(w.message) for w in donation]


# ---------------------------------------------------------------------------
# Admission: depth, HBM cost, deadlines
# ---------------------------------------------------------------------------


def test_depth_shed(cover):
    config, _tasks, sgs = cover
    svc = SubgridService(
        _forward(cover), queue=AdmissionQueue(max_depth=4)
    )
    reqs = [svc.submit(sg) for sg in sgs[:10]]
    shed = [r for r in reqs if r.result is not None]
    assert len(shed) == 6
    assert all(r.result.status == STATUS_SHED for r in shed)
    assert all(r.result.shed_reason == "depth" for r in shed)
    while svc.pump_once():
        pass
    _assert_all_ok(reqs[:4])
    st = svc.stats()
    assert st["n_shed"] == 6 and st["shed_rate"] == 0.6


def test_hbm_cost_shed(cover):
    """Projected-cost admission: distinct pending columns price their
    intermediates, so a budget covering ~one column sheds the second."""
    config, _tasks, sgs = cover
    cols = sorted({sg.off0 for sg in sgs})
    a = next(sg for sg in sgs if sg.off0 == cols[0])
    b = next(sg for sg in sgs if sg.off0 == cols[1])
    queue = AdmissionQueue(
        max_depth=100,
        hbm_budget_bytes=1500,
        request_bytes=100,
        column_bytes=1000,
    )
    svc = SubgridService(_forward(cover), queue=queue)
    r1 = svc.submit(a)          # 1 col + 1 req = 1100 <= 1500
    r2 = svc.submit(a)          # 1 col + 2 req = 1200 <= 1500
    r3 = svc.submit(b)          # 2 cols + 3 req = 2300 > 1500 -> shed
    assert r1.result is None and r2.result is None
    assert r3.result is not None and r3.result.shed_reason == "hbm"
    while svc.pump_once():
        pass
    _assert_all_ok([r1, r2])


def test_deadline_expiry(cover):
    config, _tasks, sgs = cover
    svc = SubgridService(_forward(cover))
    dead_on_arrival = svc.submit(sgs[2], deadline_s=-1.0)
    fast = svc.submit(sgs[0], deadline_s=0.005)
    slow = svc.submit(sgs[1])
    time.sleep(0.02)  # fast's deadline passes while it sits queued
    while svc.pump_once():
        pass
    assert dead_on_arrival.result.status == STATUS_EXPIRED
    assert fast.result.status == STATUS_EXPIRED
    assert slow.result.ok
    assert svc.stats()["n_expired"] == 2


def test_submit_after_deadline_sheds_expired(cover):
    config, _tasks, sgs = cover
    svc = SubgridService(_forward(cover))
    req = SubgridRequest(sgs[0], deadline_s=-1.0)
    admitted, reason = svc.queue.offer(req)
    assert not admitted and reason == "expired"


def test_queue_take_priority_order():
    q = AdmissionQueue(max_depth=10)
    reqs = [
        SubgridRequest(SubgridConfig(0, i, 16), priority=p)
        for i, p in enumerate([0, 2, 1, 2])
    ]
    for r in reqs:
        assert q.offer(r)[0]
    taken = q.take(0, limit=3)
    # highest priority first, FIFO within a priority; overflow stays
    assert [t.priority for t in taken] == [2, 2, 1]
    assert [t.config.off1 for t in taken[:2]] == [1, 3]
    assert len(q) == 1


# ---------------------------------------------------------------------------
# Fault isolation: injection, quarantine, cache eviction
# ---------------------------------------------------------------------------


def test_injected_batch_failure_retries_to_success(cover):
    config, _tasks, sgs = cover
    col0 = [sg for sg in sgs if sg.off0 == sgs[0].off0]
    state = {"armed": 1}

    def injector(reqs, attempt):
        if attempt == 0 and state["armed"]:
            state["armed"] = 0
            raise RuntimeError("injected transient failure")

    svc = SubgridService(_forward(cover), fault_injector=injector)
    reqs = svc.serve(col0)
    _assert_all_ok(reqs)
    st = svc.stats()
    assert st["batch_failures"] == 1
    assert st["retries"] == len(col0)
    assert all(r.result.path == "retry" for r in reqs)
    fwd_ref = _forward(cover)
    for sg, req in zip(col0, reqs):
        np.testing.assert_array_equal(
            np.asarray(req.result.data),
            np.asarray(fwd_ref.get_subgrid_task(sg)),
        )


def test_poisoned_request_quarantined_without_wedging(cover):
    """One malformed config (mask length mismatch) fails its coalesced
    batch; isolation retries it alone, quarantines it, and every other
    request in the column still serves."""
    config, _tasks, sgs = cover
    col0 = [sg for sg in sgs if sg.off0 == sgs[0].off0]
    poisoned = SubgridConfig(
        col0[0].off0, col0[0].off1, col0[0].size,
        np.ones(col0[0].size + 5), None,
    )
    svc = SubgridService(_forward(cover), max_retries=2)
    good = [svc.submit(sg) for sg in col0]
    bad = svc.submit(poisoned)
    while svc.pump_once():
        pass
    _assert_all_ok(good)
    assert bad.result.status == STATUS_QUARANTINED
    assert bad.result.error  # structured: carries the exception repr
    assert bad.result.retries == 2
    st = svc.stats()
    assert st["n_quarantined"] == 1 and len(svc.quarantined) == 1
    assert len(svc.queue) == 0  # nothing wedged behind the poison


def test_cache_feed_hit_and_eviction_fallback(cover):
    """A recorded-stream feed serves hits as verbatim recorded rows;
    a forced eviction makes the same lookups fall back to compute —
    degraded cost, identical results."""
    from swiftly_tpu.parallel.streamed import CachedColumnFeed
    from swiftly_tpu.utils.spill import SpillCache

    config, _tasks, sgs = cover
    col0 = [sg for sg in sgs if sg.off0 == sgs[0].off0]
    fwd = _forward(cover)
    stacked = fwd.get_subgrid_tasks(col0)
    spill = SpillCache(budget_bytes=2**28)
    spill.begin_fill(tag="serve-test")
    assert spill.put(
        [list(enumerate(col0))],
        np.stack([np.asarray(r) for r in stacked])[None],
    )
    assert spill.end_fill()
    feed = CachedColumnFeed(spill)
    assert len(feed) == len(col0)

    svc = SubgridService(fwd, cache_feed=feed)
    reqs = svc.serve(col0)
    _assert_all_ok(reqs)
    assert all(r.result.path == "cache" for r in reqs)
    assert svc.stats()["cache_hits"] == len(col0)
    fwd_ref = _forward(cover)
    for sg, req in zip(col0, reqs):
        np.testing.assert_array_equal(
            np.asarray(req.result.data),
            np.asarray(fwd_ref.get_subgrid_task(sg)),
        )

    spill.reset()  # forced eviction: the cache is no longer complete,
    # so the feed refuses up-front (counted as evictions — the stream
    # is gone, not mid-update) and compute serves
    reqs2 = svc.serve(col0)
    _assert_all_ok(reqs2)
    assert all(r.result.path in ("coalesced", "retry") for r in reqs2)
    st = svc.stats()
    assert st["cache_fallbacks"] == len(col0)
    assert feed.evicted == len(col0)
    assert feed.stale == 0
    for sg, req in zip(col0, reqs2):
        np.testing.assert_array_equal(
            np.asarray(req.result.data),
            np.asarray(fwd_ref.get_subgrid_task(sg)),
        )


def test_cache_feed_mask_mismatch_is_miss(cover):
    from swiftly_tpu.parallel.streamed import CachedColumnFeed
    from swiftly_tpu.utils.spill import SpillCache

    config, _tasks, sgs = cover
    col0 = [sg for sg in sgs if sg.off0 == sgs[0].off0]
    fwd = _forward(cover)
    stacked = fwd.get_subgrid_tasks(col0)
    spill = SpillCache(budget_bytes=2**28)
    spill.begin_fill(tag="mask-test")
    spill.put(
        [list(enumerate(col0))],
        np.stack([np.asarray(r) for r in stacked])[None],
    )
    spill.end_fill()
    feed = CachedColumnFeed(spill)
    masked = SubgridConfig(
        col0[0].off0, col0[0].off1, col0[0].size,
        np.zeros(col0[0].size), None,
    )
    assert feed.lookup(masked) is None  # masks are part of the result
    assert feed.misses == 1


def test_streamed_recorded_feed_bitidentical_to_stream(cover):
    """End-to-end with the real recorder: a stream persisted by
    `stream_column_groups(spill=...)` feeds single-request lookups
    bit-identical to the recorded stream rows."""
    from swiftly_tpu.parallel import StreamedForward
    from swiftly_tpu.utils.spill import SpillCache

    config, _tasks, sgs = cover
    sfwd = StreamedForward(
        config, _tasks, residency="device", col_group=4
    )
    spill = SpillCache(budget_bytes=2**30)
    recorded = {}
    for per_col, group in sfwd.stream_column_groups(sgs, spill=spill):
        host = np.asarray(group)
        for c, col in enumerate(per_col):
            for s, (_i, sg) in enumerate(col):
                recorded[(sg.off0, sg.off1)] = host[c, s]
    assert spill.complete
    feed = sfwd.cached_feed(spill)
    for sg in sgs:
        row = feed.lookup(sg)
        assert row is not None
        np.testing.assert_array_equal(row, recorded[(sg.off0, sg.off1)])


# ---------------------------------------------------------------------------
# Worker thread + SLO instrumentation
# ---------------------------------------------------------------------------


def test_threaded_service(cover):
    config, _tasks, sgs = cover
    svc = SubgridService(_forward(cover)).start()
    try:
        reqs = [svc.submit(sg) for sg in sgs[:8]]
        for r in reqs:
            assert r.wait(timeout=120) is not None
        _assert_all_ok(reqs)
    finally:
        svc.stop(timeout=120)
    assert svc.stats()["n_served"] == 8


def test_slo_and_latency_stats(cover):
    config, _tasks, sgs = cover
    svc = SubgridService(_forward(cover), slo_ms=1e9)
    svc.serve(sgs[:6])
    st = svc.stats()
    assert st["p50_ms"] > 0 and st["p99_ms"] >= st["p50_ms"]
    assert st["max_ms"] >= st["p99_ms"]
    assert st["slo_violations"] == 0 and st["slo_attainment"] == 1.0
    svc2 = SubgridService(_forward(cover), slo_ms=1e-9)
    svc2.serve(sgs[:2])
    st2 = svc2.stats()
    assert st2["slo_violations"] == 2 and st2["slo_attainment"] == 0.0


def test_serve_metrics_vocabulary(cover):
    """The obs wiring: serve counters/gauges/stages land in the
    registry export with the documented names."""
    config, _tasks, sgs = cover
    metrics.reset()
    metrics.enable()
    try:
        svc = SubgridService(
            _forward(cover), queue=AdmissionQueue(max_depth=4)
        )
        reqs = [svc.submit(sg) for sg in sgs[:6]]
        while svc.pump_once():
            pass
        exp = metrics.export()
    finally:
        metrics.disable()
        metrics.reset()
    counters = exp["counters"]
    assert counters["serve.requests"] == 6
    assert counters["serve.served"] == 4
    assert counters["serve.shed"] == 2
    assert counters["serve.shed.depth"] == 2
    assert counters["lru.miss"] >= 1
    assert "serve.queue_depth" in exp["gauges"]
    stages = exp["stages"]
    assert {"serve.batch", "serve.request"} <= set(stages)
    assert stages["serve.request"]["count"] == 4
    assert "p50_s" in stages["serve.request"]
