"""Tier-3 tests: end-to-end streaming round trip.

Mirrors the reference's test_api.py: full facet cover -> forward ->
identity -> backward -> finished facets, RMS < 3e-10 per facet (float64),
parameterised over queue depth, forward/backward LRU sizes, shuffled
subgrid order (order independence of the streaming accumulators), and all
backends.
"""

import random

import numpy as np
import pytest

from swiftly_tpu import (
    SwiftlyBackward,
    SwiftlyConfig,
    SwiftlyForward,
    check_facet,
    check_subgrid,
    make_facet,
    make_full_facet_cover,
    make_full_subgrid_cover,
    make_subgrid,
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

SOURCES = [(1, 1, 0)]


def roundtrip(backend, queue_size, lru_forward, lru_backward, shuffle,
              dtype=None):
    config = SwiftlyConfig(backend=backend, dtype=dtype, **TEST_PARAMS)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_configs = make_full_facet_cover(config)

    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]

    fwd = SwiftlyForward(config, facet_tasks, lru_forward, queue_size)
    bwd = SwiftlyBackward(config, facet_configs, lru_backward, queue_size)

    if shuffle:
        random.Random(42).shuffle(subgrid_configs)

    sg_errors = []
    for sg_config in subgrid_configs:
        subgrid = fwd.get_subgrid_task(sg_config)
        sg_errors.append(
            check_subgrid(
                config.image_size,
                sg_config,
                config.core.as_complex(subgrid),
                SOURCES,
            )
        )
        bwd.add_new_subgrid_task(sg_config, subgrid)

    facets = bwd.finish()
    facet_errors = [
        check_facet(
            config.image_size, fc, config.core.as_complex(facets[i]), SOURCES
        )
        for i, fc in enumerate(facet_configs)
    ]
    return sg_errors, facet_errors


@pytest.mark.parametrize(
    "queue_size,lru_forward,lru_backward,shuffle",
    [
        (100, 1, 1, False),
        (100, 2, 1, False),
        (200, 1, 2, True),
        (8, 1, 1, True),
    ],
)
def test_roundtrip_jax(queue_size, lru_forward, lru_backward, shuffle):
    sg_errors, facet_errors = roundtrip(
        "jax", queue_size, lru_forward, lru_backward, shuffle
    )
    assert max(sg_errors) < 3e-10
    assert max(facet_errors) < 3e-10


def test_roundtrip_numpy():
    sg_errors, facet_errors = roundtrip("numpy", 100, 1, 1, False)
    assert max(sg_errors) < 3e-10
    assert max(facet_errors) < 3e-10


def test_roundtrip_native():
    """The compiled C++ kernels drive the full streaming API."""
    pytest.importorskip("swiftly_tpu.native")
    from swiftly_tpu.native import native_available

    if not native_available():
        pytest.skip("native toolchain unavailable")
    sg_errors, facet_errors = roundtrip("native", 100, 2, 2, True)
    assert max(sg_errors) < 3e-10
    assert max(facet_errors) < 3e-10


# f64 planar accuracy is covered by the streaming/fused parity suites;
# test_roundtrip_jax keeps the f64-precision API round trip in tier-1 and
# test_roundtrip_planar_f32 keeps the planar backend there, so this full
# f64 planar round trip rides -m slow per the tier-1 budget.
@pytest.mark.slow
def test_roundtrip_planar_f64():
    sg_errors, facet_errors = roundtrip(
        "planar", 100, 1, 1, True, dtype=np.float64
    )
    assert max(sg_errors) < 3e-10
    assert max(facet_errors) < 3e-10


def test_roundtrip_planar_f32():
    """TPU-representative precision: relaxed thresholds."""
    sg_errors, facet_errors = roundtrip(
        "planar", 100, 1, 1, False, dtype=np.float32
    )
    assert max(sg_errors) < 1e-5
    assert max(facet_errors) < 1e-4


def test_shuffle_matches_ordered():
    """Streaming accumulation is order-independent to round-off."""
    _, ordered = roundtrip("jax", 100, 1, 1, False)
    _, shuffled = roundtrip("jax", 100, 1, 1, True)
    np.testing.assert_allclose(ordered, shuffled, atol=1e-12)


def test_backward_finish_twice_raises():
    config = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    bwd = SwiftlyBackward(config, facet_configs, 1, 10)
    bwd.finish()
    with pytest.raises(RuntimeError):
        bwd.add_new_subgrid_task(make_full_subgrid_cover(config)[0], None)


def test_batched_column_forward_matches_per_subgrid():
    """get_subgrid_tasks (one program per column) == get_subgrid_task."""
    config = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_configs = make_full_facet_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    fwd_a = SwiftlyForward(config, facet_tasks, 2, 50)
    fwd_b = SwiftlyForward(config, facet_tasks, 2, 50)
    batch = fwd_a.get_subgrid_tasks(subgrid_configs)
    for sg_config, got in zip(subgrid_configs, batch):
        single = fwd_b.get_subgrid_task(sg_config)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(single), atol=1e-14
        )


def test_batched_backward_matches_per_subgrid():
    """add_new_subgrid_tasks (column-scanned) == add_new_subgrid_task."""
    config = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_configs = make_full_facet_cover(config)
    tasks = [
        (sg, make_subgrid(config.image_size, sg, SOURCES))
        for sg in subgrid_configs
    ]
    bwd_a = SwiftlyBackward(config, facet_configs, 2, 50)
    bwd_a.add_new_subgrid_tasks(tasks)
    facets_a = bwd_a.finish()
    bwd_b = SwiftlyBackward(config, facet_configs, 2, 50)
    for sg, data in tasks:
        bwd_b.add_new_subgrid_task(sg, data)
    facets_b = bwd_b.finish()
    np.testing.assert_allclose(
        np.asarray(facets_a), np.asarray(facets_b), atol=1e-12
    )


def test_lru_cache_hit_miss_counters():
    """LRUCache.get records <name>.hit / <name>.miss (enabled only),
    and keys() exposes recency order for the serving scheduler."""
    from swiftly_tpu.api import LRUCache
    from swiftly_tpu.obs import metrics

    lru = LRUCache(2)
    lru.set("a", 1)
    lru.set("b", 2)
    metrics.reset()
    metrics.enable()
    try:
        assert lru.get("a") == 1
        assert lru.get("missing") is None
        counters = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    assert counters == {"lru.hit": 1, "lru.miss": 1}
    assert lru.keys() == ["b", "a"]  # get("a") refreshed recency
    # disabled: no counter mutation at all
    assert lru.get("b") == 2
    from swiftly_tpu.obs.metrics import export

    assert "lru.hit" not in (export()["counters"] or {})


def test_flight_queue_is_deque():
    """The in-flight buffer drains oldest-first from a deque (the old
    list.pop(0) was O(n) per admit over a serving session)."""
    from collections import deque

    from swiftly_tpu.api import FlightQueue

    q = FlightQueue(4)
    assert isinstance(q._inflight, deque)


def test_get_subgrid_tasks_fallback_warns_once_and_records_path(caplog):
    """The host-backend per-subgrid fallback warns ONCE and the
    executed dispatch path is queryable for run manifests."""
    import logging

    from swiftly_tpu import api as api_mod
    from swiftly_tpu.obs import metrics

    config = SwiftlyConfig(backend="numpy", **TEST_PARAMS)
    sgs = make_full_subgrid_cover(config)[:2]
    fcs = make_full_facet_cover(config)
    tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES)) for fc in fcs
    ]
    fwd = SwiftlyForward(config, tasks, 1, 10)
    api_mod._FALLBACK_WARNED.clear()
    metrics.reset()
    metrics.enable()
    try:
        with caplog.at_level(logging.WARNING, logger="swiftly-tpu"):
            fwd.get_subgrid_tasks(sgs)
            fwd.get_subgrid_tasks(sgs)
        counters = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    warnings = [
        r for r in caplog.records if "per-subgrid loop" in r.getMessage()
    ]
    assert len(warnings) == 1  # one-shot, however many calls
    assert api_mod.last_dispatch_path() == "per-subgrid-loop"
    assert counters["fwd.path.per-subgrid-loop"] == 2


def test_flight_queue_checksum_fallback(monkeypatch):
    """With SWIFTLY_QUEUE_CHECKSUM=1 the queue bounds in-flight work by
    genuine element pulls even when block_until_ready lies (returns
    before completion)."""
    from swiftly_tpu.api import FlightQueue

    class LazyArray:
        def __init__(self, log, i):
            self.log, self.i = log, i
            self.ndim = 2

        def block_until_ready(self):
            return self  # lies: returns without completing anything

        def __getitem__(self, idx):
            self.log.append(self.i)  # a pull genuinely completes it
            return 0.0

        def is_deleted(self):
            return False

    # default mode: the lying block_until_ready makes the depth bound
    # advisory — nothing is actually completed (the documented caveat)
    log = []
    q = FlightQueue(2)
    for a in [LazyArray(log, i) for i in range(5)]:
        q.admit(a)
    assert log == []

    monkeypatch.setenv("SWIFTLY_QUEUE_CHECKSUM", "1")
    log = []
    q = FlightQueue(2)
    for a in [LazyArray(log, i) for i in range(5)]:
        q.admit(a)
    assert log == [0, 1, 2]  # oldest items really pulled at the bound
    q.drain()
    assert log == [0, 1, 2, 3, 4]
