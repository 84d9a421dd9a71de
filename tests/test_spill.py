"""Subgrid-stream spill cache tests.

The cache must be exact (a cache-fed backward is BIT-IDENTICAL to a
replay-fed one: d2h -> host RAM/disk -> h2d of float arrays changes no
bits), must kill the backward leg's forward replays (one `fwd.passes`
counter tick however many consume passes run), and must degrade to
replay — never to a wrong answer — when the stream exceeds its budget.
"""

import numpy as np
import pytest

from swiftly_tpu import SwiftlyConfig, make_facet, make_full_facet_cover, \
    make_full_subgrid_cover
from swiftly_tpu.obs import metrics
from swiftly_tpu.parallel import StreamedBackward, StreamedForward
from swiftly_tpu.utils.spill import SpillCache

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


def _setup(backend):
    config = SwiftlyConfig(backend=backend, **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    return config, facet_configs, subgrid_configs, facet_tasks


# ---------------------------------------------------------------------------
# Cache unit behaviour
# ---------------------------------------------------------------------------


def test_spill_cache_ram_roundtrip_bitexact():
    cache = SpillCache(budget_bytes=1e9)
    rng = np.random.default_rng(0)
    arrays = [rng.standard_normal((2, 3, 4)).astype(np.float32)
              for _ in range(3)]
    cache.begin_fill()
    for k, a in enumerate(arrays):
        assert cache.put({"k": k}, a)
    assert cache.end_fill()
    assert cache.complete and len(cache) == 3
    for k, a in enumerate(arrays):
        np.testing.assert_array_equal(cache.get(k), a)
        assert cache.meta(k) == {"k": k}
    stats = cache.stats()
    assert stats["entries"] == 3 and stats["writes"] == 3
    assert stats["ram_bytes"] == sum(a.nbytes for a in arrays)
    assert stats["evictions"] == 0 and stats["disk_bytes"] == 0


def test_spill_cache_disk_backing_bitexact(tmp_path):
    """Entries past the RAM budget land on disk and read back exactly;
    the cache stays complete."""
    rng = np.random.default_rng(1)
    arrays = [rng.standard_normal((5, 7)).astype(np.float32)
              for _ in range(4)]
    # budget fits the first two entries only
    cache = SpillCache(
        budget_bytes=2 * arrays[0].nbytes, spill_dir=str(tmp_path)
    )
    cache.begin_fill()
    for k, a in enumerate(arrays):
        assert cache.put(k, a)
    assert cache.end_fill()
    stats = cache.stats()
    assert stats["complete"]
    assert stats["ram_bytes"] == 2 * arrays[0].nbytes
    assert stats["disk_bytes"] == 2 * arrays[0].nbytes
    for k, a in enumerate(arrays):
        np.testing.assert_array_equal(cache.get(k), a)
    assert cache.stats()["disk_reads"] == 2
    cache.reset()  # deletes the disk files
    import os

    assert not any(
        f.startswith("group_") for d in os.listdir(tmp_path)
        for f in (os.listdir(tmp_path / d) if (tmp_path / d).is_dir()
                  else [d])
    )


def test_spill_cache_eviction_gives_up():
    """Over budget with no disk dir: the entry is evicted, the fill ends
    incomplete, and `gave_up` tells consumers to replay."""
    cache = SpillCache(budget_bytes=8, spill_dir=None)
    cache.begin_fill()
    assert not cache.put(0, np.zeros(64, np.float32))
    assert not cache.end_fill()
    assert cache.gave_up and not cache.complete
    assert cache.stats()["evictions"] == 1


# ---------------------------------------------------------------------------
# Concurrency: the cache fabric's access pattern
# ---------------------------------------------------------------------------


def test_spill_concurrent_row_reads_vs_patch_and_eviction():
    """The fabric's real access pattern, stress-tested: >= 4 reader
    threads hammering `get_row` while the main thread runs repeated
    `begin_patch`/`patch_entry`/`end_patch` cycles and finally evicts
    the whole stream (`reset`). The reader–writer gate's contract: a
    read never observes a torn row (every row is value-uniform before
    AND after each landed patch), reads racing a patch window bounce
    with `StreamMidPatch`, eviction degrades to a clean LookupError,
    and the final payloads carry exactly the patches that ran."""
    import threading
    import time

    from swiftly_tpu.utils.spill import StreamMidPatch

    n_entries, rows, row_len, n_readers, n_patches = 4, 6, 64, 4, 10
    cache = SpillCache(budget_bytes=1e9)
    cache.begin_fill(tag="stress")
    for k in range(n_entries):
        arr = np.full((1, rows, row_len), 100.0 * k, np.float32)
        assert cache.put([[(s, None) for s in range(rows)]], arr)
    assert cache.end_fill()

    stop = threading.Event()
    errors, torn = [], []
    bounced = [0] * n_readers

    def reader(tid):
        rng = np.random.default_rng(tid)
        while not stop.is_set():
            k = int(rng.integers(n_entries))
            s = int(rng.integers(rows))
            try:
                row = cache.get_row(k, (0, s))
            except StreamMidPatch:
                bounced[tid] += 1
                continue
            except LookupError:
                continue  # raced the final reset: clean degradation
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return
            # every patch adds a uniform +1.0, so a consistent row is
            # value-uniform at ANY time; a mixed row is a torn read
            if not np.all(row == row.flat[0]):
                torn.append((k, s))

    threads = [
        threading.Thread(target=reader, args=(t,), daemon=True)
        for t in range(n_readers)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(n_patches):
            cache.begin_patch()
            try:
                for k in range(n_entries):
                    cache.patch_entry(
                        k, np.ones((1, rows, row_len), np.float32)
                    )
            finally:
                cache.end_patch()
            time.sleep(0.002)  # give readers a between-patches window

        # deterministic cross-thread bounce: with the mark up, a
        # non-patcher read must refuse rather than enter the window...
        cache.begin_patch()
        try:
            seen = {}

            def gated_read():
                try:
                    cache.get_row(0, (0, 0))
                    seen["bounced"] = False
                except StreamMidPatch:
                    seen["bounced"] = True

            t = threading.Thread(target=gated_read)
            t.start()
            t.join(timeout=10.0)
            assert seen["bounced"] is True
            # ...while the patcher thread itself still reads base rows
            assert cache.get_row(0, (0, 0)) is not None
        finally:
            cache.end_patch()

        # final payloads: base + exactly n_patches, read back intact
        for k in range(n_entries):
            np.testing.assert_array_equal(
                cache.get(k),
                np.full((1, rows, row_len), 100.0 * k + n_patches,
                        np.float32),
            )
        assert cache.stats()["patches"] == n_patches * n_entries

        # eviction mid-traffic: readers degrade cleanly, never crash
        cache.reset()
        time.sleep(0.02)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
    assert not errors, errors
    assert not torn, f"torn rows observed: {torn[:5]}"
    assert not cache.complete and len(cache) == 0


# ---------------------------------------------------------------------------
# Cache-fed streaming
# ---------------------------------------------------------------------------


def _run_partitioned_backward(config, facet_configs, subgrid_configs,
                              facet_tasks, spill, n_parts=2):
    """One forward object, n_parts sampled-backward passes over facet
    subsets, each fed via stream_column_groups(spill=...)."""
    fwd = StreamedForward(config, facet_tasks, residency="device",
                          col_group=4)
    F_sub = -(-len(facet_configs) // n_parts)
    outs = []
    for i0 in range(0, len(facet_configs), F_sub):
        bwd = StreamedBackward(
            config, list(facet_configs[i0 : i0 + F_sub]),
            residency="sampled",
        )
        for per_col, group in fwd.stream_column_groups(
            subgrid_configs, spill=spill
        ):
            bwd.add_subgrid_group(
                [[sg for _, sg in col] for col in per_col], group
            )
        outs.append(bwd.finish())
    return np.concatenate(outs)


@pytest.mark.parametrize(
    "backend",
    [pytest.param("jax", marks=pytest.mark.slow), "planar"],
)
def test_cache_fed_backward_bitidentical_to_replay(backend):
    """The tentpole equivalence pin: a facet-partitioned backward fed
    from the spill cache (1 forward + P cache feeds) is BIT-IDENTICAL
    per facet to the replay-fed one (P forwards), and the forward-pass
    counter proves the cost model changed shape."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup(backend)

    ref = _run_partitioned_backward(
        config, facet_configs, subgrid_configs, facet_tasks, spill=None
    )

    metrics.reset()
    metrics.enable()
    try:
        out = _run_partitioned_backward(
            config, facet_configs, subgrid_configs, facet_tasks,
            spill=SpillCache(budget_bytes=1e9),
        )
        counters = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    np.testing.assert_array_equal(out, ref)
    assert counters["fwd.passes"] == 1  # the replays are gone
    assert counters["spill.replay_feeds"] == 1
    assert counters["spill.prefetch_hits"] >= 1
    assert counters["spill.writes"] >= 1
    assert counters.get("spill.fallback_replays", 0) == 0


@pytest.mark.slow
def test_cache_disk_backed_feed_matches_without_prefetch(tmp_path,
                                                         monkeypatch):
    """A cache whose budget forces every entry to disk, read back with
    the background prefetch thread DISABLED (SWIFTLY_SPILL_PREFETCH=0,
    inline reads), feeds a bit-identical stream — the chunked memmap
    write + full read path AND the overlap being a pure scheduling
    change, in one pair of runs. (The prefetch-ON disk read path runs
    in every other cache-fed test via the default.)"""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    ref = _run_partitioned_backward(
        config, facet_configs, subgrid_configs, facet_tasks, spill=None
    )
    monkeypatch.setenv("SWIFTLY_SPILL_PREFETCH", "0")
    out = _run_partitioned_backward(
        config, facet_configs, subgrid_configs, facet_tasks,
        spill=SpillCache(budget_bytes=1, spill_dir=str(tmp_path)),
    )
    np.testing.assert_array_equal(out, ref)


def test_spill_eviction_falls_back_to_replay():
    """Stream exceeds the budget, no disk: the fill gives up and every
    pass replays the forward — results identical, counters honest."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    ref = _run_partitioned_backward(
        config, facet_configs, subgrid_configs, facet_tasks, spill=None
    )
    metrics.reset()
    metrics.enable()
    try:
        cache = SpillCache(budget_bytes=1, spill_dir=None)
        out = _run_partitioned_backward(
            config, facet_configs, subgrid_configs, facet_tasks,
            spill=cache,
        )
        counters = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    np.testing.assert_array_equal(out, ref)
    assert cache.gave_up and not cache.complete
    assert counters["fwd.passes"] == 2  # both passes replayed
    assert counters["spill.fallback_replays"] == 1  # pass 2 skipped fill
    assert counters["spill.evictions"] >= 1
    assert "spill.replay_feeds" not in counters


# ---------------------------------------------------------------------------
# Feed-once/fold-many scheduling
# ---------------------------------------------------------------------------


def _run_feed_scheduled_backward(config, facet_configs, subgrid_configs,
                                 facet_tasks, spill, feed_group):
    """Per-facet passes (one per facet) run under the feed-once/fold-
    many schedule: `feed_group` passes share each stream feed."""
    from swiftly_tpu.parallel import feed_backward_passes

    fwd = StreamedForward(config, facet_tasks, residency="device",
                          col_group=4)
    outs = []
    for c0 in range(0, len(facet_configs), feed_group):
        chunk = facet_configs[c0 : c0 + feed_group]
        bwds = [
            StreamedBackward(config, [fc], residency="sampled")
            for fc in chunk
        ]
        feed_backward_passes(fwd, subgrid_configs, bwds, spill=spill)
        outs.extend(bwd.finish() for bwd in bwds)
    return np.concatenate(outs)


def test_feed_once_fold_many_bitidentical_and_h2d_collapse():
    """The feed-once/fold-many tentpole pin: P per-facet passes fed in
    shared feeds of q produce BIT-IDENTICAL facets to per-pass feeding,
    run exactly ONE forward, and move exactly (n_feeds - 1) x stream
    bytes host->device where per-pass feeding moves (P - 1) x — the
    (P-1)x h2d collapse asserted from telemetry, not inferred."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    P = len(facet_configs)
    assert P >= 3  # the schedule needs a non-trivial pass count

    def run(feed_group):
        metrics.reset()
        metrics.enable()
        try:
            spill = SpillCache(budget_bytes=1e9)
            out = _run_feed_scheduled_backward(
                config, facet_configs, subgrid_configs, facet_tasks,
                spill, feed_group,
            )
            exp = metrics.export()
        finally:
            metrics.disable()
            metrics.reset()
        stream = spill.ram_bytes + spill.disk_bytes
        h2d = (exp["stages"].get("spill.h2d") or {}).get("bytes", 0)
        return out, exp["counters"], stream, h2d

    ref, c_pp, stream_pp, h2d_pp = run(feed_group=1)  # per-pass feeding
    out, c_f, stream_f, h2d_f = run(feed_group=2)     # shared feeds

    np.testing.assert_array_equal(out, ref)  # bit-identical facets
    assert c_pp["fwd.passes"] == 1 and c_f["fwd.passes"] == 1
    assert stream_pp == stream_f > 0
    n_feeds = -(-P // 2)
    assert c_f["bwd.feed_groups"] == n_feeds
    assert c_f["bwd.feed_passes"] == P
    # the h2d byte collapse: per-pass moved (P-1) x stream, the shared
    # schedule (n_feeds - 1) x
    assert h2d_pp == (P - 1) * stream_pp
    assert h2d_f == (n_feeds - 1) * stream_f
    assert h2d_f < h2d_pp


@pytest.mark.slow
def test_feed_schedule_replay_fallback_shares_forwards():
    """Without a usable cache the schedule still helps: q passes share
    each forward REPLAY, so P per-facet passes in feeds of 2 cost
    ceil(P/2) forwards instead of P — and the facets are identical to
    one all-passes-in-one-feed run (1 forward, same fold order per
    pass — every pass folds the same stream whatever the grouping)."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    P = len(facet_configs)
    metrics.reset()
    metrics.enable()
    try:
        ref = _run_feed_scheduled_backward(
            config, facet_configs, subgrid_configs, facet_tasks,
            spill=None, feed_group=P,  # one shared feed: 1 forward
        )
        c1 = metrics.export()["counters"]
        metrics.reset()
        out = _run_feed_scheduled_backward(
            config, facet_configs, subgrid_configs, facet_tasks,
            spill=None, feed_group=2,
        )
        c2 = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    np.testing.assert_array_equal(out, ref)
    assert c1["fwd.passes"] == 1
    assert c2["fwd.passes"] == -(-P // 2)


# ---------------------------------------------------------------------------
# Backward-path donation guard (shared with tests/test_serve.py)
# ---------------------------------------------------------------------------


def test_backward_path_lowers_without_unusable_donations():
    """The backward-path half of the donation sweep: every donated
    backward jit (`_bwd_sampled_fold_j` einsum AND fused-Pallas bodies,
    `_bwd_fft_fold_chunk_j`, `_bwd_ct_fold_j`) lowers without `Some
    donated buffers were not usable` — a reappearing warning means a
    silent accumulator copy on every fold dispatch (the serve-path
    half guards the fused batch, tests/test_serve.py)."""
    import jax.numpy as jnp

    from conftest import unusable_donation_warnings
    from swiftly_tpu.parallel.streamed import (
        _bwd_ct_fold_j,
        _bwd_fft_fold_chunk_j,
        _bwd_sampled_fold_j,
        _ct_fold_tables,
        sampled_row_indices,
    )

    config = SwiftlyConfig(backend="planar", **TEST_PARAMS)
    core = config.core
    F, yB = 2, TEST_PARAMS["yB_size"]
    m = core.xM_yN_size
    offs = [0, TEST_PARAMS["xA_size"]]
    krows = jnp.asarray(sampled_row_indices(core, offs))
    R = len(offs) * m
    dt = np.dtype(core.dtype)
    acc = jnp.zeros((F, yB, yB, 2), dt)
    rows = jnp.zeros((F, R, yB, 2), dt)
    e0 = jnp.zeros(F, jnp.int32)
    problems = {}

    for label, fold in (
        ("sampled_fold", _bwd_sampled_fold_j(core)),
        ("sampled_fold_pallas", _bwd_sampled_fold_j(core, True, True)),
    ):
        bad = unusable_donation_warnings(
            lambda fold=fold: fold.lower(
                acc, rows, e0, krows, jnp.int32(0)
            ).compile()
        )
        if bad:
            problems[label] = [str(w.message) for w in bad]

    rows_g = jnp.zeros((2, F, m, yB, 2), dt)
    offs_dev = jnp.asarray(np.asarray(offs, np.int32))
    foffs0 = jnp.zeros(F, dtype=int)
    fftfold = _bwd_fft_fold_chunk_j(core, 128)
    bad = unusable_donation_warnings(
        lambda: fftfold.lower(
            acc, rows_g, offs_dev, foffs0, jnp.int32(0), jnp.int32(0)
        ).compile()
    )
    if bad:
        problems["fft_fold"] = [str(w.message) for w in bad]

    Q, Pq, kmax, tab = _ct_fold_tables(core, tuple(offs))
    ctfold = _bwd_ct_fold_j(core, Q, Pq, kmax, yB)
    bad = unusable_donation_warnings(
        lambda: ctfold.lower(
            acc, rows, e0, jnp.asarray(tab), jnp.int32(0),
        ).compile()
    )
    if bad:
        problems["ct_fold"] = [str(w.message) for w in bad]
    assert not problems, problems


def test_forward_path_lowers_without_unusable_donations(monkeypatch):
    """The forward-path half of the donation sweep: the streamed column
    group step (donated accumulator), the fused sparse slab step, and
    the group finish all lower clean, einsum AND fused-Pallas bodies,
    at BOTH accumulator shapes from the r5 bench tail — the
    [1, 1, S, xM, xM, 2] streamed-partial acc and the [5, 1, S, ...]
    grouped-finish acc whose `Some donated buffers were not usable`
    warnings this guard retires (they predate the PR 2 un-donation fix;
    a reappearance means a silent xM-sized copy per slab dispatch)."""
    import jax.numpy as jnp

    from conftest import unusable_donation_warnings
    from swiftly_tpu.parallel.streamed import (
        _column_group_finish_j,
        _column_group_step_j,
        _fused_sparse_slab_step_j,
        sampled_row_indices,
    )

    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    config = SwiftlyConfig(backend="planar", **TEST_PARAMS)
    core = config.core
    m, xM = core.xM_yN_size, core.xM_size
    yB, xA = TEST_PARAMS["yB_size"], TEST_PARAMS["xA_size"]
    dt = np.dtype(core.dtype)
    Fg = 2
    problems = {}

    # the two r5 warning shapes, scaled to the test geometry: the
    # streamed-partial acc (one chunk) and the grouped-finish acc
    for n_chunks, chunk, S in ((1, 1, 3), (5, 1, 2)):
        G = n_chunks * chunk
        col_offs = [(i * xA) % TEST_PARAMS["N"] for i in range(G)]
        krows = jnp.asarray(sampled_row_indices(core, col_offs))
        acc = jnp.zeros((n_chunks, chunk, S, xM, xM, 2), dt)
        buf = jnp.zeros((Fg, G * m, yB, 2), dt)
        foffs = jnp.zeros(Fg, jnp.int32)
        so_c = jnp.zeros((n_chunks, chunk, S, 2), jnp.int32)
        m0_c = jnp.ones((n_chunks, chunk, S, xA), core._Fb.dtype)
        e0 = jnp.zeros(Fg, jnp.int32)
        f_i = jnp.zeros(4, jnp.int32)
        r_i = jnp.arange(4, dtype=jnp.int32)
        c_i = jnp.arange(4, dtype=jnp.int32)
        v = jnp.ones(4, dt)
        for colpass in ("einsum", "pallas"):
            tag = f"{colpass}[{n_chunks}x{chunk}x{S}]"
            stepfn = _column_group_step_j(core, xA, chunk, colpass)
            bad = unusable_donation_warnings(
                lambda stepfn=stepfn: stepfn.lower(
                    acc, buf, foffs, foffs, so_c
                ).compile()
            )
            if bad:
                problems[f"group_step.{tag}"] = [
                    str(w.message) for w in bad
                ]
            fused = _fused_sparse_slab_step_j(
                core, xA, chunk, Fg, yB, colpass
            )
            bad = unusable_donation_warnings(
                lambda fused=fused: fused.lower(
                    acc, f_i, r_i, c_i, v, e0, krows, foffs, foffs, so_c
                ).compile()
            )
            if bad:
                problems[f"fused_slab_step.{tag}"] = [
                    str(w.message) for w in bad
                ]
            finfn = _column_group_finish_j(core, xA, colpass)
            bad = unusable_donation_warnings(
                lambda finfn=finfn: finfn.lower(
                    acc, so_c, m0_c, m0_c
                ).compile()
            )
            if bad:
                problems[f"group_finish.{tag}"] = [
                    str(w.message) for w in bad
                ]
    assert not problems, problems
