"""Out-of-core streamed executor tests.

The streamed path must agree with the batched whole-cover path (same math
functions, different staging) and with the analytic oracle, for both
device backends, both buffer residencies, and block sizes that do / do not
divide the facet size.
"""

import numpy as np
import pytest

from swiftly_tpu import (
    SwiftlyConfig,
    check_facet,
    check_subgrid,
    make_facet,
    make_full_facet_cover,
    make_full_subgrid_cover,
    make_subgrid,
)
from swiftly_tpu.parallel import StreamedBackward, StreamedForward

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


def _setup(backend, dtype=None):
    config = SwiftlyConfig(backend=backend, dtype=dtype, **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    return config, facet_configs, subgrid_configs, facet_tasks


@pytest.mark.parametrize("backend", ["jax", "planar"])
@pytest.mark.parametrize("residency", ["host", "device"])
@pytest.mark.parametrize("col_block", [416, 128])  # exact / ragged blocks
def test_streamed_forward_vs_oracle(backend, residency, col_block):
    config, _, subgrid_configs, facet_tasks = _setup(backend)
    fwd = StreamedForward(
        config, facet_tasks, col_block=col_block, residency=residency
    )
    out = fwd.all_subgrids(subgrid_configs)
    assert out.shape[0] == len(subgrid_configs)
    for i, sg in enumerate(subgrid_configs):
        err = check_subgrid(
            config.image_size, sg, config.core.as_complex(out[i]), SOURCES
        )
        assert err < 1e-9


@pytest.mark.parametrize("backend", ["jax", "planar"])
def test_streamed_forward_matches_batched(backend):
    from swiftly_tpu import SwiftlyForward

    config, _, subgrid_configs, facet_tasks = _setup(backend)
    batched_fwd = SwiftlyForward(config, facet_tasks, 3, 64)
    ref = np.asarray(batched_fwd.all_subgrids(subgrid_configs))
    streamed = StreamedForward(config, facet_tasks, col_block=416)
    out = streamed.all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


@pytest.mark.parametrize("backend", ["jax", "planar"])
@pytest.mark.parametrize("residency", ["host", "device"])
def test_streamed_roundtrip(backend, residency):
    config, facet_configs, subgrid_configs, facet_tasks = _setup(backend)
    fwd = StreamedForward(
        config, facet_tasks, col_block=256, residency=residency
    )
    bwd = StreamedBackward(
        config, facet_configs, col_block=256, residency=residency
    )
    for items, subgrids in fwd.stream_columns(subgrid_configs):
        bwd.add_subgrids(
            [(sg, subgrids[s]) for s, (_, sg) in enumerate(items)]
        )
    facets = bwd.finish()
    for i, fc in enumerate(facet_configs):
        err = check_facet(
            config.image_size, fc, config.core.as_complex(facets[i]), SOURCES
        )
        assert err < 3e-10


def test_streamed_backward_order_independent():
    """Feeding subgrids in shuffled order / split batches is equivalent."""
    import random

    config, facet_configs, subgrid_configs, facet_tasks = _setup("jax")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]

    bwd_a = StreamedBackward(config, facet_configs, col_block=416)
    bwd_a.add_subgrids(tasks)
    ref = bwd_a.finish()

    random.Random(7).shuffle(tasks)
    bwd_b = StreamedBackward(config, facet_configs, col_block=416)
    # split into three uneven batches, columns interleaved
    bwd_b.add_subgrids(tasks[:5])
    bwd_b.add_subgrids(tasks[5:6])
    bwd_b.add_subgrids(tasks[6:])
    out = bwd_b.finish()
    # accumulation order differs -> float non-associativity; the reference's
    # own shuffle test allows 3e-10 RMS (test_api.py:125)
    np.testing.assert_allclose(out, ref, atol=1e-10)


@pytest.mark.parametrize("backend", ["jax", "planar"])
@pytest.mark.parametrize("col_group", [1, 2])
def test_streamed_device_group_chunking(backend, col_group):
    """Sampled-pass column groups produce identical results to one group."""
    config, _, subgrid_configs, facet_tasks = _setup(backend)
    ref = StreamedForward(
        config, facet_tasks, residency="device"
    ).all_subgrids(subgrid_configs)
    out = StreamedForward(
        config, facet_tasks, residency="device", col_group=col_group
    ).all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_streamed_requires_device_backend():
    config = SwiftlyConfig(backend="numpy", **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    with pytest.raises(ValueError, match="device backend"):
        StreamedForward(config, [(fc, None) for fc in facet_configs])


def test_streamed_subgrid_equals_direct_dft():
    """Streamed subgrids equal make_subgrid's direct DFT (tier-2 parity)."""
    config, _, subgrid_configs, facet_tasks = _setup("jax")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    out = fwd.all_subgrids(subgrid_configs)
    sg = subgrid_configs[0]
    direct = make_subgrid(config.image_size, sg, SOURCES)
    np.testing.assert_array_almost_equal(
        config.core.as_complex(out[0]), direct, decimal=8
    )


# ---------------------------------------------------------------------------
# Mesh-sharded streamed execution
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "residency",
    [pytest.param("host", marks=pytest.mark.slow), "device"],
)
def test_streamed_mesh_matches_single_device(residency):
    """Streamed executors on a facet-sharded mesh == single-device."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    mesh = make_facet_mesh()

    def run(config):
        facet_configs = make_full_facet_cover(config)
        subgrid_configs = make_full_subgrid_cover(config)
        facet_tasks = [
            (fc, make_facet(config.image_size, fc, SOURCES))
            for fc in facet_configs
        ]
        fwd = StreamedForward(
            config, facet_tasks, residency=residency, col_group=2
        )
        out = fwd.all_subgrids(subgrid_configs)
        bwd = StreamedBackward(config, facet_configs, residency=residency)
        for items, subgrids in fwd.stream_columns(subgrid_configs):
            bwd.add_subgrids(
                [(sg, subgrids[s]) for s, (_, sg) in enumerate(items)]
            )
        facets = bwd.finish()
        return out, facets

    cfg_mesh = SwiftlyConfig(backend="jax", mesh=mesh, **TEST_PARAMS)
    cfg_single = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    out_mesh, facets_mesh = run(cfg_mesh)
    out_single, facets_single = run(cfg_single)
    np.testing.assert_allclose(out_mesh, out_single, atol=1e-13)
    np.testing.assert_allclose(facets_mesh, facets_single, atol=1e-13)


@pytest.mark.slow
def test_streamed_mesh_planar_roundtrip():
    """Planar f64 streamed round trip on the mesh, vs the oracle."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    mesh = make_facet_mesh()
    config = SwiftlyConfig(
        backend="planar", mesh=mesh, dtype=np.float64, **TEST_PARAMS
    )
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    fwd = StreamedForward(config, facet_tasks, residency="device")
    bwd = StreamedBackward(config, facet_configs, residency="device")
    for items, subgrids in fwd.stream_columns(subgrid_configs):
        bwd.add_subgrids(
            [(sg, subgrids[s]) for s, (_, sg) in enumerate(items)]
        )
    facets = bwd.finish()
    err = max(
        check_facet(config.image_size, fc,
                    config.core.as_complex(facets[i]), SOURCES)
        for i, fc in enumerate(facet_configs)
    )
    assert err < 3e-10


def test_streamed_mesh_facets_sharded():
    """The device-resident facet planes really live facet-sharded."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    mesh = make_facet_mesh()
    config = SwiftlyConfig(backend="jax", mesh=mesh, **TEST_PARAMS)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    fwd = StreamedForward(config, facet_tasks, residency="device")
    next(iter(fwd.stream_columns(subgrid_configs)))
    (facets,) = fwd._dev_facets
    assert len(facets.sharding.device_set) == 8
    # 9 real facets padded to 16 -> 2 per device
    assert facets.shape[0] == 16


def test_col_group_budget_accounting():
    """The sampled-group sizer must fit facets + per-G transients in the
    budget (the 32k G=4 OOM regression)."""
    from swiftly_tpu.parallel.streamed import col_group_for_budget

    config, _, _, facet_tasks = _setup("jax")
    fwd = StreamedForward(config, facet_tasks)
    # huge budget -> capped by n_cols; tiny budget -> floor of 1
    assert col_group_for_budget(fwd._base, 1e15, 7) == 7
    assert col_group_for_budget(fwd._base, 1.0, 7) == 1
    # monotone in budget
    gs = [col_group_for_budget(fwd._base, b, 10**6)
          for b in (1e9, 4e9, 16e9, 64e9)]
    assert gs == sorted(gs)


# ---------------------------------------------------------------------------
# Real-facet fast path, facet-slab streaming, sampled backward
# ---------------------------------------------------------------------------


def test_real_facet_path_detected_and_matches():
    """Point-source facets are exactly real: the planar streamed forward
    stores single real planes (half the upload) and matches batched."""
    from swiftly_tpu import SwiftlyForward

    config, _, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, residency="device")
    assert fwd._facets_real
    assert fwd._facet_data[0].ndim == 2  # single plane, not (re, im) pairs
    ref = np.asarray(
        SwiftlyForward(config, facet_tasks, 3, 64).all_subgrids(
            subgrid_configs
        )
    )
    np.testing.assert_allclose(
        fwd.all_subgrids(subgrid_configs), ref, atol=1e-10
    )


def test_complex_facet_fallback_matches():
    """Facets with imaginary content fall back to the planar-pair path."""
    from swiftly_tpu import SwiftlyForward

    config, _, subgrid_configs, facet_tasks = _setup("planar")
    rng = np.random.default_rng(3)
    facet_tasks = [
        (fc, d + 1j * rng.normal(scale=0.1, size=d.shape))
        for fc, d in facet_tasks
    ]
    fwd = StreamedForward(config, facet_tasks, residency="device")
    assert not fwd._facets_real
    ref = np.asarray(
        SwiftlyForward(config, facet_tasks, 3, 64).all_subgrids(
            subgrid_configs
        )
    )
    np.testing.assert_allclose(
        fwd.all_subgrids(subgrid_configs), ref, atol=1e-10
    )


@pytest.mark.parametrize(
    "backend",
    # planar keeps both facet_group sizes in tier-1; the jax-backend
    # pair is the same slab walk at complex dtype and rides -m slow
    [pytest.param("jax", marks=pytest.mark.slow), "planar"],
)
@pytest.mark.parametrize("facet_group", [1, 2])
def test_facet_slab_streaming_matches(backend, facet_group):
    """Facet-slab-streamed column groups == facets-resident sampled path
    (slab padding and cross-slab finished accumulation are exact)."""
    config, _, subgrid_configs, facet_tasks = _setup(backend)
    ref = StreamedForward(
        config, facet_tasks, residency="device"
    ).all_subgrids(subgrid_configs)
    out = StreamedForward(
        config, facet_tasks, residency="device",
        facet_group=facet_group, col_group=4,
    ).all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_facet_slab_streaming_auto_group():
    """facet_group with auto column-group sizing (CPU: one group)."""
    config, _, subgrid_configs, facet_tasks = _setup("planar")
    ref = StreamedForward(
        config, facet_tasks, residency="device"
    ).all_subgrids(subgrid_configs)
    out = StreamedForward(
        config, facet_tasks, residency="device", facet_group=2
    ).all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_slab_stream_triple_buffer_prefetch(monkeypatch):
    """The triple-buffered grouped stream: the background staging
    thread (h2d(k+1) ∥ compute(k) ∥ d2h(k-1)) is bit-identical to the
    two-buffer SWIFTLY_STREAM_PREFETCH=0 path, the plan stamps the
    choice, and the hit counter proves the worker actually fed every
    upload (a miss means the main thread staged inline — correct but
    the overlap is gone)."""
    from swiftly_tpu.obs import metrics

    config, _, subgrid_configs, facet_tasks = _setup("planar")
    monkeypatch.setenv("SWIFTLY_STREAM_PREFETCH", "0")
    fwd_off = StreamedForward(
        config, facet_tasks, residency="device", facet_group=2,
        col_group=4,
    )
    ref = fwd_off.all_subgrids(subgrid_configs)
    assert fwd_off.last_plan["stream_prefetch"] is False
    monkeypatch.delenv("SWIFTLY_STREAM_PREFETCH")
    metrics.reset()
    metrics.enable()
    try:
        fwd_on = StreamedForward(
            config, facet_tasks, residency="device", facet_group=2,
            col_group=4,
        )
        out = fwd_on.all_subgrids(subgrid_configs)
        counters = metrics.export()["counters"]
    finally:
        metrics.disable()
        metrics.reset()
    np.testing.assert_array_equal(out, ref)
    assert fwd_on.last_plan["stream_prefetch"] is True
    assert counters["fwd.slab_prefetch_hits"] >= 1
    assert counters.get("fwd.slab_prefetch_misses", 0) == 0


def test_forward_rejects_sampled_residency():
    config = SwiftlyConfig(backend="jax", **TEST_PARAMS)
    fcs = make_full_facet_cover(config)
    with pytest.raises(ValueError, match="sampled"):
        StreamedForward(
            config,
            [(fc, np.zeros((fc.size, fc.size))) for fc in fcs],
            residency="sampled",
        )


@pytest.mark.parametrize("backend", ["jax", "planar"])
@pytest.mark.parametrize(
    "fold_group,fold_mode",
    [
        (1, "sampled"),
        (3, "sampled"),
        (1, "fft"),
        (1, "ct"),
        # the fold_group axis for the fft and ct bodies is -m slow
        # (tier-1 brushes the driver window): batching more columns per
        # fold is the same code path at a different static shape, the
        # sampled body keeps both group sizes in tier-1, auto runs ct
        # on every call of 2+ columns in the other tier-1 tests, and
        # the grouped fft/ct feed paths are exercised by the
        # add_subgrid_group chunking tests
        pytest.param(3, "fft", marks=pytest.mark.slow),
        pytest.param(3, "ct", marks=pytest.mark.slow),
    ],
)
def test_sampled_backward_matches_fft_backward(
    backend, fold_group, fold_mode, monkeypatch
):
    """All three sampled-residency fold bodies (adjoint-sampled einsum,
    FFT spectral embed, CT-factored) == the FFT-based facet pass."""
    monkeypatch.setenv("SWIFTLY_FOLD", fold_mode)
    config, facet_configs, subgrid_configs, facet_tasks = _setup(backend)
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]
    ref_b = StreamedBackward(config, facet_configs, residency="device")
    ref_b.add_subgrids(tasks)
    ref = ref_b.finish()
    out_b = StreamedBackward(
        config, facet_configs, residency="sampled", fold_group=fold_group
    )
    assert out_b._fold_mode == fold_mode
    out_b.add_subgrids(tasks)
    out = out_b.finish()
    np.testing.assert_allclose(out, ref, atol=1e-10)


def _m32k():
    """(yN, m) of the 32k round trip's configuration."""
    from swiftly_tpu import SWIFT_CONFIGS

    c = SWIFT_CONFIGS["32k[1]-n16k-512"]
    return c["yN_size"], c["xM_size"] * c["yN_size"] // c["N"]


@pytest.mark.parametrize(
    "mode,yN,n_rows,meshed,row_slab,want",
    [
        # the 32k round trip's calls (m 256, Q 128): g = 2 and g = 1
        ("auto", _m32k()[0], 2 * _m32k()[1], False, False, "ct"),
        ("auto", _m32k()[0], _m32k()[1], False, False, "ct"),
        # below CT_MIN_LANE_DEPTH * Q rows (test shapes: m 128, Q 128)
        ("auto", 512, 128, False, False, "sampled"),
        ("auto", _m32k()[0], 255, False, False, "sampled"),
        # a row slab or a facet mesh keeps the sampled body
        ("auto", _m32k()[0], 512, False, True, "sampled"),
        ("auto", _m32k()[0], 512, True, False, "sampled"),
        # an explicit SWIFTLY_FOLD overrides auto
        ("sampled", _m32k()[0], 512, False, False, "sampled"),
        ("ct", 512, 128, False, False, "ct"),
        ("fft", _m32k()[0], 512, False, False, "fft"),
    ],
)
def test_fold_body_selection(
    mode, yN, n_rows, meshed, row_slab, want, monkeypatch
):
    """`select_fold_body`: CT where the call is deep against Q, else
    the sampled body; SWIFTLY_FOLD forces one."""
    from swiftly_tpu.parallel.streamed import (
        resolve_fold_mode,
        select_fold_body,
    )

    if mode == "auto":
        monkeypatch.delenv("SWIFTLY_FOLD", raising=False)
    else:
        monkeypatch.setenv("SWIFTLY_FOLD", mode)
    got = select_fold_body(resolve_fold_mode(), yN, n_rows, meshed, row_slab)
    assert got == want


def test_fold_counters_count_every_call(monkeypatch):
    """Under auto, each fold call counts once under the body it ran: at
    the test shapes 5 columns in fold groups of 2 are two CT calls (256
    rows) and one sampled call (128 rows); both bodies time under the
    plan's priced fold stage, and the facets equal the all-sampled
    backward's."""
    from swiftly_tpu.obs import metrics

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]
    n_cols = len({sg.off0 for sg in subgrid_configs})

    def run():
        b = StreamedBackward(
            config, facet_configs, residency="sampled", fold_group=2
        )
        b.add_subgrids(tasks)
        return b.finish()

    monkeypatch.setenv("SWIFTLY_FOLD", "sampled")
    ref = run()
    monkeypatch.delenv("SWIFTLY_FOLD")
    metrics.reset()
    metrics.enable()
    try:
        out = run()
        exported = metrics.export()
    finally:
        metrics.disable()
        metrics.reset()
    counters, stages = exported["counters"], exported["stages"]
    assert counters["bwd.ct_folds"] == n_cols // 2
    assert counters["bwd.sampled_folds"] == n_cols % 2
    assert counters["bwd.ct_folds"] + counters["bwd.sampled_folds"] == (
        -(-n_cols // 2)
    )
    assert stages["bwd.sampled_fold"]["count"] == -(-n_cols // 2)
    assert stages["bwd.sampled_fold"]["flops"] > 0
    np.testing.assert_allclose(out, ref, atol=1e-10)


@pytest.mark.parametrize(
    "yB,per_col,budget,want",
    [
        # 32k: 9 facets, Q 128, Qi 88, P 128, f32 planes; 1.2 GB reserve
        (11264, 9 * (128 + 88) * 128 * 4 * 2, 1.2e9, 512),
        (11264, 1, 1e12, 11264),  # everything fits: one launch
        (416, 1000, 1.1e5, 104),  # no lane-aligned divisor of 416
    ],
)
def test_ct_fold_width(yB, per_col, budget, want):
    from swiftly_tpu.parallel.streamed import _ct_fold_width

    assert _ct_fold_width(yB, per_col, budget) == want


def test_ct_fold_several_launches_matches_sampled(monkeypatch):
    """With a small reserve the CT fold runs each call as several
    j-window launches (W < yB), and equals the sampled fold."""
    from swiftly_tpu.plan import model
    from swiftly_tpu.parallel import streamed as st

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]
    widths = []
    real_width = st._ct_fold_width

    def width(*args):
        widths.append(real_width(*args))
        return widths[-1]

    def run(mode):
        monkeypatch.setenv("SWIFTLY_FOLD", mode)
        b = StreamedBackward(
            config, facet_configs, residency="sampled", fold_group=3
        )
        b.add_subgrids(tasks)
        return b.finish()

    ref = run("sampled")
    monkeypatch.setattr(model, "DEFAULT_RESERVE_BYTES", 5e6)
    monkeypatch.setattr(st, "_ct_fold_width", width)
    out = run("ct")
    yB = TEST_PARAMS["yB_size"]
    assert widths and all(yB % w == 0 and w < yB for w in widths)
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_sampled_fold_row_blocking(monkeypatch):
    """The row-blocked adjoint fold — multiple blocks including a clamped
    final block (416 % 100 != 0) — is exactly the single-block fold.

    This is the 32k-OOM fix's correctness pin: blocking bounds the fold
    transient to [F, B, yB] instead of a second full accumulator."""
    from swiftly_tpu.parallel import streamed as st

    # the sampled body's row blocking, whatever auto would pick here
    monkeypatch.setenv("SWIFTLY_FOLD", "sampled")

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]

    def run():
        b = StreamedBackward(
            config, facet_configs, residency="sampled", fold_group=2
        )
        b.add_subgrids(tasks)
        # the fold-completion pipeline never holds more than 2 checksums
        assert len(b._fold_inflight) <= 2
        return b.finish()

    ref = run()
    st._bwd_sampled_fold_fn.cache_clear()
    st._bwd_sampled_fold_j.cache_clear()
    monkeypatch.setenv("SWIFTLY_FOLD_BLOCK_MB", "3")  # ~100-row blocks
    assert st._fold_row_block(len(facet_configs), 416, 8) < 416
    try:
        out = run()
    finally:
        st._bwd_sampled_fold_fn.cache_clear()
        st._bwd_sampled_fold_j.cache_clear()
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_facet_partitioned_sampled_backward_matches_full():
    """The 64k-scale mechanism at test size: running the sampled
    backward as per-facet-subset passes (each seeing ALL subgrids)
    and concatenating equals the single full-facet-set backward —
    the accumulator partitioning the bench uses when the whole
    image-space accumulator exceeds HBM."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, residency="device")
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]

    full_b = StreamedBackward(config, facet_configs, residency="sampled")
    full_b.add_subgrids(tasks)
    full = full_b.finish()

    parts = []
    for i0 in range(0, len(facet_configs), 2):
        part_b = StreamedBackward(
            config, facet_configs[i0 : i0 + 2], residency="sampled"
        )
        part_b.add_subgrids(tasks)
        parts.append(part_b.finish())
    np.testing.assert_allclose(np.concatenate(parts), full, atol=1e-12)


def test_row_slab_backward_matches_whole_facet():
    """The output-row-slab partition axis (the 128k mechanism): sampled
    backwards over row slabs [0, h) and [h, yB), concatenated along the
    row axis, equal the whole-facet backward — including a slab height
    that does not divide the fold's row-block tiling."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, residency="device")
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]

    full_b = StreamedBackward(config, facet_configs, residency="sampled")
    full_b.add_subgrids(tasks)
    full = full_b.finish()

    yB = facet_configs[0].size
    slabs = []
    for r0, r1 in [(0, 150), (150, yB)]:
        slab_b = StreamedBackward(
            config, facet_configs, residency="sampled", row_slab=(r0, r1)
        )
        slab_b.add_subgrids(tasks)
        out = slab_b.finish()
        assert out.shape[1] == r1 - r0
        slabs.append(out)
    np.testing.assert_allclose(
        np.concatenate(slabs, axis=1), full, atol=1e-12
    )


@pytest.mark.slow
def test_row_slab_composes_with_facet_partition():
    """Facet subsets x row slabs (the full 128k partition grid) tile the
    whole-facet backward exactly.

    ``-m slow``-gated (tier-1 brushes the driver window): each axis is
    pinned separately in tier-1 (`test_row_slab_backward_matches_whole_
    facet`, `test_facet_partitioned_sampled_backward_matches_full`),
    the feed-once/fold-many schedule tests in tests/test_spill.py pin
    multi-pass composition bit-identically, and the 128k dryrun proxy
    (tests/test_128k.py) exercises the composed grid at true geometry."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, residency="device")
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]

    full_b = StreamedBackward(config, facet_configs, residency="sampled")
    full_b.add_subgrids(tasks)
    full = full_b.finish()

    yB = facet_configs[0].size
    h = -(-yB // 2)
    facet_parts = []
    for i0 in range(0, len(facet_configs), 2):
        row_parts = []
        for r0 in range(0, yB, h):
            b = StreamedBackward(
                config, facet_configs[i0 : i0 + 2], residency="sampled",
                row_slab=(r0, min(r0 + h, yB)),
            )
            b.add_subgrids(tasks)
            row_parts.append(b.finish())
        facet_parts.append(np.concatenate(row_parts, axis=1))
    np.testing.assert_allclose(
        np.concatenate(facet_parts), full, atol=1e-12
    )


def test_row_slab_validation():
    config, facet_configs, _, _ = _setup("planar")
    yB = facet_configs[0].size
    with pytest.raises(ValueError, match="residency"):
        StreamedBackward(
            config, facet_configs, residency="device", row_slab=(0, 10)
        )
    with pytest.raises(ValueError, match="rows"):
        StreamedBackward(
            config, facet_configs, residency="sampled",
            row_slab=(10, yB + 1),
        )
    with pytest.raises(ValueError, match="sampled fold"):
        import os

        prior = os.environ.get("SWIFTLY_FOLD")
        os.environ["SWIFTLY_FOLD"] = "ct"
        try:
            StreamedBackward(
                config, facet_configs, residency="sampled",
                row_slab=(0, 10),
            )
        finally:
            if prior is None:
                del os.environ["SWIFTLY_FOLD"]
            else:
                os.environ["SWIFTLY_FOLD"] = prior


def test_streamed_rejects_empty_facets():
    config = SwiftlyConfig(backend="planar", **TEST_PARAMS)
    with pytest.raises(ValueError, match="non-empty"):
        StreamedForward(config, [], residency="device")


def test_sampled_backward_roundtrip_device_stack():
    """Forward device columns feed the sampled backward with NO host
    round trip (`add_subgrid_stack`); the round trip matches the oracle
    at the reference's own 3e-10 threshold."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks, residency="device")
    bwd = StreamedBackward(config, facet_configs, residency="sampled")
    for items, out in fwd.stream_columns(
        subgrid_configs, device_arrays=True
    ):
        bwd.add_subgrid_stack([sg for _, sg in items], out[: len(items)])
    facets = bwd.finish()
    for i, fc in enumerate(facet_configs):
        err = check_facet(
            config.image_size, fc, config.core.as_complex(facets[i]), SOURCES
        )
        assert err < 3e-10


def test_sampled_backward_checkpoint(tmp_path):
    """Sampled-residency snapshots restore exactly; cross-residency
    restores fail loudly."""
    from swiftly_tpu.utils.checkpoint import (
        restore_streamed_backward_state,
        save_streamed_backward_state,
    )

    config, facet_configs, subgrid_configs, facet_tasks = _setup("jax")
    fwd = StreamedForward(config, facet_tasks, col_block=416)
    subgrids = fwd.all_subgrids(subgrid_configs)
    tasks = [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]
    half = len(tasks) // 2

    b1 = StreamedBackward(config, facet_configs, residency="sampled")
    b1.add_subgrids(tasks[:half])
    path = tmp_path / "ck.npz"
    save_streamed_backward_state(
        path, b1, [(sg.off0, sg.off1) for sg, _ in tasks[:half]]
    )

    b2 = StreamedBackward(config, facet_configs, residency="sampled")
    done = restore_streamed_backward_state(path, b2)
    assert len(done) == half
    b2.add_subgrids(tasks[half:])
    out = b2.finish()

    ref_b = StreamedBackward(config, facet_configs, residency="sampled")
    ref_b.add_subgrids(tasks)
    ref = ref_b.finish()
    np.testing.assert_allclose(out, ref, atol=1e-10)

    b3 = StreamedBackward(config, facet_configs, residency="device")
    with pytest.raises(ValueError, match="residency"):
        restore_streamed_backward_state(path, b3)


@pytest.mark.parametrize(
    "fold_mode",
    [
        "sampled",
        # the ct/fft mesh variants run the same facet-local shard_map
        # wrapping at a different fold body; single-device fold-mode
        # parity keeps its own tier-1 coverage
        # (test_sampled_backward_matches_fft_backward), so these ride
        # -m slow per the tier-1 budget
        pytest.param("ct", marks=pytest.mark.slow),
        pytest.param("fft", marks=pytest.mark.slow),
    ],
)
def test_sampled_backward_mesh_matches_single_device(
    fold_mode, monkeypatch
):
    """The sampled backward on a facet-sharded mesh == single device,
    for every fold body (the ct/fft shard_map variants are facet-local
    with no collectives and must match exactly)."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    monkeypatch.setenv("SWIFTLY_FOLD", fold_mode)
    mesh = make_facet_mesh()

    def run(config):
        facet_configs = make_full_facet_cover(config)
        subgrid_configs = make_full_subgrid_cover(config)
        facet_tasks = [
            (fc, make_facet(config.image_size, fc, SOURCES))
            for fc in facet_configs
        ]
        fwd = StreamedForward(config, facet_tasks, col_block=416)
        subgrids = fwd.all_subgrids(subgrid_configs)
        bwd = StreamedBackward(config, facet_configs, residency="sampled")
        bwd.add_subgrids(
            [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)]
        )
        return bwd.finish()

    ref = run(SwiftlyConfig(backend="jax", **TEST_PARAMS))
    out = run(SwiftlyConfig(backend="jax", mesh=mesh, **TEST_PARAMS))
    np.testing.assert_allclose(out, ref, atol=1e-13)


def test_grouped_budget_accounting():
    from swiftly_tpu.parallel.streamed import grouped_col_group_for_budget

    config, _, _, facet_tasks = _setup("planar")
    fwd = StreamedForward(config, facet_tasks)
    base = fwd._base
    # huge budget -> capped at the (chunk-rounded) column count
    assert grouped_col_group_for_budget(base, 1e15, 40, 5, 228, True, 1, 4) == 40
    # tiny budget -> floor of one column (the CALLER picks the
    # (G, chunk) rounding since r4)
    assert grouped_col_group_for_budget(base, 1.0, 40, 5, 228, True, 1, 4) == 1
    # monotone in budget
    gs = [
        grouped_col_group_for_budget(base, b, 10**6, 5, 228, True, 1, 4)
        for b in (1e9, 4e9, 16e9, 64e9)
    ]
    assert gs == sorted(gs)


def test_sparse_facets_match_dense():
    """Device-synthesised sparse facets == dense host facets, for both
    the resident sampled path and facet-slab streaming, and for the
    sampled round trip. Also pins densify() == make_facet(...).real."""
    from swiftly_tpu import make_sparse_facet

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    sparse_tasks = [
        (fc, make_sparse_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    for (fc, dense), (_, sp) in zip(facet_tasks, sparse_tasks):
        np.testing.assert_allclose(
            sp.densify(np.float64), np.asarray(dense).real, atol=1e-12
        )

    ref = StreamedForward(
        config, facet_tasks, residency="device"
    ).all_subgrids(subgrid_configs)
    fwd_sp = StreamedForward(config, sparse_tasks, residency="device")
    out = fwd_sp.all_subgrids(subgrid_configs)
    assert fwd_sp._facets_sparse
    np.testing.assert_allclose(out, ref, atol=1e-10)

    fwd_slab = StreamedForward(
        config, sparse_tasks, residency="device", facet_group=2
    )
    out_slab = fwd_slab.all_subgrids(subgrid_configs)
    assert (fwd_slab.last_plan or {}).get("facet_source") == (
        "device-synth-sparse"
    )
    np.testing.assert_allclose(out_slab, ref, atol=1e-10)

    # synth_facet_device returns the exact dense plane
    plane = np.asarray(fwd_sp.synth_facet_device(0))
    np.testing.assert_allclose(
        plane, sparse_tasks[0][1].densify(plane.dtype), atol=0
    )


def test_sparse_facets_densify_on_host_residency():
    """Sparse descriptors still work where synthesis is unsupported
    (host residency): they densify transparently."""
    from swiftly_tpu import make_sparse_facet

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    sparse_tasks = [
        (fc, make_sparse_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    ref = StreamedForward(
        config, facet_tasks, residency="host"
    ).all_subgrids(subgrid_configs)
    fwd = StreamedForward(config, sparse_tasks, residency="host")
    assert not fwd._facets_sparse
    out = fwd.all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_mixed_sparse_dense_facets_densify():
    """A stack mixing SparseRealFacet and dense facets densifies the
    sparse entries and matches the all-dense result."""
    from swiftly_tpu import make_sparse_facet

    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")
    mixed = [
        (fc, make_sparse_facet(config.image_size, fc, SOURCES))
        if i % 2 == 0
        else (fc, data)
        for i, (fc, data) in enumerate(facet_tasks)
    ]
    ref = StreamedForward(
        config, facet_tasks, residency="device"
    ).all_subgrids(subgrid_configs)
    fwd = StreamedForward(config, mixed, residency="device")
    assert not fwd._facets_sparse  # mixed -> densified
    out = fwd.all_subgrids(subgrid_configs)
    np.testing.assert_allclose(out, ref, atol=1e-10)


@pytest.mark.parametrize(
    "facet_group",
    [pytest.param(None, marks=pytest.mark.slow), 2],
)
def test_group_feeding_matches_per_column(facet_group):
    """stream_column_groups + add_subgrid_group == per-column feeding,
    for both resident and facet-slab forward paths."""
    config, facet_configs, subgrid_configs, facet_tasks = _setup("planar")

    fwd_a = StreamedForward(
        config, facet_tasks, residency="device", facet_group=facet_group,
        col_group=4,
    )
    bwd_a = StreamedBackward(config, facet_configs, residency="sampled")
    for items, out in fwd_a.stream_columns(
        subgrid_configs, device_arrays=True
    ):
        bwd_a.add_subgrid_stack([sg for _, sg in items], out[: len(items)])
    ref = bwd_a.finish()

    fwd_b = StreamedForward(
        config, facet_tasks, residency="device", facet_group=facet_group,
        col_group=4,
    )
    bwd_b = StreamedBackward(config, facet_configs, residency="sampled")
    n_cols = 0
    for per_col, group in fwd_b.stream_column_groups(subgrid_configs):
        n_cols += len(per_col)
        bwd_b.add_subgrid_group(
            [[sg for _, sg in col] for col in per_col], group
        )
    assert n_cols == len({sg.off0 for sg in subgrid_configs})
    out = bwd_b.finish()
    np.testing.assert_allclose(out, ref, atol=1e-10)


def test_group_feeding_mesh_fallback():
    """add_subgrid_group on a mesh falls back to per-column sharded
    feeding and still reproduces the facets."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    mesh = make_facet_mesh()
    config = SwiftlyConfig(
        backend="planar", mesh=mesh, dtype=np.float64, **TEST_PARAMS
    )
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, SOURCES))
        for fc in facet_configs
    ]
    fwd = StreamedForward(config, facet_tasks, residency="device")
    bwd = StreamedBackward(config, facet_configs, residency="sampled")
    for per_col, group in fwd.stream_column_groups(subgrid_configs):
        bwd.add_subgrid_group(
            [[sg for _, sg in col] for col in per_col], group
        )
    facets = bwd.finish()
    for i, fc in enumerate(facet_configs):
        err = check_facet(
            config.image_size, fc, config.core.as_complex(facets[i]),
            SOURCES,
        )
        assert err < 3e-10


@pytest.mark.parametrize("backend", ["jax", "planar"])
def test_colpass_einsum_matches_fft_body(backend):
    """The operator-matrix einsum column pass is mathematically identical
    to the per-facet fft chain (its operators are BUILT from that chain):
    same finished subgrids, and step+finish pairs agree across modes."""
    import jax.numpy as jnp

    from swiftly_tpu.parallel.streamed import (
        _column_group_finish_fn,
        _column_group_step_fn,
        _column_pass_fwd_einsum_fn,
        _column_pass_fwd_fn,
    )

    config, _, subgrid_configs, facet_tasks = _setup(backend)
    core = config.core
    from swiftly_tpu.api import _subgrid_masks
    from swiftly_tpu.parallel.streamed import _group_full_columns

    groups = _group_full_columns(subgrid_configs)
    off0 = next(iter(groups))
    items = groups[off0]
    sg_offs = jnp.asarray([(sg.off0, sg.off1) for _, sg in items])
    masks = [_subgrid_masks(sg) for _, sg in items]
    rdt = core._Fb.dtype
    m0 = jnp.asarray(np.asarray([mk[0] for mk in masks]), rdt)
    m1 = jnp.asarray(np.asarray([mk[1] for mk in masks]), rdt)
    F = len(facet_tasks)
    foffs0 = jnp.asarray([fc.off0 for fc, _ in facet_tasks])
    foffs1 = jnp.asarray([fc.off1 for fc, _ in facet_tasks])
    rng = np.random.default_rng(7)
    m, yB = core.xM_yN_size, facet_tasks[0][0].size
    if backend == "planar":
        NMBF = jnp.asarray(rng.standard_normal((F, m, yB, 2)))
    else:
        NMBF = jnp.asarray(
            rng.standard_normal((F, m, yB))
            + 1j * rng.standard_normal((F, m, yB))
        )
    size = subgrid_configs[0].size

    import os

    prior = os.environ.get("SWIFTLY_COLPASS")
    ein = _column_pass_fwd_einsum_fn(core, size)(
        NMBF, foffs0, foffs1, sg_offs, m0, m1
    )
    os.environ["SWIFTLY_COLPASS"] = "fft"
    try:
        fft_body = _column_pass_fwd_fn(core, size)(
            NMBF, foffs0, foffs1, sg_offs, m0, m1
        )
    finally:
        if prior is None:
            del os.environ["SWIFTLY_COLPASS"]
        else:
            os.environ["SWIFTLY_COLPASS"] = prior
    np.testing.assert_allclose(
        np.asarray(ein), np.asarray(fft_body), atol=1e-10
    )

    # step(finish=False) + matching group finish agree for BOTH bodies
    S = sg_offs.shape[0]
    xM = core.xM_size
    tail = (2,) if backend == "planar" else ()
    # one-column "group": buf [F, 1*m, yB]
    buf = NMBF.reshape((F, m) + NMBF.shape[2:])
    so_g = sg_offs[None, None]
    for colpass in ("einsum", "fft"):
        acc0 = jnp.zeros((1, 1, S, xM, xM) + tail, NMBF.dtype)
        step = _column_group_step_fn(core, size, 1, colpass)
        fin = _column_group_finish_fn(core, size, colpass)
        out_pair = fin(
            step(acc0, buf, foffs0, foffs1, so_g),
            so_g, m0[None, None], m1[None, None],
        )
        np.testing.assert_allclose(
            np.asarray(out_pair[0, 0]), np.asarray(fft_body), atol=1e-10
        )


@pytest.mark.parametrize("backend", ["jax", "planar"])
def test_colpass_bwd_einsum_matches_fft_body(backend):
    """The adjoint operator-matrix backward column pass (non-default;
    SWIFTLY_COLPASS_BWD=einsum) equals the fft-chain body."""
    import jax.numpy as jnp

    from swiftly_tpu.parallel.streamed import (
        _column_pass_bwd_einsum_fn,
        _column_pass_bwd_fft_fn,
        _group_full_columns,
    )

    config, _, subgrid_configs, facet_tasks = _setup(backend)
    core = config.core
    groups = _group_full_columns(subgrid_configs)
    items = groups[next(iter(groups))]
    sg_offs = jnp.asarray([(sg.off0, sg.off1) for _, sg in items])
    F = len(facet_tasks)
    foffs0 = jnp.asarray([fc.off0 for fc, _ in facet_tasks])
    foffs1 = jnp.asarray([fc.off1 for fc, _ in facet_tasks])
    yB = facet_tasks[0][0].size
    rdt = core._Fb.dtype
    from swiftly_tpu.api import _FacetStack

    stack = _FacetStack([fc for fc, _ in facet_tasks])
    m1 = jnp.asarray(np.asarray(stack.masks1), rdt)
    rng = np.random.default_rng(11)
    S, xA = sg_offs.shape[0], subgrid_configs[0].size
    if backend == "planar":
        sgs = jnp.asarray(rng.standard_normal((S, xA, xA, 2)))
    else:
        sgs = jnp.asarray(
            rng.standard_normal((S, xA, xA))
            + 1j * rng.standard_normal((S, xA, xA))
        )
    ein = _column_pass_bwd_einsum_fn(core, yB)(
        sgs, sg_offs, foffs0, foffs1, m1
    )
    ref = _column_pass_bwd_fft_fn(core, yB)(
        sgs, sg_offs, foffs0, foffs1, m1
    )
    np.testing.assert_allclose(np.asarray(ein), np.asarray(ref), atol=1e-10)
