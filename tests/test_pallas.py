"""Pallas kernel tests (interpreter mode).

The kernels are validated against the einsum formulation on CPU; that
they compile for a v5e at real widths is tests/test_tpu_compile.py's
job.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from swiftly_tpu.ops.pallas_kernels import cmatmul_pallas, pallas_enabled


@pytest.mark.parametrize(
    "B,K,N",
    [
        (8, 16, 16),      # single block
        (300, 228, 228),  # ragged: exercises padding on every axis
        (512, 256, 512),  # multi-block contraction
    ],
)
def test_cmatmul_matches_einsum(B, K, N):
    rng = np.random.default_rng(0)
    z = rng.normal(size=(B, K)) + 1j * rng.normal(size=(B, K))
    w = rng.normal(size=(K, N)) + 1j * rng.normal(size=(K, N))
    zr = jnp.asarray(z.real, jnp.float32)
    zi = jnp.asarray(z.imag, jnp.float32)
    wr = jnp.asarray(w.real, jnp.float32)
    wi = jnp.asarray(w.imag, jnp.float32)
    outr, outi = cmatmul_pallas(
        zr, zi, wr, wi, bm=128, bn=128, bk=128, interpret=True
    )
    got = np.asarray(outr) + 1j * np.asarray(outi)
    ref = z @ w
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() / scale < 1e-5


def test_bwd_fold_pallas_matches_reference():
    """The fused adjoint-fold kernel against its dual-matmul+accumulate
    reference, on ragged shapes that exercise padding on every axis."""
    from swiftly_tpu.ops.pallas_kernels import bwd_fold_pallas

    rng = np.random.default_rng(3)
    B, J, R = 100, 300, 70
    acc_r, acc_i, bc, bs, rr, ri = (
        rng.normal(size=s).astype(np.float32)
        for s in ((B, J), (B, J), (R, B), (R, B), (R, J), (R, J))
    )
    w = rng.normal(size=(B, 1)).astype(np.float32)
    outr, outi = bwd_fold_pallas(
        *map(jnp.asarray, (acc_r, acc_i, bc, bs, rr, ri, w)),
        bm=32, bn=128, bk=32, interpret=True,
    )
    ref_r = acc_r + w * (bc.T @ rr + bs.T @ ri)
    ref_i = acc_i + w * (bc.T @ ri - bs.T @ rr)
    scale = max(np.abs(ref_r).max(), np.abs(ref_i).max())
    assert np.abs(np.asarray(outr) - ref_r).max() / scale < 1e-5
    assert np.abs(np.asarray(outi) - ref_i).max() / scale < 1e-5


def test_sampled_fold_pallas_matches_einsum_fold():
    """The full fused-Pallas sampled-fold body (interpreter mode)
    against the einsum fold, whole-facet AND row-slab: results agree to
    f32 sum-reorder tolerance (the fused kernel tiles the contraction,
    so partial-sum ORDER may differ — the tentpole's documented
    tolerance; 1e-5 relative, usually bit-identical when the
    contraction fits one tile)."""
    from swiftly_tpu import SwiftlyConfig
    from swiftly_tpu.parallel.streamed import (
        _bwd_sampled_fold_fn,
        sampled_row_indices,
    )

    params = {
        "W": 13.5625, "fov": 1.0, "N": 1024, "yB_size": 416,
        "yN_size": 512, "xA_size": 228, "xM_size": 256,
    }
    core = SwiftlyConfig(backend="planar", **params).core
    F, yB = 3, params["yB_size"]
    m = core.xM_yN_size
    offs = [0, params["xA_size"]]
    krows = jnp.asarray(sampled_row_indices(core, offs))
    rng = np.random.default_rng(4)
    rows = jnp.asarray(
        rng.normal(size=(F, len(offs) * m, yB, 2)).astype(np.float32)
    )
    e0 = jnp.asarray(np.array([-208, 0, 208], np.int32))
    ref_fold = _bwd_sampled_fold_fn(core)
    pal_fold = _bwd_sampled_fold_fn(core, True, True)
    for r0, Rs in ((0, yB), (100, 128)):  # whole facet + a row slab
        acc = jnp.asarray(
            rng.normal(size=(F, Rs, yB, 2)).astype(np.float32)
        )
        ref = ref_fold(acc, rows, e0, krows, jnp.int32(r0))
        got = pal_fold(acc, rows, e0, krows, jnp.int32(r0))
        scale = float(jnp.abs(ref).max())
        assert float(jnp.abs(got - ref).max()) / scale < 1e-5


# ---------------------------------------------------------------------------
# fused column-pass kernel (colpass_pallas): the forward-path MFU tentpole.
# Interpreter mode makes every test here a CPU tier-1 equivalence proof of
# the SAME grid program the TPU executors select via SWIFTLY_COLPASS=auto.

TEST_PARAMS = {
    "W": 13.5625, "fov": 1.0, "N": 1024, "yB_size": 416,
    "yN_size": 512, "xA_size": 228, "xM_size": 256,
}


def _colpass_fixture(F=3, S=5, seed=7):
    """A planar core + one synthetic column at the shared test geometry."""
    from swiftly_tpu import SwiftlyConfig

    core = SwiftlyConfig(backend="planar", **TEST_PARAMS).core
    m, yB, xA = core.xM_yN_size, TEST_PARAMS["yB_size"], TEST_PARAMS["xA_size"]
    rng = np.random.default_rng(seed)
    offs = [0, 192, -192, 384, -384][:F]
    foffs = jnp.asarray(np.asarray(offs, np.int32))
    sg_offs = jnp.asarray(
        [[(i * xA) % TEST_PARAMS["N"]] * 2 for i in range(S)], jnp.int32
    )
    NMBF = jnp.asarray(rng.normal(size=(F, m, yB, 2)).astype(np.float32))
    masks0 = jnp.ones((S, xA), core._Fb.dtype)
    masks1 = jnp.ones((S, xA), core._Fb.dtype)
    return core, NMBF, foffs, sg_offs, masks0, masks1


@pytest.mark.parametrize(
    "F,S,sblock,bk",
    [
        (3, 5, None, None),    # whole column, one S block, K one tile
        (3, 5, "2", None),     # ragged S: Sb=2 -> 3 blocks, 1 padded row
        (3, 5, None, "96"),    # K=Q not a block multiple: padded k loop
        pytest.param(5, 11, "3", "96", marks=pytest.mark.slow),
    ],
)
def test_colpass_fwd_pallas_matches_einsum(monkeypatch, F, S, sblock, bk):
    """The fused Pallas column pass against the einsum body: identical
    crop-finished subgrids AND identical pre-finish image-space partials
    (the group step/finish contract), to f32 sum-reorder tolerance."""
    from swiftly_tpu.parallel.streamed import (
        _column_pass_fwd_einsum_fn,
        _column_pass_fwd_pallas_fn,
    )

    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    if sblock:
        monkeypatch.setenv("SWIFTLY_COLPASS_SBLOCK", sblock)
    if bk:
        monkeypatch.setenv("SWIFTLY_COLPASS_BK", bk)
        monkeypatch.setenv("SWIFTLY_COLPASS_BM", "96")
    core, NMBF, foffs, sg_offs, masks0, masks1 = _colpass_fixture(F, S)
    xA = TEST_PARAMS["xA_size"]
    for finish in (True, False):
        ref_fn = _column_pass_fwd_einsum_fn(core, xA, finish=finish)
        pal_fn = _column_pass_fwd_pallas_fn(core, xA, finish=finish)
        ref = ref_fn(NMBF, foffs, foffs, sg_offs, masks0, masks1)
        got = pal_fn(NMBF, foffs, foffs, sg_offs, masks0, masks1)
        assert got.shape == ref.shape
        scale = float(jnp.abs(ref).max())
        assert float(jnp.abs(got - ref).max()) / scale < 1e-5, finish


@pytest.mark.parametrize("sblock", [None, "2"])
def test_colpass_bwd_pallas_matches_einsum(monkeypatch, sblock):
    """The backward column body with the fused kernel (reduce_f=False:
    Z_sf = E0_f @ emb_s @ E1_f, subgrid broadcast over facets) against
    the einsum pair — the adjoint call sites of the one shared kernel."""
    from swiftly_tpu.parallel.streamed import _column_pass_bwd_einsum_fn

    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    if sblock:
        monkeypatch.setenv("SWIFTLY_COLPASS_SBLOCK", sblock)
    F, S = 3, 5
    core, _, foffs, sg_offs, _, _ = _colpass_fixture(F, S)
    yB, xA = TEST_PARAMS["yB_size"], TEST_PARAMS["xA_size"]
    rng = np.random.default_rng(11)
    subgrids = jnp.asarray(
        rng.normal(size=(S, xA, xA, 2)).astype(np.float32)
    )
    masks1 = jnp.ones((F, yB), core._Fb.dtype)
    ref_fn = _column_pass_bwd_einsum_fn(core, yB)
    pal_fn = _column_pass_bwd_einsum_fn(core, yB, use_pallas=True)
    ref = ref_fn(subgrids, sg_offs, foffs, foffs, masks1)
    got = pal_fn(subgrids, sg_offs, foffs, foffs, masks1)
    assert got.shape == ref.shape
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(got - ref).max()) / scale < 1e-5


def test_colpass_pallas_shard_local_parity(monkeypatch):
    """Shard-local fused colpass under a facet-sharded mesh (the
    `mesh.engine` call shape: local-facet kernel reduce + one per-column
    psum) agrees with the single-chip einsum body over all facets."""
    import jax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from swiftly_tpu.parallel.streamed import (
        _colpass_operators,
        _colpass_pallas_body,
        _column_pass_fwd_einsum_fn,
    )

    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    F, S = 4, 5
    core, NMBF, foffs, sg_offs, masks0, masks1 = _colpass_fixture(F, S)
    xA = TEST_PARAMS["xA_size"]
    ref = _column_pass_fwd_einsum_fn(core, xA)(
        NMBF, foffs, foffs, sg_offs, masks0, masks1
    )
    A0, B1 = _colpass_operators(core, foffs, foffs)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("facets",))

    def shard_body(NMBF_l, foffs1_l, A0_l, B1_l):
        return _colpass_pallas_body(
            core, xA, (A0_l, B1_l), NMBF_l, foffs1_l, sg_offs,
            masks0, masks1, axis_name="facets",
        )

    got = shard_map(
        shard_body, mesh=mesh,
        in_specs=(P("facets"), P("facets"), P("facets"), P("facets")),
        out_specs=P(),
        check_vma=False,  # jax has no replication rule for pallas_call
    )(NMBF, foffs, A0, B1)
    assert got.shape == ref.shape
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(got - ref).max()) / scale < 1e-5


def test_resolve_colpass_pallas_gating(monkeypatch):
    """`resolve_colpass` pedigree: explicit pallas needs the planar
    backend (complex cores degrade to einsum), auto only picks pallas
    on TPU — so CPU tier-1 keeps einsum and bench's executed==planned
    smoke assertion stays consistent off-device."""
    from swiftly_tpu import SwiftlyConfig
    from swiftly_tpu.utils.flops import resolve_colpass

    planar = SwiftlyConfig(backend="planar", **TEST_PARAMS).core
    cplx = SwiftlyConfig(backend="jax", **TEST_PARAMS).core
    monkeypatch.setenv("SWIFTLY_COLPASS", "pallas")
    assert resolve_colpass(planar, 3) == "pallas"
    assert resolve_colpass(cplx, 3) == "einsum"
    monkeypatch.setenv("SWIFTLY_COLPASS", "auto")
    assert resolve_colpass(planar, 3) == "einsum"  # CPU: no Mosaic


def test_planar_fft_with_pallas(monkeypatch):
    """The planar direct FFT path produces identical math via Pallas."""
    from swiftly_tpu.ops import planar_backend as plk

    rng = np.random.default_rng(1)
    x = rng.normal(size=(5, 256)) + 1j * rng.normal(size=(5, 256))
    base = plk.from_planar(plk.fft(plk.to_planar(x, jnp.float32), 1))

    monkeypatch.setenv("SWIFTLY_PALLAS", "1")
    assert pallas_enabled()
    # interpret mode: patch the kernel call to force interpretation on CPU
    import functools
    from swiftly_tpu.ops import pallas_kernels

    orig = pallas_kernels.cmatmul_pallas
    monkeypatch.setattr(
        pallas_kernels,
        "cmatmul_pallas",
        functools.partial(orig, interpret=True),
    )
    got = plk.from_planar(plk.fft(plk.to_planar(x, jnp.float32), 1))
    np.testing.assert_allclose(got.real, base.real, atol=1e-4)
    np.testing.assert_allclose(got.imag, base.imag, atol=1e-4)


@pytest.mark.parametrize("n", [1, 300])
def test_degrid_pallas_matches_einsum(monkeypatch, n):
    """The fused degrid kernel (interpret mode; one and several B-tiles)
    agrees with the einsum body over the same gathered taps."""
    from swiftly_tpu.vis.degrid import degrid_batch

    rng = np.random.default_rng(n)
    row = rng.normal(size=(64, 64, 2)).astype(np.float32)
    W = 8
    iu0 = rng.integers(0, 64 - W, size=n)
    iv0 = rng.integers(0, 64 - W, size=n)
    cu = rng.normal(size=(n, W)).astype(np.float32)
    cv = rng.normal(size=(n, W)).astype(np.float32)
    ref = degrid_batch(row, iu0, iv0, cu, cv)
    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    got = degrid_batch(row, iu0, iv0, cu, cv)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
