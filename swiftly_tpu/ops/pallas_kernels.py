"""Pallas TPU kernels for the planar-complex hot ops.

The planar backend's dominant op is the complex DFT matmul: four real
[B, K] x [K, N] products combined as (rr - ii, ri + ir)
(`planar_backend._cmatmul`). As separate XLA einsums each z block is
streamed from HBM up to four times; this kernel tiles the four products
into one grid program that reads each (z, w) block pair once per output
tile and keeps both accumulators in VMEM — an HBM-bandwidth optimisation
of exactly the kind the reference delegates to its native C library
(/root/reference/src/ska_sdp_exec_swiftly/fourier_transform/core.py:487-929,
the `ska-sdp-func` fast path).

The second kernel, `bwd_fold_pallas`, fuses the streamed backward's
adjoint sampled fold (`parallel.streamed._bwd_sampled_fold_fn`): per
output-row block the fold runs TWO phase-matrix matmuls, a row-weight
scale, and an accumulate into the image accumulator — as XLA einsums
the accumulator block and both row planes stream through HBM once per
product. The kernel keeps the accumulator block in VMEM across the
whole contraction grid (initialised from the incoming block, scaled
partial products added in place), so each (rows, acc) block pair is
read once per output tile — the hot loop the reference delegates to
its native ``ska-sdp-func`` library, here as one Mosaic grid program.

The third kernel, `colpass_pallas`, fuses the forward/backward column
pass (`parallel.streamed._colpass_einsum_body` and the backward column
body): the prepare matmul, the K = F·m operator contraction, and the
complex recombination of each subgrid run as one grid program with the
output tile resident in VMEM across the facet × contraction sweep, so
the [F, xM, yN] prepared-facet transient of the einsum chain never
touches HBM. One kernel serves the forward body, the adjoint body, and
both shard-local variants under the mesh engine (``reduce_f`` flips
between the facet-summed forward product and the per-facet backward
product). Selected via ``SWIFTLY_COLPASS=pallas`` (or ``auto`` on TPU).

The fold and complex-matmul kernels are opt-in (``SWIFTLY_PALLAS=1``);
the column pass is the ``auto`` choice on TPU. Correctness is validated
in interpreter mode on any backend (tests/test_pallas.py); that every
kernel compiles for a v5e at catalogue widths is
tests/test_tpu_compile.py's job. ``SWIFTLY_PALLAS_INTERPRET=1`` forces
the Pallas interpreter at trace time — the CPU-tier escape hatch that
lets the full fold path run (and be equivalence-tested) without Mosaic.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bwd_fold_pallas", "cmatmul_pallas", "colpass_pallas",
           "pallas_enabled", "pallas_interpret"]


def pallas_enabled() -> bool:
    """True when the Pallas fast path is requested via SWIFTLY_PALLAS=1."""
    return os.environ.get("SWIFTLY_PALLAS", "0") == "1"


def pallas_interpret() -> bool:
    """True when SWIFTLY_PALLAS_INTERPRET=1 asks for interpreter-mode
    Pallas (any backend; used by the CPU tier-1 equivalence tests)."""
    return os.environ.get("SWIFTLY_PALLAS_INTERPRET", "0") == "1"


def _kernel(zr_ref, zi_ref, wr_ref, wi_ref, or_ref, oi_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        or_ref[...] = jnp.zeros_like(or_ref)
        oi_ref[...] = jnp.zeros_like(oi_ref)

    zr = zr_ref[...]
    zi = zi_ref[...]
    wr = wr_ref[...]
    wi = wi_ref[...]
    # HIGHEST matches the einsum path: default bf16 MXU passes would
    # degrade the FFT to ~1e-3 relative error (see planar_backend.matmul_precision).
    dot = functools.partial(
        jnp.dot,
        preferred_element_type=or_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )
    or_ref[...] += dot(zr, wr) - dot(zi, wi)
    oi_ref[...] += dot(zr, wi) + dot(zi, wr)


def _pad_to(a, mult, axis):
    n = a.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, rem)
    return jnp.pad(a, pads)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def cmatmul_pallas(zr, zi, wr, wi, *, bm=256, bn=256, bk=256,
                   interpret=False):
    """(zr + i zi) @ (wr + i wi) -> (out_r, out_i), fused on the MXU.

    :param zr, zi: [B, K] real/imaginary planes of the batched vectors
    :param wr, wi: [K, N] real/imaginary planes of the DFT matrix
    :param bm, bn, bk: tile sizes (batch, output, contraction)
    :param interpret: run in the Pallas interpreter (any backend)
    """
    B, K = zr.shape
    _, N = wr.shape
    bm, bn, bk = min(bm, B), min(bn, N), min(bk, K)

    zr_p = _pad_to(_pad_to(zr, bm, 0), bk, 1)
    zi_p = _pad_to(_pad_to(zi, bm, 0), bk, 1)
    wr_p = _pad_to(_pad_to(wr, bk, 0), bn, 1)
    wi_p = _pad_to(_pad_to(wi, bk, 0), bn, 1)
    Bp, Kp = zr_p.shape
    _, Np = wr_p.shape

    grid = (Bp // bm, Np // bn, Kp // bk)
    z_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    out_shape = jax.ShapeDtypeStruct((Bp, Np), zr.dtype)

    outr, outi = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[z_spec, z_spec, w_spec, w_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="cmatmul_pallas",
    )(zr_p, zi_p, wr_p, wi_p)
    return outr[:B, :N], outi[:B, :N]


def _fold_kernel(ar_ref, ai_ref, bc_ref, bs_ref, rr_ref, ri_ref, w_ref,
                 or_ref, oi_ref):
    """One adjoint-fold output tile: out = acc + w * (Bcᵀ@Rr + Bsᵀ@Ri,
    Bcᵀ@Ri − Bsᵀ@Rr). The accumulator tile loads into VMEM once (k==0)
    and every contraction step's weighted partial product adds in place
    — no HBM round trip per product, which is the whole point."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        or_ref[...] = ar_ref[...]
        oi_ref[...] = ai_ref[...]

    bc = bc_ref[...]  # [bk, bm] block of the phase matrix
    bs = bs_ref[...]
    rr = rr_ref[...]  # [bk, bn] block of the rotated row planes
    ri = ri_ref[...]
    w = w_ref[...]    # [bm, 1] row weights (Fb window x keep mask)
    # contract over axis 0 of BOTH operands (the fold's "r" index);
    # HIGHEST matches the einsum fold's matmul_precision default
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=or_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )
    or_ref[...] += w * (dot(bc, rr) + dot(bs, ri))
    oi_ref[...] += w * (dot(bc, ri) - dot(bs, rr))


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def bwd_fold_pallas(acc_r, acc_i, bc, bs, rr, ri, w, *, bm=256, bn=256,
                    bk=256, interpret=False):
    """Fused adjoint-fold block: acc + w ⊙ ((Bc − i·Bs)ᵀ @ (Rr + i·Ri)).

    The planar sampled fold's per-block einsum pair plus accumulate as
    ONE grid program (see `parallel.streamed._bwd_sampled_fold_fn`'s
    Pallas body, which flattens the facet axis into the j axis before
    calling here).

    :param acc_r, acc_i: [B, J] accumulator planes (the current block)
    :param bc, bs: [R, B] adjoint DFT phase planes for the block's
        output rows (cos/sin of −kt·i)
    :param rr, ri: [R, J] phase-rotated row planes (facet axis folded
        into J)
    :param w: [B, 1] per-output-row weight (Fb window × keep mask)
    :param bm, bn, bk: tile sizes (rows, output, contraction)
    :param interpret: run in the Pallas interpreter (any backend)
    """
    B, J = acc_r.shape
    R = bc.shape[0]
    bm, bn, bk = min(bm, B), min(bn, J), min(bk, R)

    ar_p = _pad_to(_pad_to(acc_r, bm, 0), bn, 1)
    ai_p = _pad_to(_pad_to(acc_i, bm, 0), bn, 1)
    bc_p = _pad_to(_pad_to(bc, bk, 0), bm, 1)
    bs_p = _pad_to(_pad_to(bs, bk, 0), bm, 1)
    rr_p = _pad_to(_pad_to(rr, bk, 0), bn, 1)
    ri_p = _pad_to(_pad_to(ri, bk, 0), bn, 1)
    w_p = _pad_to(w, bm, 0)
    Bp, Jp = ar_p.shape
    Rp = bc_p.shape[0]

    grid = (Bp // bm, Jp // bn, Rp // bk)
    a_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    b_spec = pl.BlockSpec((bk, bm), lambda i, j, k: (k, i))
    r_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    w_spec = pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0))
    out_shape = jax.ShapeDtypeStruct((Bp, Jp), acc_r.dtype)

    outr, outi = pl.pallas_call(
        _fold_kernel,
        grid=grid,
        in_specs=[a_spec, a_spec, b_spec, b_spec, r_spec, r_spec, w_spec],
        out_specs=[a_spec, a_spec],
        out_shape=[out_shape, out_shape],
        interpret=interpret,
        name="bwd_fold_pallas",
    )(ar_p, ai_p, bc_p, bs_p, rr_p, ri_p, w_p)
    return outr[:B, :J], outi[:B, :J]


# Scoped-VMEM limit for the column-pass kernel. The P contraction runs
# whole per grid step, so the double-buffered [bm, P] and [P, bk] planes
# grow with xM: the 64k/128k-n64k backward (P = xM = 1024) needs ~26 MiB,
# over the compiler's 16 MiB default (refused by the v5e AOT compile,
# tests/test_tpu_compile.py). v5e has 128 MiB of VMEM.
_COLPASS_VMEM_LIMIT = 64 * 2**20


def _colpass_kernel(ar_ref, ai_ref, xr_ref, xi_ref, br_ref, bi_ref,
                    or_ref, oi_ref, *, reduce_f):
    """One fused column-pass output tile: out (+)= A_f @ X_sf @ B_f.

    The grid iterates (s, i, j, f, k) with f/k innermost, so the output
    tile stays resident in VMEM across the whole facet × contraction
    sweep — the prepare matmul (dot #1) and the operator contraction
    (dot #2) never round-trip a partial through HBM, which is what the
    separate XLA einsum dispatches in `_colpass_einsum_body` cost us.
    With ``reduce_f`` the facet axis folds into the accumulator
    (forward body: P_s = Σ_f A0_f @ Xn_sf @ B1_f); without it each
    facet writes its own output plane (backward body: Z_sf)."""
    f = pl.program_id(3)
    k = pl.program_id(4)
    first = (f == 0) & (k == 0) if reduce_f else k == 0

    @pl.when(first)
    def _init():
        or_ref[...] = jnp.zeros_like(or_ref)
        oi_ref[...] = jnp.zeros_like(oi_ref)

    ar = ar_ref[0]     # [bm, P]
    ai = ai_ref[0]
    xr = xr_ref[0, 0]  # [P, bk]
    xi = xi_ref[0, 0]
    br = br_ref[0]     # [bk, bn]
    bi = bi_ref[0]
    # HIGHEST matches the einsum body's matmul_precision default
    dot = functools.partial(
        jnp.dot,
        preferred_element_type=or_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )
    tr = dot(ar, xr) - dot(ai, xi)  # [bm, bk]
    ti = dot(ar, xi) + dot(ai, xr)
    pr = dot(tr, br) - dot(ti, bi)  # [bm, bn]
    pi = dot(tr, bi) + dot(ti, br)
    or_ref[...] += pr.reshape(or_ref.shape)
    oi_ref[...] += pi.reshape(oi_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("reduce_f", "bm", "bn", "bk", "interpret")
)
def colpass_pallas(ar, ai, xr, xi, br, bi, *, reduce_f=True, bm=256,
                   bn=256, bk=256, interpret=False):
    """Fused complex triple product A_f @ X_sf @ B_f over an S block.

    The column pass's whole per-subgrid contraction — prepare matmul,
    operator einsums, complex recombination — as ONE grid program:

    * forward body: A = A0 [F, xM, m], X = gathered facet columns
      [S, F, m, m], B = B1 [F, m, xM], ``reduce_f=True`` →
      out [S, xM, xM] (facet sum folded into the VMEM accumulator).
      Dot #1 IS the prepare matmul, so the [F, xM, yN] H transient of
      the einsum body never exists.
    * backward body: A = E0 [F, m, xM], X = embedded subgrids
      [S, 1, xM, xM] (broadcast over f), B = E1 [F, xM, m],
      ``reduce_f=False`` → out [S, F, m, m].

    :param ar, ai: [F, M, P] left operator planes
    :param xr, xi: [S, Fx, P, Q] per-subgrid middle planes; Fx is F or
        1 (broadcast over the facet axis)
    :param br, bi: [F, Q, N] right operator planes
    :param reduce_f: sum over the facet axis into the accumulator
    :param bm, bn, bk: tile sizes (M rows, N cols, Q contraction); the
        P contraction runs whole per grid step (padded to 128 lanes)
    :param interpret: run in the Pallas interpreter (any backend)
    """
    F, M, P = ar.shape
    S, Fx = xr.shape[0], xr.shape[1]
    Q, N = br.shape[1], br.shape[2]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, Q)

    ar_p = _pad_to(_pad_to(ar, bm, 1), 128, 2)
    ai_p = _pad_to(_pad_to(ai, bm, 1), 128, 2)
    xr_p = _pad_to(_pad_to(xr, 128, 2), bk, 3)
    xi_p = _pad_to(_pad_to(xi, 128, 2), bk, 3)
    br_p = _pad_to(_pad_to(br, bk, 1), bn, 2)
    bi_p = _pad_to(_pad_to(bi, bk, 1), bn, 2)
    Mp, Pp = ar_p.shape[1], ar_p.shape[2]
    Qp, Np = br_p.shape[1], br_p.shape[2]

    grid = (S, Mp // bm, Np // bn, F, Qp // bk)
    a_spec = pl.BlockSpec((1, bm, Pp), lambda s, i, j, f, k: (f, i, 0))
    if Fx == 1:
        x_spec = pl.BlockSpec(
            (1, 1, Pp, bk), lambda s, i, j, f, k: (s, 0, 0, k))
    else:
        x_spec = pl.BlockSpec(
            (1, 1, Pp, bk), lambda s, i, j, f, k: (s, f, 0, k))
    b_spec = pl.BlockSpec((1, bk, bn), lambda s, i, j, f, k: (f, k, j))
    if reduce_f:
        o_spec = pl.BlockSpec((1, bm, bn), lambda s, i, j, f, k: (s, i, j))
        out_shape = jax.ShapeDtypeStruct((S, Mp, Np), ar.dtype)
    else:
        o_spec = pl.BlockSpec(
            (1, 1, bm, bn), lambda s, i, j, f, k: (s, f, i, j))
        out_shape = jax.ShapeDtypeStruct((S, F, Mp, Np), ar.dtype)

    outr, outi = pl.pallas_call(
        functools.partial(_colpass_kernel, reduce_f=reduce_f),
        grid=grid,
        in_specs=[a_spec, a_spec, x_spec, x_spec, b_spec, b_spec],
        out_specs=[o_spec, o_spec],
        out_shape=[out_shape, out_shape],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_COLPASS_VMEM_LIMIT
        ),
        interpret=interpret,
        name="colpass_pallas",
    )(ar_p, ai_p, xr_p, xi_p, br_p, bi_p)
    return outr[..., :M, :N], outi[..., :M, :N]
