"""Out-of-core streamed execution: transforms larger than device memory.

The whole-cover batched path (`swiftly_tpu.parallel.batched`) keeps the
prepared facet stack `BF_Fs` [F, yN, yB] resident on device; at N = 32768
that is ~13 GiB and at N = 65536 ~53 GiB — beyond a single chip's HBM.
This module runs the same transform with bounded device residency by
streaming through host memory, which is the TPU realisation of the
reference's design goal of "minimising memory residency" while "generating
arbitrary grid chunks" (reference docs/src/index.rst:11-12; the column
intermediates mirror its LRU-bounded NMBF_BF / NAF_MNAF working sets,
api.py:300-324,402-438).

Forward (facets -> subgrids), two device passes:

1. *Facet pass* — with `residency="host"`: stream facet column-blocks
   [F, yB, Cb] to the device; prepare along axis 0 and extract the
   contribution rows for EVERY subgrid column offset in one program ->
   [K, F, m, Cb], landing in the host-RAM `NMBF_all` buffer
   [K, F, m, yB] (total size equals one prepared facet stack re-indexed
   by column: K*m ≈ yN). With `residency="device"` the facet pass is a
   sampled DFT instead: facets upload once and stay in HBM, and each
   group of columns' contribution rows is one einsum — no NMBF buffer
   exists (see `_facet_pass_sampled_j`).
2. *Column pass* — per subgrid column k: take the column's [F, m, yB]
   rows (host upload, or a slice of the sampled group buffer), prepare
   along axis 1, extract/accumulate/finish all S subgrids of the column
   in one program -> [S, xA, xA].

Backward (subgrids -> facets) is the exact dual:

1. *Column pass* — per column: fold the column's subgrids into a
   NAF_MNAF accumulator (scan), finish axis 1 + mask -> NAF_BMNAF
   [F, m, yB], accumulated per-column into `NAF_all` [K, F, m, yB].
2. *Facet pass* — stream `NAF_all` column-blocks [K, F, m, Cb] back;
   embed each column's rows at its offset (axis-0 add_to_facet), sum
   over columns, finish axis 0 + mask -> facet blocks [F, yB, Cb].

Peak device residency is a handful of [F, m, yN]-scale blocks (~1 GiB at
N = 32768) regardless of N; host residency is one [K, F, m, yB] buffer.
All stage programs are built from the same `*_math` primitives as the
batched path, so streamed and batched results are numerically identical.
"""

from __future__ import annotations

import functools
import logging
import time

import numpy as np

logger = logging.getLogger(__name__)


def _rss_gib():
    """Resident set size in GiB (cheap /proc read; 0.0 if unavailable)."""
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * 4096 / 2**30
    except Exception:  # pragma: no cover - non-linux
        return 0.0

from ..ops.core import (
    add_to_facet_math,
    add_to_subgrid_math,
    extract_from_facet_math,
    finish_facet_math,
    prepare_facet_math,
)
from .batched import (
    _mask_along,
    facet_contrib_to_subgrid,
    finish_masked_subgrid,
)

__all__ = ["StreamedForward", "StreamedBackward", "feed_backward_passes"]


def _planar(core):
    return core.backend == "planar"


def _tail(core):
    """Trailing data-layout axes: the planar backend carries (re, im)."""
    return (2,) if _planar(core) else ()


def _np_dtype(core):
    return np.dtype(core.dtype)


def _real_plane_or_none(core, data):
    """The facet's real plane as [yB, yB] float, or None if it has any
    imaginary content (or the backend is not planar).

    Point-source facet models are exactly real; detecting that here lets
    the sampled-DFT path store/upload HALF the bytes and skip half its
    einsums. One full host-side pass over the data — the same cost the
    planar layout conversion pays anyway.
    """
    if not _planar(core):
        return None
    data = np.asarray(data)
    if data.ndim and data.shape[-1] == 2 and not np.iscomplexobj(data):
        if np.any(data[..., 1]):
            return None
        return np.asarray(data[..., 0], dtype=_np_dtype(core))
    if np.iscomplexobj(data) and np.any(data.imag):
        return None
    return np.asarray(data.real, dtype=_np_dtype(core))


def _to_host_layout(core, data):
    """One facet/subgrid as a host numpy array in device layout."""
    if _planar(core):
        data = np.asarray(data)
        if data.ndim and data.shape[-1] == 2 and not np.iscomplexobj(data):
            return np.asarray(data, dtype=_np_dtype(core))
        # assign planes directly (casting on write): no full-precision
        # stacked intermediate — this path handles multi-GiB facets
        out = np.empty(data.shape + (2,), dtype=_np_dtype(core))
        out[..., 0] = data.real
        out[..., 1] = data.imag
        return out
    return np.asarray(data, dtype=_np_dtype(core))


import jax  # noqa: E402

from jax.sharding import PartitionSpec as _P  # noqa: E402

from jax import shard_map as _shard_map  # noqa: E402

from .mesh import (  # noqa: E402
    FACET_AXIS,
    mesh_size as _mesh_size,
    resolve_collective as _resolve_collective_env,
    varying,
)
from .sharded import collective_sum as _collective_sum  # noqa: E402

from ..obs import metrics as _metrics  # noqa: E402
from ..obs import trace as _trace  # noqa: E402
from ..resilience import degrade as _degrade  # noqa: E402
from ..resilience.faults import fault_point as _fault_point  # noqa: E402
from ..resilience.retry import retry_transient as _retry  # noqa: E402


def _scoped(name, fn):
    """Wrap a stage body in ``jax.named_scope`` so its compiled HLO ops
    carry the stage name — the trace-side half of the shared stage
    vocabulary (the host-side half is the TraceAnnotation each
    ``obs.metrics`` stage enters while a profiler session records, of
    the same name minus the "swiftly/" prefix). Zero runtime cost:
    the scope exists only at trace time, as op-name metadata."""

    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return wrapped


# ---------------------------------------------------------------------------
# Stage programs
#
# Each stage has a pure body builder (`*_fn`) shared by the single-device
# jit (`*_j`) and the facet-sharded shard_map variant (`*_sharded`). On a
# mesh every per-facet op is shard-local; the only collective in the whole
# streamed pipeline is one psum per subgrid column in the forward column
# pass (`axis_name` below).
# ---------------------------------------------------------------------------


def _jit(static=(), donate=()):
    return functools.partial(
        jax.jit, static_argnums=static, donate_argnums=donate
    )


def _shmap(fn, mesh, in_specs, out_specs, donate=()):
    # check_vma=False: jax has no replication rule for pallas_call, so
    # the checker rejects any body that lowers the fused colpass kernel
    # (SWIFTLY_COLPASS=pallas under the mesh engine). The psum placement
    # is pinned by the body builders themselves.
    mapped = _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=donate)


def _facet_pass_fwd_fn(core):
    """facet block [F, yB', Cb] -> contribution rows [K, F, m, Cb]."""
    p = core._p

    def fn(facet_block, foffs0, col_offs0):
        def per_facet(fb, off0):
            prep = prepare_facet_math(p, core._Fb, core.yN_size, fb, off0, 0)

            def per_col(sg_off0):
                return extract_from_facet_math(
                    p, core.xM_yN_size, core.N, core.yN_size, prep, sg_off0, 0
                )

            return jax.vmap(per_col)(col_offs0)  # [K, m, Cb]

        out = jax.vmap(per_facet)(facet_block, foffs0)  # [F, K, m, Cb]
        return jax.numpy.swapaxes(out, 0, 1)  # [K, F, m, Cb]

    return fn


@functools.lru_cache(maxsize=None)
def _facet_pass_fwd_j(core):
    return _jit()(
        _scoped("swiftly/fwd.facet_pass", _facet_pass_fwd_fn(core))
    )


@functools.lru_cache(maxsize=None)
def _facet_pass_fwd_sharded(core, mesh):
    """Facet-sharded forward facet pass (all ops shard-local)."""
    return _shmap(
        _scoped("swiftly/fwd.facet_pass", _facet_pass_fwd_fn(core)), mesh,
        in_specs=(_P(FACET_AXIS), _P(FACET_AXIS), _P()),
        out_specs=_P(None, FACET_AXIS),
    )


# -- operator-matrix (einsum) column pass -----------------------------------
#
# Every per-facet op in the forward column pass after the axis-1 prep is
# LINEAR with a statically-shaped [xM, m] operator: the axis-0 chain
# fft -> roll -> Fn window -> wrapped_embed (`add_to_subgrid_math`) is a
# matrix A0_f, the axis-1 chain a matrix op1_f, and the finish iFFTs fold
# into them (iFFT along an axis commutes with cropping the OTHER axis).
# The whole column pass then collapses to two big einsums,
#
#   H    = A0_f @ NMBF_BF_f                  [F, xM, yN]   (shared by all S)
#   P_s  = sum_f gather_s(H_f) @ op1_f^T     [xM, xM]      (K = F*m)
#
# and the per-subgrid finish is a crop + mask (no FFT left). Versus the
# per-facet chain this roughly doubles the matmul FLOPs but removes the
# scan-over-facets accumulator traffic, the per-(facet, subgrid) rolls and
# embeds, and the m-sized matmul tiles that ran at ~9% of the MXU ceiling
# (measured, scripts/roofline.py): the K = F*m contraction folds the facet
# reduction into the MXU. The operators are built IN-TRACE by applying the
# existing `*_math` chain to an identity block — correctness by
# construction, ~1 ms per program, and both spmd modes reuse the body.
#
# `SWIFTLY_COLPASS` selects the body (einsum|fft|pallas|auto, default
# auto; read at TRACE time like SWIFTLY_PRECISION — the lru-cached jits
# bake it in). "auto" resolves per program via
# `utils.flops.resolve_colpass`: the fused Pallas kernel on TPU (the
# whole per-subgrid triple product A0 @ Xn @ B1 as one grid program,
# `ops.pallas_kernels.colpass_pallas` — no [F, xM, yN] H transient, no
# per-einsum dispatch gaps), einsum elsewhere (it measured faster than
# the fft chain at EVERY forward shape tried, resident full-stack AND
# Fg=1 slabs). The BACKWARD pass (`resolve_colpass_bwd`) follows the
# same auto rule (pallas on TPU, einsum otherwise).


from ..utils.flops import (  # noqa: E402
    resolve_colpass as _resolve_colpass,
    resolve_colpass_bwd as _resolve_colpass_bwd,
)


def _colpass_sblock() -> int:
    """Subgrids per einsum block: bounds the [Sb, F, xM, m] gather
    transient. Default 256 covers every catalogue column in ONE block
    (S <= 293 at 128k) — measured 13% faster than Sb=64 at 32k (the
    lax.map blocks padded the short tail and serialized); the knob
    remains for configs whose [S, F, xM, m] gather would not fit."""
    import os

    return max(1, int(os.environ.get("SWIFTLY_COLPASS_SBLOCK", "256")))


def _colpass_blocks():
    """(bm, bn, bk) tile sizes for the fused Pallas column-pass kernel
    (`SWIFTLY_COLPASS_BM/BN/BK`, default 256 each — xM/m fit in one or
    two MXU-aligned tiles at every catalogue scale). Read at TRACE time;
    `plan/autotune.refit` learns measured-best blocks from artifact
    history and `scripts/plan_explain.py --colpass` prints them so
    operators can export the env."""
    import os

    return (
        max(8, int(os.environ.get("SWIFTLY_COLPASS_BM", "256"))),
        max(8, int(os.environ.get("SWIFTLY_COLPASS_BN", "256"))),
        max(8, int(os.environ.get("SWIFTLY_COLPASS_BK", "256"))),
    )


def _ceinsum(core, spec, a, b):
    """Complex einsum (spec written for the logical axes): planar arrays
    contract via 4 real MXU einsums, complex backends directly."""
    import jax.numpy as jnp

    if _planar(core):
        from ..ops.planar_backend import _cmatmul

        outr, outi = _cmatmul(
            a[..., 0], a[..., 1], (b[..., 0], b[..., 1]), spec, a.dtype
        )
        return jnp.stack([outr, outi], axis=-1)
    return jnp.einsum(spec, a, b)


def _colpass_operators(core, foffs0, foffs1):
    """Forward column-pass operators, built in-trace from an identity.

    A0 [F, xM, m(,2)]: axis-0 `add_to_subgrid_math` with the finish iFFT
    folded along the output axis. B1 [F, m, xM(,2)]: the axis-1 operator
    in row-basis layout (B1[f, j, b] = op1_f[b, j]), iFFT folded, so the
    stage-2 contraction is `X[..., j] . B1[f, j, b]`.
    """
    import jax.numpy as jnp

    p = core._p
    m, xM = core.xM_yN_size, core.xM_size
    if _planar(core):
        eye = (
            jnp.zeros((m, m, 2), core.dtype)
            .at[:, :, 0]
            .set(jnp.eye(m, dtype=core.dtype))
        )
    else:
        eye = jnp.eye(m, dtype=core.dtype)

    def a0(off0):
        A = add_to_subgrid_math(p, core._Fn, xM, core.N, eye, off0, 0)
        return p.ifft(A, 0)

    def b1(off1):
        B = add_to_subgrid_math(p, core._Fn, xM, core.N, eye, off1, 1)
        return p.ifft(B, 1)

    return jax.vmap(a0)(foffs0), jax.vmap(b1)(foffs1)


def _crop_masked_subgrid(core, P, sg_offs, subgrid_size, mask0, mask1):
    """Finish an IMAGE-space padded subgrid: crop both axes + masks (the
    iFFTs already live in the einsum operators)."""
    p = core._p
    out = p.wrapped_extract(P, subgrid_size, sg_offs[0], 0)
    out = p.wrapped_extract(out, subgrid_size, sg_offs[1], 1)
    out = _mask_along(p, out, mask0, 0)
    return _mask_along(p, out, mask1, 1)


def _blocked_collective(block, sg_offs, axis_name, collective, n_shards):
    """Run the Sb-blocked per-column contraction and reduce the facet
    axis. psum: lax.map over blocks, one blocking all-reduce at the end
    (the existing schedule). ring: unrolled block loop with each block
    ring-reduced the moment its contraction finishes — block k's chunk
    rotations are data-independent of block k+1's contraction, so XLA
    schedules the `ppermute` steps concurrently with the next block's
    local einsum/Pallas work instead of fencing after all of them."""
    import jax.numpy as jnp

    S = sg_offs.shape[0]
    Sb = min(_colpass_sblock(), S)
    nb = -(-S // Sb)
    Sb = -(-S // nb)  # rebalanced: pad < nb, never a near-full block
    if nb == 1:
        P = block(sg_offs)
        if axis_name is not None:
            P = _collective_sum(P, axis_name, collective, n_shards)
        return P
    pad = nb * Sb - S
    so_p = (
        jnp.concatenate([sg_offs, jnp.repeat(sg_offs[-1:], pad, 0)])
        if pad
        else sg_offs
    )
    so_b = so_p.reshape((nb, Sb) + so_p.shape[1:])
    if axis_name is not None and collective == "ring":
        parts = [
            _collective_sum(block(so_b[i]), axis_name, "ring", n_shards)
            for i in range(nb)
        ]
        return jnp.concatenate(parts, axis=0)[:S]
    P = jax.lax.map(block, so_b)
    P = P.reshape((nb * Sb,) + P.shape[2:])[:S]
    if axis_name is not None:
        P = _collective_sum(P, axis_name, collective, n_shards)
    return P


def _colpass_einsum_body(
    core, subgrid_size, ops, NMBF, foffs1, sg_offs, masks0, masks1,
    axis_name=None, finish=True, collective="psum", n_shards=None,
):
    """One column through the einsum column pass, with prebuilt `ops`
    (so group callers hoist the operator build out of their column loop).

    ``collective`` picks the facet-axis reduction: one blocking psum per
    column, or the `ppermute` ring — multi-block columns ring-reduce
    each Sb block as soon as its contraction finishes (unrolled loop
    instead of lax.map), so block k's chunk rotation overlaps block
    k+1's local einsum in the emitted schedule.
    """
    import jax.numpy as jnp

    p = core._p
    m, yN = core.xM_yN_size, core.yN_size
    A0, B1 = ops

    def prep1(x, off1):
        return prepare_facet_math(p, core._Fb, yN, x, off1, 1)

    NMBF_BF = jax.vmap(prep1)(NMBF, foffs1)  # [F, m, yN(,2)]
    H = _ceinsum(core, "fai,fij->faj", A0, NMBF_BF)  # [F, xM, yN(,2)]

    def block(so_blk):
        def gather(so):
            return extract_from_facet_math(
                p, m, core.N, yN, H, so[1], 2
            )  # [F, xM, m(,2)]

        X = jax.vmap(gather)(so_blk)  # [Sb, F, xM, m(,2)]
        return _ceinsum(core, "sfaj,fjb->sab", X, B1)  # [Sb, xM, xM(,2)]

    P = _blocked_collective(block, sg_offs, axis_name, collective, n_shards)
    if not finish:
        return P

    def fin(Pi, so, m0, m1):
        return _crop_masked_subgrid(core, Pi, so, subgrid_size, m0, m1)

    return jax.vmap(fin)(P, sg_offs, masks0, masks1)


def _colpass_pallas_body(
    core, subgrid_size, ops, NMBF, foffs1, sg_offs, masks0, masks1,
    axis_name=None, finish=True, interpret=None, collective="psum",
    n_shards=None,
):
    """One column through the FUSED Pallas column pass.

    The same contraction as `_colpass_einsum_body`, reassociated per
    subgrid: P_s = Σ_f A0_f @ Xn_sf @ B1_f, where Xn_sf gathers the
    subgrid's m columns from NMBF_BF directly — the gather acts on the
    output (j) axis of H = A0 @ NMBF_BF, so it commutes past the
    stage-1 contraction and the [F, xM, yN] H transient (~2.4 GB at
    128k) never materialises; the gather transient shrinks from
    [Sb, F, xM, m] to [Sb, F, m, m]. Prepare matmul, K = F*m operator
    contraction and the complex recombination run as ONE grid program
    with the output tile resident in VMEM (`colpass_pallas`,
    reduce_f=True). Pre-finish partials and the crop finish are
    identical to the einsum body's (image space), so the two bodies are
    drop-in interchangeable for every caller — including the group
    step/finish pairing and the shard-local psum placement.
    """
    import jax.numpy as jnp

    from ..ops.pallas_kernels import colpass_pallas, pallas_interpret

    p = core._p
    m, yN = core.xM_yN_size, core.yN_size
    A0, B1 = ops
    if interpret is None:
        interpret = pallas_interpret()
    bm, bn, bk = _colpass_blocks()

    def prep1(x, off1):
        return prepare_facet_math(p, core._Fb, yN, x, off1, 1)

    NMBF_BF = jax.vmap(prep1)(NMBF, foffs1)  # [F, m, yN, 2]

    def block(so_blk):
        def gather(so):
            return extract_from_facet_math(
                p, m, core.N, yN, NMBF_BF, so[1], 2
            )  # [F, m, m, 2]

        Xn = jax.vmap(gather)(so_blk)  # [Sb, F, m, m, 2]
        Pr, Pi = colpass_pallas(
            A0[..., 0], A0[..., 1],
            Xn[..., 0], Xn[..., 1],
            B1[..., 0], B1[..., 1],
            reduce_f=True, bm=bm, bn=bn, bk=bk, interpret=interpret,
        )
        return jnp.stack([Pr, Pi], axis=-1)  # [Sb, xM, xM, 2]

    P = _blocked_collective(block, sg_offs, axis_name, collective, n_shards)
    if not finish:
        return P

    def fin(Pi_, so, m0, m1):
        return _crop_masked_subgrid(core, Pi_, so, subgrid_size, m0, m1)

    return jax.vmap(fin)(P, sg_offs, masks0, masks1)


def _column_pass_fwd_einsum_fn(core, subgrid_size, axis_name=None,
                               finish=True, collective="psum", n_shards=None):
    def fn(NMBF, foffs0, foffs1, sg_offs, masks0=None, masks1=None):
        ops = _colpass_operators(core, foffs0, foffs1)
        return _colpass_einsum_body(
            core, subgrid_size, ops, NMBF, foffs1, sg_offs, masks0,
            masks1, axis_name, finish, collective, n_shards,
        )

    return fn


def _column_pass_fwd_pallas_fn(core, subgrid_size, axis_name=None,
                               finish=True, collective="psum", n_shards=None):
    def fn(NMBF, foffs0, foffs1, sg_offs, masks0=None, masks1=None):
        ops = _colpass_operators(core, foffs0, foffs1)
        return _colpass_pallas_body(
            core, subgrid_size, ops, NMBF, foffs1, sg_offs, masks0,
            masks1, axis_name, finish, None, collective, n_shards,
        )

    return fn


def _column_pass_fwd_fn(core, subgrid_size, axis_name=None,
                        collective="psum", n_shards=None):
    """NMBF column [F, m, yB] -> the column's subgrids [S, xA, xA].

    Trace-time dispatcher: the fused Pallas kernel or the
    operator-matrix einsum body per `resolve_colpass` (both share the
    image-space partial/crop-finish contract), the per-facet fft chain
    otherwise. Callers that need PRE-finish partials (the facet-slab
    group step) pick a body explicitly instead — the fft body's
    partials live in a different space (grid, not image) and must pair
    with the matching group finish.
    """
    bodies = {
        "einsum": _column_pass_fwd_einsum_fn(
            core, subgrid_size, axis_name, True, collective, n_shards
        ),
        "pallas": _column_pass_fwd_pallas_fn(
            core, subgrid_size, axis_name, True, collective, n_shards
        ),
        "fft": _column_pass_fwd_fft_fn(
            core, subgrid_size, axis_name, True, collective, n_shards
        ),
    }

    def fn(NMBF, foffs0, foffs1, sg_offs, masks0=None, masks1=None):
        body = bodies[_resolve_colpass(core, NMBF.shape[0])]
        return body(NMBF, foffs0, foffs1, sg_offs, masks0, masks1)

    return fn


def _column_pass_fwd_fft_fn(core, subgrid_size, axis_name=None, finish=True,
                            collective="psum", n_shards=None):
    """The per-facet fft-chain column pass: the facet reduction is a
    lax.scan accumulating one [S, xM, xM] buffer (each step: one facet's
    contributions to ALL S subgrids, S-batched matmuls) — a
    vmap-over-S-of-sum-over-F materialises every (S, F) contribution
    block at once, which OOMs a 16 GiB chip at the 32k scale. With
    `axis_name`, F is the local facet shard and the reduction finishes
    with ONE collective (psum or ppermute ring) over the accumulated
    partials — the streamed pipeline's only collective.

    With ``finish=False`` the PRE-finish GRID-space partials [S, xM, xM]
    are returned (no masks consumed): the facet-slab path accumulates
    those across slabs and finishes ONCE per column group — at 64k the
    per-slab finish was 44% of all FLOPs.
    """
    p = core._p

    def fn(NMBF, foffs0, foffs1, sg_offs, masks0=None, masks1=None):
        def prep1(x, off1):
            return prepare_facet_math(p, core._Fb, core.yN_size, x, off1, 1)

        NMBF_BF = jax.vmap(prep1)(NMBF, foffs1)  # [F, m, yN]

        def facet_step(acc, xs):
            bf, f0, f1 = xs
            per_sg = jax.vmap(
                lambda so: facet_contrib_to_subgrid(core, bf, f0, f1, so[1])
            )(sg_offs)  # [S, xM, xM]
            return acc + per_sg, None

        S = sg_offs.shape[0]
        init = jax.numpy.zeros(
            (S, core.xM_size, core.xM_size) + NMBF.shape[3:],
            dtype=NMBF.dtype,
        )
        if axis_name is not None:
            # the carry mixes in facet-sharded offsets: tag it varying
            init = varying(init, axis_name)
        partials, _ = jax.lax.scan(
            facet_step, init, (NMBF_BF, foffs0, foffs1)
        )
        if axis_name is not None:
            partials = _collective_sum(
                partials, axis_name, collective, n_shards
            )
        if not finish:
            return partials

        def fin(summed, sg_off_pair, m0, m1):
            return finish_masked_subgrid(
                core, summed, sg_off_pair, subgrid_size, m0, m1
            )

        return jax.vmap(fin)(partials, sg_offs, masks0, masks1)

    return fn


@functools.lru_cache(maxsize=None)
def _column_pass_fwd_j(core, subgrid_size):
    return _jit()(
        _scoped(
            "swiftly/fwd.column_pass",
            _column_pass_fwd_fn(core, subgrid_size),
        )
    )


@functools.lru_cache(maxsize=None)
def _column_pass_fwd_sharded_cached(core, mesh, subgrid_size, collective):
    return _shmap(
        _scoped(
            "swiftly/fwd.column_pass",
            _column_pass_fwd_fn(
                core, subgrid_size, axis_name=FACET_AXIS,
                collective=collective, n_shards=_mesh_size(mesh),
            ),
        ),
        mesh,
        in_specs=(
            _P(FACET_AXIS), _P(FACET_AXIS), _P(FACET_AXIS),
            _P(), _P(), _P(),
        ),
        out_specs=_P(),
    )


def _column_pass_fwd_sharded(core, mesh, subgrid_size):
    """The sharded column pass under the CURRENT collective schedule.

    SWIFTLY_MESH_COLLECTIVE is resolved per CALL and keys the compiled-
    program cache, so one process can bench psum and ring back to back
    without a stale cached program shadowing the requested schedule."""
    return _column_pass_fwd_sharded_cached(
        core, mesh, subgrid_size, _resolve_collective_env(_mesh_size(mesh))
    )


def _column_pass_fwd_group_fn(core, subgrid_size, axis_name=None,
                              collective="psum", n_shards=None):
    """Sampled group buffer [F, G*m, yB] -> subgrids [G, S, xA, xA].

    vmaps the column pass over a whole sampled-DFT group: one dispatch
    per G columns instead of G, and the per-subgrid small-matmul stages
    gain a G-times larger batch dimension (the column pass is MXU-
    utilisation-bound at m-sized tiles, measured ~2.7 TFLOP/s per
    column alone on v5e).
    """
    m = core.xM_yN_size
    colfn = _column_pass_fwd_fft_fn(
        core, subgrid_size, axis_name, True, collective, n_shards
    )

    def fn(buf, foffs0, foffs1, sg_offs_g, masks0_g, masks1_g):
        F = buf.shape[0]
        G = sg_offs_g.shape[0]
        NMBF_g = jax.numpy.moveaxis(
            buf.reshape((F, G, m) + buf.shape[2:]), 1, 0
        )  # [G, F, m, yB(,2)]

        mode = _resolve_colpass(core, F)
        if mode in ("einsum", "pallas"):
            # operators hoisted across the group's columns; columns run
            # sequentially (lax.map) — each column's einsums are already
            # MXU-wide, and a G-batched vmap would scale the [F, xM, yN]
            # H transient by G (OOM at 32k G=9). Under the ring schedule
            # the sequential columns are exactly the interleave: column
            # k's chunk rotations have no dependence on column k+1's
            # contraction, so the rotation rides under the next column's
            # local matmuls.
            ops = _colpass_operators(core, foffs0, foffs1)
            body = (
                _colpass_einsum_body
                if mode == "einsum"
                else _colpass_pallas_body
            )

            def per_col(xs):
                NMBF, so, m0, m1 = xs
                if body is _colpass_pallas_body:
                    return body(
                        core, subgrid_size, ops, NMBF, foffs1, so, m0, m1,
                        axis_name, True, None, collective, n_shards,
                    )
                return body(
                    core, subgrid_size, ops, NMBF, foffs1, so, m0, m1,
                    axis_name, True, collective, n_shards,
                )

            return jax.lax.map(
                per_col, (NMBF_g, sg_offs_g, masks0_g, masks1_g)
            )

        def per_col(NMBF, so, m0, m1):
            return colfn(NMBF, foffs0, foffs1, so, m0, m1)

        return jax.vmap(per_col)(NMBF_g, sg_offs_g, masks0_g, masks1_g)

    return fn


@functools.lru_cache(maxsize=None)
def _column_pass_fwd_group_j(core, subgrid_size):
    return _jit()(
        _scoped(
            "swiftly/fwd.column_pass",
            _column_pass_fwd_group_fn(core, subgrid_size),
        )
    )


@functools.lru_cache(maxsize=None)
def _column_pass_fwd_group_sharded_cached(core, mesh, subgrid_size,
                                          collective):
    return _shmap(
        _scoped(
            "swiftly/fwd.column_pass",
            _column_pass_fwd_group_fn(
                core, subgrid_size, axis_name=FACET_AXIS,
                collective=collective, n_shards=_mesh_size(mesh),
            ),
        ),
        mesh,
        in_specs=(
            _P(FACET_AXIS), _P(FACET_AXIS), _P(FACET_AXIS),
            _P(), _P(), _P(),
        ),
        out_specs=_P(),
    )


def _column_pass_fwd_group_sharded(core, mesh, subgrid_size):
    """Group column pass under the CURRENT collective schedule (see
    `_column_pass_fwd_sharded` — same call-time resolution)."""
    return _column_pass_fwd_group_sharded_cached(
        core, mesh, subgrid_size, _resolve_collective_env(_mesh_size(mesh))
    )


def _bwd_scatter_rows(core, Z, sg_offs, axis_name=None):
    """One column's per-subgrid contribution blocks [S, F, m, m(,2)] ->
    the NAF_MNAF accumulator [F, m, yN(,2)] with ONE scatter-add.

    Replaces the per-subgrid lax.scan whose [F, m, yN] carry (302 MB at
    32k) crossed HBM once per subgrid — measured 2.9% of the matmul
    ceiling for the whole backward column pass (scripts/roofline.py
    --bwd). The destination index of block row j for subgrid offset
    scaled is (yN//2 - m//2 + scaled + ((j - scaled) mod m)) mod yN —
    the roll+wrapped-embed of `add_to_facet_math` as one index map
    (the same window arithmetic as `sampled_row_indices`); duplicate
    indices (overlapping windows) accumulate in the scatter.
    """
    import jax.numpy as jnp

    from ..ops.core import scaled_offset

    m, yN = core.xM_yN_size, core.yN_size
    S = Z.shape[0]
    F = Z.shape[1]
    scaled = scaled_offset(sg_offs[:, 1], yN, core.N)  # [S]
    j = jnp.arange(m)
    idx = (
        yN // 2 - m // 2 + scaled[:, None]
        + jnp.mod(j[None, :] - scaled[:, None], m)
    ) % yN  # [S, m]
    Zm = jnp.moveaxis(Z, 0, 2)  # [F, m, S, m(,2)]
    Zm = Zm.reshape((F, m, S * m) + Z.shape[4:])
    zeros = jnp.zeros((F, m, yN) + Z.shape[4:], dtype=Z.dtype)
    if axis_name is not None:
        zeros = varying(zeros, axis_name)
    return zeros.at[:, :, idx.reshape(-1)].add(Zm)


def _bwd_colpass_operators(core, foffs0, foffs1):
    """Backward (adjoint) column-pass operators, built in-trace from an
    identity block.

    E0 [F, m, xM(,2)]: the axis-0 `extract_from_subgrid_math` chain with
    the prepare-fft folded in (fft along an axis commutes with the other
    axis's ops). E1 [F, xM, m(,2)]: the axis-1 chain in row-basis layout
    (E1[f, b, j] = op1_f[j, b]).
    """
    import jax.numpy as jnp

    from ..ops.core import extract_from_subgrid_math

    p = core._p
    m, xM = core.xM_yN_size, core.xM_size
    if _planar(core):
        eye = (
            jnp.zeros((xM, xM, 2), core.dtype)
            .at[:, :, 0]
            .set(jnp.eye(xM, dtype=core.dtype))
        )
    else:
        eye = jnp.eye(xM, dtype=core.dtype)

    def e0(off0):
        return extract_from_subgrid_math(
            p, core._Fn, m, xM, core.N, p.fft(eye, 0), off0, 0
        )

    def e1(off1):
        return extract_from_subgrid_math(
            p, core._Fn, m, xM, core.N, p.fft(eye, 1), off1, 1
        )

    return jax.vmap(e0)(foffs0), jax.vmap(e1)(foffs1)


def _column_pass_bwd_einsum_fn(
    core, facet_size, axis_name=None, use_pallas=False
):
    """Operator-matrix backward column pass (adjoint of the forward
    einsum pass): the per-(facet, subgrid) extract chains collapse into
    two K=xM einsums; the per-subgrid scatter into the [F, m, yN]
    accumulator stays a scan (its positions are per-subgrid).

    ``use_pallas`` swaps the per-block einsum pair for the fused kernel
    (`colpass_pallas`, reduce_f=False: Z_sf = E0_f @ emb_s @ E1_f with
    the embedded subgrid broadcast over the facet axis) — everything
    around it (Sb blocking, scatter, finish) is shared."""
    import jax.numpy as jnp

    p = core._p
    xM = core.xM_size

    def fn(subgrids, sg_offs, foffs0, foffs1, masks1):
        E0, E1 = _bwd_colpass_operators(core, foffs0, foffs1)

        def emb_one(sg, so):
            x = p.wrapped_embed(sg, xM, so[0], 0)
            return p.wrapped_embed(x, xM, so[1], 1)

        S = sg_offs.shape[0]
        Sb = min(_colpass_sblock(), S)
        nb = -(-S // Sb)
        Sb = -(-S // nb)  # rebalanced: pad < nb, never a near-full block
        pad = nb * Sb - S
        sg_p, so_p = subgrids, sg_offs
        if pad:
            # zero-padded subgrids contribute exactly nothing
            zpad = jnp.zeros(
                (pad,) + subgrids.shape[1:], dtype=subgrids.dtype
            )
            sg_p = jnp.concatenate([subgrids, zpad])
            so_p = jnp.concatenate(
                [sg_offs, jnp.repeat(sg_offs[-1:], pad, 0)]
            )

        def block(xs):
            sg_blk, so_blk = xs
            emb = jax.vmap(emb_one)(sg_blk, so_blk)  # [Sb, xM, xM(,2)]
            if use_pallas:
                from ..ops.pallas_kernels import (
                    colpass_pallas, pallas_interpret,
                )

                bm, bn, bk = _colpass_blocks()
                Zr, Zi = colpass_pallas(
                    E0[..., 0], E0[..., 1],
                    emb[:, None, ..., 0], emb[:, None, ..., 1],
                    E1[..., 0], E1[..., 1],
                    reduce_f=False, bm=bm, bn=bn, bk=bk,
                    interpret=pallas_interpret(),
                )
                return jnp.stack([Zr, Zi], axis=-1)  # [Sb, F, m, m, 2]
            Y = _ceinsum(core, "fia,sab->sfib", E0, emb)
            return _ceinsum(core, "sfib,fbj->sfij", Y, E1)  # [Sb,F,m,m]

        if nb == 1:
            Z = block((sg_p, so_p))
        else:
            Z = jax.lax.map(
                block,
                (
                    sg_p.reshape((nb, Sb) + sg_p.shape[1:]),
                    so_p.reshape((nb, Sb) + so_p.shape[1:]),
                ),
            )
            Z = Z.reshape((nb * Sb,) + Z.shape[2:])
        # padded rows are zero blocks: the scatter adds nothing for them
        acc = _bwd_scatter_rows(core, Z, so_p, axis_name)

        def fin(a, off1, m1):
            x = finish_facet_math(p, core._Fb, facet_size, a, off1, 1)
            return _mask_along(p, x, m1, 1)

        return jax.vmap(fin)(acc, foffs1, masks1)

    return fn


def _column_pass_bwd_fn(core, facet_size, axis_name=None):
    """A column's subgrids [S, xA, xA] -> NAF_BMNAF rows [F, m, yB].

    Trace-time dispatcher (einsum vs fused-pallas vs fft chain) on the
    program's facet count — `resolve_colpass_bwd`, overridable with
    SWIFTLY_COLPASS_BWD. All bodies produce identical finished rows, so
    unlike the forward no caller pairing is needed."""
    bodies = {
        "einsum": _column_pass_bwd_einsum_fn(core, facet_size, axis_name),
        "pallas": _column_pass_bwd_einsum_fn(
            core, facet_size, axis_name, use_pallas=True
        ),
        "fft": _column_pass_bwd_fft_fn(core, facet_size, axis_name),
    }

    def fn(subgrids, sg_offs, foffs0, foffs1, masks1):
        body = bodies[_resolve_colpass_bwd(core, foffs0.shape[0])]
        return body(subgrids, sg_offs, foffs0, foffs1, masks1)

    return fn


def _column_pass_bwd_fft_fn(core, facet_size, axis_name=None):
    """The per-facet fft-chain backward column pass: batched prepare +
    per-(subgrid, facet) extract chains, then ONE scatter-add into the
    accumulator layout. (The previous per-subgrid `lax.scan` fold moved
    the [F, m, yN] carry through HBM once per subgrid — 2.9% of the
    matmul ceiling, the slowest stage in the whole pipeline; the [S, F,
    m, m] contribution stack is only ~350 MB at 32k, so materialising
    it and scattering once is strictly better.)"""
    from ..ops.core import prepare_subgrid_math
    from .batched import subgrid_contrib_to_facet

    import jax.numpy as jnp

    p = core._p

    def fn(subgrids, sg_offs, foffs0, foffs1, masks1):
        def prep_one(sg, so):
            return prepare_subgrid_math(p, core.xM_size, sg, so)

        def per_sg(pp):
            return jax.vmap(
                lambda f0, f1: subgrid_contrib_to_facet(core, pp, f0, f1)
            )(foffs0, foffs1)  # [F, m, m(,2)]

        def block_z(sg_b, so_b):
            prepped = jax.vmap(prep_one)(sg_b, so_b)  # [Sb, xM, xM]
            return jax.vmap(per_sg)(prepped)  # [Sb, F, m, m(,2)]

        # the [S, F, m, m] contribution stack is blocked by Sb like the
        # einsum body's gather transient; Sb is rebalanced to ceil(S/nb)
        # so the zero-pad never exceeds nb-1 rows (a raw 256-block split
        # of S=293 would pad 219 dead rows — 1.75x the stage's FLOPs)
        S = sg_offs.shape[0]
        Sb = min(_colpass_sblock(), S)
        nb = -(-S // Sb)
        Sb = -(-S // nb)
        if nb == 1:
            NAF_MNAFs = _bwd_scatter_rows(
                core, block_z(subgrids, sg_offs), sg_offs, axis_name
            )
        else:
            pad = nb * Sb - S
            sg_p, so_p = subgrids, sg_offs
            if pad:
                # zero-padded subgrids scatter exactly nothing
                sg_p = jnp.concatenate(
                    [subgrids,
                     jnp.zeros((pad,) + subgrids.shape[1:], subgrids.dtype)]
                )
                so_p = jnp.concatenate(
                    [sg_offs, jnp.repeat(sg_offs[-1:], pad, 0)]
                )

            def fold(acc, xs):
                sg_b, so_b = xs
                return (
                    acc
                    + _bwd_scatter_rows(
                        core, block_z(sg_b, so_b), so_b, axis_name
                    ),
                    None,
                )

            F = foffs0.shape[0]
            init = jnp.zeros(
                (F, core.xM_yN_size, core.yN_size) + subgrids.shape[3:],
                dtype=subgrids.dtype,
            )
            if axis_name is not None:
                init = varying(init, axis_name)
            NAF_MNAFs, _ = jax.lax.scan(
                fold,
                init,
                (
                    sg_p.reshape((nb, Sb) + sg_p.shape[1:]),
                    so_p.reshape((nb, Sb) + so_p.shape[1:]),
                ),
            )

        def fin(acc, off1, m1):
            x = finish_facet_math(p, core._Fb, facet_size, acc, off1, 1)
            return _mask_along(p, x, m1, 1)

        return jax.vmap(fin)(NAF_MNAFs, foffs1, masks1)

    return fn


@functools.lru_cache(maxsize=None)
def _column_pass_bwd_j(core, facet_size):
    return _jit()(
        _scoped(
            "swiftly/bwd.column_pass",
            _column_pass_bwd_fn(core, facet_size),
        )
    )


@functools.lru_cache(maxsize=None)
def _column_pass_bwd_group_j(core, facet_size):
    """A whole column GROUP's backward column passes as one dispatch:
    subgrids [G, S, xA, xA(,2)] -> rows [G, F, m, yB(,2)]. Per-dispatch
    latency made per-column dispatch the dominant cost of the backward
    leg on an earlier runtime (~0.1 s per chain); on the chip tool's
    machine that is still to be measured."""
    fn = _column_pass_bwd_fn(core, facet_size)
    return _jit()(
        _scoped(
            "swiftly/bwd.column_pass",
            jax.vmap(fn, in_axes=(0, 0, None, None, None)),
        )
    )


@functools.lru_cache(maxsize=None)
def _column_pass_bwd_sharded(core, mesh, facet_size):
    """Facet-sharded backward column pass (subgrids replicated; the split
    and fold are shard-local, no collectives)."""
    return _shmap(
        _scoped(
            "swiftly/bwd.column_pass",
            _column_pass_bwd_fn(core, facet_size, axis_name=FACET_AXIS),
        ),
        mesh,
        in_specs=(
            _P(), _P(), _P(FACET_AXIS), _P(FACET_AXIS), _P(FACET_AXIS),
        ),
        out_specs=_P(FACET_AXIS),
    )


def _facet_pass_bwd_fn(core, facet_size, axis_name=None):
    """NAF_BMNAF column-blocks [K, F, m, Cb] -> facet blocks [F, yB, Cb]."""
    p = core._p

    def fn(blocks, col_offs0, foffs0, masks0):
        def fold(carry, xs):
            blk, off0 = xs  # [F, m, Cb]
            emb = jax.vmap(
                lambda c: add_to_facet_math(p, core.yN_size, core.N, c, off0, 0)
            )(blk)
            return carry + emb, None

        F = foffs0.shape[0]
        init = jax.numpy.zeros(
            (F, core.yN_size) + blocks.shape[3:], dtype=blocks.dtype
        )
        if axis_name is not None:
            init = varying(init, axis_name)
        acc, _ = jax.lax.scan(fold, init, (blocks, col_offs0))

        def fin(a, off0, m0):
            x = finish_facet_math(p, core._Fb, facet_size, a, off0, 0)
            return _mask_along(p, x, m0, 0)

        return jax.vmap(fin)(acc, foffs0, masks0)

    return fn


@functools.lru_cache(maxsize=None)
def _facet_pass_bwd_j(core, facet_size):
    return _jit()(
        _scoped(
            "swiftly/bwd.facet_pass",
            _facet_pass_bwd_fn(core, facet_size),
        )
    )


@functools.lru_cache(maxsize=None)
def _facet_pass_bwd_sharded(core, mesh, facet_size):
    return _shmap(
        _scoped(
            "swiftly/bwd.facet_pass",
            _facet_pass_bwd_fn(core, facet_size, axis_name=FACET_AXIS),
        ),
        mesh,
        in_specs=(
            _P(None, FACET_AXIS), _P(), _P(FACET_AXIS), _P(FACET_AXIS),
        ),
        out_specs=_P(FACET_AXIS),
    )


# -- sampled-DFT facet pass -------------------------------------------------
#
# The forward facet pass per output row r of subgrid column offset sigma is
# a LINEAR map of the facet column f[j] (j < yB):
#
#   NMBF[r] = roll(wrapped_extract(ifft(wrapped_embed(Fb*f, yN, delta)),
#                                  m, s), s)[r]
#           = (1/yN) sum_j Fb[j] f[j] w^{(e0 + j) * kt_r},  w = e^{+2pi i/yN}
#
# with s = sigma*yN/N, kt_r = ((yN//2 - m//2 + s + ((r - s) mod m)) mod yN)
# - yN//2 the extracted spectral row index and e0 = delta - yB//2 the
# embedding shift (wrapped_embed start yN//2 - yB//2 + delta, minus the
# ifft centre yN//2). The phase separates: w^{e0*kt} (per facet, per row)
# times w^{j*kt} (facet-independent). So the WHOLE pass for any set of
# output rows is one complex matmul against A[r, j] = Fb[j]/yN * w^{j*kt_r}
# plus a per-facet diagonal phase — compute scales with rows actually
# needed, which makes column-group chunking free (no FFT recompute), and
# the FLOPs land on the MXU as a single large einsum.


def sampled_row_indices(core, col_offs0):
    """Centred spectral row indices kt [G*m] for a group of subgrid
    column offsets (int32; validated against the FFT-based pass by tests).
    """
    m = core.xM_yN_size
    yN = core.yN_size
    r = np.arange(m)
    rows = []
    for off0 in col_offs0:
        s = int(off0) * yN // core.N
        k = (yN // 2 - m // 2 + s + ((r - s) % m)) % yN
        rows.append(k - yN // 2)
    return np.concatenate(rows).astype(np.int32)


def _mulmod(a, b, yN):
    """(a*b) mod yN in int32, exact for any yN <= 2**16 (all catalogue
    sizes).

    A direct int32 product overflows once yN*yB exceeds 2**31 (e.g. the
    64k configs); int64 is unreliable here because jax silently downcasts
    it without x64. Instead reduce both operands mod yN and split b into
    8-bit limbs: every partial product stays below yN * 2**8 <= 2**24.
    """
    import jax.numpy as jnp

    if yN > 1 << 16:  # pragma: no cover - no such catalogue entry
        raise ValueError(f"phase computation requires yN <= 65536, got {yN}")
    a = jnp.mod(a, yN)
    b = jnp.mod(b, yN)
    b_hi, b_lo = b >> 8, b & 0xFF
    hi = jnp.mod(a * b_hi, yN) << 8
    return jnp.mod(hi + a * b_lo, yN)


def _sampled_phases(core, residues):
    import jax.numpy as jnp

    theta = (2 * np.pi / core.yN_size) * residues
    return jnp.cos(theta), jnp.sin(theta)


def _sampled_A_real(core, yB, dt, krows):
    """The sampled-DFT phase matrix pair (A_re, A_im) [R, yB] for real
    facets (krows-dependent only; factored from the pass body so a
    caller that batches multiple slabs against one krows set can build
    it once)."""
    import jax.numpy as jnp

    yN = core.yN_size
    fb = core._p.extract_mid(core._Fb, yB, 0) / yN  # [yB] real
    j = jnp.arange(yB, dtype=jnp.int32)
    a_cos, a_sin = _sampled_phases(
        core, _mulmod(krows[:, None], j[None, :], yN)
    )
    return (a_cos * fb[None, :]).astype(dt), (a_sin * fb[None, :]).astype(dt)


def _sampled_apply_real(core, A_re, A_im, Fr, e0, krows):
    """Apply a prebuilt sampled phase matrix to a real facet slab
    [F, yB, yB] -> rows [F, R, yB, 2] (the per-facet e0 phase rotation
    included). The `_facet_pass_sampled_fn(real)` body."""
    import jax.numpy as jnp

    yN = core.yN_size
    dt = Fr.dtype
    from ..ops.planar_backend import matmul_precision

    prec = matmul_precision()
    f = lambda a, b: jnp.einsum("rj,fjc->frc", a, b, precision=prec)
    out_re = f(A_re, Fr)
    out_im = f(A_im, Fr)
    p_cos, p_sin = _sampled_phases(
        core, _mulmod(e0.astype(jnp.int32)[:, None], krows[None, :], yN)
    )  # [F, R]
    p_cos = p_cos.astype(dt)[..., None]
    p_sin = p_sin.astype(dt)[..., None]
    return jnp.stack(
        [
            out_re * p_cos - out_im * p_sin,
            out_re * p_sin + out_im * p_cos,
        ],
        axis=-1,
    )


@functools.lru_cache(maxsize=None)
def _facet_pass_sampled_fn(core, real_facets=False):
    """facets [F, yB, Y(,2)] -> sampled contribution rows [F, R, Y(,2)].

    `krows` are centred spectral indices (from `sampled_row_indices`),
    `e0` the per-facet embedding shifts (facet_off0 - yB//2). One einsum
    per call; works for the full column set or any chunk of it. Body
    builder shared by the single-device jit and the facet-sharded
    shard_map variant.

    With ``real_facets`` (planar backend only) the facets arrive as a
    single real plane [F, yB, yB] — the zero imaginary plane's two
    einsums are dropped, halving both the FLOPs and the facet upload
    volume. Exact, not an approximation: point-source facet models are
    real-valued (reference ``make_facet_from_sources``), and the caller
    verifies the imaginary plane is identically zero before choosing
    this path.
    """
    import jax.numpy as jnp

    yN = core.yN_size

    def phases(residues):
        theta = (2 * np.pi / yN) * residues
        return jnp.cos(theta), jnp.sin(theta)

    if real_facets:
        if not _planar(core):  # pragma: no cover - guarded by caller
            raise ValueError("real_facets requires the planar backend")

        def fn(Fr, e0, krows):
            A_re, A_im = _sampled_A_real(core, Fr.shape[1], Fr.dtype, krows)
            return _sampled_apply_real(core, A_re, A_im, Fr, e0, krows)

    elif _planar(core):
        # Planes arrive as SEPARATE arrays (Fr, Fi), not a trailing axis:
        # slicing a stacked [F, yB, yB, 2] inside the program would
        # materialise multi-GiB plane copies next to the resident stack.

        def fn(Fr, Fi, e0, krows):
            yB = Fr.shape[1]
            dt = Fr.dtype
            fb = core._p.extract_mid(core._Fb, yB, 0) / yN  # [yB] real
            j = jnp.arange(yB, dtype=jnp.int32)
            a_cos, a_sin = phases(_mulmod(krows[:, None], j[None, :], yN))
            A_re = (a_cos * fb[None, :]).astype(dt)
            A_im = (a_sin * fb[None, :]).astype(dt)
            from ..ops.planar_backend import matmul_precision

            prec = matmul_precision()
            f = lambda a, b: jnp.einsum(
                "rj,fjc->frc", a, b, precision=prec
            )
            out_re = f(A_re, Fr) - f(A_im, Fi)
            out_im = f(A_re, Fi) + f(A_im, Fr)
            p_cos, p_sin = phases(
                _mulmod(
                    e0.astype(jnp.int32)[:, None], krows[None, :], yN
                )
            )  # [F, R]
            p_cos = p_cos.astype(dt)[..., None]
            p_sin = p_sin.astype(dt)[..., None]
            return jnp.stack(
                [
                    out_re * p_cos - out_im * p_sin,
                    out_re * p_sin + out_im * p_cos,
                ],
                axis=-1,
            )

    else:

        def fn(facets, e0, krows):
            yB = facets.shape[1]
            fb = core._p.extract_mid(core._Fb, yB, 0) / yN
            j = jnp.arange(yB, dtype=jnp.int32)
            a_cos, a_sin = phases(_mulmod(krows[:, None], j[None, :], yN))
            A = (a_cos + 1j * a_sin).astype(core.dtype) * fb[None, :]
            out = jnp.einsum("rj,fjc->frc", A, facets)
            p_cos, p_sin = phases(
                _mulmod(
                    e0.astype(jnp.int32)[:, None], krows[None, :], yN
                )
            )
            phi = (p_cos + 1j * p_sin).astype(core.dtype)
            return out * phi[..., None]

    return fn


@functools.lru_cache(maxsize=None)
def _facet_pass_sampled_j(core, real_facets=False):
    return _jit()(
        _scoped(
            "swiftly/fwd.sampled_facet_pass",
            _facet_pass_sampled_fn(core, real_facets),
        )
    )


@functools.lru_cache(maxsize=None)
def _facet_pass_sampled_sharded(core, mesh, real_facets=False):
    """Facet-sharded sampled-DFT facet pass: each device's einsum covers
    its local facets only (no collectives; the facet sum happens later in
    the column pass psum)."""
    if real_facets:
        n_arrays = 1  # single real plane
    else:
        n_arrays = 2 if _planar(core) else 1  # planes vs complex facets
    in_specs = tuple([_P(FACET_AXIS)] * n_arrays) + (_P(FACET_AXIS), _P())
    return _shmap(
        _scoped(
            "swiftly/fwd.sampled_facet_pass",
            _facet_pass_sampled_fn(core, real_facets),
        ),
        mesh,
        in_specs=in_specs,
        out_specs=_P(FACET_AXIS),
    )


# -- sampled-DFT backward facet pass (the exact adjoint) --------------------
#
# The backward facet pass along axis 0 is, per facet f and output row i:
#
#   out[f, i] = fb[i] * wrapped_extract(fft(sum_k wrapped_embed(
#                   roll(rows_k[f], -s_k), yN, s_k)), yB, delta_f)[i]
#
# Tracing one element rows_k[f, r] through embed+roll shows it lands at
# spectral position q_k(r) = (kt_r + yN//2) mod yN — the SAME kt indices
# the forward extracts (sampled_row_indices). The centred fft then gives
#
#   out[f, i] = fb[i] * sum_k sum_r rows_k[f, r] * w^{-kt_r (e0_f + i)}
#
# (w = e^{+2pi i/yN}, e0_f = facet_off0 - yB//2, NO 1/yN — fft is
# unnormalised where the forward's ifft carried the 1/yN). So the whole
# backward facet pass is the conjugate-phase transpose of the forward's
# sampled matmul: one einsum per column (group) accumulating directly
# into the [F, yB, yB] image-space facet accumulator — which is the SIZE
# OF THE OUTPUT, the minimal possible device state. No NAF_all buffer,
# no host round trip, no d2h until the final (verified-on-device) facets.


def _fold_row_block(F, yB, itemsize):
    """Static output-row block size for the adjoint fold's scan.

    The fold's einsum transients are [F, B, yB]-shaped; bounding B keeps
    each one to ~SWIFTLY_FOLD_BLOCK_MB (default 192) regardless of yB —
    the unblocked fold materialised a full [F, yB, yB, 2] (~2x the
    accumulator, ~18 GiB at 32k) next to the donated accumulator, which
    is exactly what OOM'd the 32k round trip on a 16 GiB chip.
    """
    import os

    target = float(os.environ.get("SWIFTLY_FOLD_BLOCK_MB", "192")) * 1e6
    per_row = max(1, F * yB * itemsize)
    B = int(target // per_row)
    if B >= yB:
        return yB
    return max(1, (B // 128) * 128 or B)


@functools.lru_cache(maxsize=None)
def _bwd_sampled_fold_fn(core, use_pallas=False, interpret=False):
    """acc [F, yB, yB(,2)] += adjoint-sampled fold of rows [F, R, yB(,2)].

    `rows` are a column group's NAF_BMNAF rows concatenated along R (the
    output of the backward column pass, already finished+masked along
    axis 1); `krows` their centred spectral indices; `e0` the per-facet
    embedding shifts. Validated against the FFT-based `_facet_pass_bwd`
    by tests/test_streamed.py.

    The fold accumulates in bounded output-row blocks (`_fold_row_block`)
    via a lax.scan whose carry is the donated accumulator: per block one
    [F, B, yB]-shaped einsum lands in acc through a dynamic slice update,
    so peak transient memory is a few blocks, not a second full
    accumulator. The final (clamped) block re-covers rows the previous
    block already folded; `keep` zeroes those contributions, making the
    tiling exact for any yB.

    ``row0`` (traced int32) is the ROW-SLAB offset: the accumulator may
    cover only output rows [row0, row0 + acc.shape[1]) of the facet —
    the "ri" einsum index restricts trivially, so a facet whose full
    [yB, yB] accumulator exceeds HBM (one 128k facet: 16.2 GiB) splits
    into HBM-sized row slabs, each an independent backward pass over
    the same subgrid stream. Whole-facet callers pass row0 = 0; the
    full facet width is read off the rows' pass-through j axis.

    With ``use_pallas`` (planar only; `ops.pallas_kernels.pallas_enabled`
    resolves the opt-in at trace time like SWIFTLY_COLPASS) each block's
    einsum pair + row-weight scale + accumulate runs as ONE fused
    `bwd_fold_pallas` grid program with the accumulator block pinned in
    VMEM — the facet axis folds into the kernel's j axis, so the fused
    matmuls stay MXU-deep at any facet count. The fused kernel tiles
    the contraction, so its partial-sum ORDER differs from the einsum
    body: results agree to f32 sum-reorder tolerance (~1e-5 relative,
    pinned by tests/test_pallas.py), not bit-identically. ``interpret``
    routes through the Pallas interpreter (CPU validation).
    """
    import jax.numpy as jnp

    yN = core.yN_size

    def phases(residues):
        theta = (2 * np.pi / yN) * residues
        return jnp.cos(theta), jnp.sin(theta)

    if use_pallas and not _planar(core):  # pragma: no cover - guarded
        raise ValueError("the Pallas fold requires the planar backend")

    if _planar(core) and use_pallas:
        from ..ops.pallas_kernels import bwd_fold_pallas

        def fn(acc, rows, e0, krows, row0):
            F, Rs = acc.shape[0], acc.shape[1]
            yB = rows.shape[2]  # full facet width (pass-through j axis)
            R = rows.shape[1]
            dt = acc.dtype
            fb = core._p.extract_mid(core._Fb, yB, 0)  # [yB] real
            p_cos, p_sin = phases(
                _mulmod(e0.astype(jnp.int32)[:, None], krows[None, :], yN)
            )
            p_cos = p_cos.astype(dt)[..., None]
            p_sin = p_sin.astype(dt)[..., None]
            Rr, Ri = rows[..., 0], rows[..., 1]
            # the [R, F*yB] layout folds the facet axis into the kernel's
            # output-column axis (hoisted out of the block scan — the
            # rotated planes are block-invariant)
            rr_flat = jnp.moveaxis(
                Rr * p_cos + Ri * p_sin, 0, 1
            ).reshape(R, F * yB)
            ri_flat = jnp.moveaxis(
                Ri * p_cos - Rr * p_sin, 0, 1
            ).reshape(R, F * yB)
            B = min(_fold_row_block(F, yB, np.dtype(dt).itemsize), Rs)
            n_blk = -(-Rs // B)
            fbj = jnp.asarray(fb, dt)

            def body(carry, xs):
                i0, start = xs
                ii = start + jnp.arange(B, dtype=jnp.int32)  # slab-rel
                keep = (ii >= i0).astype(dt)
                i_abs = row0 + ii  # absolute row: phases + Fb weight
                b_cos, b_sin = phases(
                    _mulmod(krows[:, None], i_abs[None, :], yN)
                )
                w = (
                    jax.lax.dynamic_slice_in_dim(fbj, row0 + start, B)
                    * keep
                )
                z = jnp.int32(0)
                cur = jax.lax.dynamic_slice(
                    carry, (z, start, z, z), (F, B, yB, 2)
                )
                out_r, out_i = bwd_fold_pallas(
                    jnp.moveaxis(cur[..., 0], 0, 1).reshape(B, F * yB),
                    jnp.moveaxis(cur[..., 1], 0, 1).reshape(B, F * yB),
                    b_cos.astype(dt),
                    b_sin.astype(dt),
                    rr_flat,
                    ri_flat,
                    w[:, None].astype(dt),
                    interpret=interpret,
                )
                new = jnp.stack(
                    [
                        jnp.moveaxis(out_r.reshape(B, F, yB), 0, 1),
                        jnp.moveaxis(out_i.reshape(B, F, yB), 0, 1),
                    ],
                    axis=-1,
                )
                return (
                    jax.lax.dynamic_update_slice(
                        carry, new, (z, start, z, z)
                    ),
                    None,
                )

            i0s = jnp.arange(n_blk, dtype=jnp.int32) * B
            starts = jnp.minimum(i0s, Rs - B)
            acc, _ = jax.lax.scan(body, acc, (i0s, starts))
            return acc

    elif _planar(core):

        def fn(acc, rows, e0, krows, row0):
            F, Rs = acc.shape[0], acc.shape[1]
            yB = rows.shape[2]  # full facet width (pass-through j axis)
            dt = acc.dtype
            fb = core._p.extract_mid(core._Fb, yB, 0)  # [yB] real, no 1/yN
            # conjugate per-facet phase: rows * w^{-e0_f kt_r}
            p_cos, p_sin = phases(
                _mulmod(e0.astype(jnp.int32)[:, None], krows[None, :], yN)
            )  # [F, R]
            p_cos = p_cos.astype(dt)[..., None]
            p_sin = p_sin.astype(dt)[..., None]
            Rr, Ri = rows[..., 0], rows[..., 1]
            Rr2 = Rr * p_cos + Ri * p_sin
            Ri2 = Ri * p_cos - Rr * p_sin
            from ..ops.planar_backend import matmul_precision

            prec = matmul_precision()
            f = lambda a, b: jnp.einsum(
                "ri,frj->fij", a, b, precision=prec
            )
            B = min(_fold_row_block(F, yB, np.dtype(dt).itemsize), Rs)
            n_blk = -(-Rs // B)
            fbj = jnp.asarray(fb, dt)

            def body(carry, xs):
                i0, start = xs
                ii = start + jnp.arange(B, dtype=jnp.int32)  # slab-rel
                keep = (ii >= i0).astype(dt)
                i_abs = row0 + ii  # absolute row: phases + Fb weight
                b_cos, b_sin = phases(
                    _mulmod(krows[:, None], i_abs[None, :], yN)
                )
                Bc = b_cos.astype(dt)
                Bs = b_sin.astype(dt)
                out_re = f(Bc, Rr2) + f(Bs, Ri2)
                out_im = f(Bc, Ri2) - f(Bs, Rr2)
                w = (
                    jax.lax.dynamic_slice_in_dim(fbj, row0 + start, B)
                    * keep
                )
                out = jnp.stack([out_re, out_im], axis=-1)
                out = out * w[None, :, None, None]
                z = jnp.int32(0)
                cur = jax.lax.dynamic_slice(
                    carry, (z, start, z, z), (F, B, yB, 2)
                )
                return (
                    jax.lax.dynamic_update_slice(
                        carry, cur + out, (z, start, z, z)
                    ),
                    None,
                )

            i0s = jnp.arange(n_blk, dtype=jnp.int32) * B
            starts = jnp.minimum(i0s, Rs - B)
            acc, _ = jax.lax.scan(body, acc, (i0s, starts))
            return acc

    else:

        def fn(acc, rows, e0, krows, row0):
            F, Rs = acc.shape[0], acc.shape[1]
            yB = rows.shape[2]  # full facet width (pass-through j axis)
            fb = core._p.extract_mid(core._Fb, yB, 0)
            p_cos, p_sin = phases(
                _mulmod(e0.astype(jnp.int32)[:, None], krows[None, :], yN)
            )
            phi = (p_cos - 1j * p_sin).astype(core.dtype)  # [F, R]
            rows2 = rows * phi[..., None]
            B = min(
                _fold_row_block(F, yB, np.dtype(core.dtype).itemsize), Rs
            )
            n_blk = -(-Rs // B)
            fbj = jnp.asarray(fb)

            def body(carry, xs):
                i0, start = xs
                ii = start + jnp.arange(B, dtype=jnp.int32)  # slab-rel
                keep = ii >= i0
                i_abs = row0 + ii  # absolute row: phases + Fb weight
                b_cos, b_sin = phases(
                    _mulmod(krows[:, None], i_abs[None, :], yN)
                )
                Bm = (b_cos - 1j * b_sin).astype(core.dtype)  # [R, B]
                out = jnp.einsum("ri,frj->fij", Bm, rows2)
                w = jnp.where(
                    keep,
                    jax.lax.dynamic_slice_in_dim(fbj, row0 + start, B),
                    0,
                )
                out = out * w[None, :, None].astype(core.dtype)
                z = jnp.int32(0)
                cur = jax.lax.dynamic_slice(
                    carry, (z, start, z), (F, B, yB)
                )
                return (
                    jax.lax.dynamic_update_slice(
                        carry, cur + out, (z, start, z)
                    ),
                    None,
                )

            i0s = jnp.arange(n_blk, dtype=jnp.int32) * B
            starts = jnp.minimum(i0s, Rs - B)
            acc, _ = jax.lax.scan(body, acc, (i0s, starts))
            return acc

    return fn


@functools.lru_cache(maxsize=None)
def _bwd_sampled_fold_j(core, use_pallas=False, interpret=False):
    return _jit(donate=(0,))(
        _scoped(
            "swiftly/bwd.sampled_fold",
            _bwd_sampled_fold_fn(core, use_pallas, interpret),
        )
    )


def resolve_fold_kernel(core, meshed=False) -> str:
    """Sampled-fold kernel body: "pallas" when the opt-in
    (SWIFTLY_PALLAS=1) applies — planar backend, single device — else
    "einsum". Read at trace time like SWIFTLY_COLPASS (the lru-cached
    jits bake the choice in)."""
    from ..ops.pallas_kernels import pallas_enabled

    if pallas_enabled() and _planar(core) and not meshed:
        return "pallas"
    return "einsum"


@functools.lru_cache(maxsize=None)
def _sampled_finish_j(core):
    """Apply the axis-0 facet masks to the sampled accumulator (the Fb
    weighting and spectral extraction already happened in the fold).

    The accumulator is DONATED: it is the size of the whole facet stack
    (9.8 GiB at 32k) and the caller never reuses it — an undonated
    finish materialises a second stack next to it, which is exactly what
    OOM'd the 32k round trip at the finish step."""

    def fn(acc, masks0):
        m = masks0[:, :, None]
        if _planar(core):
            m = m[..., None]
        return acc * m

    return _jit(donate=(0,))(_scoped("swiftly/bwd.finish", fn))


@functools.lru_cache(maxsize=None)
def _bwd_sampled_fold_sharded(core, mesh):
    """Facet-sharded fold: each device updates its local facets' image
    accumulator (no collectives — rows and acc share the facet axis)."""
    return _shmap(
        _scoped("swiftly/bwd.sampled_fold", _bwd_sampled_fold_fn(core)),
        mesh,
        in_specs=(
            _P(FACET_AXIS), _P(FACET_AXIS), _P(FACET_AXIS), _P(), _P(),
        ),
        out_specs=_P(FACET_AXIS),
        donate=(0,),
    )


# -- FFT (spectral-embed) backward fold --------------------------------------
#
# The sampled fold's adjoint DFT costs 8 * R_g * yB^2 * F per column group
# — R_g grows with the group, so fold FLOPs are ~flat per COLUMN
# (1.7e14 at 32k, the single largest block of the backward's wall-clock,
# measured 13.7% of peak). But the identical accumulation runs as the
# reference-shaped adjoint chain: scatter-embed each column's rows at its
# spectral window (`add_to_facet_math` — duplicate positions accumulate),
# ONE matmul-FFT finish (`finish_facet_math`), add into the donated image
# accumulator. Cost per GROUP is F * fft(yN over yB) + embeds — flat in
# group size — so at fold groups of 3+ columns it beats the sampled fold
# outright and keeps improving with bigger groups. Exactness: every step
# is the linear op the `_facet_pass_bwd` path runs (tested equal to the
# sampled fold), and fft(sum of embeds) == sum over groups by linearity.
# The [F, yN, Cj] spectral transient is bounded by chunking the
# pass-through output axis j (clamped starts + `keep` masking make any
# yB exact, the `_fold_row_block` pattern).


def _fft_fold_chunk(core, F, yB) -> int:
    """Static j-chunk width for the FFT fold's spectral transient
    [F, yN, Cj(,2)] — ~SWIFTLY_FFT_FOLD_CHUNK_MB (default 96) regardless
    of config; lane-aligned like `_fold_row_block`. The matmul-FFT keeps
    ~3 chunk-sized intermediates live, so the fold's peak transient is
    ~3x this target — 96 MB fits the roundtrip reserve that the sampled
    fold's 192 MB row blocks calibrated (384 MB OOM'd the 32k roundtrip
    at col_group=3)."""
    import os

    target = float(os.environ.get("SWIFTLY_FFT_FOLD_CHUNK_MB", "96")) * 1e6
    dsize = np.dtype(core.dtype).itemsize * (2 if _planar(core) else 1)
    per_col = max(1, F * core.yN_size * dsize)
    C = int(target // per_col)
    if C >= yB:
        return yB
    return max(1, (C // 128) * 128 or C)


def _bwd_fft_fold_chunk_fn(core, Cj, axis_name=None):
    """One j-chunk of the FFT fold: acc [F, yB, yB(,2)] += embed+fft+
    finish of rows_g[:, :, :, start:start+Cj].

    Dispatched once per chunk from a host loop with the accumulator
    donated across dispatches (the sampled fold's proven pattern) — a
    lax.scan carrying the multi-GiB accumulator through this body either
    lost input/output aliasing (compile-time "Used 18.07G of 15.75G") or
    hung the remote AOT compiler outright. `j0`/`start` are traced
    device scalars so every chunk reuses ONE compiled program; the
    clamped final chunk re-covers columns the previous chunk already
    folded and `keep` zeroes those, making the tiling exact for any yB.

    Emits the same accumulator contract as `_bwd_sampled_fold_fn` (Fb
    weighting and spectral extraction applied; axis-0 masks left to the
    finish), so the two folds are drop-in interchangeable per group.
    """
    import jax.numpy as jnp

    p = core._p
    yN = core.yN_size

    def fn(acc, rows_g, col_offs0, foffs0, j0, start):
        g = rows_g.shape[0]
        F, yB = acc.shape[0], acc.shape[1]
        tail = rows_g.shape[4:]
        z = jnp.int32(0)
        blk = jax.lax.dynamic_slice(
            rows_g,
            (z, z, z, start) + (z,) * len(tail),
            (g, F, rows_g.shape[2], Cj) + tail,
        )  # [g, F, m, Cj(,2)]
        spec = jnp.zeros((F, yN, Cj) + tail, dtype=rows_g.dtype)
        if axis_name is not None:
            spec = varying(spec, axis_name)
        # unrolled over the group's columns (g <= the feeding group cap)
        for k in range(g):
            spec = spec + jax.vmap(
                lambda c, k=k: add_to_facet_math(
                    p, yN, core.N, c, col_offs0[k], 0
                )
            )(blk[k])

        def fin(sp, off0):
            return finish_facet_math(p, core._Fb, yB, sp, off0, 0)

        out = jax.vmap(fin)(spec, foffs0)  # [F, yB, Cj(,2)]
        j = start + jnp.arange(Cj, dtype=jnp.int32)
        keep = (j >= j0).astype(rows_g.dtype)
        out = out * keep[None, None, :].reshape(
            (1, 1, Cj) + (1,) * len(tail)
        )
        cur = jax.lax.dynamic_slice(
            acc, (z, z, start) + (z,) * len(tail), (F, yB, Cj) + tail
        )
        return jax.lax.dynamic_update_slice(
            acc, cur + out, (z, z, start) + (z,) * len(tail)
        )

    return fn


@functools.lru_cache(maxsize=None)
def _bwd_fft_fold_chunk_j(core, Cj):
    return _jit(donate=(0,))(
        _scoped("swiftly/bwd.fft_fold", _bwd_fft_fold_chunk_fn(core, Cj))
    )


@functools.lru_cache(maxsize=None)
def _bwd_fft_fold_chunk_sharded(core, mesh, Cj):
    """Facet-sharded FFT fold chunk (embed + fft are facet-local; no
    collectives — rows and acc share the facet axis)."""
    return _shmap(
        _scoped(
            "swiftly/bwd.fft_fold",
            _bwd_fft_fold_chunk_fn(core, Cj, axis_name=FACET_AXIS),
        ),
        mesh,
        in_specs=(
            _P(FACET_AXIS), _P(None, FACET_AXIS), _P(), _P(FACET_AXIS),
            _P(), _P(),
        ),
        out_specs=_P(FACET_AXIS),
        donate=(0,),
    )


# -- Cooley-Tukey sampled backward fold --------------------------------------
#
# The sampled fold evaluates out[f, i, j] = fb[i] * sum_r rows[f, r, j] *
# W^{-kt_r * (e0_f + i)} (W = e^{+2pi i/yN}) as one dense [i, r] DFT per
# call: 8 * R * yB^2 * F FLOPs, flat per COLUMN. Factoring the kernel the
# Cooley-Tukey way over kt_r = Q*a_r + b_r and i = q*P + p (P = yN/Q):
#
#   W^{-kt (e0 + i)} = W^{-kt (e0 + p)} * e^{-2pi i b q / Q}
#
# (kt * q * P = a*q*yN + b*q*P), the fold becomes two dense stages with
# no scatters or rolls:
#   1. group rows by b-lane (a gather: a column's m consecutive kt values
#      hit each b-lane ceil(m/Q) times at most) and sum each lane's rows
#      times their phases W^{-kt (e0 + p)}: G[f,b,p,j], a multiply-add
#      over the g*ceil(m/Q) rows of a lane, on the vector units
#   2. one [q, b] DFT matmul: out[f,q,p,j] -> i = q*P + p
#      (K = 2Q = 256 in planar form, flat in the number of rows)
# Stage 2 costs 8 * Qi * Q * P * yB * F FLOPs a call (Qi = yB/P rows of
# q) — R/Q times fewer than the dense fold. Exact: pure index algebra,
# pinned against the sampled fold by tests at every backend.

# `select_fold_body` takes the CT body for calls of at least this many
# rows per lane (R >= CT_MIN_LANE_DEPTH * Q): the shallowest depth timed
# on the chip, where CT won (TPU v5e, 32k, R = 2Q: 130 ms against the
# sampled body's 260 ms a call)
CT_MIN_LANE_DEPTH = 2


@functools.lru_cache(maxsize=256)
def _ct_fold_tables(core, col_offs0):
    """Index table of the CT fold of one column group (``col_offs0`` a
    tuple of column offsets).

    Returns (Q, P, kmax, tab): ``tab`` is int32 [R + g*Q*kmax], the
    group's spectral row indices kt (`sampled_row_indices`) followed by
    `r_idx[c, b, k]`, the row (into the R = g*m concatenated rows) of
    the k-th row of column c that lands in b-lane b, or -1 where the
    lane has fewer rows. One array, so a call puts one table on the
    device, as the sampled body puts its kt.
    """
    import math

    yN = core.yN_size
    m = core.xM_yN_size
    Q = math.gcd(128, yN)
    P = yN // Q
    kmax = -(-m // Q)
    g = len(col_offs0)
    kt = sampled_row_indices(core, col_offs0)
    lane = (kt.astype(np.int64) % Q).reshape(g, m)
    r_idx = np.full((g, Q, kmax), -1, dtype=np.int32)
    for c in range(g):
        order = np.argsort(lane[c], kind="stable")
        lanes = lane[c][order]
        rank = np.arange(m) - np.searchsorted(lanes, lanes)
        r_idx[c, lanes, rank] = c * m + order
    return Q, P, kmax, np.concatenate([kt, r_idx.ravel()])


@functools.lru_cache(maxsize=None)
def _ct_fold_width(yB, per_col_bytes, budget) -> int:
    """Static j-width W of one CT fold launch: the widest divisor of yB
    whose stage planes (``per_col_bytes`` per output column, all facets:
    `_ct_column_bytes`) fit ``budget`` — the HBM the plan reserves for
    the fold's transients (`plan.model.DEFAULT_RESERVE_BYTES`, which the
    sampled fold's row blocks also live in). Lane-aligned (a multiple of
    128) where yB has such a divisor, so each window is whole tiles of
    the accumulator. At 32k (9 facets, 2.0 MB a column) the 1.2 GB
    reserve gives W 512: 22 launches a call, 1.02 GB of transients
    (compiled for v5e). On a v5e the call took 131 ms at W 512, 132 ms
    at W 256 and 136 ms at W 1024 (g = 2): the width sets the memory,
    not the time."""
    cap = max(1, min(yB, int(budget // max(1, per_col_bytes))))
    fits = [w for w in range(1, cap + 1) if yB % w == 0]
    aligned = [w for w in fits if w % 128 == 0]
    return max(aligned or fits)


def _ct_column_bytes(core, n_facets, yB):
    """Bytes of the CT fold's live stage planes per output column of a
    launch: the lane sums and the stage-2 output, re and im, of every
    facet."""
    import math

    Q = math.gcd(128, core.yN_size)
    P = core.yN_size // Q
    planes = 2 * (Q + -(-yB // P)) * P * n_facets
    itemsize = np.dtype(core.dtype).itemsize
    return planes * (itemsize if _planar(core) else itemsize // 2)


def _layout(x, major_to_minor):
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(x, Layout(major_to_minor))


@functools.lru_cache(maxsize=None)
def _bwd_ct_fold_fn(core, Q, P, kmax, W):
    """acc [F, yB, yB(,2)] += one j-window [j0, j0+W) of the CT-factored
    adjoint-sampled fold of concatenated column rows [F, R, yB(,2)]
    (the accumulator contract of `_bwd_sampled_fold_fn`); the caller
    loops yB/W windows (``j0`` a multiple of W), donating the
    accumulator across launches.

    ``tab`` is `_ct_fold_tables`' packed kt + lane table. Everything is
    batched over the facets: per window the live planes are the lane
    sums G [F, Q, P, W] and the stage-2 output [F, Qi, P, W] (complex),
    which `_ct_fold_width` sizes W for. The window is one slot of the
    accumulator seen as [F, yB, yB/W, W(,2)], laid out as the
    accumulator is (re/im inside each row's lane tiles): the slot index
    is a major axis, so its slice/update is one in-place fusion. Sliced
    on its minor axis instead, the update ran at a tenth of the HBM rate
    (262 of 630 ms a call on a v5e at 32k).
    """
    import jax.numpy as jnp

    yN = core.yN_size
    planar = _planar(core)

    def fn(acc, rows, e0, tab, j0):
        F, yB = acc.shape[0], acc.shape[1]
        R = rows.shape[1]
        n = tab.shape[0] - R
        g = n // (Q * kmax)
        rdt = acc.dtype if planar else core._Fb.real.dtype
        Qi = -(-yB // P)
        z = jnp.int32(0)
        ztail = (z,) * (acc.ndim - 3)
        kt, r_idx = tab[:R], tab[R:]
        r_safe = jnp.maximum(r_idx, 0)
        # stage-1 phases W^{-kt (e0 + p)} of every gathered row, zero on
        # the lanes' pad slots: [F, n, P]
        pj = jnp.arange(P, dtype=jnp.int32)
        res = _mulmod(
            jnp.take(kt, r_safe)[None, :, None],
            e0.astype(jnp.int32)[:, None, None] + pj[None, None, :],
            yN,
        )
        t_cos, t_sin = _sampled_phases(core, res)
        live = (r_idx >= 0).astype(rdt)[None, :, None]
        t_re = (t_cos * live).astype(rdt).reshape(F, g, Q, kmax, P)
        t_im = (-t_sin * live).astype(rdt).reshape(F, g, Q, kmax, P)
        # stage-2 DFT D[q, b] = e^{-2pi i q b / Q}
        qb = jnp.mod(
            jnp.arange(Qi, dtype=jnp.int32)[:, None]
            * jnp.arange(Q, dtype=jnp.int32)[None, :],
            Q,
        )
        theta = (-2 * np.pi / Q) * qb.astype(rdt)
        d_re, d_im = jnp.cos(theta), jnp.sin(theta)
        fb = core._p.extract_mid(core._Fb, yB, 0)  # [yB] real, no 1/yN
        fb = jnp.asarray(fb.real if not planar else fb, rdt)

        from ..ops.planar_backend import matmul_precision

        prec = matmul_precision()
        blk = jax.lax.dynamic_slice(
            rows, (z, z, j0) + ztail, (F, R, W) + rows.shape[3:]
        )
        x = jnp.take(blk, r_safe, axis=1).reshape(
            (F, g, Q, kmax, W) + rows.shape[3:]
        )
        # stage 1: per lane, a multiply-add over its g*kmax rows
        terms = [(c, k) for c in range(g) for k in range(kmax)]
        if planar:
            x_re, x_im = x[..., 0], x[..., 1]
            g_re = g_im = 0
            for c, k in terms:
                tr = t_re[:, c, :, k, :, None]
                ti = t_im[:, c, :, k, :, None]
                xr = x_re[:, c, :, k, None, :]
                xi = x_im[:, c, :, k, None, :]
                g_re = g_re + tr * xr - ti * xi
                g_im = g_im + tr * xi + ti * xr
            # stage 2 as ONE real matmul over (re/im, b):
            # [[Dr, -Di], [Di, Dr]] @ [Gr; Gi]
            d2 = jnp.stack(
                [
                    jnp.concatenate([d_re, d_im], axis=0),
                    jnp.concatenate([-d_im, d_re], axis=0),
                ],
                axis=1,
            )  # [2Qi, 2, Q]
            o = jnp.einsum(
                "qcb,fcbpj->fqpj",
                d2,
                jnp.stack([g_re, g_im], axis=1),
                precision=prec,
            )  # [F, 2Qi, P, W]: re rows, then im rows
            # one transpose puts re/im next to j, as the accumulator has
            o = jnp.moveaxis(o.reshape(F, 2, Qi, P, W), 1, -1)
        else:
            t = (t_re + 1j * t_im).astype(core.dtype)
            gs = 0
            for c, k in terms:
                gs = gs + t[:, c, :, k, :, None] * x[:, c, :, k, None, :]
            d = (d_re + 1j * d_im).astype(core.dtype)
            o = jnp.einsum("qb,fbpj->fqpj", d, gs, precision=prec)
        tail = acc.shape[3:]
        out = o.reshape((F, Qi * P, 1, W) + tail)[:, :yB] * fb.reshape(
            (1, yB, 1, 1) + (1,) * len(tail)
        )
        # the window as a slot of a [F, yB, yB/W, W(,2)] view: its index
        # is a major axis (each slot whole lane tiles), so the add lands
        # in place in the donated accumulator's own layout
        order = (0, 1, 2) + ((4, 3) if planar else (3,))
        view = _layout(acc.reshape((F, yB, yB // W, W) + tail), order)
        at = (z, z, j0 // W, z) + ztail
        cur = jax.lax.dynamic_slice(view, at, (F, yB, 1, W) + tail)
        view = jax.lax.dynamic_update_slice(
            view, cur + _layout(out, order).astype(acc.dtype), at
        )
        return _layout(view, order).reshape(acc.shape)

    return fn


@functools.lru_cache(maxsize=None)
def _bwd_ct_fold_j(core, Q, P, kmax, W):
    return _jit(donate=(0,))(
        _scoped("swiftly/bwd.ct_fold", _bwd_ct_fold_fn(core, Q, P, kmax, W))
    )


@functools.lru_cache(maxsize=None)
def _bwd_ct_fold_sharded(core, mesh, Q, P, kmax, W):
    """Facet-sharded CT fold (all stages facet-local; no collectives)."""
    return _shmap(
        _scoped("swiftly/bwd.ct_fold", _bwd_ct_fold_fn(core, Q, P, kmax, W)),
        mesh,
        in_specs=(
            _P(FACET_AXIS), _P(FACET_AXIS), _P(FACET_AXIS), _P(), _P(),
        ),
        out_specs=_P(FACET_AXIS),
        donate=(0,),
    )


def resolve_fold_mode() -> str:
    """SWIFTLY_FOLD = auto | sampled | ct | fft: the fold body of a
    `StreamedBackward`. An explicit body is forced on every call (the
    fft body at 32k took 4.0-4.2 s a call on a v5e, against 0.13 s for
    ct and 0.26-0.40 s for sampled); "auto", the default, lets
    `select_fold_body` pick per call."""
    import os

    mode = os.environ.get("SWIFTLY_FOLD", "auto")
    if mode not in ("ct", "fft", "sampled", "auto"):
        raise ValueError(
            f"SWIFTLY_FOLD must be ct|fft|sampled|auto, got {mode!r}"
        )
    return mode


def select_fold_body(mode, yN, n_rows, meshed=False, row_slab=False):
    """The body one fold call of ``n_rows`` concatenated rows runs.

    An explicit ``mode`` is kept. "auto" takes the CT-factored body where
    the call is deep against the CT lane count Q = gcd(128, yN), n_rows
    >= CT_MIN_LANE_DEPTH * Q, and the sampled body otherwise. A CT call
    costs about the same whatever its depth; the dense sampled call
    grows with it. On a v5e at 32k (9 facets of 11264, Q 128, m 256
    rows a column) a call took, in device time: CT 130 ms at 256 rows
    and 131 ms at 512; sampled 260 ms and 397 ms. Row slabs (the CT body
    folds whole facets) and facet meshes (no cell runs a backward on
    one yet) keep the sampled body.
    """
    import math

    if mode != "auto":
        return mode
    if meshed or row_slab:
        return "sampled"
    Q = math.gcd(128, yN)
    return "ct" if n_rows >= CT_MIN_LANE_DEPTH * Q else "sampled"


# -- device-side sparse facet synthesis -------------------------------------


@functools.lru_cache(maxsize=None)
def _synth_slab_j(core, Fg, yB):
    """Scatter (facet, row, col, val) pixels into a zeroed real slab
    [Fg, yB, yB] — the device-side synthesis of point-source-model
    facets (`ops.oracle.SparseRealFacet`). Uploading coordinates instead
    of planes turns facet-slab streaming from h2d-bound (2 GB per 64k
    slab, once per column group) into compute-bound."""
    import jax.numpy as jnp

    dt = _np_dtype(core)

    def fn(f, r, c, v):
        z = jnp.zeros((Fg, yB, yB), dtype=dt)
        return z.at[f, r, c].add(v)

    return _jit()(_scoped("swiftly/fwd.facet_synth", fn))


# -- facet-group forward column step ----------------------------------------
#
# At N >= 65536 the facet stack exceeds HBM (36.5 GB planar at 64k), so
# the sampled-DFT path streams FACET GROUPS: columns are processed in
# groups of G, and within a column group the facets arrive in slabs of
# `facet_group`; each slab's PRE-FINISH contribution is ADDED into a
# per-column-group [G, S, xM, xM] accumulator (every stage of the
# transform is linear in the facets, so cross-slab accumulation is
# exact), and the finish (iFFT/crop/masks) runs ONCE per column group —
# finishing per slab cost n_slabs-1 extra finish passes, 44% of all
# FLOPs at 64k. Device residency: one facet slab + the accumulator +
# one sampled group buffer — bounded regardless of N.


def _column_group_step_fn(core, subgrid_size, chunk, colpass):
    """One facet slab's PRE-FINISH contribution, added into the group acc.

    acc [n_chunks, chunk, S, xM, xM(,2)]; buf [Fg, G*m, yB(,2)] is the
    slab's sampled rows for the whole column group (G = n_chunks*chunk).
    Columns are scanned `chunk` at a time to bound the per-step
    transient. The finish (iFFT/crop/masks) is NOT applied here: it
    runs ONCE per group (`_column_group_finish_j`) after all slabs
    accumulated — finishing per slab cost n_slabs-1 extra finish passes,
    44% of all FLOPs at 64k.

    `colpass` (einsum|pallas|fft) is EXPLICIT here: the fft body
    accumulates partials in a different space (grid, vs image for
    einsum/pallas), so the executor resolves the choice once (from its
    facet_group) and passes the same value to this step and to
    `_column_group_finish_j`.
    """
    m = core.xM_yN_size
    matrix_mode = colpass in ("einsum", "pallas")
    colfn = (
        None if matrix_mode
        else _column_pass_fwd_fft_fn(core, subgrid_size, finish=False)
    )
    matrix_body = (
        _colpass_einsum_body if colpass == "einsum" else _colpass_pallas_body
    )

    def fn(acc, buf, foffs0, foffs1, sg_offs_g):
        Fg = buf.shape[0]
        n_chunks = acc.shape[0]
        G = n_chunks * acc.shape[1]
        NMBF_g = jax.numpy.moveaxis(
            buf.reshape((Fg, G, m) + buf.shape[2:]), 1, 0
        )  # [G, Fg, m, yB(,2)]
        NMBF_c = NMBF_g.reshape((n_chunks, acc.shape[1]) + NMBF_g.shape[1:])

        if matrix_mode:
            # operator build hoisted out of the chunk scan (loop-invariant)
            ops = _colpass_operators(core, foffs0, foffs1)

            def one_col(nm, so):
                return matrix_body(
                    core, subgrid_size, ops, nm, foffs1, so, None, None,
                    finish=False,
                )

            def step(carry, xs):
                c, nm, so = xs
                out = jax.vmap(one_col)(nm, so)  # [chunk, S, xM, xM(,2)]
                return carry.at[c].add(out), None
        else:

            def step(carry, xs):
                c, nm, so = xs
                out = jax.vmap(colfn, in_axes=(0, None, None, 0))(
                    nm, foffs0, foffs1, so
                )  # [chunk, S, xM, xM(,2)]
                return carry.at[c].add(out), None

        idx = jax.numpy.arange(n_chunks)
        acc, _ = jax.lax.scan(step, acc, (idx, NMBF_c, sg_offs_g))
        return acc

    return fn


@functools.lru_cache(maxsize=None)
def _column_group_step_j(core, subgrid_size, chunk, colpass):
    return _jit(donate=(0,))(
        _scoped(
            "swiftly/fwd.slab_step",
            _column_group_step_fn(core, subgrid_size, chunk, colpass),
        )
    )


@functools.lru_cache(maxsize=None)
def _fused_sparse_slab_step_j(core, subgrid_size, chunk, Fg, yB, colpass):
    """ONE program per facet slab: sparse synthesis -> sampled-DFT pass
    -> column-group step, with the group accumulator donated through.

    An earlier runtime paid ~0.1 s of latency per dispatch chain
    (scripts/roofline.py; on the chip tool's machine still to be
    measured); the unfused slab path cost three dispatches per slab.
    Fusing also lets XLA schedule the scatter and einsum together and
    drops the intermediate slab buffer's round trip through HBM
    allocation. Fusing FURTHER — the whole slab loop as one
    lax.scan program per column group — was measured 3x SLOWER at 64k
    (188.6 s vs 61.7 s full cover): the nested while-loops (slab scan >
    chunk scan > S-block map) serialize XLA's scheduling, so one
    dispatch per slab with the depth-2 checksum pipeline stands.

    The synthesis and the sampled pass each carry their own scope inside
    the program's ``swiftly/fwd.slab_step``, which is left holding the
    column step alone, so a device trace credits each stage apart."""
    import jax.numpy as jnp

    sam = _facet_pass_sampled_fn(core, real_facets=True)
    step = _column_group_step_fn(core, subgrid_size, chunk, colpass)
    dt = _np_dtype(core)

    def fn(acc, f, r, c, v, e0, krows, foffs0, foffs1, so_c):
        with jax.named_scope("swiftly/fwd.facet_synth"):
            # the barrier keeps the zero fill the instruction this scope
            # names: without it XLA rebuilds a one-facet slab's fill
            # with no scope (it sinks the slab's reshape into a new
            # broadcast); the same fill and in-place update run
            zeros = jax.lax.optimization_barrier(
                jnp.zeros((Fg, yB, yB), dtype=dt)
            )
            slab = zeros.at[f, r, c].add(v)
        with jax.named_scope("swiftly/fwd.sampled_facet_pass"):
            buf = sam(slab, e0, krows)
        return step(acc, buf, foffs0, foffs1, so_c)

    return _jit(donate=(0,))(_scoped("swiftly/fwd.slab_step", fn))


def _column_group_finish_fn(core, subgrid_size, colpass):
    """Finish a whole group's accumulated partials in one program:
    [n_chunks, chunk, S, xM, xM(,2)] -> finished subgrids
    [n_chunks, chunk, S, xA, xA(,2)]. The einsum and pallas column
    passes accumulate IMAGE-space partials (iFFTs folded into their
    operators), so their finish is crop + masks; the fft pass
    accumulates grid-space partials and finishes with the crop iFFTs.
    `colpass` must be the value the executor passed to the
    `_column_group_step_fn` that filled the accumulator."""
    einsum_mode = colpass in ("einsum", "pallas")

    def fn(acc, sg_offs_g, masks0_g, masks1_g):
        def fin(summed, so, m0, m1):
            if einsum_mode:
                return _crop_masked_subgrid(
                    core, summed, so, subgrid_size, m0, m1
                )
            return finish_masked_subgrid(
                core, summed, so, subgrid_size, m0, m1
            )

        per_col = jax.vmap(fin)  # over S
        per_chunk = jax.vmap(per_col)  # over chunk
        return jax.vmap(per_chunk)(acc, sg_offs_g, masks0_g, masks1_g)

    return fn


@functools.lru_cache(maxsize=None)
def _column_group_finish_j(core, subgrid_size, colpass):
    # the accumulator is NOT donated: the finish crops xM -> xA, so no
    # output ever matches the donated buffer's shape and XLA ignored the
    # donation with a "Some donated buffers were not usable:
    # f32[...,xM,xM,2]" warning per compile (BENCH_r05 tail). The buffer
    # frees at the caller's `del acc` exactly as before.
    return _jit()(
        _scoped(
            "swiftly/fwd.group_finish",
            _column_group_finish_fn(core, subgrid_size, colpass),
        )
    )




# ---------------------------------------------------------------------------
# Shared plumbing
# ---------------------------------------------------------------------------


class _StreamedBase:
    def __init__(self, swiftly_config, facet_configs, col_block, residency):
        from ..api import _FacetStack

        self.config = swiftly_config
        self.core = swiftly_config.core
        self.mesh = getattr(swiftly_config, "mesh", None)
        if self.core.backend in ("numpy", "native"):
            raise ValueError(
                "Streamed execution requires a device backend "
                "('jax' or 'planar')"
            )
        if residency not in ("host", "device", "sampled"):
            raise ValueError(
                f"residency must be host|device|sampled, got {residency}"
            )
        if not facet_configs:
            raise ValueError(
                "facet_configs must be non-empty (the streamed paths "
                "size their programs from the first facet)"
            )
        self.residency = residency
        self.stack = _FacetStack(
            facet_configs, pad_to=_mesh_size(self.mesh)
        )
        self.col_block = int(col_block)
        yB = self.stack.size
        self._n_blocks = -(-yB // self.col_block)
        self._yB_pad = self._n_blocks * self.col_block
        self._foffs0 = self._place(np.asarray(self.stack.offs0))
        self._foffs1 = self._place(np.asarray(self.stack.offs1))
        rdt = self.core._Fb.dtype
        # realised once: per-call conversion/upload would sit on the hot
        # per-column accumulation path
        self._masks0_dev = self._place(np.asarray(self.stack.masks0, rdt))
        self._masks1_dev = self._place(np.asarray(self.stack.masks1, rdt))

    def _place(self, arr, facet_axis: int = 0):
        """Upload an array, facet-sharding `facet_axis` over the mesh (or
        plain default placement without one). Multihost-safe: on a pod
        slice each process supplies only its facet shard (see
        `mesh.place_facet_sharded`)."""
        import jax.numpy as jnp

        if self.mesh is None:
            return jnp.asarray(arr)
        from .mesh import place_facet_sharded

        return place_facet_sharded(arr, self.mesh, facet_axis)

    def _alloc_buffer(self, n_cols):
        F, m, yB = len(self.stack), self.core.xM_yN_size, self._yB_pad
        shape = (n_cols, F, m, yB) + _tail(self.core)
        return np.zeros(shape, dtype=_np_dtype(self.core))


def _whole_group_yield(groups, grp, G, arr):
    """(per_col_items, group_array) for a whole-group yield: real items
    per column, and the group array with the short final group's padded
    (repeated-last-column) entries sliced off — folding those would
    double-count."""
    per_col = [
        [it for it in groups[off0] if it[0] is not None] for off0 in grp
    ]
    return per_col, (arr if len(grp) == G else arr[: len(grp)])


def _group_full_columns(subgrid_configs):
    """Group configs by off0, padding ragged columns to equal length.

    Sparse/irregular covers leave columns with unequal subgrid counts;
    the stacked column programs need one static S. Short columns are
    padded with zero-mask configs whose rows are computed then discarded
    — exact (masks zero the padded outputs) and cheap (padding is at
    most one column's worth of work). Padded entries carry index None
    and sit at the END of each column, so program rows [0:n_real] always
    match the real items.
    """
    from ..api import _group_columns, _pad_ragged_columns

    groups, rectangular = _group_columns(
        list(enumerate(subgrid_configs)),
        key=lambda item: item[1],
        require_one_size=True,
    )
    if not rectangular:
        size = next(iter(groups.values()))[0][1].size
        _pad_ragged_columns(groups, size)
    return groups


class CachedColumnFeed:
    """On-demand lookups into a recorded subgrid stream.

    The sequential sibling (`StreamedForward._replay_spilled_groups`)
    feeds backward passes the whole stream in order; this feed is the
    SERVING-path view of the same `utils.spill.SpillCache`: it indexes
    every recorded subgrid by ``(off0, off1, size)`` at construction,
    and `lookup` returns one host row — a RAM slice or a single-row
    memmap read for disk-backed entries — so an individual request is
    answered without a device dispatch and without materialising a
    whole group stack.

    Exactness contract: a hit is a verbatim copy of the recorded
    stream's row (the cache stores plain float arrays), so a feed-served
    request is bit-identical to the streamed forward that recorded it.
    A config whose offsets match but whose masks differ from the
    recorded one is a MISS (masks are part of the result), as is any
    config the stream never covered. A hit whose backing entry has been
    evicted since indexing raises LookupError — consumers
    (`serve.SubgridService`) treat that as the signal to fall back to
    recomputation, the serving twin of the cache's degrade-to-replay
    contract.

    Version pinning: the feed captures the cache's ``stream_version``
    at construction (the `delta.FacetDeltaLedger` stamp). Once an
    incremental facet update moves the cache's version, every lookup
    raises LookupError — a feed indexed before the patch can never
    serve a row recorded (or patched) for a different facet stack;
    consumers rebuild the feed (`serve.SubgridService
    .post_facet_update`) or fall back to compute.
    """

    def __init__(self, spill, *, index=None, stream_version=None):
        if not getattr(spill, "complete", False):
            raise ValueError(
                "CachedColumnFeed requires a COMPLETE spill cache "
                "(begin_fill/put/end_fill with nothing evicted); an "
                "incomplete stream would silently miss-serve"
            )
        self._spill = spill
        self.stream_version = int(
            getattr(spill, "stream_version", 0)
            if stream_version is None else stream_version
        )
        # views over one shared stream (`cache.SharedStreamTier`) pass
        # a prebuilt index so N replicas don't re-scan the stream's
        # metadata N times; plain feeds build their own
        self._index = self.build_index(spill) if index is None else index
        self.hits = 0
        self.misses = 0
        self.evicted = 0
        self.stale = 0

    @staticmethod
    def build_index(spill):
        """``(off0, off1, size) -> (k, c, s, recorded config)`` over a
        complete recorded stream — the per-subgrid lookup table. Built
        once per stream and shareable across feeds: patch-mode facet
        updates rewrite entry PAYLOADS in place, so row coordinates
        (and therefore this index) survive them; only a re-record
        (replay) invalidates it."""
        index = {}
        for k in range(len(spill)):
            for c, col in enumerate(spill.meta(k)):
                for s, (_i, sg) in enumerate(col):
                    index[(sg.off0, sg.off1, sg.size)] = (k, c, s, sg)
        return index

    def __len__(self):
        return len(self._index)

    @staticmethod
    def _masks_match(a, b):
        ma = np.ones(a.size) if a.mask0 is None else np.asarray(a.mask0)
        mb = np.ones(b.size) if b.mask0 is None else np.asarray(b.mask0)
        if not np.array_equal(ma, mb):
            return False
        ma = np.ones(a.size) if a.mask1 is None else np.asarray(a.mask1)
        mb = np.ones(b.size) if b.mask1 is None else np.asarray(b.mask1)
        return np.array_equal(ma, mb)

    def _gate(self):
        """The serve gate: raises LookupError unless the backing stream
        is safe to read at this feed's pinned version (not mid-patch,
        still complete, version unmoved). Factored out of `lookup` so
        views that front this feed with a hot-row L1
        (`cache.FabricFeedView`) can run the SAME gate before serving
        an L1 row — an L1 hit must never outlive the version or bypass
        a patch window."""
        if getattr(self._spill, "patching", False):
            self.stale += 1
            if _metrics.enabled():
                _metrics.count("spill.feed_stale")
            raise LookupError(
                "cached stream is mid-update (a facet patch or replay "
                "is rewriting its entries); fall back to compute and "
                "rebuild the feed once the update lands"
            )
        if not getattr(self._spill, "complete", False):
            self.evicted += 1
            if _metrics.enabled():
                _metrics.count("spill.feed_evictions")
            raise LookupError(
                "recorded stream is no longer complete (a reset or "
                "eviction dropped its entries since this feed was "
                "indexed); fall back to compute"
            )
        current = int(getattr(self._spill, "stream_version", 0))
        if current != self.stream_version:
            self.stale += 1
            if _metrics.enabled():
                _metrics.count("spill.feed_stale")
            raise LookupError(
                f"cached stream version moved "
                f"({self.stream_version} -> {current}); this feed "
                "indexes a superseded facet stack — rebuild it"
            )

    def lookup(self, config):
        """The recorded host row for ``config``, or None on a miss;
        raises LookupError when the index hit an evicted entry or the
        whole recorded stream was dropped (a ``reset`` cleared
        ``complete`` — counted as an eviction), when the cache's
        stream version moved since this feed was built (a facet
        update patched the rows — this feed is stale), or when the
        cache is mid-rewrite (``patching`` set by
        `utils.spill.SpillCache.begin_patch`, which also brackets a
        replay's reset-to-refill window) — a partially-patched stream
        must never serve, even to a concurrent reader that races the
        patcher."""
        self._gate()
        hit = self._index.get((config.off0, config.off1, config.size))
        if hit is None or not self._masks_match(config, hit[3]):
            self.misses += 1
            if _metrics.enabled():
                _metrics.count("spill.feed_misses")
            return None
        k, c, s, _cfg = hit
        try:
            row = self._spill.get_row(k, (c, s))
        except (IndexError, FileNotFoundError, OSError) as exc:
            self.evicted += 1
            if _metrics.enabled():
                _metrics.count("spill.feed_evictions")
            raise LookupError(
                f"recorded stream entry {k} for subgrid "
                f"({config.off0}, {config.off1}) was evicted"
            ) from exc
        self.hits += 1
        if _metrics.enabled():
            _metrics.count("spill.feed_hits")
        return row


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


class StreamedForward:
    """Facets -> subgrids with bounded device residency.

    :param swiftly_config: SwiftlyConfig (device backend)
    :param facet_tasks: list of (FacetConfig, facet_data) pairs
    :param col_block: facet columns per streamed block (device working-set
        knob; the analogue of the reference's queue/LRU sizing)
    :param residency: execution strategy — "host" (default) runs the
        FFT-based facet pass and buffers NMBF_all in host RAM (scales to
        any N that fits host RAM); "device" selects the facets-resident
        sampled-DFT path: facets upload once and stay in HBM, each column
        group's contribution rows are one einsum, and no NMBF buffer or
        host round-trip exists at all (requires the facet stack to fit
        HBM)
    """

    def __init__(self, swiftly_config, facet_tasks, col_block=512,
                 residency="host", col_group=None, facet_group=None):
        if residency == "sampled":
            raise ValueError(
                "residency='sampled' is a StreamedBackward strategy; the "
                "forward equivalent is residency='device' (sampled DFT)"
            )
        self._base = _StreamedBase(
            swiftly_config, [cfg for cfg, _ in facet_tasks], col_block,
            residency,
        )
        core = self.core = self._base.core
        self.stack = self._base.stack
        # Facet data held host-side in device layout, one array per facet
        # (never stacked: the stack is larger than any single block).
        # All-real facets (planar) are stored as single real planes —
        # half the host RAM and half the upload volume; the sampled path
        # then also skips the zero imaginary plane's einsums. A task's
        # data may be a CALLABLE returning the facet (lazy construction:
        # at 64k one complex128 facet is 8 GB — materialising all of them
        # before conversion would double the host footprint).
        store, real_flags, sparse_flags = [], [], []
        from ..ops.oracle import SparseRealFacet

        sparse_ok = (
            _planar(core)
            and self._base.residency == "device"
            and self._base.mesh is None
        )
        # set-up: every facet converted to its host layout
        with _metrics.stage("fwd.facet_prepare"):
            for _, d in facet_tasks:
                raw = d() if callable(d) else d
                if isinstance(raw, SparseRealFacet):
                    # keep sparse where the device-synthesis paths can use
                    # it (planar single-device sampled executors); densify
                    # for everything else
                    if sparse_ok:
                        store.append(raw)
                        real_flags.append(True)
                        sparse_flags.append(True)
                        continue
                    raw = raw.densify(_np_dtype(core))
                plane = _real_plane_or_none(core, raw)
                if plane is not None:
                    store.append(plane)
                    real_flags.append(True)
                else:
                    store.append(_to_host_layout(core, raw))
                    real_flags.append(False)
                sparse_flags.append(False)
                del raw
            # all-or-nothing: mixed sparse/dense stacks densify the sparse
            # entries (the synthesis programs scatter the WHOLE slab/stack)
            self._facets_sparse = bool(sparse_flags) and all(sparse_flags)
            if not self._facets_sparse and any(sparse_flags):
                for i, (s, is_sp) in enumerate(zip(store, sparse_flags)):
                    if is_sp:
                        store[i] = s.densify(_np_dtype(core))
            self._facets_real = all(real_flags)
            if not self._facets_real and any(real_flags):
                # mixed: re-expand the real planes to planar pairs
                for i, (s, is_real) in enumerate(zip(store, real_flags)):
                    if is_real:
                        pair = np.zeros(s.shape + (2,), dtype=s.dtype)
                        pair[..., 0] = s
                        store[i] = pair
        self._facet_data = store
        self._sparse_pad = None  # fixed per-facet pixel pad (one compile)
        self.col_group = col_group
        # facet_group: max facets device-resident at once (sampled path).
        # None = auto (all resident if the stack fits the HBM budget,
        # else slabs of 1 streamed per column group).
        self.facet_group = facet_group
        self._dev_facets = None
        self._nmbf = None
        self._col_index = None
        self.last_plan = None  # set by the sampled-path generators
        # extra device bytes the CALLER keeps resident during streaming
        # (e.g. an uploaded oracle-sample stack); subtracted from the HBM
        # budget the auto-sizers see
        self.hbm_headroom = 0
        # extra per-group output stacks the auto-sizers must price: the
        # spill-cache fill keeps ONE extra finished [G, S, xA, xA] stack
        # live (the previous group, until its d2h copy lands)
        self.spill_out_stacks = 0

    # -- sparse synthesis --------------------------------------------------

    def _sparse_pixels(self, i0, i1):
        """Concatenated (facet, row, col, val) pixel arrays for facets
        [i0, i1), facet index relative to i0, zero-padded to a fixed
        per-facet maximum so every slab shares ONE compiled scatter
        program (padding scatters value 0 at (0,0,0) — exact)."""
        n_real = self._base.stack.n_real
        if self._sparse_pad is None:
            self._sparse_pad = max(
                [d.n_pixels for d in self._facet_data] + [1]
            )
        width = i1 - i0
        pad_to = self._sparse_pad * width
        f = np.zeros(pad_to, np.int32)
        r = np.zeros(pad_to, np.int32)
        c = np.zeros(pad_to, np.int32)
        v = np.zeros(pad_to, _np_dtype(self.core))
        k = 0
        for j, i in enumerate(range(i0, min(i1, n_real))):
            sp = self._facet_data[i]
            n = sp.n_pixels
            f[k : k + n] = j
            r[k : k + n] = sp.rows
            c[k : k + n] = sp.cols
            v[k : k + n] = sp.vals
            k += n
        return f, r, c, v

    def synth_facet_device(self, i):
        """Facet i's dense real plane [yB, yB], synthesised on device
        (sparse mode only) — e.g. the round-trip reference for on-device
        RMS checks without a multi-GB upload."""
        if not self._facets_sparse:
            raise ValueError("synth_facet_device requires sparse facets")
        yB = self._base.stack.size
        fn = _synth_slab_j(self.core, 1, yB)
        return fn(*self._sparse_pixels(i, i + 1))[0]

    # -- facet pass --------------------------------------------------------

    def _facet_block(self, j0):
        """Host-side [F, yB, Cb(,2)] block of all facets' columns."""
        core, stack = self.core, self._base.stack
        Cb = self._base.col_block
        yB = stack.size
        shape = (len(stack), yB, Cb) + _tail(core)
        block = np.zeros(shape, dtype=_np_dtype(core))
        j1 = min(j0 + Cb, yB)
        for i, data in enumerate(self._facet_data):
            if self._facets_real and _planar(core):
                block[i, :, : j1 - j0, 0] = data[:, j0:j1]
            else:
                block[i, :, : j1 - j0] = data[:, j0:j1]
        return block

    def _build_nmbf(self, col_offs0):
        import jax.numpy as jnp

        base = self._base
        core = base.core
        if base.mesh is not None:
            fwd = _facet_pass_fwd_sharded(core, base.mesh)
        else:
            fwd = _facet_pass_fwd_j(core)
        col_offs0_j = jnp.asarray(col_offs0)
        buf = base._alloc_buffer(len(col_offs0))
        Cb = base.col_block
        pending = []  # (j0, device result) — simple 2-deep pipeline
        for j0 in range(0, base._yB_pad, Cb):
            with _metrics.stage("fwd.facet_pass") as st:
                block = self._facet_block(j0)
                st.bytes_moved = int(block.nbytes)  # h2d upload volume
                out = fwd(base._place(block), base._foffs0, col_offs0_j)
            pending.append((j0, out))
            if len(pending) > 1:
                pj, pout = pending.pop(0)
                with _metrics.stage("fwd.d2h") as st:
                    host = np.asarray(pout)
                    st.bytes_moved = int(host.nbytes)
                buf[:, :, :, pj : pj + Cb] = host
        for pj, pout in pending:
            with _metrics.stage("fwd.d2h") as st:
                host = np.asarray(pout)
                st.bytes_moved = int(host.nbytes)
            buf[:, :, :, pj : pj + Cb] = host
        self._nmbf = buf
        self._col_index = {int(off0): k for k, off0 in enumerate(col_offs0)}

    def _nmbf_column(self, k):
        """The k'th column's [F, m, yB] rows as a device array
        (facet-sharded on a mesh)."""
        yB = self._base.stack.size
        return self._base._place(self._nmbf[k][:, :, :yB])

    # -- column pass -------------------------------------------------------

    def _column_program(self, colfn, NMBF, items):
        from ..api import _subgrid_masks

        import jax.numpy as jnp

        base = self._base
        core = base.core
        rdt = core._Fb.dtype
        sg_offs = jnp.asarray([(sg.off0, sg.off1) for _, sg in items])
        ms = [_subgrid_masks(sg) for _, sg in items]
        return colfn(
            NMBF,
            base._foffs0,
            base._foffs1,
            sg_offs,
            jnp.asarray(np.stack([m[0] for m in ms]), rdt),
            jnp.asarray(np.stack([m[1] for m in ms]), rdt),
        )

    def _sampled_generator(self, groups, size, whole_groups=False):
        """Select the sampled-path generator (facets-resident vs
        facet-slab-streamed) — the ONE place the facet_group heuristic
        lives for both per-column and whole-group streaming."""
        fg = self.facet_group
        if fg is None and not self._facet_stack_fits():
            fg = 1
        if fg is not None and fg < self._base.stack.n_total:
            return self._grouped_device_columns(
                groups, size, fg, whole_groups=whole_groups
            )
        return self._device_columns(
            groups, size, whole_groups=whole_groups
        )

    def stream_column_groups(self, subgrid_configs, spill=None):
        """Yield (per_col_items, group_subgrids) per COLUMN GROUP of the
        sampled-DFT paths: `per_col_items` is a list (one entry per
        column) of [(input_index, SubgridConfig), ...] and
        `group_subgrids` the whole group's DEVICE array
        [G, S, xA, xA(,2)]. For consumers that process groups in one
        dispatch (e.g. `StreamedBackward.add_subgrid_group`) — slicing
        per column and re-dispatching per column pays the per-dispatch
        latency G+ times over.

        With ``spill`` (a `utils.spill.SpillCache`) the stream is
        PERSISTED: the first call runs ONE forward pass, copying each
        group's finished stack d2h one group behind the compute (the
        copy overlaps the next group's dispatch chain), and every later
        call with a complete cache yields the SAME stream from host RAM
        (or disk) with the next group's h2d upload prefetched ahead of
        the consumer — no forward replay. A facet- or row-slab-
        partitioned backward (P consume passes) thus costs 1 forward +
        P cache feeds instead of P forwards + P backwards. If the
        stream exceeds the cache budget the fill gives up and every
        call replays the forward (exact, just the old cost model).
        """
        subgrid_configs = list(subgrid_configs)
        groups = _group_full_columns(subgrid_configs)
        size = subgrid_configs[0].size
        if self._base.residency != "device":
            raise ValueError(
                "stream_column_groups is a sampled-path (residency="
                "'device') API"
            )
        spill_tag = (
            len(subgrid_configs), size,
            (subgrid_configs[0].off0, subgrid_configs[0].off1),
            (subgrid_configs[-1].off0, subgrid_configs[-1].off1),
        )
        if spill is not None and spill.complete:
            if spill.tag != spill_tag:
                raise ValueError(
                    f"spill cache holds a different subgrid stream "
                    f"(tag {spill.tag} != {spill_tag}); reset() it or "
                    "pass the cover it was recorded for"
                )
            if _metrics.enabled():
                _metrics.count("spill.replay_feeds")
            n_yielded = 0
            try:
                for item in self._replay_spilled_groups(spill):
                    yield item
                    n_yielded += 1
                return
            except OSError as exc:
                # degradation ladder: a cached group stayed unreadable
                # past its retries mid-feed — fall back to replaying the
                # forward and resume the stream at the exact group the
                # cache failed on (groups stream in deterministic
                # order). Costs one forward pass; never a wrong answer.
                logger.warning(
                    "spill cache read failed at group %d (%s: %s); "
                    "replaying the forward for the rest of this pass",
                    n_yielded, type(exc).__name__, exc,
                )
                _degrade.record(
                    "spill", "replay_fallback",
                    f"group {n_yielded}: {type(exc).__name__}: {exc}",
                )
                spill.gave_up = True
                spill.complete = False
                if _metrics.enabled():
                    _metrics.count("spill.fallback_replays")
                    _metrics.count("fwd.passes")
                for k, item in enumerate(
                    self._sampled_generator(groups, size, whole_groups=True)
                ):
                    if k >= n_yielded:
                        yield item
                return
        if spill is not None and spill.gave_up:
            # a previous fill overflowed the budget: re-recording would
            # overflow again — replay the forward without the d2h cost
            if _metrics.enabled():
                _metrics.count("spill.fallback_replays")
            spill = None
        if _metrics.enabled():
            _metrics.count("fwd.passes")
        gen = self._sampled_generator(groups, size, whole_groups=True)
        if spill is None:
            yield from gen
            return
        self.spill_out_stacks = 1  # the sizers price the held-back stack
        try:
            spill.begin_fill(tag=spill_tag)
            prev = None
            for per_col, out_g in gen:
                # store group k-1 while group k's dispatch chain runs:
                # the d2h pull waits only on k-1's compute, so transfer
                # and compute overlap at depth 1
                if prev is not None:
                    self._spill_store(spill, *prev)
                prev = (per_col, out_g)
                yield per_col, out_g
            if prev is not None:
                self._spill_store(spill, *prev)
            spill.end_fill()
        finally:
            self.spill_out_stacks = 0

    def cached_feed(self, spill):
        """A `CachedColumnFeed` over a stream this forward recorded —
        the on-demand serving view (`swiftly_tpu.serve`) of the same
        cache the partitioned backward consumes sequentially. Requires
        a complete fill (one prior `stream_column_groups(spill=...)`
        pass)."""
        return CachedColumnFeed(spill)

    def _spill_store(self, spill, per_col, out_g):
        """Copy one yielded group's stack to the cache (d2h + put)."""
        if spill.gave_up:
            return  # an earlier eviction voided the fill: skip the d2h

        def pull():
            _fault_point("transfer.d2h")
            with _metrics.stage("spill.write") as st:
                arr = np.asarray(out_g)
                st.bytes_moved = int(arr.nbytes)
            return arr

        host = _retry(pull, site="transfer.d2h")
        if spill.put(per_col, host) and _metrics.enabled():
            _metrics.count("spill.writes")
            _metrics.count("spill.bytes_written", int(host.nbytes))

    def _replay_spilled_groups(self, spill):
        """Yield the cached stream with double-buffered h2d prefetch:
        group k+1's upload is DISPATCHED before group k is yielded, so
        the wire runs under the consumer's compute on group k.

        The host-side cache read of group k+1 (a disk read for
        disk-backed entries — the serial cost that used to sit between
        yields, blocking the consumer's fold dispatch) additionally runs
        on a background thread while the consumer computes on group k
        (``SWIFTLY_SPILL_PREFETCH=0`` disables the thread; the read
        then happens inline exactly as before). Failure semantics are
        unchanged: a read that stays failed past its retries raises
        HERE, before the previous group's yield, so the caller's
        replay-fallback resumes at the right group."""
        import concurrent.futures

        import jax.numpy as jnp

        import os

        use_thread = (
            os.environ.get("SWIFTLY_SPILL_PREFETCH", "1") != "0"
            and len(spill) > 1
        )
        tctx = _trace.current()

        def read(k):
            # worker threads adopt the caller's span so the spill.read
            # stage nests under the right feed in the timeline
            if _trace.current() != tctx:
                _trace.adopt(tctx)
            with _metrics.stage("spill.read") as st:
                host = spill.get(k)
                st.bytes_moved = int(host.nbytes)
            return host

        ex = (
            concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="swiftly-spill-read"
            )
            if use_thread
            else None
        )
        pending = None
        try:
            fut = ex.submit(read, 0) if ex is not None else None
            for k in range(len(spill)):
                # the feed's group span closes before the yield (generator
                # contextvars leak to the consumer between yields)
                with _trace.span("spill.feed_group", cat="spill", group=k):
                    if fut is not None:
                        host = fut.result()
                        fut = (
                            ex.submit(read, k + 1)
                            if k + 1 < len(spill)
                            else None
                        )
                        if _metrics.enabled():
                            _metrics.count("spill.async_reads")
                    else:
                        host = read(k)

                    def upload():
                        _fault_point("transfer.h2d")
                        with _metrics.stage("spill.h2d") as st:
                            arr = jnp.asarray(host)
                            st.bytes_moved = int(host.nbytes)
                        return arr

                    dev = _retry(upload, site="transfer.h2d")
                if _metrics.enabled():
                    _metrics.count("spill.prefetch_hits")
                if pending is not None:
                    yield pending
                pending = (spill.meta(k), dev)
            if pending is not None:
                yield pending
        finally:
            if ex is not None:
                ex.shutdown(wait=False, cancel_futures=True)

    def stream_columns(self, subgrid_configs, device_arrays=False):
        """Yield (col_items, subgrids) per column; one device program each.

        `col_items` is the column's [(input_index, SubgridConfig), ...];
        `subgrids` the matching stacked [S, xA, xA(,2)] — a host array by
        default, or the raw device array with `device_arrays=True` (for
        on-device consumers: device->host bandwidth may be the bottleneck
        on remote-attached TPUs).
        """
        subgrid_configs = list(subgrid_configs)
        groups = _group_full_columns(subgrid_configs)
        size = subgrid_configs[0].size
        if _metrics.enabled():
            _metrics.count("fwd.passes")
        if self._base.residency == "device":
            gen = self._sampled_generator(groups, size)
        else:
            if self._base.mesh is not None:
                colfn = _column_pass_fwd_sharded(
                    self.core, self._base.mesh, size
                )
            else:
                colfn = _column_pass_fwd_j(self.core, size)
            gen = self._host_columns(groups, colfn)
        if device_arrays:
            yield from gen
            return
        def pull(arr):
            def once():
                _fault_point("transfer.d2h")
                with _metrics.stage("fwd.d2h") as st:
                    host = np.asarray(arr)
                    st.bytes_moved = int(host.nbytes)
                return host

            return _retry(once, site="transfer.d2h")

        pending = []
        for items, out in gen:
            pending.append((items, out))
            if len(pending) > 1:
                pitems, pout = pending.pop(0)
                yield pitems, pull(pout)
        for pitems, pout in pending:
            yield pitems, pull(pout)

    def _host_columns(self, groups, colfn):
        """Host-buffered NMBF_all: FFT facet pass + per-column upload."""
        col_offs0 = list(groups)
        if self._nmbf is None or any(
            int(o) not in self._col_index for o in col_offs0
        ):
            self._build_nmbf(col_offs0)
        cp_flops = coll_bytes = 0
        if _metrics.enabled():
            from ..utils.flops import column_pass_flops
            from ..utils.profiling import column_collective_bytes

            base = self._base
            first = next(iter(groups.values()))
            colpass = _resolve_colpass(
                self.core, base.stack.n_total // _mesh_size(base.mesh)
            )
            cp_flops = column_pass_flops(
                self.core, base.stack.n_real, len(first),
                first[0][1].size, colpass,
            )
            coll_bytes = column_collective_bytes(
                self.core, _mesh_size(base.mesh), len(first), "forward"
            )
        for off0 in col_offs0:
            prog_items = groups[off0]  # incl. zero-mask padding at the end
            items = [it for it in prog_items if it[0] is not None]
            with _metrics.stage("fwd.h2d") as st:
                NMBF = self._nmbf_column(self._col_index[int(off0)])
                st.bytes_moved = int(getattr(NMBF, "nbytes", 0))
            with _metrics.stage(
                "fwd.column_pass", flops=cp_flops, bytes_moved=coll_bytes
            ):
                out = self._column_program(colfn, NMBF, prog_items)
            yield items, out

    def _upload_resident_facets(self):
        """Upload (or device-synthesise) the resident facet stack for the
        sampled path — the one-time cost of residency='device', recorded
        as the `fwd.facet_stack` (host stack of each plane) and
        `fwd.facet_upload` (its h2d, or the device synthesis) stages."""
        base = self._base
        core = base.core
        yB = base.stack.size
        n_pad = base.stack.n_total - base.stack.n_real
        if self._facets_sparse:
            with _metrics.stage("fwd.facet_upload") as st:
                # synthesise the resident stack on device: kilobytes of
                # coordinates uploaded instead of the multi-GB planes
                fn = _synth_slab_j(core, base.stack.n_total, yB)
                self._dev_facets = (
                    fn(*self._sparse_pixels(0, base.stack.n_total)),
                )
                st.bytes_moved = int(self._dev_facets[0].nbytes)
            return
        if self._facets_real:
            parts = [self._facet_data]
        elif _planar(core):
            # upload re/im planes as separate contiguous arrays (the
            # sampled program must not slice them out of a stacked
            # array — that would copy the multi-GiB stack)
            parts = [[d[..., p] for d in self._facet_data] for p in (0, 1)]
        else:
            parts = [[np.asarray(d) for d in self._facet_data]]
        planes = []
        for part in parts:  # one plane on the host at a time
            with _metrics.stage("fwd.facet_stack"):
                host = np.ascontiguousarray(
                    np.stack(part + [np.zeros_like(part[0])] * n_pad)
                )
            with _metrics.stage("fwd.facet_upload") as st:
                planes.append(base._place(host))
                st.bytes_moved = int(host.nbytes)
            del host
        self._dev_facets = tuple(planes)

    def _device_columns(self, groups, subgrid_size, whole_groups=False):
        """Facets-resident sampled-DFT pass in column groups.

        Facets upload ONCE and stay on device; each group of G columns'
        contribution rows is one einsum dispatch (compute proportional to
        the rows extracted, so chunking is free), and the group's G
        column passes run as ONE vmapped dispatch; nothing round-trips
        through the host. Device residency = facets + one [F, G*m, yB]
        group buffer + two in-flight [G, S, xA, xA] output stacks.
        """
        import jax
        import jax.numpy as jnp

        base = self._base
        core = base.core
        yB = base.stack.size
        if self._dev_facets is None:
            self._upload_resident_facets()
        e0 = base._place(
            (base.stack.offs0 - yB // 2).astype(np.int32)
        )
        col_offs0 = list(groups)
        G = self.col_group or self._auto_col_group(len(col_offs0))
        self.last_plan = {
            "mode": "resident", "col_group": G,
            # resolve from the PER-SHARD facet count: on a mesh the
            # shard_map bodies see local facets only, and the recorded
            # body must be the executed one
            "colpass": _resolve_colpass(
                core, base.stack.n_total // _mesh_size(base.mesh)
            ),
        }
        if self.last_plan["colpass"] == "pallas":
            bm, bn, bk = _colpass_blocks()
            self.last_plan["colpass_blocks"] = {
                "bm": bm, "bn": bn, "bk": bk,
                "sblock": _colpass_sblock(),
            }
        colpass_stage = "fwd.column_pass" + (
            ".pallas" if self.last_plan["colpass"] == "pallas" else ""
        )
        if base.mesh is not None:
            self.last_plan["mesh_shards"] = _mesh_size(base.mesh)
            self.last_plan["collective"] = _resolve_collective_env(
                _mesh_size(base.mesh)
            )
            samfn = _facet_pass_sampled_sharded(
                core, base.mesh, self._facets_real
            )
            gcolfn = _column_pass_fwd_group_sharded(
                core, base.mesh, subgrid_size
            )
        else:
            samfn = _facet_pass_sampled_j(core, self._facets_real)
            gcolfn = _column_pass_fwd_group_j(core, subgrid_size)
        from ..api import _subgrid_masks

        rdt = core._Fb.dtype
        fp_flops = cp_flops = coll_bytes = 0
        if _metrics.enabled():
            from ..utils.flops import (
                column_pass_flops,
                sampled_facet_pass_flops,
            )
            from ..utils.profiling import column_collective_bytes

            _metrics.gauge("fwd.plan", dict(self.last_plan))
            S = len(next(iter(groups.values())))
            fp_flops = sampled_facet_pass_flops(
                core, base.stack.n_real, yB, G * core.xM_yN_size,
                self._facets_real,
            )
            cp_flops = G * column_pass_flops(
                core, base.stack.n_real, S, subgrid_size,
                self.last_plan["colpass"],
            )
            coll_bytes = G * column_collective_bytes(
                core, _mesh_size(base.mesh), S, "forward"
            )
        prev_tail = None  # backpressure marker: group g-1's output stack
        for g0 in range(0, len(col_offs0), G):
            grp = col_offs0[g0 : g0 + G]
            # pad a short final group to the full G (row indices repeat the
            # last column; its outputs are skipped below) — a smaller krows
            # shape would trigger a full recompile of the sampled program
            grp_padded = grp + [grp[-1]] * (G - len(grp))
            krows = jnp.asarray(sampled_row_indices(core, grp_padded))
            sg_offs_g, m0_g, m1_g = [], [], []
            for off0 in grp_padded:
                prog_items = groups[off0]  # incl. zero-mask padding
                sg_offs_g.append(
                    [(sg.off0, sg.off1) for _, sg in prog_items]
                )
                ms = [_subgrid_masks(sg) for _, sg in prog_items]
                m0_g.append([mk[0] for mk in ms])
                m1_g.append([mk[1] for mk in ms])
            # JAX dispatch is asynchronous: without a wait the host loop
            # races ahead and every group buffer stays live at once,
            # overcommitting HBM. The wait is a genuine host round trip,
            # an 8-byte checksum pull of the previous group: on an
            # earlier runtime block_until_ready returned before the queue
            # drained (whether the chip's does is still to be measured).
            # one trace span per column group (run → leg → pass →
            # COLUMN GROUP → stage); closed before the yield because a
            # generator's contextvars are visible to the consumer
            # between yields — the consumer's spans must not nest here
            with _trace.span(
                "fwd.column_group", cat="fwd",
                group=g0 // G, n_cols=len(grp),
            ):
                if prev_tail is not None:
                    with _metrics.stage("fwd.drain"):
                        np.asarray(prev_tail)
                with _metrics.stage(
                    "fwd.sampled_facet_pass", flops=fp_flops
                ):
                    buf = samfn(*self._dev_facets, e0, krows)
                with _metrics.stage(
                    colpass_stage, flops=cp_flops,
                    bytes_moved=coll_bytes,
                ):
                    out_g = gcolfn(
                        buf,
                        base._foffs0,
                        base._foffs1,
                        jnp.asarray(sg_offs_g),
                        jnp.asarray(np.asarray(m0_g), rdt),
                        jnp.asarray(np.asarray(m1_g), rdt),
                    )  # [G, S, xA, xA(,2)]
                prev_tail = jnp.sum(out_g)
            if _metrics.enabled():
                _metrics.count(
                    "fwd.subgrids",
                    sum(
                        1
                        for off0 in grp
                        for it in groups[off0]
                        if it[0] is not None
                    ),
                )
                _metrics.count("fwd.column_groups")
                if self.last_plan["colpass"] == "pallas":
                    _metrics.count("fwd.pallas_cols", len(grp))
            if whole_groups:
                yield _whole_group_yield(groups, grp, G, out_g)
                continue
            for gi, off0 in enumerate(grp):
                prog_items = groups[off0]
                items = [it for it in prog_items if it[0] is not None]
                yield items, out_g[gi]

    def _grouped_device_columns(
        self, groups, subgrid_size, facet_group, whole_groups=False
    ):
        """Sampled-DFT pass streaming FACET SLABS: stacks larger than HBM.

        Column groups of G are the outer loop; within one, facet slabs of
        `facet_group` upload in turn and each slab's FINISHED contribution
        is added into the group's [G, S, xA, xA] accumulator (exact —
        every stage incl. the finish iFFT/crop/masks is linear in the
        facets). Device residency is one slab + the accumulator + one
        sampled buffer, bounded regardless of N; the cost is re-uploading
        the facet stack once per column group (h2d, overlapped with
        compute by the depth-2 dispatch pipeline below).
        """
        import collections

        import jax.numpy as jnp

        from ..api import _subgrid_masks

        base = self._base
        core = base.core
        if base.mesh is not None:
            raise ValueError(
                "facet_group streaming is a single-device strategy; on a "
                "mesh the facet stack is already sharded across devices — "
                "add devices instead of slabs"
            )
        yB = base.stack.size
        F_total = base.stack.n_total
        Fg = int(facet_group)
        n_slabs = -(-F_total // Fg)
        F_pad = n_slabs * Fg
        rdt = core._Fb.dtype

        col_offs0 = list(groups)
        first_col = next(iter(groups.values()))
        S = len(first_col)
        # slab pipeline depth: 2 overlaps upload with compute; at scales
        # where two slabs alone would eat half the budget (128k: one slab
        # is 8.1 GiB) fall back to 1 slab in flight
        budget = self._hbm_budget()
        fsize = np.dtype(core.dtype).itemsize * (
            1 if self._facets_real else (2 if _planar(core) else 1)
        )
        slab_bytes = Fg * yB * yB * fsize
        depth = 2
        if budget is not None and 2 * slab_bytes > 0.5 * budget:
            depth = 1
        # a synthesised slab is scratch of the program that makes it,
        # never a buffer between dispatches, so the fused step keeps one
        # slab queued whatever the slab's size: the host's dispatch of
        # slab d then hides behind slab d-1 on the device
        in_flight = 2 if self._facets_sparse else depth
        chunk = 4
        if self.col_group:
            # honour an explicit G exactly: pick the largest chunk that
            # divides it rather than silently rounding G down
            G = max(1, int(self.col_group))
            chunk = next(c for c in (4, 3, 2, 1) if G % c == 0)
        else:
            if budget is None:
                G = len(col_offs0)
                chunk = next(c for c in (4, 3, 2, 1) if G % c == 0)
            else:
                # evaluate every (chunk, G) pair: chunk scales the
                # in-step transients, so a SMALLER chunk can buy a
                # bigger G — and fewer groups (fewer sampled dispatches
                # at the runtime's latency floor) dominates the cost.
                # Tie-break on larger chunk (batches the fft body's
                # small matmuls; harmless for the einsum body).
                G, chunk = max(
                    (
                        (
                            max(1, (Gc // c) * c if Gc >= c else Gc),
                            c,
                        )
                        for c in (4, 3, 2, 1)
                        for Gc in (
                            grouped_col_group_for_budget(
                                base, budget, len(col_offs0), S,
                                subgrid_size, self._facets_real, Fg, c,
                                slab_depth=depth, warn=False,
                                extra_out_stacks=self.spill_out_stacks,
                            ),
                        )
                    ),
                    key=lambda t: (t[0], t[1]),
                )
        chunk = min(chunk, G)
        G = max(1, (G // chunk) * chunk)
        if not self.col_group and budget is not None:
            # re-evaluate the SELECTED (post-clamp) pair with the
            # warning armed: the sweep probed quietly, and warning for
            # a chunk size that is never dispatched would cry wolf
            grouped_col_group_for_budget(
                base, budget, len(col_offs0), S, subgrid_size,
                self._facets_real, Fg, chunk, slab_depth=depth,
                extra_out_stacks=self.spill_out_stacks,
            )
        n_chunks = G // chunk
        colpass = _resolve_colpass(core, Fg)
        n_groups = -(-len(col_offs0) // G)
        # triple-buffered streaming: a background thread fills staging
        # buffer (d+1) % 3 (pure host memcpy) while the main thread
        # dispatches slab d's async h2d and compute — h2d(k+1) ∥
        # compute(k) ∥ d2h(k-1). Disabled for the sparse-synth path (no
        # host staging exists) and via SWIFTLY_STREAM_PREFETCH=0.
        import os as _os

        use_prefetch = (
            not self._facets_sparse
            and _os.environ.get("SWIFTLY_STREAM_PREFETCH", "1") != "0"
            and n_slabs * n_groups > 1
        )
        n_stage = 3 if use_prefetch else 2
        self.last_plan = {
            "mode": "grouped", "col_group": G, "facet_group": Fg,
            "n_slabs": n_slabs, "slab_depth": depth,
            "facet_source": (
                "device-synth-sparse" if self._facets_sparse else "host"
            ),
            "colpass": colpass,
            "stream_prefetch": use_prefetch,
        }
        if colpass == "pallas":
            bm, bn, bk = _colpass_blocks()
            self.last_plan["colpass_blocks"] = {
                "bm": bm, "bn": bn, "bk": bk,
                "sblock": _colpass_sblock(),
            }
        if base.mesh is not None:
            self.last_plan["collective"] = _resolve_collective_env(
                _mesh_size(base.mesh)
            )
        fp_flops = step_flops = coll_bytes = 0
        if _metrics.enabled():
            from ..utils.flops import (
                column_pass_flops,
                sampled_facet_pass_flops,
            )
            from ..utils.profiling import column_collective_bytes

            _metrics.gauge("fwd.plan", dict(self.last_plan))
            fp_flops = sampled_facet_pass_flops(
                core, Fg, yB, G * core.xM_yN_size, self._facets_real
            )
            # the whole column-pass pipeline's FLOPs attributed to the
            # slab step (the group finish's iFFT/crop share is folded in
            # — the two stages are one pipeline split only for memory)
            step_flops = G * column_pass_flops(
                core, Fg, S, subgrid_size, colpass
            )
            coll_bytes = G * column_collective_bytes(
                core, _mesh_size(base.mesh), S, "forward"
            )

        # per-slab facet metadata, padded with zero facets to F_pad
        offs0 = np.concatenate(
            [np.asarray(base.stack.offs0), np.zeros(F_pad - F_total, int)]
        )
        offs1 = np.concatenate(
            [np.asarray(base.stack.offs1), np.zeros(F_pad - F_total, int)]
        )
        e0 = (offs0 - yB // 2).astype(np.int32)

        # Rotating host staging: building a fresh np.stack per slab
        # grows host RSS by one slab per dispatch at hour scale
        # (slab-sized arenas are retained, and async h2d can pin
        # buffers) — fatal at 64k where a slab is 2 GB and a pass uploads
        # ~70 of them. A fixed ring of persistent buffers rotates
        # instead: two without the prefetch thread (buffer i%2 reused
        # only after slab i-2's checksum — transfer AND compute — was
        # pulled), three with it (the worker refills buffer (d+1)%3
        # while slab d dispatches; that buffer was last used by slab
        # d-2, whose checksum the depth-2 drain pulled before slab d
        # dispatched, so the h2d engine is done reading it).
        n_planes = 2 if (_planar(core) and not self._facets_real) else 1
        stage = (
            None
            if self._facets_sparse  # synthesised on device: no staging
            else [
                [
                    np.empty((Fg, yB, yB), dtype=_np_dtype(core))
                    for _ in range(n_planes)
                ]
                for _ in range(n_stage)
            ]
        )

        def host_slab(s0, slot):
            bufs = stage[slot]
            for k in range(Fg):
                i = s0 + k
                for pi, buf in enumerate(bufs):
                    if i >= base.stack.n_real:
                        buf[k] = 0
                    elif n_planes == 2:
                        buf[k] = self._facet_data[i][..., pi]
                    else:
                        buf[k] = self._facet_data[i]
            return tuple(bufs)

        samfn = _facet_pass_sampled_j(core, self._facets_real)
        stepfn = _column_group_step_j(core, subgrid_size, chunk, colpass)
        finfn = _column_group_finish_j(core, subgrid_size, colpass)
        fusedfn = (
            _fused_sparse_slab_step_j(
                core, subgrid_size, chunk, Fg, yB, colpass
            )
            if self._facets_sparse
            else None
        )
        tail = _tail(core)
        xM = core.xM_size
        # depth-2 completion pipeline: before uploading slab i, wait for
        # slab i-2's column step (8-byte checksum pull; whether
        # block_until_ready alone is completion on the chip is still to
        # be measured), bounding live slabs to 2.
        pending = collections.deque()
        n_slab_dispatch = 0  # continuous across groups: staging slot
        total_dispatch = n_slabs * n_groups
        # the prefetch worker fills by GLOBAL dispatch index: every group
        # sweeps the same s0 sequence, so slab d stages facet rows
        # (d % n_slabs) * Fg regardless of which group consumes it
        tctx = _trace.current()

        def _fill(d):
            if _trace.current() != tctx:
                _trace.adopt(tctx)
            with _metrics.stage("fwd.slab_prefetch"):
                return host_slab((d % n_slabs) * Fg, d % n_stage)

        prefetch_ex = None
        prefetch_fut = None  # (dispatch index, future)
        if use_prefetch:
            import concurrent.futures

            prefetch_ex = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="swiftly-slab-stage"
            )
            prefetch_fut = (0, prefetch_ex.submit(_fill, 0))
        t_start = time.time()
        logger.info(
            "grouped stream: %d columns in groups of %d (chunk %d), "
            "%d facet slabs of %d per group%s",
            len(col_offs0), G, chunk, n_slabs, Fg,
            " (prefetch thread)" if use_prefetch else "",
        )
        try:
            for g0 in range(0, len(col_offs0), G):
                grp = col_offs0[g0 : g0 + G]
                # one trace span per column group (the tentpole hierarchy:
                # run → leg → pass → COLUMN GROUP → stage); entered/exited
                # explicitly so it closes BEFORE the yield — contextvars
                # set in a generator are visible to the consumer between
                # yields, and the consumer's spans must not nest in here
                grp_span = _trace.span(
                    "fwd.column_group", cat="fwd",
                    group=g0 // G, n_cols=len(grp),
                )
                grp_span.__enter__()
                grp_padded = grp + [grp[-1]] * (G - len(grp))
                krows = jnp.asarray(sampled_row_indices(core, grp_padded))
                sg_offs_g, m0_g, m1_g = [], [], []
                for off0 in grp_padded:
                    prog_items = groups[off0]  # incl. zero-mask padding
                    sg_offs_g.append(
                        [(sg.off0, sg.off1) for _, sg in prog_items]
                    )
                    ms = [_subgrid_masks(sg) for _, sg in prog_items]
                    m0_g.append([mk[0] for mk in ms])
                    m1_g.append([mk[1] for mk in ms])

                def _chunked(x, dt=None):
                    a = jnp.asarray(np.asarray(x), dt)
                    return a.reshape((n_chunks, chunk) + a.shape[1:])

                so_c = _chunked(sg_offs_g)
                m0_c = _chunked(m0_g, rdt)
                m1_c = _chunked(m1_g, rdt)
                # PRE-finish accumulator ([.., xM, xM], 1.31x the finished
                # size): the finish runs once per group, not once per slab
                acc = jnp.zeros(
                    (n_chunks, chunk, S, xM, xM) + tail,
                    dtype=_np_dtype(core),
                )
                slab_dev = None
                for s0 in range(0, F_pad, Fg):
                    while len(pending) >= in_flight:
                        with _metrics.stage("fwd.drain"):
                            np.asarray(pending.popleft())
                    # drop the previous slab BEFORE uploading the next: at
                    # depth 1 both must never be live together
                    # slot from a CONTINUOUS dispatch counter, not the
                    # per-group slab index: with odd slabs-per-group a
                    # group-local slot would reuse the buffer of the
                    # previous group's final slab before its checksum (h2d
                    # + compute completion) was pulled
                    slab_dev = None  # noqa: F841 - releases device buffers
                    if fusedfn is not None:
                        # one dispatch: synth + sampled pass + column step
                        with _metrics.stage(
                            "fwd.slab_step",
                            flops=fp_flops + step_flops,
                            bytes_moved=coll_bytes,
                        ):
                            acc = fusedfn(
                                acc,
                                *self._sparse_pixels(s0, s0 + Fg),
                                jnp.asarray(e0[s0 : s0 + Fg]),
                                krows,
                                jnp.asarray(offs0[s0 : s0 + Fg]),
                                jnp.asarray(offs1[s0 : s0 + Fg]),
                                so_c,
                            )
                        if _metrics.enabled():
                            _metrics.count("fwd.synth_slabs")
                            _metrics.count(
                                "fwd.synth_pixels",
                                sum(
                                    d.n_pixels
                                    for d in self._facet_data[s0 : s0 + Fg]
                                ),
                            )
                    else:
                        d = n_slab_dispatch
                        bufs = None
                        if prefetch_fut is not None and prefetch_fut[0] == d:
                            # bounded wait: a wedged fill thread must
                            # degrade to a counted miss (inline fill of
                            # the same slot with the same bytes), never
                            # stall the stream — host_slab is a pure
                            # memcpy, so 120 s is ~2 orders above any
                            # real slab
                            try:
                                with _metrics.stage("fwd.slab_wait"):
                                    bufs = prefetch_fut[1].result(
                                        timeout=120.0
                                    )
                                _metrics.count("fwd.slab_prefetch_hits")
                            except concurrent.futures.TimeoutError:
                                prefetch_fut[1].cancel()
                            prefetch_fut = None
                        if bufs is None:
                            if use_prefetch:
                                _metrics.count("fwd.slab_prefetch_misses")
                            with _metrics.stage("fwd.slab_stage"):
                                bufs = host_slab(s0, d % n_stage)
                        with _metrics.stage("fwd.slab_upload") as st:
                            slab_dev = tuple(
                                base._place(a) for a in bufs
                            )
                            st.bytes_moved = sum(
                                int(a.nbytes) for a in slab_dev
                            )
                        # h2d for slab d is dispatched: the worker may now
                        # refill buffer (d+1) % 3 — last used by slab d-2,
                        # whose checksum the drain above already pulled
                        if prefetch_ex is not None and d + 1 < total_dispatch:
                            prefetch_fut = (
                                d + 1,
                                prefetch_ex.submit(_fill, d + 1),
                            )
                        with _metrics.stage(
                            "fwd.sampled_facet_pass", flops=fp_flops
                        ):
                            buf = samfn(
                                *slab_dev,
                                jnp.asarray(e0[s0 : s0 + Fg]),
                                krows,
                            )
                        with _metrics.stage(
                            "fwd.slab_step",
                            flops=step_flops,
                            bytes_moved=coll_bytes,
                        ):
                            acc = stepfn(
                                acc,
                                buf,
                                jnp.asarray(offs0[s0 : s0 + Fg]),
                                jnp.asarray(offs1[s0 : s0 + Fg]),
                                so_c,
                            )
                    n_slab_dispatch += 1
                    pending.append(jnp.sum(acc))
                    if logger.isEnabledFor(logging.INFO):
                        logger.info(
                            "  group %d/%d slab %d/%d dispatched  t=%.0fs "
                            "rss=%.1fGiB",
                            g0 // G + 1, -(-len(col_offs0) // G),
                            s0 // Fg + 1, n_slabs,
                            time.time() - t_start, _rss_gib(),
                        )
                # finish the whole group in one program (acc freed by the
                # `del` below — donation can't alias it into the cropped
                # output; the runtime orders the finish after the pending
                # slab steps on the same buffer, and the depth-2 checksum
                # pipeline keeps bounding live slabs)
                with _metrics.stage("fwd.group_finish"):
                    finished = finfn(acc, so_c, m0_c, m1_c)
                del acc
                grp_span.__exit__(None, None, None)
                if _metrics.enabled():
                    _metrics.count(
                        "fwd.subgrids",
                        sum(
                            1
                            for off0 in grp
                            for it in groups[off0]
                            if it[0] is not None
                        ),
                    )
                    _metrics.count("fwd.column_groups")
                    if colpass == "pallas":
                        _metrics.count("fwd.pallas_cols", len(grp))
                if whole_groups:
                    flat = finished.reshape((G,) + finished.shape[2:])
                    yield _whole_group_yield(groups, grp, G, flat)
                    continue
                for gi, off0 in enumerate(grp):
                    prog_items = groups[off0]
                    items = [it for it in prog_items if it[0] is not None]
                    yield items, finished[gi // chunk, gi % chunk]
        finally:
            if prefetch_ex is not None:
                prefetch_ex.shutdown(wait=False, cancel_futures=True)

    def _hbm_budget(self):
        """Per-device HBM budget in bytes (None = unlimited, e.g. CPU).

        Delegates to the unified parser `plan.hbm_budget_bytes`
        (SWIFTLY_HBM_BUDGET if set, else the usable capacity from
        `utils.profiling.probe_hbm_bytes`, else 14e9 as a last resort);
        the executor keeps its historical CPU-is-unlimited semantics
        (``honor_env_on_cpu=False``)."""
        from ..plan.model import hbm_budget_bytes

        return hbm_budget_bytes(
            headroom=self.hbm_headroom, default=14e9,
            honor_env_on_cpu=False,
        )

    def _facet_stack_fits(self):
        """Whether the whole facet stack can stay device-resident with
        room for at least a one-column working set."""
        budget = self._hbm_budget()
        if budget is None:
            return True
        return (
            facet_stack_bytes(self._base, self._facets_real) + 3e9 <= budget
        )

    def _auto_col_group(self, n_cols):
        """Largest column-group whose buffer + transients fit the budget
        (facets-resident sampled path). On CPU the full column set is one
        group."""
        budget = self._hbm_budget()
        if budget is None:
            return n_cols
        return col_group_for_budget(
            self._base, budget, n_cols, real=self._facets_real,
            extra_out_stacks=self.spill_out_stacks,
        )

    def all_subgrids(self, subgrid_configs):
        """Every subgrid, in request order, as one host array [n, xA, xA]."""
        out = None
        for items, subgrids in self.stream_columns(subgrid_configs):
            if out is None:
                out = np.zeros(
                    (len(subgrid_configs),) + subgrids.shape[1:],
                    dtype=subgrids.dtype,
                )
            for s, (i, _) in enumerate(items):
                out[i] = subgrids[s]
        return out


def facet_stack_bytes(base, real=False):
    """Device bytes of the (padded) resident facet stack."""
    core = base.core
    itemsize = np.dtype(core.dtype).itemsize
    per_el = itemsize if real else itemsize * (2 if _planar(core) else 1)
    yB = base.stack.size
    F = base.stack.n_total // _mesh_size(base.mesh)
    return F * yB * yB * per_el


def grouped_col_group_for_budget(
    base, budget, n_cols, S, subgrid_size, real, facet_group, chunk,
    slab_depth=2, warn=True, extra_out_stacks=0,
):
    """Largest column-group G for the facet-slab-streamed sampled path.

    Live per unit G: the slab's sampled buffer [Fg, m, yB] plus its
    in-step [G, Fg, m, yB] transpose, and the finished accumulator row
    [S, xA, xA]. Flat: `slab_depth` facet slabs in flight (the upload
    pipeline; 1 at scales where two slabs alone overflow HBM), the
    per-chunk scan transients ([chunk, S, xM, xM] carry + prep1 rows),
    and a trig/fragmentation reserve. ``warn=False`` evaluates quietly —
    the executor's (G, chunk) sweep probes chunks it may not select and
    re-warns only for the chosen pair. ``extra_out_stacks`` prices
    additional caller-held [S, xA, xA]-per-unit-G output stacks: the
    spill-cache fill holds the previous group's finished stack until
    its d2h copy lands (`StreamedForward.spill_out_stacks`), and a
    consumer pinning group stacks for other reasons can account for
    them the same way.

    CALIBRATION BASIS (r5): the consumer-transient term was relaxed from
    3x to 2x [S, xA, xA] against measured 128k boundaries on a 16 GiB
    v5e — G=4 streams green where the 3x model allowed only G=2, and
    the OOM edge sits at G=6 with two groups in flight. Configs between
    the calibrated points sit closer to that edge, with the bench's
    `_oom_soft` shrink-and-retry as the backstop; the operator escape
    hatch is ``SWIFTLY_HBM_BUDGET`` (explicit byte budget — lower it to
    move any config away from the edge, raise it on bigger-HBM parts).
    See docs/observability.md for how to read the plan gauges a run
    records.
    """
    core = base.core
    dsize = np.dtype(core.dtype).itemsize * (2 if _planar(core) else 1)
    fsize = np.dtype(core.dtype).itemsize * (1 if real else 2)
    yB = base.stack.size
    m = core.xM_yN_size
    xM = core.xM_size
    xA = subgrid_size
    slab_b = slab_depth * facet_group * yB * yB * fsize
    grouped_colpass = _resolve_colpass(core, facet_group)
    if grouped_colpass == "einsum":
        # per column in the chunk vmap: prep1 rows, the H buffer plus its
        # wrap-extended gather copy, and one [Sb, Fg, xM, m] gather block
        Sb = min(_colpass_sblock(), S)
        Sb = -(-S // -(-S // Sb))  # executed blocks are rebalanced
        chunk_b = (
            chunk * S * xM * xM
            + chunk * facet_group * (
                m * core.yN_size
                + xM * (2 * core.yN_size + m)
                + Sb * xM * m
            )
        ) * dsize
    elif grouped_colpass == "pallas":
        # the fused kernel has NO H buffer (the prepare matmul runs
        # inside the grid program) and its gather block is [Sb, Fg, m,
        # m] — counted twice for the kernel's padded operand copies
        Sb = min(_colpass_sblock(), S)
        Sb = -(-S // -(-S // Sb))  # executed blocks are rebalanced
        chunk_b = (
            chunk * S * xM * xM
            + chunk * facet_group * (
                m * core.yN_size + 2 * Sb * m * m
            )
        ) * dsize
    else:
        chunk_b = (
            chunk * S * xM * xM + chunk * facet_group * m * core.yN_size
        ) * dsize
    # 4x the group buffer: the sampled pass materialises out_re/out_im
    # and their stacked pair next to the [Fg, G*m, yB] buffer and its
    # in-step transpose. The accumulator is pre-finish [S, xM, xM];
    # the finished group array plus the depth-2 pipeline's in-flight
    # copy add 2x [S, xA, xA]. (Was 3x after the BENCH_r04 32k OOMs;
    # recalibrated against measured 128k runs — G=4 streams green where
    # the 3x model allowed only G=2, and the OOM boundary sits at G=6
    # with two groups in flight.)
    per_G = (
        4 * facet_group * m * yB + S * xM * xM
        + (2 + extra_out_stacks) * S * xA * xA
    ) * dsize
    reserve = 0.6e9
    headroom = budget - slab_b - chunk_b - reserve
    if warn and headroom <= per_G:
        # a provably-unfittable plan must not proceed silently: the
        # minimum group still gets dispatched (fail-soft callers catch
        # the OOM and resize), but the operator is told why
        logger.warning(
            "HBM budget %.2f GiB cannot fit even one %d-column chunk "
            "(flat costs %.2f GiB + %.2f GiB per column group); "
            "proceeding with the minimum group — expect OOM, reduce "
            "facet_group or raise SWIFTLY_HBM_BUDGET",
            budget / 2**30, chunk,
            (slab_b + chunk_b + reserve) / 2**30, per_G / 2**30,
        )
    # no chunk rounding here: the caller picks the (G, chunk) pair —
    # rounding G down to a chunk multiple at this level cost 64k a
    # third of its group size
    G = int(headroom // per_G)
    return max(1, min(G, ((n_cols + chunk - 1) // chunk) * chunk))


def col_group_for_budget(base, budget, n_cols, real=False,
                         extra_out_stacks=0):
    """Largest sampled-DFT column-group G whose working set fits `budget`
    bytes on one device (facet stack + per-G transients).

    Live per unit G (every G-proportional buffer counts here so the
    sizing scales to devices with more HBM than the calibration point):
      - sampled group buffer [F, m, yB] and its in-program [G,F,m,yB]
        transpose                              -> 2 * F*m*yB
      - prep1 output [F, m, yN]                -> F*m*yN
      - the scan carry [S, xM, xM]             -> S*xM^2
      - two in-flight output stacks [S,xA,xA]  -> 2 * S*xA^2
    plus a flat reserve for trig tables and fragmentation. The reserve
    is calibrated against measured 32k runs on a 16 GiB v5e: G=4 fits
    and is fastest (17.5 s vs 18.5 s at G=2); the pre-scan vmap layout
    OOM'd (see `_column_pass_fwd_fn`). On a mesh the facet stack and
    group buffers are sharded: everything counts PER DEVICE.
    """
    core = base.core
    dsize = np.dtype(core.dtype).itemsize * (2 if _planar(core) else 1)
    yB = base.stack.size
    facets_b = facet_stack_bytes(base, real)
    F = len(base.stack) // _mesh_size(base.mesh)
    reserve = 0.4e9  # calibrated: yields G=4 at the v5e 14e9 default
    m = core.xM_yN_size
    xA = base.config.max_subgrid_size
    xM = core.xM_size
    S = -(-core.N // xA)
    resident_colpass = _resolve_colpass(core, F)
    if resident_colpass in ("einsum", "pallas"):
        # the einsum/pallas group fn maps columns SEQUENTIALLY, so the
        # column transients (prep1 rows, gather block, image partials
        # — plus for einsum the H buffer + its wrap-extended copy) are
        # flat — only the sampled group buffer (with its einsum plane
        # transients and in-program transpose) and the in-flight output
        # stacks scale with G
        Sb = min(_colpass_sblock(), S)
        Sb = -(-S // -(-S // Sb))  # executed blocks are rebalanced
        if resident_colpass == "einsum":
            flat_col = (
                F * m * core.yN_size
                + F * xM * (2 * core.yN_size + m)
                + Sb * F * xM * m
                + S * xM * xM
            ) * dsize
        else:
            # pallas: no H buffer; [Sb, F, m, m] gather block counted
            # twice for the kernel's padded operand copies
            flat_col = (
                F * m * core.yN_size
                + 2 * Sb * F * m * m
                + S * xM * xM
            ) * dsize
        col_b = (
            3 * F * m * yB + (2 + extra_out_stacks) * S * xA * xA
        ) * dsize
        headroom = budget - facets_b - reserve - flat_col
    else:
        col_b = (
            2 * F * m * yB + F * m * core.yN_size
            + S * xM * xM + (2 + extra_out_stacks) * S * xA * xA
        ) * dsize
        headroom = budget - facets_b - reserve
    if headroom <= col_b:
        logger.warning(
            "HBM budget %.2f GiB cannot fit the resident facet stack "
            "(%.2f GiB) plus one column group (%.2f GiB); proceeding "
            "with G=1 — expect OOM, use facet_group slab streaming or "
            "raise SWIFTLY_HBM_BUDGET",
            budget / 2**30, facets_b / 2**30, col_b / 2**30,
        )
    G = int(headroom // col_b)
    return max(1, min(n_cols, G))


# ---------------------------------------------------------------------------
# Feed-once/fold-many scheduling
# ---------------------------------------------------------------------------


def feed_backward_passes(forward, subgrid_configs, backwards, spill=None,
                         progress=None, feed_index=None):
    """Feed ONE pass over the subgrid stream to MANY backward passes.

    A facet × row-slab partitioned backward runs P independent
    `StreamedBackward` passes over the SAME subgrid stream; feeding each
    pass separately moves the whole cached stream host→device P times
    (the 64k ledger's dominant waste after the spill cache removed the
    forward replays). This helper is the feed-once/fold-many schedule:
    each cached column group is uploaded ONCE and every pending pass's
    adjoints for that group are applied on-device before the stream
    advances — (len(backwards) − 1)× of the feed's ``spill.h2d`` bytes
    gone. How many passes may share a feed is a plan decision
    (`plan.compiler.plan_backward_feed` sizes it so all the shared
    accumulators + fold pipelines fit the HBM budget next to the feed's
    working set); the caller chunks its pass list accordingly and calls
    this once per chunk.

    Works with any forward/backward pair that speaks the streamed API
    (`stream_column_groups` / `add_subgrid_group`) — the mesh engines
    (`swiftly_tpu.mesh`) inherit it, so the multi-chip backward consumes
    the same schedule.

    Instrumentation: the whole shared feed is one ``bwd.feed_group``
    trace span, and a ``bwd.feed_group`` stage records the wall spent
    BLOCKED ON THE FEED (generator advance: cache read + h2d dispatch,
    i.e. the part the async prefetch and the fold overlap hide) with the
    cache-fed h2d bytes attributed — the measured counterpart of the
    plan's ``bwd.feed_group`` stage prediction, refit by
    `plan.autotune` like any other stage. Counters: ``bwd.feed_groups``
    (feeds run) and ``bwd.feed_passes`` (passes served). When the
    caller stamps ``feed_index`` and a LATER feed (index > 0) runs
    uncached — the replay spill policy, where each feed past the first
    re-runs the forward — the blocked-on-feed wall is recorded as
    ``fwd.replay`` instead, the measured counterpart of the plan's
    replay pricing (`plan.model.price_backward`, ``allow_spill=False``).
    The plan-accuracy ledger (`obs.ledger`) joins both names.

    :param forward: a `StreamedForward` (or `mesh.MeshStreamedForward`)
    :param subgrid_configs: the cover every pass consumes
    :param backwards: the `StreamedBackward` passes sharing this feed
    :param spill: the shared `utils.spill.SpillCache` (pass 1 of the
        whole schedule records it; later feeds replay from it)
    :param progress: optional callable(n_subgrids_folded) — heartbeat
    :param feed_index: this feed's position in the schedule (0-based);
        lets an uncached later feed attribute its wall to
        ``fwd.replay`` (None: always ``bwd.feed_group``)
    :returns: number of column groups fed
    """
    backwards = list(backwards)
    if not backwards:
        return 0
    cached = spill is not None and getattr(spill, "complete", False)
    n_groups = 0
    feed_wall = 0.0
    feed_bytes = 0
    with _trace.span(
        "bwd.feed_group", cat="bwd", n_passes=len(backwards)
    ):
        gen = forward.stream_column_groups(subgrid_configs, spill=spill)
        while True:
            t0 = time.monotonic()
            try:
                per_col, group = next(gen)
            except StopIteration:
                break
            feed_wall += time.monotonic() - t0
            if cached:
                feed_bytes += int(getattr(group, "nbytes", 0))
            n_groups += 1
            cols = [[sg for _, sg in col] for col in per_col]
            for bwd in backwards:
                bwd.add_subgrid_group(cols, group)
            if progress is not None:
                progress(sum(len(c) for c in cols) * len(backwards))
    if _metrics.enabled():
        _metrics.count("bwd.feed_groups")
        _metrics.count("bwd.feed_passes", len(backwards))
        if feed_index is not None and feed_index > 0 and not cached:
            # uncached later feed: the forward re-ran to regenerate the
            # stream, so the blocked wall is replay cost — the plan's
            # fwd.replay stage, not shared-feed traffic
            _metrics.observe(
                "fwd.replay", feed_wall, bytes_moved=feed_bytes
            )
        else:
            _metrics.observe(
                "bwd.feed_group", feed_wall, bytes_moved=feed_bytes
            )
    return n_groups


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


class StreamedBackward:
    """Subgrids -> facets with bounded device residency.

    Subgrids are fed column-grouped in any order; repeated columns
    accumulate (every fold is linear). `finish()` streams the column
    buffer back through the device to emit the facet stack.

    :param residency: "host" buffers per-column NAF rows in host RAM;
        "device" keeps them as device arrays (both sized K*[F, m, yB]);
        "sampled" folds each column's rows STRAIGHT into a device
        [F, yB, yB] image-space facet accumulator via the adjoint
        sampled-DFT einsum (see `_bwd_sampled_fold_fn`) — device state
        equals the OUTPUT size, the strategy for 32k+ scale where the
        per-column row set (K*F*m*yB ~ 30 GB at 32k) fits neither HBM
        nor a d2h budget.
    :param fold_group: ("sampled") columns folded per einsum dispatch —
        batches the adjoint contraction depth to fold_group*m rows.
    :param row_slab: ("sampled") optional (r0, r1) OUTPUT-ROW SLAB: the
        image-space accumulator covers only facet rows [r0, r1) — the
        adjoint fold's "ri" index restricts trivially, so a facet whose
        whole accumulator exceeds HBM (one 128k facet: 16.2 GiB) splits
        into row slabs, each an independent pass over the same subgrid
        stream (pair with the spill cache so the forward runs once).
        `finish()` then emits [F, r1 - r0, yB] slabs; slabs concatenated
        along axis 1 equal the whole-facet backward (pinned by tests).
    """

    def __init__(self, swiftly_config, facet_configs, col_block=512,
                 residency="host", fold_group=4, row_slab=None):
        self._base = _StreamedBase(
            swiftly_config, facet_configs, col_block, residency
        )
        self.core = self._base.core
        self.stack = self._base.stack
        self._naf = {}  # off0 -> host/device [F, m, yB_pad(,2)] rows
        self._acc = None  # ("sampled") device [F, yB, yB(,2)] accumulator
        self._fold_group = max(1, int(fold_group))
        self._fold_mode = resolve_fold_mode()  # auto | sampled | ct | fft
        self._row_slab = None
        if row_slab is not None:
            r0, r1 = int(row_slab[0]), int(row_slab[1])
            yB = self._base.stack.size
            if residency != "sampled":
                raise ValueError("row_slab requires residency='sampled'")
            if self._fold_mode not in ("sampled", "auto"):
                raise ValueError(
                    "row_slab requires the sampled fold body "
                    f"(SWIFTLY_FOLD=sampled|auto, got {self._fold_mode!r})"
                )
            if not (0 <= r0 < r1 <= yB):
                raise ValueError(
                    f"row_slab {(r0, r1)} outside the facet rows [0, {yB})"
                )
            self._row_slab = (r0, r1)
        self._pending_rows = []  # ("sampled") [(off0, rows [F, m, yB(,2)])]
        self._ct_starts = {}  # CT fold launch width -> device window starts
        # ("sampled") depth-2 fold-completion pipeline: dispatch is
        # asynchronous and block_until_ready was not completion on an
        # earlier runtime (on the chip: still to be measured), so a
        # checksum of each fold's output is pulled before
        # dispatching the fold after next — bounding live fold transients
        # and row buffers to two folds' worth (mirrors the forward's
        # _device_columns/_grouped_device_columns pattern).
        import collections

        self._fold_inflight = collections.deque()
        # ("sampled") column-pass completion pipeline: bounds live
        # NAF_BMNAF row buffers ([F, m, yB, 2], ~208 MB each at 32k) to
        # ~2 + fold_group — without it a caller feeding a whole column
        # group back-to-back keeps every column's rows live at once
        # (the BENCH_r04 32k roundtrip OOM ledger gap).
        self._rows_inflight = collections.deque()
        self._finished = False
        # (off0, off1) of every folded subgrid — the resume ledger the
        # autosave snapshots and `restore_streamed_backward_state`
        # repopulates, so a resumed feed loop knows what to skip
        self.processed = []
        self._autosave = None

    def enable_autosave(self, path, every_subgrids=0, every_s=0.0):
        """Periodic checkpointing driven by the feed itself: snapshot to
        `path` (atomic, checksummed, keep-N rotated — `utils.checkpoint`)
        every `every_subgrids` folded subgrids and/or every `every_s`
        seconds of wall clock, whichever fires first. The snapshot
        carries this session's ``processed`` ledger, so a killed run
        resumes via `restore_streamed_backward_state` + skipping the
        processed keys. Zero overhead beyond a counter until a save is
        due. Pass neither to disable."""
        every_subgrids = int(every_subgrids)
        every_s = float(every_s)
        if every_subgrids <= 0 and every_s <= 0:
            self._autosave = None
            return
        self._autosave = {
            "path": str(path),
            "every_n": every_subgrids,
            "every_s": every_s,
            "since": 0,
            "last_t": time.monotonic(),
        }

    def _autosave_tick(self, n_folded):
        a = self._autosave
        if a is None:
            return
        a["since"] += n_folded
        now = time.monotonic()
        due = (a["every_n"] > 0 and a["since"] >= a["every_n"]) or (
            a["every_s"] > 0 and now - a["last_t"] >= a["every_s"]
        )
        if not due:
            return
        from ..utils.checkpoint import save_streamed_backward_state

        save_streamed_backward_state(a["path"], self, self.processed)
        a["since"] = 0
        a["last_t"] = time.monotonic()
        _metrics.count("ckpt.autosaves")
        _trace.instant("ckpt.autosave_tick", cat="ckpt",
                       processed=len(self.processed))

    def _bwd_cp_flops(self, n_subgrids, subgrid_size):
        """Analytic FLOPs of one backward column pass over `n_subgrids`
        (stage attribution; 0 when metrics are disabled)."""
        if not _metrics.enabled():
            return 0
        from ..utils.flops import bwd_column_pass_flops

        base = self._base
        colpass = _resolve_colpass_bwd(
            self.core, base.stack.n_total // _mesh_size(base.mesh)
        )
        return bwd_column_pass_flops(
            self.core, base.stack.n_real, n_subgrids, base.stack.size,
            subgrid_size, colpass,
        )

    def add_subgrids(self, tasks):
        """Fold (SubgridConfig, subgrid_data) pairs into the accumulators."""
        if self._finished:
            raise RuntimeError("finish() was already called")
        groups = {}
        for sg, data in tasks:
            groups.setdefault(sg.off0, []).append((sg, data))
        for group in groups.values():
            self.add_subgrid_stack([sg for sg, _ in group],
                                   [d for _, d in group])

    def add_subgrid_stack(self, sg_configs, subgrids):
        """Fold one column's subgrids, given as a stack.

        :param sg_configs: the column's SubgridConfigs (one shared off0)
        :param subgrids: matching [S, xA, xA(,2)] — a DEVICE array (e.g.
            straight from `StreamedForward.stream_columns(...,
            device_arrays=True)`, no host round trip), or any host
            array/list of per-subgrid arrays.
        """
        import jax.numpy as jnp

        if self._finished:
            raise RuntimeError("finish() was already called")
        _fault_point("bwd.feed")
        base = self._base
        core = base.core
        off0s = {sg.off0 for sg in sg_configs}
        if len(off0s) != 1:
            raise ValueError(
                f"add_subgrid_stack takes ONE column, got offsets {off0s}"
            )
        off0 = off0s.pop()
        yB = base.stack.size
        h2d_bytes = 0
        if hasattr(subgrids, "sharding"):  # already a placed jax array
            subgrids = jnp.asarray(subgrids)
        else:
            subgrids = jnp.stack(
                [jnp.asarray(_to_host_layout(core, d)) for d in subgrids]
            )
            h2d_bytes = int(subgrids.nbytes)
        sg_offs = jnp.asarray([(sg.off0, sg.off1) for sg in sg_configs])
        if base.mesh is not None:
            colfn = _column_pass_bwd_sharded(core, base.mesh, yB)
        else:
            colfn = _column_pass_bwd_j(core, yB)
        if base.residency == "sampled":
            # genuine completion pull of the column before last (8-byte
            # host round trip) before dispatching another column pass
            while len(self._rows_inflight) >= 2:
                with _metrics.stage("bwd.drain"):
                    np.asarray(self._rows_inflight.popleft())
        cp_bytes = h2d_bytes
        if _metrics.enabled():
            from ..utils.profiling import column_collective_bytes

            cp_bytes += column_collective_bytes(
                core, _mesh_size(base.mesh), len(sg_configs), "backward",
                subgrid_size=sg_configs[0].size,
            )
            _metrics.count("bwd.subgrids_folded", len(sg_configs))
        with _metrics.stage(
            "bwd.column_pass",
            flops=self._bwd_cp_flops(len(sg_configs), sg_configs[0].size),
            bytes_moved=cp_bytes,
        ):
            rows = colfn(
                subgrids,
                sg_offs,
                base._foffs0,
                base._foffs1,
                base._masks1_dev,
            )  # [F, m, yB] (facet-sharded on a mesh)
        key = int(off0)
        if base.residency == "sampled":
            self._rows_inflight.append(jnp.sum(rows[:, 0]))
            self._pending_rows.append((key, rows))
            if len(self._pending_rows) >= self._fold_group:
                self._flush_folds()
            self.processed.extend(
                (sg.off0, sg.off1) for sg in sg_configs
            )
            self._autosave_tick(len(sg_configs))
            return
        pad = base._yB_pad - yB
        if pad:
            widths = [(0, 0), (0, 0), (0, pad)] + [
                (0, 0) for _ in _tail(core)
            ]
            rows = jnp.pad(rows, widths)
        if base.residency == "device":
            prev = self._naf.get(key)
            self._naf[key] = rows if prev is None else prev + rows
        else:
            if key in self._naf:
                self._naf[key] += np.asarray(rows)
            else:
                self._naf[key] = np.array(rows)  # writable copy
        self.processed.extend((sg.off0, sg.off1) for sg in sg_configs)
        self._autosave_tick(len(sg_configs))

    def _ensure_acc(self):
        import jax.numpy as jnp

        base = self._base
        if self._acc is None:
            r0, r1 = self._row_slab or (0, base.stack.size)
            shape = (
                base.stack.n_total, r1 - r0, base.stack.size
            ) + _tail(base.core)
            if base.mesh is not None:
                self._acc = base._place(
                    np.zeros(shape, dtype=_np_dtype(base.core))
                )
            else:
                self._acc = jnp.zeros(shape, dtype=_np_dtype(base.core))

    def _drain_folds(self, depth=1):
        """Pull fold checksums down to `depth` in flight (genuine 8-byte
        host round trips — see _fold_inflight comment in __init__)."""
        while len(self._fold_inflight) > depth:
            with _metrics.stage("bwd.drain"):
                np.asarray(self._fold_inflight.popleft())

    def _fold_rows(self, offs, rows_cat):
        """("sampled") one adjoint fold of concatenated column rows
        [F, g*m, yB(,2)] into the image-space accumulator, through the
        body `select_fold_body` picks for the call: the CT-factored body
        where the call is deep enough, else the direct adjoint-sampled
        einsum (or SWIFTLY_FOLD's forced body). Either body's wall is
        the plan's priced ``bwd.sampled_fold`` stage, at the fold's
        modelled FLOPs; the body shows in its counter and its
        ``swiftly/`` device scope."""
        import jax.numpy as jnp

        base = self._base
        core = base.core
        yB = base.stack.size
        self._ensure_acc()
        e0 = getattr(self, "_e0_dev", None)
        if e0 is None:
            e0 = self._e0_dev = base._place(
                (np.asarray(base.stack.offs0) - yB // 2).astype(np.int32)
            )
        self._drain_folds()
        body = select_fold_body(
            self._fold_mode, core.yN_size, int(rows_cat.shape[1]),
            meshed=base.mesh is not None,
            row_slab=self._row_slab is not None,
        )
        if body == "ct":
            from ..plan import model as _plan_model

            Q, P, kmax, tab = _ct_fold_tables(
                core, tuple(int(o) for o in offs)
            )
            F = base.stack.n_total // _mesh_size(base.mesh)
            W = _ct_fold_width(
                yB, _ct_column_bytes(core, F, yB),
                _plan_model.DEFAULT_RESERVE_BYTES,
            )
            if base.mesh is not None:
                foldfn = _bwd_ct_fold_sharded(
                    core, base.mesh, Q, P, kmax, W
                )
            else:
                foldfn = _bwd_ct_fold_j(core, Q, P, kmax, W)
            if _metrics.enabled():
                _metrics.count("bwd.ct_folds")
            # the window starts go to the device once, not every launch
            starts = self._ct_starts.get(W)
            if starts is None:
                starts = self._ct_starts[W] = [
                    jnp.int32(j0) for j0 in range(0, yB, W)
                ]
            tab = jnp.asarray(tab)
            with _metrics.stage("bwd.sampled_fold",
                                flops=self._fold_flops(rows_cat)):
                for j0 in starts:
                    self._acc = foldfn(self._acc, rows_cat, e0, tab, j0)
        else:
            krows = jnp.asarray(sampled_row_indices(core, offs))
            if base.mesh is not None:
                foldfn = _bwd_sampled_fold_sharded(core, base.mesh)
            else:
                from ..ops.pallas_kernels import pallas_interpret

                kernel = resolve_fold_kernel(core)
                foldfn = _bwd_sampled_fold_j(
                    core, kernel == "pallas", pallas_interpret()
                )
                if kernel == "pallas" and _metrics.enabled():
                    _metrics.count("bwd.pallas_folds")
            if _metrics.enabled():
                _metrics.count("bwd.sampled_folds")
            row0 = jnp.int32((self._row_slab or (0, 0))[0])
            with _metrics.stage("bwd.sampled_fold",
                                flops=self._fold_flops(rows_cat)):
                self._acc = foldfn(self._acc, rows_cat, e0, krows, row0)
        # the checksum slice depends on the whole fold having executed
        self._fold_inflight.append(jnp.sum(self._acc[:, 0]))

    def _fold_flops(self, rows_cat):
        """The modelled FLOPs of one fold call (`bwd_fold_flops`, the
        same work whichever body runs it), or 0 with metrics off."""
        if not _metrics.enabled():
            return 0
        from ..utils.flops import bwd_fold_flops

        base = self._base
        yB = base.stack.size
        flops = bwd_fold_flops(
            base.core, base.stack.n_real, yB, int(rows_cat.shape[1])
        )
        if self._row_slab is not None:
            # fold FLOPs scale with the output rows computed
            r0, r1 = self._row_slab
            flops = int(flops * (r1 - r0) / yB)
        return flops

    def _fold_rows_fft(self, offs, rows_g):
        """("sampled", fft fold) one FFT-based adjoint fold of a column
        group's rows [g, F, m, yB(,2)] into the image accumulator —
        dispatched as one donation-chained program per j-chunk."""
        import jax.numpy as jnp

        base = self._base
        core = base.core
        yB = base.stack.size
        self._ensure_acc()
        offs_dev = jnp.asarray(np.asarray(offs, dtype=np.int32))
        F = base.stack.n_total // _mesh_size(base.mesh)
        Cj = min(_fft_fold_chunk(core, F, yB), yB)
        if base.mesh is not None:
            foldfn = _bwd_fft_fold_chunk_sharded(core, base.mesh, Cj)
        else:
            foldfn = _bwd_fft_fold_chunk_j(core, Cj)
        self._drain_folds()
        with _metrics.stage("bwd.fft_fold"):
            for ci in range(-(-yB // Cj)):
                j0 = ci * Cj
                start = min(j0, yB - Cj)
                self._acc = foldfn(
                    self._acc, rows_g, offs_dev, base._foffs0,
                    jnp.int32(j0), jnp.int32(start),
                )
        self._fold_inflight.append(jnp.sum(self._acc[:, 0]))

    def _flush_folds(self):
        """("sampled") fold the pending columns' rows into the image-space
        accumulator: one fold over the pending group, via the body
        `select_fold_body` picks (or the fft body SWIFTLY_FOLD forces)."""
        import jax.numpy as jnp

        if not self._pending_rows:
            return
        offs = [o for o, _ in self._pending_rows]
        if self._fold_mode == "fft":
            rows_g = jnp.stack([r for _, r in self._pending_rows])
            self._fold_rows_fft(offs, rows_g)
        else:
            rows_cat = (
                self._pending_rows[0][1]
                if len(self._pending_rows) == 1
                else jnp.concatenate(
                    [r for _, r in self._pending_rows], axis=1
                )
            )  # [F, P*m, yB(,2)]
            self._fold_rows(offs, rows_cat)
        self._pending_rows = []

    def add_subgrid_group(self, col_sg_lists, subgrids_group):
        """("sampled") fold a whole forward column GROUP in TWO
        dispatches: one vmapped column pass over the group's stacked
        subgrids and one adjoint fold over the G*m concatenated rows —
        feeding the same group per column pays the per-dispatch latency
        2G+ times (the dominant backward-leg cost on an earlier runtime).

        :param col_sg_lists: per-column lists of SubgridConfigs (one
            shared off0 each). Columns may hold FEWER configs than the
            group array's S rows — the trailing rows are the forward's
            zero-mask padding, which is exactly zero and folds to zero
            whatever offsets are assumed for it.
        :param subgrids_group: device [G, S, xA, xA(,2)], e.g. one yield
            of `StreamedForward.stream_column_groups`.
        """
        import jax.numpy as jnp

        if self._finished:
            raise RuntimeError("finish() was already called")
        if self._base.residency != "sampled":
            raise ValueError(
                "add_subgrid_group requires residency='sampled'"
            )
        _fault_point("bwd.feed")
        base = self._base
        if base.mesh is not None:
            # per-column sharded path (the group-batched column pass is
            # single-device; on a mesh the latency it amortises is not
            # the bottleneck anyway) — but fold batching and the
            # autosave tick still follow the GROUP contract: pending
            # folds flush at both group boundaries and the autosave
            # fires once per group, so a kill+resume refeeds whole
            # groups with fold batching identical before and after
            # (the same bit-identity contract as the single-device
            # group path below; per-column ticks would let a snapshot
            # land mid-group and straddle fold concatenations).
            self._flush_folds()
            autosave, self._autosave = self._autosave, None
            n_group = 0
            try:
                for gi, col in enumerate(col_sg_lists):
                    self.add_subgrid_stack(
                        col, subgrids_group[gi][: len(col)]
                    )
                    n_group += len(col)
            finally:
                self._autosave = autosave
            self._flush_folds()
            self._autosave_tick(n_group)
            return
        core = base.core
        yB = base.stack.size
        S = subgrids_group.shape[1]
        offs, sg_offs = [], []
        for col in col_sg_lists:
            off0s = {sg.off0 for sg in col}
            if len(off0s) != 1:
                raise ValueError(
                    f"each group entry must be ONE column, got {off0s}"
                )
            off0 = off0s.pop()
            offs.append(int(off0))
            pairs = [(sg.off0, sg.off1) for sg in col]
            pairs += [(off0, 0)] * (S - len(pairs))  # zero-pad rows
            sg_offs.append(pairs)
        # flush any pending per-column rows first so fold order follows
        # feed order (accumulation is exact either way — linearity)
        self._flush_folds()
        colfn = _column_pass_bwd_group_j(core, yB)
        sg_offs_np = np.asarray(sg_offs)
        # batch cap = fold_group: an uncapped group's [G, F, m, yB] rows
        # plus the fold's rotated copies would blow the headroom the
        # forward's sizers were given (rows are ~208 MB per 32k column;
        # bench.py's roundtrip headroom term (2*fold_group+2)*row_bytes
        # covers this capped batch's live set, validated green at 32k)
        cap = max(1, int(self._fold_group))
        G = len(offs)
        for j in range(0, G, cap):
            # no separate rows checksum here: each chunk's fold consumes
            # its rows immediately, so the fold pipeline's depth-2 pull
            # (_fold_rows) transitively bounds live rows to two chunks'
            # worth — a separate rows pull would add one host round trip
            # per chunk for backpressure the fold already provides
            g = len(offs[j : j + cap])
            if _metrics.enabled():
                _metrics.count("bwd.subgrids_folded", g * S)
            with _metrics.stage(
                "bwd.column_pass",
                flops=g * self._bwd_cp_flops(S, int(subgrids_group.shape[2])),
            ):
                rows = colfn(
                    jnp.asarray(subgrids_group[j : j + cap]),
                    jnp.asarray(sg_offs_np[j : j + cap]),
                    base._foffs0,
                    base._foffs1,
                    base._masks1_dev,
                )  # [g, F, m, yB(,2)]
            if self._fold_mode == "fft":
                # the FFT fold takes per-column rows directly; its cost
                # is flat in g, so the whole chunk folds in one dispatch
                self._fold_rows_fft(offs[j : j + cap], rows)
                continue
            rows_cat = jnp.moveaxis(rows, 0, 1).reshape(
                (rows.shape[1], rows.shape[0] * rows.shape[2])
                + rows.shape[3:]
            )  # [F, g*m, yB(,2)]
            self._fold_rows(offs[j : j + cap], rows_cat)
        # the whole group folded: ledger + autosave AT GROUP BOUNDARIES
        # only — the processed set then always covers whole groups, so a
        # resumed feed loop skips group-by-group and fold batching (per
        # cap chunk within each group) is identical before and after a
        # kill (the chaos drill's bit-identity rests on this)
        n_group = 0
        for col in col_sg_lists:
            self.processed.extend((sg.off0, sg.off1) for sg in col)
            n_group += len(col)
        self._autosave_tick(n_group)

    def finish_device(self):
        """("sampled") the finished facet stack [F_total, yB, yB(,2)] as a
        DEVICE array — callers at 32k+ scale verify/consume it on device
        (a full host pull of the stack is gigabytes of d2h)."""
        if self._base.residency != "sampled":
            raise ValueError("finish_device() requires residency='sampled'")
        if self._finished:
            raise RuntimeError("finish() was already called")
        self._flush_folds()
        if self._acc is None:
            raise RuntimeError("No subgrids were added")
        fn = _sampled_finish_j(self.core)
        masks0 = self._base._masks0_dev
        if self._row_slab is not None:
            # the finish mask is over the output-row axis: slice it to
            # the slab (the j axis and everything else stay full-width)
            r0, r1 = self._row_slab
            masks0 = masks0[:, r0:r1]
        acc, self._acc = self._acc, None  # donated to the finish program
        with _metrics.stage("bwd.finish"):
            out = fn(acc, masks0)
        self._finished = True
        return out

    def finish(self):
        """Emit the finished facet stack [F, yB, yB(,2)] (host array)."""
        import jax.numpy as jnp

        if self._base.residency == "sampled":
            return np.asarray(self.finish_device())[: self.stack.n_real]
        if self._finished:
            raise RuntimeError("finish() was already called")
        base = self._base
        core = base.core
        stack = base.stack
        yB = stack.size
        Cb = base.col_block
        col_offs0 = sorted(self._naf)
        if not col_offs0:
            raise RuntimeError("No subgrids were added")
        if base.mesh is not None:
            finfn = _facet_pass_bwd_sharded(core, base.mesh, yB)
        else:
            finfn = _facet_pass_bwd_j(core, yB)
        col_offs0_j = jnp.asarray(col_offs0)
        masks0 = base._masks0_dev
        facets = np.zeros(
            (len(stack), yB, yB) + _tail(core), dtype=_np_dtype(core)
        )
        pending = []
        for j0 in range(0, base._yB_pad, Cb):
            if base.residency == "device":
                blocks = jnp.stack(
                    [
                        jax.lax.dynamic_slice_in_dim(
                            self._naf[o], j0, Cb, axis=2
                        )
                        for o in col_offs0
                    ]
                )
            else:
                blocks = base._place(
                    np.stack(
                        [self._naf[o][:, :, j0 : j0 + Cb] for o in col_offs0]
                    ),
                    facet_axis=1,
                )
            with _metrics.stage("bwd.facet_pass"):
                out = finfn(blocks, col_offs0_j, base._foffs0, masks0)
            pending.append((j0, out))
            if len(pending) > 1:
                pj, pout = pending.pop(0)
                j1 = min(pj + Cb, yB)
                with _metrics.stage("bwd.d2h") as st:
                    host = np.asarray(pout)
                    st.bytes_moved = int(host.nbytes)
                facets[:, :, pj:j1] = host[:, :, : j1 - pj]
        for pj, pout in pending:
            j1 = min(pj + Cb, yB)
            if j1 > pj:
                with _metrics.stage("bwd.d2h") as st:
                    host = np.asarray(pout)
                    st.bytes_moved = int(host.nbytes)
                facets[:, :, pj:j1] = host[:, :, : j1 - pj]
        self._finished = True
        return facets[: stack.n_real]
