"""Batched degridding: gather + small dense contraction, jitted.

One dispatch answers every sample of one served subgrid row: gather the
[B, W, W] pixel patches, then contract each against the separable tap
weights — ``vis[b] = sum_ij patch[b, i, j] * cu[b, i] * cv[b, j]``.
Real arithmetic throughout (tap weights are real, rows arrive as
real/imag planes), which is also what makes `vis.grid` the EXACT
adjoint: the same gather indices and the same real weights, transposed.

Batch sizes are padded to power-of-two buckets (the serve scheduler's
bucket discipline, `serve.scheduler.bucket_shape`) so the jit cache
holds O(log max_batch) programs per subgrid shape instead of one per
request size.

The contraction runs as XLA einsums by default — CPU tier-1 exercises
the same program the TPU runs. ``SWIFTLY_PALLAS=1`` selects a fused
Pallas kernel for the weight outer-product + patch reduction (one VMEM
pass per B-block instead of materialising the [B, W, W] weight plane in
HBM); ``SWIFTLY_PALLAS_INTERPRET=1`` runs it in interpreter mode so the
CPU tier can equivalence-test the kernel (`ops.pallas_kernels`
discipline).
"""

from __future__ import annotations

import functools

import numpy as np

from ..ops.pallas_kernels import pallas_enabled, pallas_interpret

__all__ = ["bucket_size", "degrid_batch", "split_row_planes"]

_MAX_BUCKET = 4096


def bucket_size(n, max_bucket=_MAX_BUCKET):
    """Smallest power-of-two >= n (capped) — the jit-cache bucket.

    The floor is 2, not 1: XLA compiles the B=1 einsum with a
    different reduction order than every B>=2 bucket (measured ~1ulp
    drift), which would break the contract that a sample's bits do not
    depend on how its batch was coalesced. Padding the singleton to a
    2-lane bucket keeps every bucket on the same vectorised program
    family, so per-lane results are bitwise identical across buckets.
    """
    b = 2
    while b < n and b < max_bucket:
        b *= 2
    return b


def split_row_planes(row):
    """A served subgrid row as (real, imag) float planes.

    Accepts the three layouts the serve path produces: planar
    ``[..., 2]`` host/device arrays (the planar backend and every
    recorded stream of it), complex arrays (jax/numpy backends), and
    real arrays (imag plane zero).
    """
    arr = np.asarray(row)
    if arr.ndim == 3 and arr.shape[-1] == 2:
        return arr[..., 0], arr[..., 1]
    if np.iscomplexobj(arr):
        return np.ascontiguousarray(arr.real), np.ascontiguousarray(
            arr.imag
        )
    return arr, np.zeros_like(arr)


@functools.lru_cache(maxsize=None)
def _degrid_fn(support, use_pallas):
    """Jitted [B]-bucket degrid body for one tap count."""
    import jax
    import jax.numpy as jnp

    offs = jnp.arange(support)

    def gather(plane, iu0, iv0):
        iu = iu0[:, None] + offs  # [B, W]
        iv = iv0[:, None] + offs
        return plane[iu[:, :, None], iv[:, None, :]]  # [B, W, W]

    if not use_pallas:

        def body(row_r, row_i, iu0, iv0, cu, cv):
            pr = gather(row_r, iu0, iv0)
            pi = gather(row_i, iu0, iv0)
            vr = jnp.einsum("bij,bi,bj->b", pr, cu, cv)
            vi = jnp.einsum("bij,bi,bj->b", pi, cu, cv)
            return vr, vi

        return jax.jit(body)

    from jax.experimental import pallas as pl

    def kernel(pr_ref, pi_ref, cu_ref, cv_ref, vr_ref, vi_ref):
        # one VMEM pass: both separable tap reductions fused per
        # B-block (VPU work; W*W is tiny, the win is never
        # materialising [B, W, W] weights in HBM). Mosaic lowers a
        # last-axis reduction to a 2-D [B, 1] output, not a reduction
        # over two axes to a 1-D one.
        cu = cu_ref[...]
        cv = cv_ref[...][:, None, :]
        vr_ref[...] = jnp.sum(
            jnp.sum(pr_ref[...] * cv, axis=2) * cu, axis=1, keepdims=True
        )
        vi_ref[...] = jnp.sum(
            jnp.sum(pi_ref[...] * cv, axis=2) * cu, axis=1, keepdims=True
        )

    def body(row_r, row_i, iu0, iv0, cu, cv):
        pr = gather(row_r, iu0, iv0)
        pi = gather(row_i, iu0, iv0)
        B = pr.shape[0]
        bb = min(B, 256)  # B is a power of two; [bb, W, W] pads to 128 lanes
        p_spec = pl.BlockSpec((bb, support, support), lambda i: (i, 0, 0))
        c_spec = pl.BlockSpec((bb, support), lambda i: (i, 0))
        o_spec = pl.BlockSpec((bb, 1), lambda i: (i, 0))
        out = jax.ShapeDtypeStruct((B, 1), pr.dtype)
        vr, vi = pl.pallas_call(
            kernel,
            grid=(B // bb,),
            in_specs=[p_spec, p_spec, c_spec, c_spec],
            out_specs=[o_spec, o_spec],
            out_shape=(out, out),
            interpret=pallas_interpret(),
        )(pr, pi, cu, cv)
        return vr[:, 0], vi[:, 0]

    return jax.jit(body)


def degrid_batch(row, iu0, iv0, cu, cv, *, support=None):
    """Degrid one sample batch off one served subgrid row.

    :param row: the served row ([size, size] complex / real /
        planar ``[..., 2]``)
    :param iu0/iv0: [B] first-tap indices into the row (from
        `vis.mapping.VisCoverIndex.map_samples`)
    :param cu/cv: [B, W] separable tap weights
        (`vis.kernel.VisKernel.weights`)
    :return: [B] complex128 visibilities (host)

    The same jitted body serves cache-fed host rows and
    compute-fallback device rows: identical row BITS in give identical
    sample bits out, which is what makes the cache-vs-compute
    bit-identity contract of `serve` carry over to samples
    (tests/test_vis.py pins it).
    """
    row_r, row_i = split_row_planes(row)
    n = int(np.asarray(iu0).size)
    W = int(cu.shape[1]) if support is None else int(support)
    b = bucket_size(n)
    dt = row_r.dtype
    iu0_p = np.zeros(b, dtype=np.int32)
    iv0_p = np.zeros(b, dtype=np.int32)
    cu_p = np.zeros((b, W), dtype=dt)
    cv_p = np.zeros((b, W), dtype=dt)
    iu0_p[:n] = iu0
    iv0_p[:n] = iv0
    cu_p[:n] = cu
    cv_p[:n] = cv
    fn = _degrid_fn(W, pallas_enabled() or pallas_interpret())
    vr, vi = fn(row_r, row_i, iu0_p, iv0_p, cu_p, cv_p)
    return np.asarray(vr)[:n] + 1j * np.asarray(vi)[:n]
