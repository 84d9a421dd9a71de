"""Explicit shard_map + psum execution of the hot streaming kernels.

The GSPMD path (swiftly_tpu.parallel.batched with facet-sharded inputs)
lets XLA infer the collectives. This module is the explicit alternative:
the facet stack is mapped over the mesh's facet axis with `jax.shard_map`,
each device reduces its local facets' contributions, and one `lax.psum`
over ICI/DCN produces the subgrid — the deterministic, hand-placed
collective schedule for the reference's facet-contribution sum
(/root/reference/src/ska_sdp_exec_swiftly/api_helper.py:73-112, where the
sum is Dask worker-to-worker transfers + a task-side loop).

Forward (`subgrid_from_columns_sharded`):
  per-device: vmap over local facets -> local partial padded subgrid
  collective: psum over the facet axis     [the only cross-device traffic:
                                            one xM x xM buffer per subgrid]
  replicated: finish (iFFT + crop) + masks

The facet-axis reduction itself has two schedules (SWIFTLY_MESH_COLLECTIVE):
the blocking `lax.psum` above, or `ring_allreduce` — a reduce-scatter +
all-gather built from 2(n-1) `lax.ppermute` chunk rotations whose steps
overlap neighbouring compute instead of fencing it (same sum up to
reduction order; see docs/multichip.md "Collective schedules").

Backward (`split_subgrid_sharded`):
  replicated: prepare_subgrid (pad + FFT) on every device
  per-device: vmap extract -> facet-sharded NAF_NAFs  [traffic: the xA x xA
                                            subgrid broadcast at placement]

Column/facet accumulation stays elementwise per facet (no collectives), so
the batched kernels handle it under either mode. The per-facet math bodies
are shared with the batched module (`facet_contrib_to_subgrid`,
`subgrid_contrib_to_facet`), so the two spmd modes cannot diverge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map as _shard_map

import numpy as np

from ..ops.core import prepare_subgrid_math
from .batched import (
    _accumulate_facet_fn,
    _as_real,
    _extract_columns_fn,
    _finish_facets_fn,
    _split_accumulate_fn,
    facet_contrib_to_subgrid,
    finish_masked_subgrid,
    subgrid_contrib_to_facet,
)
from .mesh import FACET_AXIS, mesh_size, resolve_collective, varying


def ring_allreduce(x, axis_name: str, n_shards: int | None = None):
    """Facet-axis all-reduce as a `ppermute` ring: reduce-scatter then
    all-gather, 2(n-1) neighbour rotations of a 1/n-size chunk.

    The buffer is flattened and split into n equal chunks (zero-padded to
    a multiple of n — exact, the pad never aliases real elements). Each
    shard owns one chunk's running sum; every reduce-scatter step rotates
    the partial one hop around the ring and folds in the local copy of
    the chunk now in flight, so after n-1 steps shard i holds the fully
    reduced chunk (i+1) % n. The all-gather phase rotates the finished
    chunks the rest of the way around. Per-step traffic is size/n vs the
    whole buffer for a blocking psum, and each step's `ppermute` has no
    data dependence on neighbouring column contractions — XLA is free to
    run the rotation concurrently with the next facet block's local
    einsum (the overlap the mesh engine's triple-buffer feed completes).

    Exactness: every shard accumulates each chunk in the SAME ring
    order, so the result is deterministic and shard-count-reproducible,
    but the reduction ORDER differs from psum's tree — expect float
    rounding drift within the documented tolerance (docs/multichip.md),
    not bit-identity. Zero-padded facet shards (9-over-8 cover) add
    exact zeros, so padding never widens the drift.
    """
    n = int(n_shards) if n_shards is not None else jax.lax.psum(1, axis_name)
    if n <= 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    flat = x.reshape(-1)
    per = -(-flat.size // n)
    pad = n * per - flat.size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    parts = flat.reshape(n, per)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def chunk(k):
        return jax.lax.dynamic_index_in_dim(parts, k % n, 0, keepdims=False)

    with jax.named_scope("swiftly/mesh.ring_step"):
        acc = chunk(idx)
        for s in range(1, n):  # reduce-scatter
            acc = jax.lax.ppermute(acc, axis_name, perm)
            acc = acc + chunk(idx - s)
        own = (idx + 1) % n  # shard i finishes chunk (i+1) % n
        gathered = jnp.zeros((n, per), acc.dtype)
        gathered = jax.lax.dynamic_update_index_in_dim(gathered, acc, own, 0)
        cur = acc
        for s in range(1, n):  # all-gather
            cur = jax.lax.ppermute(cur, axis_name, perm)
            gathered = jax.lax.dynamic_update_index_in_dim(
                gathered, cur, (own - s) % n, 0
            )
    out = gathered.reshape(-1)
    if pad:
        out = out[: x.size]
    return out.reshape(x.shape)


def collective_sum(x, axis_name: str, collective: str = "psum",
                   n_shards: int | None = None):
    """The facet-axis reduction under the selected schedule: blocking
    `lax.psum` (XLA all-reduce, under its own ``swiftly/mesh.psum``
    scope: the host stage timing the same wait has that name) or the
    `ppermute` ring."""
    if collective == "ring":
        return ring_allreduce(x, axis_name, n_shards)
    with jax.named_scope("swiftly/mesh.psum"):
        return jax.lax.psum(x, axis_name)


def _mapped(fn, mesh, in_specs, out_specs, check_vma: bool = True):
    """shard_map with an optional check_vma=False escape hatch.

    Ring kernels mix `ppermute`/`axis_index` results into replicated
    outputs — correct (every shard materialises the same gathered sum)
    but not provable by the replication checker, so they opt out the
    same way streamed.py's `_shmap` does. psum kernels keep the check.
    """
    return _shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


def _scoped(name, fn):
    """Wrap a kernel body in ``jax.named_scope`` so its compiled HLO ops
    carry the stage name (shared vocabulary with the host-side stage
    timers in ``obs.metrics``; zero runtime cost — trace-time only)."""

    def wrapped(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return wrapped


__all__ = [
    "backward_all_sharded",
    "collective_sum",
    "forward_all_sharded",
    "ring_allreduce",
    "split_accumulate_sharded",
    "split_subgrid_sharded",
    "subgrid_from_columns_sharded",
    "subgrids_from_columns_sharded",
]


# Bounded: long-lived processes sweeping many configurations must not pin
# every (core, mesh) pair's compiled executable forever. Evicted kernels
# simply recompile on next use.
@functools.lru_cache(maxsize=32)
def _forward_kernel(core, mesh, subgrid_size: int, collective: str = "psum"):
    """Build the jitted shard_map program for one (core, mesh, size,
    collective)."""
    n_shards = mesh_size(mesh)

    def body(NMBF_BFs, offs0, offs1, sg_offs, mask0, mask1):
        contrib = lambda NMBF_BF, foff0, foff1: facet_contrib_to_subgrid(
            core, NMBF_BF, foff0, foff1, sg_offs[1]
        )
        # Local reduction over this shard's facets, then one all-reduce.
        local = jnp.sum(jax.vmap(contrib)(NMBF_BFs, offs0, offs1), axis=0)
        summed = collective_sum(local, FACET_AXIS, collective, n_shards)
        return finish_masked_subgrid(
            core, summed, sg_offs, subgrid_size, mask0, mask1
        )

    mapped = _mapped(
        _scoped("swiftly/fwd.column_pass", body),
        mesh=mesh,
        in_specs=(P(FACET_AXIS), P(FACET_AXIS), P(FACET_AXIS), P(), P(), P()),
        out_specs=P(),
        check_vma=collective != "ring",
    )
    return jax.jit(mapped)


def subgrid_from_columns_sharded(
    core, mesh, NMBF_BFs, offs0, offs1, sg_off0, sg_off1, subgrid_size, masks
):
    """Facet-sharded NMBF_BFs [F, m, yN] -> replicated subgrid [xA, xA].

    Same contract as ``batched.subgrid_from_columns_batch`` but with the
    facet reduction expressed as an explicit collective over the mesh
    (``lax.psum`` or the `ppermute` ring, per SWIFTLY_MESH_COLLECTIVE —
    resolved at call time so psum and ring can run in one process).
    """
    fn = _forward_kernel(
        core, mesh, subgrid_size, resolve_collective(mesh_size(mesh))
    )
    rdt = core._Fb.dtype
    return fn(
        NMBF_BFs,
        jnp.asarray(offs0),
        jnp.asarray(offs1),
        jnp.asarray([sg_off0, sg_off1]),
        jnp.asarray(masks[0], rdt),
        jnp.asarray(masks[1], rdt),
    )


@functools.lru_cache(maxsize=32)
def _backward_kernel(core, mesh):
    def body(subgrid, sg_offs, offs0, offs1):
        prepped = prepare_subgrid_math(
            core._p, core.xM_size, subgrid, sg_offs
        )
        extract = lambda foff0, foff1: subgrid_contrib_to_facet(
            core, prepped, foff0, foff1
        )
        return jax.vmap(extract)(offs0, offs1)

    mapped = _shard_map(
        _scoped("swiftly/bwd.column_pass", body),
        mesh=mesh,
        in_specs=(P(), P(), P(FACET_AXIS), P(FACET_AXIS)),
        out_specs=P(FACET_AXIS),
    )
    return jax.jit(mapped)


def split_subgrid_sharded(
    core, mesh, subgrid, sg_off0, sg_off1, offs0, offs1
):
    """Replicated subgrid [xA, xA] -> facet-sharded NAF_NAFs [F, m, m].

    Same contract as ``batched.split_subgrid_batch``; the subgrid is
    broadcast once, extraction is device-local per facet shard.
    """
    fn = _backward_kernel(core, mesh)
    return fn(
        core._prep(subgrid),
        jnp.asarray([sg_off0, sg_off1]),
        jnp.asarray(offs0),
        jnp.asarray(offs1),
    )


# ---------------------------------------------------------------------------
# Fused column/whole-cover mesh programs
#
# The per-subgrid kernels above cost one dispatch (and one psum) per
# subgrid — dispatch-latency-bound on remote-attached devices, exactly the
# disease the single-device fused paths cured. These kernels batch a whole
# column (or the whole cover) into ONE shard_map program with ONE psum per
# column: per-device work scales with local facets (F/d), cross-device
# traffic is one [S, xM, xM] buffer per column.
# ---------------------------------------------------------------------------


def _column_partial_then_finish(core, cols, offs0, offs1, off0, col_sg_offs1,
                                col_m0, col_m1, subgrid_size,
                                collective="psum", n_shards=None):
    """Local facet reduction for all S subgrids of one column, one
    collective, then the (replicated) finishes. Shared by the column and
    whole-cover kernels."""

    def partial_sg(off1):
        contrib = lambda NMBF_BF, foff0, foff1: facet_contrib_to_subgrid(
            core, NMBF_BF, foff0, foff1, off1
        )
        return jnp.sum(jax.vmap(contrib)(cols, offs0, offs1), axis=0)

    partial = jax.vmap(partial_sg)(col_sg_offs1)  # [S, xM, xM] local
    # one collective per column: blocking all-reduce or ppermute ring
    summed = collective_sum(partial, FACET_AXIS, collective, n_shards)

    def fin(s, off1, m0, m1):
        return finish_masked_subgrid(
            core, s, jnp.stack([off0, off1]), subgrid_size, m0, m1
        )

    return jax.vmap(fin)(summed, col_sg_offs1, col_m0, col_m1)


@functools.lru_cache(maxsize=32)
def _forward_column_kernel(core, mesh, subgrid_size: int,
                           collective: str = "psum"):
    """One column's S subgrids in one program: single collective per
    column (all-reduce or ppermute ring)."""
    n_shards = mesh_size(mesh)

    def body(NMBF_BFs, offs0, offs1, off0, sg_offs1, masks0, masks1):
        return _column_partial_then_finish(
            core, NMBF_BFs, offs0, offs1, off0, sg_offs1, masks0, masks1,
            subgrid_size, collective, n_shards,
        )

    mapped = _mapped(
        _scoped("swiftly/fwd.column_pass", body),
        mesh=mesh,
        in_specs=(
            P(FACET_AXIS), P(FACET_AXIS), P(FACET_AXIS), P(), P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=collective != "ring",
    )
    return jax.jit(mapped)


def subgrids_from_columns_sharded(
    core, mesh, NMBF_BFs, offs0, offs1, sg_offs_list, subgrid_size, masks_list
):
    """All subgrids of one column on the mesh: [S, xA, xA], one dispatch.

    Mesh analogue of ``batched.subgrids_from_columns_batch``: local facet
    reduction + a single collective for the whole stacked column.
    """
    fn = _forward_column_kernel(
        core, mesh, subgrid_size, resolve_collective(mesh_size(mesh))
    )
    rdt = core._Fb.dtype
    return fn(
        NMBF_BFs,
        jnp.asarray(offs0),
        jnp.asarray(offs1),
        jnp.asarray(sg_offs_list[0][0]),
        jnp.asarray([so[1] for so in sg_offs_list]),
        jnp.asarray(np.stack([m[0] for m in masks_list]), rdt),
        jnp.asarray(np.stack([m[1] for m in masks_list]), rdt),
    )


@functools.lru_cache(maxsize=32)
def _forward_all_kernel(core, mesh, subgrid_size: int,
                        collective: str = "psum"):
    """The whole forward cover as ONE shard_map program.

    Scan over columns; per column: extract the local facets' column
    blocks, reduce their contributions for all S subgrids, one
    collective, finish. O(1) dispatches and O(columns) collectives for
    the entire transform — the mesh analogue of
    ``batched.forward_all_batch``. Under the ring schedule the scanned
    column's `ppermute` rotations carry no dependence on the next
    column's extraction/contraction, so the rotation overlaps the next
    column's local work instead of fencing it.
    """
    n_shards = mesh_size(mesh)

    def body(BF_Fs, offs0, offs1, col_offs0, sg_offs1, masks0, masks1):
        def one_column(_, xs):
            off0, col_sg_offs1, col_m0, col_m1 = xs
            cols = _extract_columns_fn(core, BF_Fs, off0, offs1)
            return None, _column_partial_then_finish(
                core, cols, offs0, offs1, off0, col_sg_offs1, col_m0,
                col_m1, subgrid_size, collective, n_shards,
            )

        _, subgrids = jax.lax.scan(
            one_column, None, (col_offs0, sg_offs1, masks0, masks1)
        )
        return subgrids

    mapped = _mapped(
        _scoped("swiftly/fwd.fused_forward", body),
        mesh=mesh,
        in_specs=(
            P(FACET_AXIS), P(FACET_AXIS), P(FACET_AXIS), P(), P(), P(), P(),
        ),
        out_specs=P(),
        check_vma=collective != "ring",
    )
    return jax.jit(mapped)


def forward_all_sharded(
    core, mesh, BF_Fs, offs0, offs1, col_offs0, sg_offs1, subgrid_size,
    masks0, masks1,
):
    """The full forward cover on the mesh: [C, S, xA, xA], one dispatch.

    Same contract as ``batched.forward_all_batch`` with the facet
    reduction as one explicit collective per scanned column.
    """
    fn = _forward_all_kernel(
        core, mesh, subgrid_size, resolve_collective(mesh_size(mesh))
    )
    rdt = core._Fb.dtype
    return fn(
        BF_Fs,
        jnp.asarray(offs0),
        jnp.asarray(offs1),
        jnp.asarray(col_offs0),
        jnp.asarray(sg_offs1),
        _as_real(masks0, rdt),
        _as_real(masks1, rdt),
    )


@functools.lru_cache(maxsize=32)
def _backward_column_kernel(core, mesh):
    """Fold one column's stacked subgrids into the facet-sharded
    per-column accumulator — all facet work is local (the subgrids are
    replicated; no collectives at all)."""

    def body(subgrids, sg_offs_arr, offs0, offs1, NAF_MNAFs):
        return _split_accumulate_fn(
            core, subgrids, sg_offs_arr, (offs0, offs1), NAF_MNAFs
        )

    mapped = _shard_map(
        _scoped("swiftly/bwd.column_pass", body),
        mesh=mesh,
        in_specs=(
            P(), P(), P(FACET_AXIS), P(FACET_AXIS), P(FACET_AXIS),
        ),
        out_specs=P(FACET_AXIS),
    )
    return jax.jit(mapped, donate_argnums=4)


def split_accumulate_sharded(
    core, mesh, subgrids, sg_offs_list, offs0, offs1, NAF_MNAFs
):
    """Mesh analogue of ``batched.split_accumulate_batch``: one dispatch
    folds a whole column of subgrids into its facet-sharded accumulator
    (donated)."""
    if isinstance(subgrids, (list, tuple)):
        subgrids = jnp.stack([core._prep(sg) for sg in subgrids])
    fn = _backward_column_kernel(core, mesh)
    return fn(
        subgrids,
        jnp.asarray(sg_offs_list),
        jnp.asarray(offs0),
        jnp.asarray(offs1),
        NAF_MNAFs,
    )


@functools.lru_cache(maxsize=32)
def _backward_all_kernel(core, mesh, facet_size: int):
    """The whole backward cover as ONE shard_map program.

    Subgrids arrive replicated; every facet-side op (extract, accumulate,
    finish) is local to the facet shard, so the program needs NO
    collectives — the facet stack materialises sharded (out_specs
    P(facet)). Mesh analogue of ``batched.backward_all_batch``.
    """

    def body(subgrids, sg_offs, offs0, offs1, masks0, masks1):
        F = offs0.shape[0]
        # scan carries must be tagged shard-varying up front: their
        # updates mix in the facet-sharded offsets/masks
        zeros_col = varying(
            jnp.zeros(
                (F, core.xM_yN_size, core.yN_size) + subgrids.shape[4:],
                dtype=subgrids.dtype,
            ),
            FACET_AXIS,
        )

        def one_column(MNAF_BMNAFs, xs):
            col_sgs, col_offs = xs
            NAF_MNAFs = _split_accumulate_fn(
                core, col_sgs, col_offs, (offs0, offs1), zeros_col
            )
            MNAF_BMNAFs = _accumulate_facet_fn(
                core, NAF_MNAFs, col_offs[0, 0], offs1, masks1, facet_size,
                MNAF_BMNAFs,
            )
            return MNAF_BMNAFs, None

        init = varying(
            jnp.zeros(
                (F, core.yN_size, facet_size) + subgrids.shape[4:],
                dtype=subgrids.dtype,
            ),
            FACET_AXIS,
        )
        MNAF_BMNAFs, _ = jax.lax.scan(one_column, init, (subgrids, sg_offs))
        return _finish_facets_fn(core, MNAF_BMNAFs, offs0, masks0, facet_size)

    mapped = _shard_map(
        _scoped("swiftly/bwd.fused_backward", body),
        mesh=mesh,
        in_specs=(
            P(), P(), P(FACET_AXIS), P(FACET_AXIS), P(FACET_AXIS),
            P(FACET_AXIS),
        ),
        out_specs=P(FACET_AXIS),
    )
    return jax.jit(mapped)


def backward_all_sharded(
    core, mesh, subgrids, sg_offs, offs0, offs1, masks0, masks1, facet_size
):
    """The full backward cover on the mesh: facets [F, yB, yB], one
    dispatch, zero collectives (facet work is shard-local).

    Same contract as ``batched.backward_all_batch``.
    """
    if isinstance(subgrids, (list, tuple)):
        subgrids = jnp.stack(
            [jnp.stack([core._prep(sg) for sg in col]) for col in subgrids]
        )
    fn = _backward_all_kernel(core, mesh, facet_size)
    rdt = core._Fb.dtype
    return fn(
        subgrids,
        jnp.asarray(np.asarray(sg_offs)),
        jnp.asarray(offs0),
        jnp.asarray(offs1),
        _as_real(masks0, rdt),
        _as_real(masks1, rdt),
    )
