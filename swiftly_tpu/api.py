"""Streaming forward/backward API.

`SwiftlyForward` streams subgrids out of a set of facets; `SwiftlyBackward`
streams subgrids in and accumulates facets. Both bound their working set:

* prepared facets (`BF_Fs`) are computed once and reused for every subgrid;
* per-column intermediates are cached/accumulated in an LRU keyed by the
  subgrid column offset `off0` — forward recomputes on miss, backward folds
  the evicted column into the per-facet accumulators;
* a flight queue caps the number of in-flight device computations
  (JAX dispatch is asynchronous; the queue blocks on the oldest result,
  which is the TPU equivalent of the reference's Dask
  `TaskQueue`/`distributed.wait` backpressure, api.py:466-522).

Subgrids may be produced/consumed in any order — every accumulation is a
sum of linear contributions (the shuffle-order test relies on this).

API parity: reference SwiftlyForward/SwiftlyBackward
(/root/reference/src/ska_sdp_exec_swiftly/api.py:217-463), re-designed for
single-program batched execution over stacked facets.
"""

from __future__ import annotations

import logging
from collections import deque

import jax
import numpy as np

from .models.config import FacetConfig, SubgridConfig, SwiftlyConfig
from .obs import metrics as _metrics
from .models.covers import (
    make_full_facet_cover,
    make_full_subgrid_cover,
    make_sparse_facet_cover,
    sparse_fov_cover_offsets,
)
from .ops.oracle import make_facet_from_sources, make_subgrid_from_sources
from .parallel import batched, sharded
from .parallel.mesh import mesh_size as _mesh_size, pad_to_shards

log = logging.getLogger("swiftly-tpu")

__all__ = [
    "FacetConfig",
    "SubgridConfig",
    "SwiftlyConfig",
    "SwiftlyForward",
    "SwiftlyBackward",
    "FlightQueue",
    "LRUCache",
    "backward_all",
    "check_facet",
    "check_residual",
    "check_subgrid",
    "last_dispatch_path",
    "make_facet",
    "make_real_facet",
    "make_full_facet_cover",
    "make_full_subgrid_cover",
    "make_sparse_facet_cover",
    "make_subgrid",
    "sparse_fov_cover_offsets",
]


# ---------------------------------------------------------------------------
# Oracle helpers (host-side)
# ---------------------------------------------------------------------------


def make_facet(image_size, facet_config, sources):
    """Build a facet's data from a source list (test/demo input)."""
    return make_facet_from_sources(
        sources,
        image_size,
        facet_config.size,
        [facet_config.off0, facet_config.off1],
        [facet_config.mask0, facet_config.mask1],
    )


def make_real_facet(image_size, facet_config, sources, dtype=None):
    """`make_facet` as a sparse-built real plane (f32 by default).

    == make_facet(...).real, built without the dense complex
    intermediate — the input path for large-N streamed drivers (one 64k
    facet is 8 GB complex but 2 GB as its real plane, and point-source
    facets are zeros plus a handful of mask-scaled pixels)."""
    from .ops.oracle import make_real_facet_plane_from_sources

    kwargs = {} if dtype is None else {"dtype": dtype}
    return make_real_facet_plane_from_sources(
        sources,
        image_size,
        facet_config.size,
        [facet_config.off0, facet_config.off1],
        [facet_config.mask0, facet_config.mask1],
        **kwargs,
    )


def make_sparse_facet(image_size, facet_config, sources, dtype=None):
    """`make_facet` as a `SparseRealFacet` descriptor (coords + values).

    The input path for streamed executors at 64k+ scale: the facet
    plane is synthesised ON DEVICE from these few pixels, so facet-slab
    streaming re-uploads kilobytes per column group instead of the
    multi-GB dense stack. `densify()` == `make_facet(...).real`."""
    from .ops.oracle import make_sparse_real_facet_from_sources

    kwargs = {} if dtype is None else {"dtype": dtype}
    return make_sparse_real_facet_from_sources(
        sources,
        image_size,
        facet_config.size,
        [facet_config.off0, facet_config.off1],
        [facet_config.mask0, facet_config.mask1],
        **kwargs,
    )


def make_subgrid(image_size, sg_config, sources):
    """Build a subgrid's data by direct DFT (test/demo input)."""
    return make_subgrid_from_sources(
        sources,
        image_size,
        sg_config.size,
        [sg_config.off0, sg_config.off1],
        [sg_config.mask0, sg_config.mask1],
    )


def check_facet(image_size, facet_config, approx_facet, sources):
    """RMS error of a computed facet vs the analytic source model."""
    facet = make_facet(image_size, facet_config, sources)
    return float(np.sqrt(np.mean(np.abs(facet - np.asarray(approx_facet)) ** 2)))


def check_subgrid(image_size, sg_config, approx_subgrid, sources):
    """RMS error of a computed subgrid vs the direct-DFT source model."""
    approx_subgrid = np.asarray(approx_subgrid)
    subgrid = make_subgrid_from_sources(
        sources,
        image_size,
        approx_subgrid.shape[0],
        [sg_config.off0, sg_config.off1],
        [sg_config.mask0, sg_config.mask1],
    )
    return float(np.sqrt(np.mean(np.abs(subgrid - approx_subgrid) ** 2)))


def check_residual(residual):
    """RMS of a residual array."""
    return float(np.sqrt(np.mean(np.abs(np.asarray(residual)) ** 2)))


# ---------------------------------------------------------------------------
# Working-set control
# ---------------------------------------------------------------------------


class LRUCache:
    """Small LRU: bounds the number of live column buffers.

    `set` returns the evicted (key, value) once capacity is exceeded —
    eviction is what triggers the backward fold step. Parity: reference
    LRUCache (api.py:525-590).

    Hit/miss counters (``<name>.hit`` / ``<name>.miss``, recorded only
    while metrics are enabled) make column-cache effectiveness visible
    in serve/bench telemetry — a serving workload whose column locality
    the scheduler fails to exploit shows up as a rising ``lru.miss``.
    """

    def __init__(self, capacity: int, name: str = "lru"):
        self.capacity = capacity
        self._store = {}  # insertion-ordered; order == recency
        self._hit_name = f"{name}.hit"
        self._miss_name = f"{name}.miss"

    def get(self, key):
        """Return the cached value and refresh its recency, or None."""
        if key not in self._store:
            if _metrics.enabled():
                _metrics.count(self._miss_name)
            return None
        if _metrics.enabled():
            _metrics.count(self._hit_name)
        value = self._store.pop(key)
        self._store[key] = value
        return value

    def keys(self):
        """Cached keys, oldest first (recency order) — the serving
        scheduler's column-locality signal."""
        return list(self._store)

    def set(self, key, value):
        """Insert/refresh; returns (evicted_key, evicted_value) or
        (None, None)."""
        self._store.pop(key, None)
        self._store[key] = value
        if len(self._store) <= self.capacity:
            return None, None
        oldest = next(iter(self._store))
        return oldest, self._store.pop(oldest)

    def pop_all(self):
        """Drain the cache oldest-first, yielding (key, value)."""
        while self._store:
            oldest = next(iter(self._store))
            yield oldest, self._store.pop(oldest)

    def __len__(self):
        return len(self._store)


class FlightQueue:
    """Bounds in-flight asynchronous device work, counted in LOGICAL
    TASKS (subgrids), not bytes.

    JAX dispatches computations asynchronously; unbounded dispatch can
    enqueue arbitrarily much device work and host memory. `admit` blocks on
    the oldest in-flight result once `depth` computations are outstanding —
    the streaming analogue of the reference's TaskQueue (api.py:466-522),
    whose unit is also a task. Batched/fused paths admit one slot per
    subgrid even when many subgrids share one program's output array, so
    `queue_size` keeps its meaning across execution paths; byte-level
    control is the sharding layout plus the streamed executors'
    HBM-budgeted group sizing (`col_group_for_budget`). Where
    `block_until_ready` returns early, the streamed paths use
    checksum-pull backpressure instead of this queue.
    """

    def __init__(self, depth: int):
        import os

        self.depth = depth
        # deque: the queue drains oldest-first on every admit past the
        # bound, and list.pop(0) is O(n) per pop — O(n^2) across a long
        # serving session's stream of admissions
        self._inflight = deque()
        # On runtimes whose block_until_ready returns before the dispatch
        # queue has drained (whether the chip's does is a measurement
        # still to be made), blocking is not backpressure. With
        # SWIFTLY_QUEUE_CHECKSUM=1 `_ready` instead PULLS one element of
        # each item to the host — a genuine device round trip that cannot
        # complete before the producing computation has, so the
        # queue-depth bound is real on such runtimes too (the streamed
        # executors' built-in checksum pipelines use the same trick
        # unconditionally).
        self._checksum = os.environ.get("SWIFTLY_QUEUE_CHECKSUM") == "1"

    def _ready(self, item):
        # Accumulators are donated to their successor computation; a
        # queued buffer may therefore already be deleted by the time we
        # would block on it — its successor in the queue covers it.
        deleted = getattr(item, "is_deleted", None)
        if deleted is not None and deleted():
            return
        if self._checksum and hasattr(item, "ndim"):
            np.asarray(item[(0,) * item.ndim])
            return
        if hasattr(item, "block_until_ready"):
            item.block_until_ready()

    def admit(self, arrays):
        """Register newly dispatched arrays, blocking if the queue is full."""
        if not isinstance(arrays, (list, tuple)):
            arrays = [arrays]
        self._inflight.extend(arrays)
        while len(self._inflight) > self.depth:
            self._ready(self._inflight.popleft())

    def drain(self):
        """Block until all in-flight work completes."""
        while self._inflight:
            self._ready(self._inflight.popleft())


# ---------------------------------------------------------------------------
# Facet stacking
# ---------------------------------------------------------------------------


class _FacetStack:
    """Stacked facet metadata: offsets and realised masks as arrays.

    When running on a mesh the stack is zero-padded to a multiple of the
    mesh size; padded entries have zero masks and contribute exact zeros
    to every (linear) accumulation.
    """

    def __init__(self, facet_configs, pad_to: int = 1):
        if not facet_configs:
            raise ValueError("At least one facet is required")
        sizes = {cfg.size for cfg in facet_configs}
        if len(sizes) != 1:
            raise ValueError("All facets must share one size")
        self.size = sizes.pop()
        self.configs = list(facet_configs)
        self.n_real = len(facet_configs)
        self.n_total = pad_to_shards(self.n_real, pad_to)
        n_pad = self.n_total - self.n_real

        def mask_row(mask):
            return np.ones(self.size) if mask is None else np.asarray(mask)

        zero_mask = np.zeros(self.size)
        self.offs0 = np.array([c.off0 for c in facet_configs] + [0] * n_pad)
        self.offs1 = np.array([c.off1 for c in facet_configs] + [0] * n_pad)
        self.masks0 = np.stack(
            [mask_row(c.mask0) for c in facet_configs] + [zero_mask] * n_pad
        )
        self.masks1 = np.stack(
            [mask_row(c.mask1) for c in facet_configs] + [zero_mask] * n_pad
        )

    def pad_data(self, stacked):
        """Zero-pad stacked per-facet data [n_real, ...] to [n_total, ...]."""
        if self.n_total == self.n_real:
            return stacked
        pad = np.zeros((self.n_total - self.n_real,) + stacked.shape[1:],
                       dtype=stacked.dtype)
        return np.concatenate([stacked, pad])

    def __len__(self):
        return self.n_total




def _place(core, mesh, arr, shard_facets: bool):
    """Device-place an array: facet-sharded over the mesh or replicated.

    With no mesh, returns the array unchanged (the batched kernels place
    it on the default device)."""
    if mesh is None:
        return arr
    import jax
    from .parallel.mesh import place_facet_sharded, replicated_sharding

    if np.iscomplexobj(arr):
        arr = core._prep(np.asarray(arr))
    if shard_facets:
        # multihost-safe: each process supplies only its facet shard
        return place_facet_sharded(arr, mesh)
    return jax.device_put(arr, replicated_sharding(mesh))


def _use_shard_map(config):
    return getattr(config, "spmd_mode", "shard_map") == "shard_map"


# Which execution path served the latest column-batched forward request.
# Silent degradation is the failure mode here: `get_subgrid_tasks` falls
# back to the per-subgrid loop on host backends, and a serving/bench run
# that quietly took the slow path produces numbers nobody can interpret.
# The fallback therefore warns ONCE per reason and the executed path is
# recorded (gauge `fwd.dispatch_path` + `last_dispatch_path()`) so run
# manifests can stamp how their requests were actually served.
_LAST_DISPATCH_PATH = None
_FALLBACK_WARNED = set()


def last_dispatch_path():
    """The path the most recent batched-forward call executed:
    ``"batched-column"``, ``"sharded-column"``, or the host
    ``"per-subgrid-loop"`` fallback (None before any call)."""
    return _LAST_DISPATCH_PATH


def _record_dispatch_path(path, fallback_reason=None):
    global _LAST_DISPATCH_PATH
    _LAST_DISPATCH_PATH = path
    if _metrics.enabled():
        _metrics.gauge("fwd.dispatch_path", path)
        _metrics.count(f"fwd.path.{path}")
    if fallback_reason and fallback_reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(fallback_reason)
        log.warning(
            "get_subgrid_tasks falling back to the per-subgrid loop "
            "(%s): column batching unavailable — O(subgrids) dispatches "
            "instead of O(columns)", fallback_reason,
        )
        _metrics.event(
            "fwd.path_fallback", path=path, reason=fallback_reason
        )


@jax.jit
def _unstack(stacked):
    """A column's stacked subgrids as per-subgrid arrays in ONE dispatch.

    Indexing the stack eagerly runs one program per subgrid, over every
    device of a mesh; on the XLA:CPU virtual mesh those tiny programs
    starve the column's all-reduce of pool threads until its rendezvous
    aborts (reproduced with tests/test_sharded.py under ``-n 6``)."""
    return tuple(stacked)


def _subgrid_masks(sg_config):
    size = sg_config.size
    m0 = np.ones(size) if sg_config.mask0 is None else np.asarray(sg_config.mask0)
    m1 = np.ones(size) if sg_config.mask1 is None else np.asarray(sg_config.mask1)
    return m0, m1


def _group_columns(subgrid_configs, key=lambda sg: sg, require_one_size=False):
    """Group items by subgrid column offset (off0), preserving order.

    :param key: maps an item to its SubgridConfig
    :param require_one_size: raise on mixed subgrid sizes (callers whose
        output is stacked cannot handle them); otherwise mixed sizes just
        make the grouping non-rectangular
    :return: (groups, rectangular) — groups is {off0: [item, ...]};
        rectangular is True when all subgrids share one size and all
        columns have equal length (the shape the fused whole-cover
        programs require).
    """
    groups = {}
    for item in subgrid_configs:  # may be any iterable, incl. a generator
        groups.setdefault(key(item).off0, []).append(item)
    if not groups:
        raise ValueError("At least one subgrid is required")
    sizes = {key(item).size for col in groups.values() for item in col}
    if require_one_size and len(sizes) != 1:
        raise ValueError(
            f"All subgrids must share one size for stacked output "
            f"(got sizes {sorted(sizes)})"
        )
    rectangular = (
        len(sizes) == 1 and len({len(v) for v in groups.values()}) == 1
    )
    return groups, rectangular


def _pad_ragged_columns(groups, size, make_pad=None):
    """Pad ragged columns ({off0: [(index, SubgridConfig), ...]}) to equal
    length with zero-mask entries (index None) appended at the end.

    Exact by construction: a zero mask zeroes a padded entry's output
    (forward), and zero data contributes zeros to every linear
    accumulation (backward). `make_pad(off0, first_config)` customises
    the padded item; the default appends (None, zero-mask config).
    """
    max_S = max(len(col) for col in groups.values())
    zero_mask = np.zeros(size)
    for off0, col in groups.items():
        first = col[0][1] if make_pad is None else None
        while len(col) < max_S:
            if make_pad is not None:
                col.append(make_pad(off0, col[0]))
            else:
                col.append(
                    (
                        None,
                        SubgridConfig(
                            off0, first.off1, size, zero_mask, zero_mask
                        ),
                    )
                )
    return max_S


# ---------------------------------------------------------------------------
# Forward: facets -> subgrids
# ---------------------------------------------------------------------------


class SwiftlyForward:
    """Stream subgrids out of a facet set.

    :param swiftly_config: SwiftlyConfig
    :param facet_tasks: list of (FacetConfig, facet_data) pairs
    :param lru_forward: number of column intermediates kept resident
    :param queue_size: in-flight computation cap
    """

    def __init__(self, swiftly_config, facet_tasks, lru_forward=1,
                 queue_size=20):
        self.config = swiftly_config
        self.core = swiftly_config.core
        self.mesh = getattr(swiftly_config, "mesh", None)
        self.stack = _FacetStack(
            [cfg for cfg, _ in facet_tasks], pad_to=_mesh_size(self.mesh)
        )
        self._facet_data = [data for _, data in facet_tasks]
        self._BF_Fs = None
        self._offs0 = _place(self.core, self.mesh, self.stack.offs0, True)
        self._offs1 = _place(self.core, self.mesh, self.stack.offs1, True)
        self.lru = LRUCache(lru_forward)
        self.queue = FlightQueue(queue_size)

    def adopt_facet_tasks(self, facet_tasks):
        """Swap in a new facet stack: drops the prepared facet planes
        and the column LRU, and rebuilds the stack descriptors, so
        every later subgrid computes from the new data. The serve
        path's update hook (`serve.SubgridService.post_facet_update`)
        calls this so its compute fallback — feed misses, evicted rows,
        stale feeds — never serves a superseded stack. Callables are
        materialised and sparse descriptors densified, matching the
        constructor's expectations."""
        data = []
        for _, d in facet_tasks:
            d = d() if callable(d) else d
            if hasattr(d, "densify"):
                d = d.densify()
            data.append(d)
        self.stack = _FacetStack(
            [cfg for cfg, _ in facet_tasks], pad_to=_mesh_size(self.mesh)
        )
        self._facet_data = data
        self._BF_Fs = None
        self._offs0 = _place(self.core, self.mesh, self.stack.offs0, True)
        self._offs1 = _place(self.core, self.mesh, self.stack.offs1, True)
        self.lru = LRUCache(self.lru.capacity)
        return self

    def _get_BF_Fs(self):
        if self._BF_Fs is None:
            with _metrics.stage("fwd.prepare_facets") as st:
                facets = self.stack.pad_data(
                    np.stack(
                        [
                            np.asarray(d, dtype=complex)
                            for d in self._facet_data
                        ]
                    )
                )
                st.bytes_moved = int(facets.nbytes)  # h2d upload volume
                facets = _place(self.core, self.mesh, facets, True)
                self._BF_Fs = batched.prepare_facets_batch(
                    self.core, facets, self._offs0
                )
        return self._BF_Fs

    def _get_columns(self, off0):
        cols = self.lru.get(off0)
        if cols is None:
            cols = batched.extract_columns_batch(
                self.core, self._get_BF_Fs(), off0, self._offs1
            )
            self.lru.set(off0, cols)
        return cols

    def get_subgrid_task(self, subgrid_config):
        """Compute one subgrid (asynchronous device array)."""
        cols = self._get_columns(subgrid_config.off0)
        if self.mesh is not None and _use_shard_map(self.config):
            subgrid = sharded.subgrid_from_columns_sharded(
                self.core,
                self.mesh,
                cols,
                self._offs0,
                self._offs1,
                subgrid_config.off0,
                subgrid_config.off1,
                subgrid_config.size,
                _subgrid_masks(subgrid_config),
            )
        else:
            subgrid = batched.subgrid_from_columns_batch(
                self.core,
                cols,
                self._offs0,
                self._offs1,
                subgrid_config.off0,
                subgrid_config.off1,
                subgrid_config.size,
                _subgrid_masks(subgrid_config),
            )
        self.queue.admit([subgrid])
        return subgrid

    def get_subgrid_tasks(self, subgrid_configs):
        """Compute many subgrids, one program per column.

        Groups the requests by column offset (off0) and computes each
        column's subgrids in a single batched program — same results as
        mapping `get_subgrid_task`, with far fewer dispatches. On a mesh
        the column program runs under shard_map with a single psum per
        column (or via GSPMD inference in "gspmd" mode). Returns the
        subgrids in input order.
        """
        if self.core.backend in ("numpy", "native"):
            _record_dispatch_path(
                "per-subgrid-loop",
                fallback_reason=f"backend={self.core.backend!r}",
            )
            return [self.get_subgrid_task(sg) for sg in subgrid_configs]
        _record_dispatch_path(
            "sharded-column"
            if self.mesh is not None and _use_shard_map(self.config)
            else "batched-column"
        )
        groups = {}  # (off0, size) -> list of input indices
        for i, sg in enumerate(subgrid_configs):
            groups.setdefault((sg.off0, sg.size), []).append(i)
        results = [None] * len(subgrid_configs)
        for (off0, size), idxs in groups.items():
            cols = self._get_columns(off0)
            sg_offs = [
                (subgrid_configs[i].off0, subgrid_configs[i].off1)
                for i in idxs
            ]
            masks = [_subgrid_masks(subgrid_configs[i]) for i in idxs]
            if self.mesh is not None and _use_shard_map(self.config):
                stacked = sharded.subgrids_from_columns_sharded(
                    self.core, self.mesh, cols, self._offs0, self._offs1,
                    sg_offs, size, masks,
                )
            else:
                stacked = batched.subgrids_from_columns_batch(
                    self.core, cols, self._offs0, self._offs1, sg_offs,
                    size, masks,
                )
            # One queue slot per subgrid, not per program: queue_size
            # keeps bounding in-flight *subgrids* regardless of batching.
            self.queue.admit([stacked] * len(idxs))
            for i, sg in zip(idxs, _unstack(stacked)):
                results[i] = sg
        return results

    def all_subgrids(self, subgrid_configs):
        """Every requested subgrid as ONE fused program.

        Returns a stacked device array [n, xA, xA(, 2)] in request order —
        a single XLA dispatch (scan over columns) and thus a single host
        sync for the entire forward transform; the latency-optimal path
        for remote-attached TPUs. On a mesh the fused program runs under
        shard_map with one psum per scanned column ("gspmd" mode lets XLA
        infer the same collectives). Irregular (ragged-column) covers
        stay on the fused path via exact zero-mask padding; only host
        backends fall back to per-column streaming. All subgrids must
        share one size (the output is stacked); raises ValueError
        otherwise.
        """
        subgrid_configs = list(subgrid_configs)
        groups, rectangular = _group_columns(
            enumerate(subgrid_configs),
            key=lambda item: item[1],
            require_one_size=True,
        )
        if self.core.backend in ("numpy", "native"):
            tasks = self.get_subgrid_tasks(subgrid_configs)
            return np.stack([np.asarray(t) for t in tasks])
        import jax.numpy as jnp

        size = subgrid_configs[0].size
        if not rectangular:
            # Ragged (sparse/irregular) cover: pad short columns with
            # zero-mask entries — exact (padded rows are computed then
            # discarded; their masks are all zero) and cheap, and it
            # keeps the whole cover a single fused dispatch.
            _pad_ragged_columns(groups, size)
        col_offs0 = list(groups)
        max_S = len(groups[col_offs0[0]])
        sg_offs1, masks0, masks1, rows = [], [], [], {}
        for c, off0 in enumerate(col_offs0):
            col = groups[off0]
            for s, (i, _) in enumerate(col):
                if i is not None:
                    rows[i] = c * max_S + s
            sg_offs1.append([sg.off1 for _, sg in col])
            ms = [_subgrid_masks(sg) for _, sg in col]
            masks0.append([m[0] for m in ms])
            masks1.append([m[1] for m in ms])
        fused_flops = 0
        if _metrics.enabled():
            from .utils.flops import forward_batched_flops

            fused_flops = forward_batched_flops(
                self.core,
                n_facets=self.stack.n_real,
                facet_size=self.stack.size,
                n_columns=len(col_offs0),
                subgrids_per_column=max_S,
                subgrid_size=size,
            )
            _metrics.count("fwd.subgrids", len(subgrid_configs))
        with _metrics.stage("fwd.fused_forward", flops=fused_flops):
            if self.mesh is not None and _use_shard_map(self.config):
                stacked = sharded.forward_all_sharded(
                    self.core, self.mesh, self._get_BF_Fs(), self._offs0,
                    self._offs1, col_offs0, sg_offs1, size, masks0, masks1,
                )
            else:
                stacked = batched.forward_all_batch(
                    self.core, self._get_BF_Fs(), self._offs0, self._offs1,
                    col_offs0, sg_offs1, size, masks0, masks1,
                )
        flat = stacked.reshape(
            (len(col_offs0) * max_S,) + stacked.shape[2:]
        )
        n = len(subgrid_configs)
        order = [rows[i] for i in range(n)]
        if order != list(range(n)):
            flat = jnp.take(flat, jnp.asarray(order), axis=0)
        elif flat.shape[0] != n:  # identity order but tail padding rows
            flat = flat[:n]
        # One queue slot per subgrid (not per program), like
        # get_subgrid_tasks: queue_size keeps bounding in-flight subgrids.
        self.queue.admit([flat] * len(subgrid_configs))
        return flat


# ---------------------------------------------------------------------------
# Backward: subgrids -> facets
# ---------------------------------------------------------------------------


class SwiftlyBackward:
    """Stream subgrids in; accumulate and finish facets.

    :param swiftly_config: SwiftlyConfig
    :param facets_config_list: FacetConfigs describing the output facets
    :param lru_backward: number of column accumulators kept live
    :param queue_size: in-flight computation cap
    """

    def __init__(self, swiftly_config, facets_config_list, lru_backward=1,
                 queue_size=20):
        self.config = swiftly_config
        self.core = swiftly_config.core
        self.mesh = getattr(swiftly_config, "mesh", None)
        self.stack = _FacetStack(
            facets_config_list, pad_to=_mesh_size(self.mesh)
        )
        self._offs0 = _place(self.core, self.mesh, self.stack.offs0, True)
        self._offs1 = _place(self.core, self.mesh, self.stack.offs1, True)
        self._masks0 = _place(self.core, self.mesh, self.stack.masks0, True)
        self._masks1 = _place(self.core, self.mesh, self.stack.masks1, True)
        self.lru = LRUCache(lru_backward)
        self.queue = FlightQueue(queue_size)
        self._MNAF_BMNAFs = None
        self._finished = False

    def _zeros(self, shape):
        core = self.core
        if core.backend in ("numpy", "native"):
            return np.zeros(shape, dtype=complex)
        import jax.numpy as jnp

        if core.backend == "planar":
            zeros = jnp.zeros(shape + (2,), dtype=core.dtype)
        else:
            zeros = jnp.zeros(shape, dtype=core.dtype)
        if self.mesh is not None:
            zeros = _place(core, self.mesh, zeros, True)
        return zeros

    def add_new_subgrid_task(self, subgrid_config, subgrid_data):
        """Fold one subgrid into the streaming accumulators."""
        if self._finished:
            raise RuntimeError("finish() was already called")
        core, stack = self.core, self.stack
        off0, off1 = subgrid_config.off0, subgrid_config.off1

        if self.mesh is not None and _use_shard_map(self.config):
            NAF_NAFs = sharded.split_subgrid_sharded(
                core, self.mesh, subgrid_data, off0, off1,
                self._offs0, self._offs1,
            )
        else:
            NAF_NAFs = batched.split_subgrid_batch(
                core, subgrid_data, off0, off1, self._offs0, self._offs1
            )

        col = self.lru.get(off0)
        if col is None:
            col = self._zeros(
                (len(stack), core.xM_yN_size, core.yN_size)
            )
        col = batched.accumulate_column_batch(core, NAF_NAFs, off1, col)

        evicted_off0, evicted = self.lru.set(off0, col)
        if evicted is not None:
            self._fold_column(evicted_off0, evicted)
        self.queue.admit([col])
        return col

    def add_new_subgrid_tasks(self, tasks):
        """Fold many (subgrid_config, subgrid_data) pairs, one program per
        column.

        Equivalent to mapping `add_new_subgrid_task`; groups the inputs by
        column offset (off0) and folds each group with a single scanned
        program. Accumulation is linear, so grouping does not change the
        result.
        """
        if self._finished:
            raise RuntimeError("finish() was already called")
        if self.core.backend in ("numpy", "native"):
            for sg_config, data in tasks:
                self.add_new_subgrid_task(sg_config, data)
            return
        core, stack = self.core, self.stack
        groups = {}
        for sg_config, data in tasks:
            groups.setdefault((sg_config.off0, sg_config.size), []).append(
                (sg_config, data)
            )
        for (off0, _size), group in groups.items():
            col = self.lru.get(off0)
            if col is None:
                col = self._zeros((len(stack), core.xM_yN_size, core.yN_size))
            subgrid_data = [d for _, d in group]
            sg_offs = [(sg.off0, sg.off1) for sg, _ in group]
            if self.mesh is not None and _use_shard_map(self.config):
                col = sharded.split_accumulate_sharded(
                    core, self.mesh, subgrid_data, sg_offs,
                    self._offs0, self._offs1, col,
                )
            else:
                col = batched.split_accumulate_batch(
                    core, subgrid_data, sg_offs, self._offs0, self._offs1,
                    col,
                )
            evicted_off0, evicted = self.lru.set(off0, col)
            if evicted is not None:
                self._fold_column(evicted_off0, evicted)
            self.queue.admit([col] * len(group))

    def _fold_column(self, off0, col):
        core, stack = self.core, self.stack
        if self._MNAF_BMNAFs is None:
            self._MNAF_BMNAFs = self._zeros(
                (len(stack), core.yN_size, stack.size)
            )
        self._MNAF_BMNAFs = batched.accumulate_facet_batch(
            core, col, off0, self._offs1, self._masks1, stack.size,
            self._MNAF_BMNAFs,
        )
        self.queue.admit([self._MNAF_BMNAFs])

    def finish(self):
        """Drain accumulators and return the finished facet stack
        [F, yB, yB]."""
        for off0, col in self.lru.pop_all():
            self._fold_column(off0, col)
        if self._MNAF_BMNAFs is None:
            self._MNAF_BMNAFs = self._zeros(
                (len(self.stack), self.core.yN_size, self.stack.size)
            )
        with _metrics.stage("bwd.finish"):
            facets = batched.finish_facets_batch(
                self.core,
                self._MNAF_BMNAFs,
                self._offs0,
                self._masks0,
                self.stack.size,
            )
            self.queue.drain()
        self._finished = True
        return facets[: self.stack.n_real]


def backward_all(swiftly_config, facet_configs, subgrid_tasks):
    """The full subgrid->facet transform as ONE fused program.

    :param subgrid_tasks: list of (SubgridConfig, subgrid_data) pairs
        covering the grid
    :return: finished facet stack [F, yB, yB(, 2)] matching facet_configs

    Single XLA dispatch (scan over subgrid columns); numerically identical
    to streaming the same subgrids through `SwiftlyBackward` (every
    accumulation is a sum of linear contributions). On a mesh the fused
    program runs under shard_map with facet-shard-local accumulation (no
    collectives; "gspmd" mode lets XLA infer the same). Ragged covers
    stay on the fused path via exact zero-data padding; mixed subgrid
    sizes and host backends fall back to the streaming path.
    """
    core = swiftly_config.core
    mesh = getattr(swiftly_config, "mesh", None)
    subgrid_tasks = list(subgrid_tasks)
    groups, rectangular = _group_columns(
        subgrid_tasks, key=lambda item: item[0]
    )
    sizes = {sg.size for sg, _ in subgrid_tasks}
    if len(sizes) != 1 or core.backend in ("numpy", "native"):
        bwd = SwiftlyBackward(swiftly_config, facet_configs)
        bwd.add_new_subgrid_tasks(subgrid_tasks)
        return bwd.finish()
    if not rectangular:
        # Ragged cover: pad short columns with zero-data subgrids —
        # exact, since every accumulation is linear in the subgrid data.
        size = sizes.pop()
        zero_data = np.zeros((size, size), dtype=complex)
        _pad_ragged_columns(
            groups, size,
            make_pad=lambda off0, first: (
                SubgridConfig(off0, first[0].off1, size), zero_data
            ),
        )

    stack = _FacetStack(facet_configs, pad_to=_mesh_size(mesh))
    # nested lists: the batch kernels prep and stack them themselves
    subgrids = [[d for _, d in groups[off0]] for off0 in groups]
    sg_offs = [
        [(sg.off0, sg.off1) for sg, _ in groups[off0]] for off0 in groups
    ]
    offs0 = _place(core, mesh, stack.offs0, True)
    offs1 = _place(core, mesh, stack.offs1, True)
    masks0 = _place(core, mesh, stack.masks0, True)
    masks1 = _place(core, mesh, stack.masks1, True)
    fused_flops = 0
    if _metrics.enabled():
        from .utils.flops import backward_batched_flops

        n_cols = len(groups)
        fused_flops = backward_batched_flops(
            core,
            n_facets=stack.n_real,
            facet_size=stack.size,
            n_columns=n_cols,
            subgrids_per_column=len(next(iter(groups.values()))),
            subgrid_size=subgrid_tasks[0][0].size,
        )
    with _metrics.stage("bwd.fused_backward", flops=fused_flops):
        if mesh is not None and _use_shard_map(swiftly_config):
            facets = sharded.backward_all_sharded(
                core, mesh, subgrids, sg_offs, offs0, offs1,
                masks0, masks1, stack.size,
            )
        else:
            facets = batched.backward_all_batch(
                core, subgrids, sg_offs, offs0, offs1, masks0, masks1,
                stack.size,
            )
    return facets[: stack.n_real]
