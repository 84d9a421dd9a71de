"""Declarative cost model: one pricing of the streamed pipeline's stages.

Until this package existed, the same geometry was priced four ways —
`bench._plan_backward_passes` + the bench HBM sizers, the
`serve.scheduler` power-of-two buckets, `utils.spill.SpillCache`
budgeting, and the serve admission byte projections — each with its own
copy of the arithmetic (ROADMAP item 4). This module is the single
model those consumers now share: it takes ``(N, facet/subgrid geometry,
dtype, HBM budget, device count)`` as a `PlanInputs` and prices every
stage (facet prep, column groups, sampled fold, spill traffic, d2h/h2d,
serve batch shapes) as bytes + FLOPs + an estimated wall built from
`CostCoefficients` — static defaults, or per-stage throughputs refit
from measured artifact history by `plan.autotune`.

The FLOP formulas are NOT re-derived here: every stage count delegates
to `utils.flops` (the same functions the obs instrumentation attributes
with), so the model can never silently diverge from what the executors
report. Likewise the forward group sizing reuses the calibrated
`parallel.streamed` sizers through a geometry shim (`PlanInputs.base()`)
instead of forking their transient accounting. DaggerFFT
(arXiv 2601.12209) is the task-graph/cost-model framing; "Large-Scale
DFT on TPUs" (arXiv 2002.03260) is why the mesh layout must fall out of
the same model rather than a separate heuristic (see
`compiler.MeshLayout`).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, replace

import numpy as np

__all__ = [
    "CostCoefficients",
    "DEFAULT_FWD_MIN_BYTES",
    "DEFAULT_RESERVE_BYTES",
    "PlanInputs",
    "StageCost",
    "bucket_shape",
    "bucket_sizes",
    "hbm_budget_bytes",
    "price_collective_candidates",
    "price_collective_stage",
    "price_colpass_candidates",
    "projected_column_bytes",
    "projected_request_bytes",
]

# The backward planner's residency constants (what the forward's
# auto-sizers must be left, plus fold row-blocks + donation-copy slack).
# Measured on the 32k roundtrip (see bench.py r2 notes); ONE definition
# here, consumed by bench and the compiler alike.
DEFAULT_FWD_MIN_BYTES = 3.3e9
DEFAULT_RESERVE_BYTES = 1.2e9


def hbm_budget_bytes(headroom=0.0, device=None, default=None,
                     honor_env_on_cpu=True):
    """Per-device HBM budget in bytes — THE parser of SWIFTLY_HBM_BUDGET.

    ``None`` means unlimited (CPU / unknown device with no ``default``).
    Every call site that used to read the env var itself (bench.py's
    backward sizing, `parallel.streamed._hbm_budget`) now delegates
    here, so the env contract cannot fork again.

    :param headroom: caller-held resident bytes subtracted from the
        budget (e.g. `StreamedForward.hbm_headroom`)
    :param default: fallback bytes when the probe finds nothing on an
        accelerator (the streamed executors pass their historical 14e9;
        bench passes None — "unpartitioned")
    :param honor_env_on_cpu: bench semantics (True) apply an explicit
        SWIFTLY_HBM_BUDGET even on CPU — useful to exercise partitioned
        plans in CPU tests; the streamed executors (False) stay
        unlimited on CPU regardless, their historical behaviour.
    """
    env = os.environ.get("SWIFTLY_HBM_BUDGET")
    if env and honor_env_on_cpu:
        return float(env) - headroom
    try:
        import jax

        dev = device if device is not None else jax.devices()[0]
        platform = dev.platform
    except Exception:  # pragma: no cover - jax unavailable/uninitialised
        dev, platform = None, None
    if platform == "cpu":
        return None
    if env:
        return float(env) - headroom
    from ..utils.profiling import probe_hbm_bytes

    limit = probe_hbm_bytes(dev) if platform else None
    if limit is None:
        limit = default
    if limit is None:
        return None
    return limit - headroom


# ---------------------------------------------------------------------------
# Geometry
# ---------------------------------------------------------------------------


class _GeomCore:
    """The geometry surface of a backend core, detached from any backend
    state — just enough for `utils.flops` and the `parallel.streamed`
    sizers to price a plan without building facet data or touching a
    device."""

    def __init__(self, N, yN, xM, dtype_bytes, planar):
        self.N = int(N)
        self.yN_size = int(yN)
        self.xM_size = int(xM)
        self.xM_yN_size = int(xM) * int(yN) // int(N)
        self.backend = "planar" if planar else "jax"
        self.dtype = np.dtype(
            {4: np.float32, 8: np.float64}[int(dtype_bytes)]
            if planar
            else {4: np.complex64, 8: np.complex128}.get(
                int(dtype_bytes) // 2, np.complex64
            )
        )


class _GeomStack:
    def __init__(self, size, n):
        self.size = int(size)
        self.n_real = self.n_total = int(n)

    def __len__(self):
        return self.n_total


class _GeomConfig:
    def __init__(self, xA):
        self.max_subgrid_size = int(xA)


class _GeomBase:
    """Duck-typed `_StreamedBase` for the calibrated streamed sizers."""

    def __init__(self, core, stack, config):
        self.core = core
        self.stack = stack
        self.config = config
        self.mesh = None


@dataclass(frozen=True)
class PlanInputs:
    """Everything the plan compiler needs to price one cover.

    Geometry is the COVER's, not just the catalogue row's, so sparse /
    partial covers price what they actually run (`from_cover`).
    """

    N: int
    yB: int                      # padded facet size
    yN: int
    xA: int                      # subgrid size
    xM: int
    n_facets: int
    n_columns: int               # distinct subgrid column offsets
    subgrids_per_column: int
    dtype_bytes: int = 4
    planar: bool = True
    real_facets: bool = False
    hbm_budget: float | None = None   # per-device bytes; None = unlimited
    n_devices: int = 1
    fold_group: int = 2
    max_batch: int = 64               # serve coalescing cap
    config_name: str | None = None

    # -- constructors --------------------------------------------------------

    @classmethod
    def from_config(cls, name, **overrides):
        """Inputs for a full cover of one catalogue config."""
        from ..models import SWIFT_CONFIGS

        params = SWIFT_CONFIGS[name]
        N, yB = params["N"], params["yB_size"]
        xA = params["xA_size"]
        n_side = -(-N // xA)
        return cls(
            N=N, yB=yB, yN=params["yN_size"], xA=xA,
            xM=params["xM_size"],
            n_facets=(-(-N // yB)) ** 2,
            n_columns=n_side, subgrids_per_column=n_side,
            config_name=name,
            **overrides,
        )

    @classmethod
    def from_cover(cls, config, facet_configs, subgrid_configs,
                   **overrides):
        """Inputs priced from an ACTUAL cover (sparse/partial included)."""
        core = config.core
        n_cols = len({sg.off0 for sg in subgrid_configs})
        planar = core.backend == "planar"
        return cls(
            N=config.image_size, yB=facet_configs[0].size,
            yN=core.yN_size, xA=subgrid_configs[0].size,
            xM=core.xM_size,
            n_facets=len(facet_configs), n_columns=n_cols,
            subgrids_per_column=len(subgrid_configs) // n_cols,
            dtype_bytes=np.dtype(core.dtype).itemsize,
            planar=planar,
            **overrides,
        )

    def replace(self, **kw):
        return replace(self, **kw)

    # -- derived geometry ------------------------------------------------------

    @property
    def m(self):
        """Contribution rows per column (xM * yN / N)."""
        return self.xM * self.yN // self.N

    @property
    def per_el(self):
        """Bytes per grid element (planar keeps (re, im) planes)."""
        return self.dtype_bytes * (2 if self.planar else 1)

    @property
    def n_subgrids(self):
        return self.n_columns * self.subgrids_per_column

    @property
    def per_facet_acc_bytes(self):
        """One facet's whole [yB, yB] image accumulator."""
        return self.yB * self.yB * self.per_el

    @property
    def per_facet_row_bytes(self):
        """One facet's [m, yB] column-rows buffer."""
        return self.m * self.yB * self.per_el

    @property
    def stream_bytes(self):
        """The whole subgrid stream (what one spill fill persists)."""
        return self.n_subgrids * self.xA * self.xA * self.per_el

    @property
    def facet_stack_bytes(self):
        per = self.dtype_bytes if self.real_facets else self.per_el
        return self.n_facets * self.yB * self.yB * per

    def base(self):
        """Geometry shim the `parallel.streamed` sizers accept."""
        return _GeomBase(
            _GeomCore(self.N, self.yN, self.xM, self.dtype_bytes,
                      self.planar),
            _GeomStack(self.yB, self.n_facets),
            _GeomConfig(self.xA),
        )

    def inputs_hash(self):
        """Deterministic short hash of the pricing inputs (stamped into
        artifacts so two plans are comparable iff their hashes match)."""
        from ..obs.manifest import config_hash
        from dataclasses import asdict

        return config_hash(asdict(self))


# ---------------------------------------------------------------------------
# Serve batch shapes + admission byte projections
# ---------------------------------------------------------------------------


def bucket_shape(n):
    """Next power of two >= n — the serve compile-shape bucket (one
    definition; `serve.scheduler` delegates here)."""
    b = 1
    while b < n:
        b *= 2
    return b


def bucket_sizes(max_batch):
    """The distinct dispatch shapes bucket padding can produce under a
    ``max_batch`` cap: 1 (the single-request program) and every power
    of two up to the cap, with the cap itself as the largest shape."""
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(int(max_batch))
    return sizes


def _per_element_bytes(core):
    return np.dtype(core.dtype).itemsize * (
        2 if core.backend == "planar" else 1
    )


def projected_request_bytes(config):
    """Projected HBM bytes of one finished subgrid — the admission
    queue's per-request cost (moved here from `serve.service`; the
    service and `serve.fleet` both price from this one definition)."""
    return config.max_subgrid_size ** 2 * _per_element_bytes(config.core)


def projected_column_bytes(fwd):
    """Projected HBM bytes of one pending column's intermediates — the
    [F, m, yN] ``extract_columns_batch`` product the coalescing batcher
    materialises once per column program."""
    core = fwd.core
    return (
        len(fwd.stack) * core.xM_yN_size * core.yN_size
        * _per_element_bytes(core)
    )


# ---------------------------------------------------------------------------
# Stage pricing
# ---------------------------------------------------------------------------


@dataclass
class StageCost:
    """One stage's priced cost: FLOPs and/or bytes plus the wall the
    coefficients predict for it."""

    name: str
    flops: int = 0
    bytes_moved: int = 0
    dispatches: int = 0
    wall_s: float = 0.0

    def as_dict(self):
        out = {"wall_s": round(self.wall_s, 4)}
        if self.flops:
            out["flops"] = int(self.flops)
        if self.bytes_moved:
            out["bytes"] = int(self.bytes_moved)
        if self.dispatches:
            out["dispatches"] = int(self.dispatches)
        return out


# Default per-stage-family effective throughputs. DELIBERATELY coarse:
# they rank alternatives and give an order-of-magnitude wall; artifact
# blocks stamp ``coeffs_source: "default"`` so nothing downstream
# (bench_compare's mispricing flag) treats an uncalibrated prediction
# as a measured contract. The anchors come from an earlier v5e runtime
# that no longer exists (forward ~26% of the 65.7 TF/s f32-HIGHEST
# peak, backward fold 13.7%, ~0.1 s dispatch latency per chain); they
# are still to be measured on the chip tool's machine.
_DEFAULT_FLOPS_PER_S = {
    "fwd": 17e12,
    # the fused Pallas column pass targets >=30% of the 65.7 TF/s
    # f32-HIGHEST v5e peak (vs 18.1% measured for the einsum chain,
    # roofline_32k.jsonl) — coarse anchor until autotune refits the
    # exact stage name from a recorded pallas run
    "fwd.column_pass.pallas": 22e12,
    "bwd.column_pass": 9e12,
    "bwd.column_pass.pallas": 12e12,
    "bwd.sampled_fold": 9e12,
    "bwd": 9e12,
    # visibility degrid/grid: gather/scatter plus a [B, W, W]
    # contraction — VPU work with data-dependent addressing, nowhere
    # near MXU rates. Coarse anchors that RANK bucket candidates in
    # `plan.vis.price_vis`; the stages record attributed flops under
    # the same names, so `plan.autotune.refit` supersedes them from
    # the first recorded `bench.py --vis` artifact
    "vis.degrid": 2e12,
    "vis.grid": 1e12,
}
_DEFAULT_BYTES_PER_S = {
    "spill.h2d": 6e9,
    "spill.write": 3e9,
    "spill.read": 6e9,
    # hot-row L1 hits in the serve cache fabric: an in-process dict
    # probe plus one row memcpy-equivalent — far above the spill L2's
    # read path, which a miss falls through to (`plan.price_cache_tier`
    # ranks L1 size against it)
    "cache.l1": 20e9,
    # the feed-once/fold-many stage: wall BLOCKED on the shared feed
    # (cache read + h2d dispatch, after the async prefetch and the fold
    # overlap hide what they can) per cache-fed byte. Defaults to the
    # wire rate; the measured stage the executor records under the same
    # name refits it to the post-overlap effective rate
    "bwd.feed_group": 6e9,
    # per-link ICI ring bandwidth anchor (v5e ~45 GB/s effective);
    # coarse like every default — it ranks mesh plans, it is not a
    # contract (measured coefficients refit it like any other stage)
    "mesh.psum": 45e9,
    # the ppermute ring moves the same wire bytes over the same links
    # (XLA's all-reduce on a 1-D mesh IS a ring) — the ring schedule's
    # win is overlap, modelled as RING_OVERLAP_DISCOUNT below, not a
    # faster default rate. A measured mesh.ring_step coefficient (the
    # engine's stage timer records EXPOSED wall, overlap already
    # subtracted) replaces both the rate and the discount.
    "mesh.ring_step": 45e9,
}
_DEFAULT_DISPATCH_S = 0.1

# Fraction of the ring collective's raw wire wall hidden behind the next
# facet block's shard-local contraction and the next group's h2d staging
# fill (the engine stores one group BEHIND compute and the triple-buffer
# prefetch thread fills staging concurrently — mesh/engine._spill_store).
# A coarse default-pedigree anchor like the rates above: it RANKS the
# ring against the blocking psum; a refit mesh.ring_step rate (measured
# exposed wall) supersedes it (`price_collective_candidates` then prices
# with zero additional discount).
RING_OVERLAP_DISCOUNT = 0.6


@dataclass
class CostCoefficients:
    """Per-stage throughput coefficients the wall model divides by.

    ``source`` records pedigree: ``"default"`` (static anchors above),
    ``"measured"`` (refit from raw artifact telemetry by
    `plan.autotune.refit`) or ``"ledger"`` (fit from the accumulated
    ``plan_accuracy`` calibration history by
    `plan.autotune.refit_from_ledger`). The compiler only lets
    CALIBRATED coefficients (`calibrated` — measured or ledger) change
    plan parameters; defaults rank alternatives but the seed heuristics
    keep the choice, so seed-geometry plans stay provably equivalent to
    the pre-plan forks.
    """

    flops_per_s: dict = field(default_factory=dict)
    bytes_per_s: dict = field(default_factory=dict)
    dispatch_s: float = _DEFAULT_DISPATCH_S
    source: str = "default"
    n_records: int = 0
    platform: str | None = None
    # measured-best Pallas column-pass tile sizes from artifact history
    # ({"bm", "bn", "bk", "sblock"}, `plan.autotune.refit`) — None until
    # a recorded pallas run exists; surfaced by `scripts/plan_explain.py
    # --colpass` for export as SWIFTLY_COLPASS_BM/BN/BK/SBLOCK
    colpass_blocks: dict | None = None

    @property
    def calibrated(self):
        """Measurement-backed pedigree — what unlocks plan parameter
        selection in `compiler.compile_plan`."""
        return self.source in ("measured", "ledger")

    def flops_rate(self, stage):
        for key in (stage, stage.split(".")[0]):
            if key in self.flops_per_s:
                return self.flops_per_s[key]
            if key in _DEFAULT_FLOPS_PER_S:
                return _DEFAULT_FLOPS_PER_S[key]
        return _DEFAULT_FLOPS_PER_S["bwd"]

    def bytes_rate(self, stage):
        for key in (stage, stage.split(".")[0]):
            if key in self.bytes_per_s:
                return self.bytes_per_s[key]
            if key in _DEFAULT_BYTES_PER_S:
                return _DEFAULT_BYTES_PER_S[key]
        return _DEFAULT_BYTES_PER_S["spill.h2d"]

    def price(self, name, flops=0, bytes_moved=0, dispatches=0):
        wall = dispatches * self.dispatch_s
        if flops:
            wall += flops / self.flops_rate(name)
        if bytes_moved:
            wall += bytes_moved / self.bytes_rate(name)
        return StageCost(name, int(flops), int(bytes_moved),
                         int(dispatches), wall)


def price_forward(inputs, coeffs, colpass=None):
    """Stage costs of one streamed forward pass over the cover."""
    from ..utils.flops import (
        forward_sampled_flops,
        resolve_colpass,
        sampled_facet_pass_flops,
    )

    core = inputs.base().core
    if colpass is None:
        colpass = resolve_colpass(core, inputs.n_facets)
    total = forward_sampled_flops(
        core, n_facets=inputs.n_facets, facet_size=inputs.yB,
        n_columns=inputs.n_columns,
        subgrids_per_column=inputs.subgrids_per_column,
        subgrid_size=inputs.xA, real_facets=inputs.real_facets,
        colpass=colpass,
    )
    facet_pass = sampled_facet_pass_flops(
        core, inputs.n_facets, inputs.yB, inputs.n_columns * inputs.m,
        real_facets=inputs.real_facets,
    )
    col_stage = "fwd.column_pass" + (
        ".pallas" if colpass == "pallas" else ""
    )
    return [
        coeffs.price("fwd.sampled_facet_pass", flops=facet_pass),
        coeffs.price(col_stage, flops=total - facet_pass),
    ]


def price_colpass_candidates(inputs, coeffs):
    """Ranked forward column-pass candidates (einsum vs pallas).

    Prices ONLY the column-pass stage of each body (the facet pass is
    identical) with that body's exact FLOP shape and its own coefficient
    stage name — so a refit pallas coefficient prices the pallas row
    with measured pedigree while einsum keeps its own. Returns dicts
    sorted fastest-first; the executor's `resolve_colpass` keeps the
    CHOICE (defaults only rank, the compiler's measured-coefficients
    rule), the ranking is recorded in the artifact for the operator.
    """
    from ..utils.flops import (
        forward_sampled_flops,
        sampled_facet_pass_flops,
    )

    core = inputs.base().core
    facet_pass = sampled_facet_pass_flops(
        core, inputs.n_facets, inputs.yB, inputs.n_columns * inputs.m,
        real_facets=inputs.real_facets,
    )
    out = []
    for colpass in ("einsum", "pallas"):
        total = forward_sampled_flops(
            core, n_facets=inputs.n_facets, facet_size=inputs.yB,
            n_columns=inputs.n_columns,
            subgrids_per_column=inputs.subgrids_per_column,
            subgrid_size=inputs.xA, real_facets=inputs.real_facets,
            colpass=colpass,
        )
        stage = "fwd.column_pass" + (
            ".pallas" if colpass == "pallas" else ""
        )
        cost = coeffs.price(stage, flops=total - facet_pass)
        out.append({
            "colpass": colpass,
            "coeff_stage": stage,
            "flops": int(total - facet_pass),
            "flops_per_s": coeffs.flops_rate(stage),
            "predicted_wall_s": round(cost.wall_s, 4),
        })
    out.sort(key=lambda c: c["predicted_wall_s"])
    return out


def price_collective_stage(coeffs, collective, bytes_moved):
    """The planned facet-axis collective as one priced `StageCost`.

    ``psum`` prices the blocking all-reduce at the ``mesh.psum`` rate.
    ``ring`` prices the same wire bytes at the ``mesh.ring_step`` rate
    and — when that rate is still the default anchor — applies the
    `RING_OVERLAP_DISCOUNT` (the hidden-behind-compute fraction). A
    MEASURED mesh.ring_step coefficient already is the exposed rate
    (the engine's stage timer runs after the overlapped work), so no
    discount stacks on top of it.
    """
    stage = "mesh.ring_step" if collective == "ring" else "mesh.psum"
    cost = coeffs.price(stage, bytes_moved=bytes_moved)
    if collective == "ring" and stage not in coeffs.bytes_per_s:
        cost.wall_s *= 1.0 - RING_OVERLAP_DISCOUNT
    return cost


def price_collective_candidates(inputs, coeffs, mesh=None,
                                mode="roundtrip-streamed"):
    """Ranked facet-axis collective candidates (psum vs ring).

    The mesh analogue of `price_colpass_candidates`: each schedule is
    priced over the SAME layout's collective bytes with its own
    coefficient stage as pedigree. The ring row carries the schedule
    shape — 2(shards-1) `ppermute` steps of per-chunk bytes (the
    per-column buffer split `shards` ways) — and the overlap discount
    applied (0 when a measured mesh.ring_step rate prices the exposed
    wall directly). Returns dicts sorted fastest-first; like the
    colpass table, defaults only RANK — the executor's
    `resolve_collective` (env) and the compiler's calibrated-gate keep
    the choice.
    """
    if mesh is None:
        from .compiler import plan_mesh_layout

        mesh = plan_mesh_layout(inputs, mode=mode)
    shards = int(mesh.facet_shards)
    total = int(mesh.collective_bytes_total)
    if shards <= 1 or not total:
        return []
    steps = 2 * (shards - 1)
    per_column = int(mesh.collective_bytes_per_column)
    out = []
    for collective in ("psum", "ring"):
        stage = "mesh.ring_step" if collective == "ring" else "mesh.psum"
        measured = stage in coeffs.bytes_per_s
        cost = price_collective_stage(coeffs, collective, total)
        out.append({
            "collective": collective,
            "coeff_stage": stage,
            "bytes": total,
            "steps": 1 if collective == "psum" else steps,
            "chunk_bytes": (
                per_column if collective == "psum"
                else per_column // max(1, steps * shards)
            ),
            "overlap_discount": (
                0.0 if collective == "psum" or measured
                else RING_OVERLAP_DISCOUNT
            ),
            "bytes_per_s": coeffs.bytes_rate(stage),
            "predicted_wall_s": round(cost.wall_s, 4),
        })
    out.sort(key=lambda c: c["predicted_wall_s"])
    return out


def price_backward(inputs, parts, fold_group, coeffs,
                   spill_fed=True, colpass=None, feed_group=1):
    """Stage costs of a facet x row-slab partitioned sampled backward.

    Every pass consumes the whole subgrid stream — but under the
    feed-once/fold-many schedule ``feed_group`` passes SHARE each feed
    (`parallel.streamed.feed_backward_passes`), so the stream crosses
    the wire once per FEED, not once per pass. With ``spill_fed`` the
    feeds after the first read the recorded stream back host->device
    (the ``bwd.feed_group`` stage, priced by bytes); without a usable
    cache each later feed replays the forward instead — still once per
    feed, the schedule helps the replay model identically. Fold FLOPs
    restrict with the pass's output-row slab (the "ri" index
    restriction is free). ``feed_group=1`` reproduces the pre-schedule
    per-pass-feed pricing exactly.
    """
    from ..utils.flops import (
        bwd_column_pass_flops,
        bwd_fold_flops,
        resolve_colpass_bwd,
    )

    core = inputs.base().core
    if colpass is None:
        colpass = resolve_colpass_bwd(core, inputs.n_facets)
    col_flops = fold_flops = 0
    for i0, i1, r0, r1 in parts:
        F_pass = i1 - i0
        col_flops += inputs.n_columns * bwd_column_pass_flops(
            core, F_pass, inputs.subgrids_per_column, inputs.yB,
            inputs.xA, colpass,
        )
        fold_flops += int(
            bwd_fold_flops(core, F_pass, inputs.yB,
                           inputs.n_columns * inputs.m)
            * (r1 - r0) / inputs.yB
        )
    n_passes = len(parts)
    n_feeds = -(-n_passes // max(1, int(feed_group)))
    folds_per_pass = -(-inputs.n_columns // max(1, fold_group))
    bwd_col_stage = "bwd.column_pass" + (
        ".pallas" if colpass == "pallas" else ""
    )
    stages = [
        coeffs.price(bwd_col_stage, flops=col_flops,
                     dispatches=n_passes * folds_per_pass),
        coeffs.price("bwd.sampled_fold", flops=fold_flops,
                     dispatches=n_passes * folds_per_pass),
    ]
    if spill_fed and n_feeds > 1:
        stages.append(
            coeffs.price("spill.write",
                         bytes_moved=inputs.stream_bytes)
        )
        stages.append(
            coeffs.price("bwd.feed_group",
                         bytes_moved=(n_feeds - 1) * inputs.stream_bytes,
                         dispatches=n_feeds - 1)
        )
    elif n_feeds > 1:
        # replay cost model: feeds 2..n re-run the forward (aggregated
        # into one stage — the per-feed split adds nothing)
        replays = price_forward(inputs, coeffs)
        stages.append(
            StageCost(
                "fwd.replay",
                (n_feeds - 1) * sum(s.flops for s in replays),
                (n_feeds - 1) * sum(s.bytes_moved for s in replays),
                (n_feeds - 1) * sum(s.dispatches for s in replays),
                (n_feeds - 1) * sum(s.wall_s for s in replays),
            )
        )
    return stages
