"""Benchmark: streaming facet->subgrid forward transform throughput.

Runs the full forward pass (every subgrid of the cover) for one or more
catalogue configurations on the available accelerator with the TPU-native
planar backend, checks RMS vs the direct-DFT oracle on sample subgrids,
and reports:

* wall-clock for the whole cover,
* vs_baseline — ratio against the numpy reference backend on the same
  machine (measured on small configs, sample-extrapolated on large ones —
  see `baseline_estimated`),
* tflops / mfu_pct — analytic FLOP count of the matmul-FFT pipeline
  (exact: every op is an einsum of known shape, `swiftly_tpu.utils.flops`)
  divided by wall-clock, and as % of the chip's published peak.

Prints ONE JSON line per configuration; the LAST line is the headline
metric (the north-star large-N config).

Environment knobs:
  BENCH_CONFIGS  comma-separated "name:mode" entries; modes:
                 batched | roundtrip | streamed | roundtrip-streamed
                 (default: 4k batched, 4k round-trip, 32k streamed,
                 32k round-trip-streamed, 64k streamed — headline last)
  BENCH_CONFIG / BENCH_MODE  legacy single-config override
  BENCH_COL_GROUP / BENCH_FACET_GROUP / BENCH_FOLD_GROUP  streamed-path
                 sizing overrides (default: HBM-budget auto)

Modes: "batched" keeps the prepared facet stack resident and runs the
whole cover as one fused program; "roundtrip" additionally feeds every
subgrid back through the fused backward transform and checks the facet
round-trip RMS (the reference demo's end-to-end shape); "streamed" uses
the sampled-DFT column groups with device-resident facets — or, when
the stack exceeds HBM (64k+ on a 16 GiB chip), facet-slab streaming
with exact cross-slab accumulation; "roundtrip-streamed" feeds the
streamed forward's device columns straight into the sampled-residency
backward (adjoint einsum) and verifies the reproduced facets on device.
Streamed accuracy is checked on >= max(100, 2%) oracle subgrids via
device-side residuals (n_rms_samples in the output records the count).
"""

import json
import logging
import os
import sys
import time
import traceback

import numpy as np

log = logging.getLogger("bench")


# Centre-relative source positions (fractions of N) for _bench_sources —
# module-level so the sparse-FoV rescale divisor derives from the SAME
# table (no hand-kept constant to go stale when the spread set changes).
_BENCH_SOURCE_FRACTIONS = [
    (-0.41, -0.37), (-0.23, 0.11), (-0.05, 0.43), (0.02, -0.19),
    (0.17, 0.31), (0.29, -0.45), (0.36, 0.07), (0.44, -0.02),
]


def _bench_source_radius():
    """Max centre-relative RADIUS of the spread source table — the
    sparse-FoV rescale divisor. Derived from the table itself so an
    edit to the fractions can never silently leave a stale divisor that
    lets corner sources escape the covered circle."""
    return max((a * a + b * b) ** 0.5 for a, b in _BENCH_SOURCE_FRACTIONS)


def _bench_sources(N):
    """Point sources SPREAD across the whole image (centre-relative,
    fractions of N), so every subgrid column band carries nontrivial
    signal and the oracle RMS check has power everywhere.

    A single source at the origin leaves far columns at ~1e-17 PSWF-tail
    amplitudes — which is how the r4 128k artifact failed to detect an
    int32 offset-scaling overflow that extracted half the cover's columns
    from the wrong window (see ops.core.scaled_offset).
    """
    return [
        (1.0 + 0.25 * k, int(a * N), int(b * N))
        for k, (a, b) in enumerate(_BENCH_SOURCE_FRACTIONS)
    ]


def _build(backend, params, dtype=None, streamed=False, sparse_fov=None):
    from swiftly_tpu import (
        SwiftlyConfig,
        SwiftlyForward,
        make_full_facet_cover,
        make_full_subgrid_cover,
        make_facet,
        make_sparse_facet_cover,
        sparse_fov_cover_offsets,
    )

    config = SwiftlyConfig(backend=backend, dtype=dtype, **params)
    if sparse_fov:
        # circular-FoV sparse facet cover (the reference's
        # demo_sparse_facet shape): facets exist only where the FoV
        # needs them; sources are scaled into the covered circle so the
        # sparse cover represents the whole sky model exactly
        fov_pixels = int(config.image_size * sparse_fov)
        offsets, masks = sparse_fov_cover_offsets(config, fov_pixels)
        facet_configs = make_sparse_facet_cover(
            config.max_facet_size, offsets, masks
        )
        lim_frac = max(
            sparse_fov / 2
            - config.max_facet_size / (2 * config.image_size),
            4 / config.image_size,
        )
        # rescale by the spread set's max RADIUS so every source lands
        # inside the circle of covered facet CENTRES — bounding
        # per-coordinate instead lets corner sources escape the cover
        # (reported as oracle RMS failures)
        rad = _bench_source_radius()
        sources = [
            (w, int(r * lim_frac / rad), int(c * lim_frac / rad))
            for (w, r, c) in _bench_sources(config.image_size)
        ]
    else:
        facet_configs = make_full_facet_cover(config)
        sources = _bench_sources(config.image_size)
    subgrid_configs = make_full_subgrid_cover(config)
    if streamed:
        from swiftly_tpu.parallel import StreamedForward

        # sparse facet descriptors: point-source facets are zeros plus a
        # few mask-scaled pixels, so hand the streamed executors the
        # pixels (densify() == make_facet(...).real, pinned by tests) —
        # the dense planes are then SYNTHESISED on device, so facet-slab
        # streaming uploads kilobytes per column group instead of the
        # multi-GB stack (how much that saves on the chip tool's h2d path
        # is a measurement still to be made).
        # BENCH_DENSE_FACETS=1 restores the dense host planes to measure
        # the upload-bound path.
        from swiftly_tpu import make_real_facet, make_sparse_facet

        rdt = np.float32 if dtype is None else np.dtype(dtype)
        if os.environ.get("BENCH_DENSE_FACETS"):
            facet_tasks = [
                (fc, (lambda fc=fc: make_real_facet(
                    config.image_size, fc, sources, dtype=rdt)))
                for fc in facet_configs
            ]
        else:
            facet_tasks = [
                (fc, make_sparse_facet(
                    config.image_size, fc, sources, dtype=rdt))
                for fc in facet_configs
            ]
        col_group = int(os.environ.get("BENCH_COL_GROUP", "0")) or None
        facet_group = int(os.environ.get("BENCH_FACET_GROUP", "0")) or None
        t0 = time.time()
        fwd = StreamedForward(
            config, facet_tasks, residency="device", col_group=col_group,
            facet_group=facet_group,
        )
        log.info("facet data built+laid out in %.1fs (real=%s)",
                 time.time() - t0, fwd._facets_real)
    else:
        facet_tasks = [
            (fc, make_facet(config.image_size, fc, sources))
            for fc in facet_configs
        ]
        fwd = SwiftlyForward(config, facet_tasks, lru_forward=2,
                             queue_size=64)
    return config, fwd, facet_configs, subgrid_configs, sources


def _oracle_sample_stack(config, subgrid_configs, sources, min_n=100,
                         target_pct=2.0, max_bytes=3e8):
    """Device-resident oracle subgrids for >= max(min_n, target_pct%) of
    the cover, spread evenly, + the index map.

    The accuracy check at 32k+ scale: residuals are computed ON DEVICE
    against these uploaded references (pulling subgrids to compare
    host-side would add d2h traffic to the timed pass; its cost on the
    chip tool's machine is still to be measured). The stack is capped
    at `max_bytes` residency: the uncapped 2% of the 128k cover was
    2.57 GiB of HBM, which alone forced the column-group search from
    G=2 down to the dispatch-bound G=1 plan (the r4 128k run's 10.1%
    MFU); 300 MB still spreads samples over every column band, and the
    multi-point-source model gives every band real signal to check."""
    import jax.numpy as jnp

    from swiftly_tpu import make_subgrid

    core0 = config.core
    sg_bytes = subgrid_configs[0].size ** 2 * (
        np.dtype(core0.dtype).itemsize
        * (2 if core0.backend == "planar" else 1)
    )
    n = len(subgrid_configs)
    n_s = min(n, max(min_n, int(n * target_pct / 100)))
    n_s = max(1, min(n_s, int(max_bytes // sg_bytes)))
    stride = max(1, n // n_s)
    idxs = list(range(0, n, stride))
    t0 = time.time()
    core = config.core
    host = []
    for i in idxs:
        ref = make_subgrid(config.image_size, subgrid_configs[i], sources)
        if core.backend == "planar":
            rdt = np.dtype(core.dtype)
            host.append(
                np.stack(
                    [ref.real.astype(rdt), ref.imag.astype(rdt)], axis=-1
                )
            )
        else:
            host.append(np.asarray(ref, dtype=core.dtype))
    stack = jnp.asarray(np.stack(host))
    log.info("oracle sample stack: %d subgrids (%.2f GiB) in %.1fs",
             len(idxs), stack.nbytes / 2**30, time.time() - t0)
    return {i: k for k, i in enumerate(idxs)}, stack


import functools


@functools.lru_cache(maxsize=None)
def _chunk_rms2_fn(Cr, yB):
    """Jitted per-row-chunk |dev - sparse_ref|^2 sum over facet ``f`` of
    a stack [n, yB, yB, 2]: synthesises the reference rows [j0, j0+Cr)
    by scattering the point-source pixels (out-of-chunk pixels drop),
    and reads the facet in place, so neither a full [yB, yB] reference
    plane nor a copy of the facet materialises next to the live
    accumulator. Cached so facet-partition passes share ONE compile."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(stack, f, r, c, v, j0):
        z = jnp.int32(0)
        chunk = jax.lax.dynamic_slice(
            stack, (f, j0, z, z), (1, Cr, yB, 2)
        )[0]
        # rows below the chunk must be remapped to a POSITIVE
        # out-of-bounds index: negative traced indices wrap numpy-style
        # (mode="drop" only discards past-the-end), which double-placed
        # every pixel into the following chunk
        rr = jnp.where((r >= j0) & (r < j0 + Cr), r - j0, Cr)
        ref = jnp.zeros((Cr, yB), chunk.dtype).at[rr, c].add(
            v, mode="drop"
        )
        res_re = chunk[..., 0] - ref
        res_im = chunk[..., 1]
        return jnp.sum(res_re * res_re + res_im * res_im)

    return fn


def _rms2_device(core, got, want):
    """Mean |residual|^2 of one subgrid/facet pair, on device."""
    import jax.numpy as jnp

    res = got - want
    if core.backend == "planar":
        return jnp.mean(jnp.sum(res * res, axis=-1))
    return jnp.mean(jnp.abs(res) ** 2)


def _is_oom(exc) -> bool:
    # one shared classifier (resilience.retry.is_oom) behind every OOM
    # ladder; imported lazily so `import bench` stays jax-free
    from swiftly_tpu.resilience.retry import is_oom

    return is_oom(exc)


def _shrink_streamed_plan(fwd, extra, fold_group=None) -> bool:
    """Halve the streamed working set after an on-chip OOM.

    Order: column group first (the dominant per-dispatch transient), then
    the backward fold group, then force facet-slab streaming. Returns
    False when nothing is left to shrink (the OOM then propagates).
    """
    plan = fwd.last_plan or {}
    G = plan.get("col_group") or 0
    shrunk = False
    if G > 1:
        fwd.col_group = max(1, G // 2)
        shrunk = True
    elif fold_group is not None and fold_group[0] > 1:
        fold_group[0] = max(1, fold_group[0] // 2)
        shrunk = True
    elif (
        plan.get("mode") == "resident" or not plan
    ) and fwd.facet_group != 1:
        # resident facets + minimum group still OOM — or the OOM fired
        # during the resident-stack upload itself, before any plan was
        # recorded: stream facet slabs instead
        for arr in fwd._dev_facets or ():
            arr.delete()
        fwd._dev_facets = None
        fwd.facet_group = 1
        shrunk = True
    if shrunk:
        extra["oom_retries"] = extra.get("oom_retries", 0) + 1
        extra["degraded_plan"] = {
            "col_group": fwd.col_group,
            "facet_group": fwd.facet_group,
            "fold_group": fold_group[0] if fold_group else None,
        }
    return shrunk


def _oom_soft(run, fwd, extra, fold_group=None, retries=2):
    """Run `run()`; on RESOURCE_EXHAUSTED shrink the plan and retry.

    An OOM must yield a slower number plus a warning in the JSON — never
    a dead benchmark (BENCH_r03 was rc=124 from exactly one such OOM).
    """
    import gc

    for attempt in range(retries + 1):
        try:
            return run()
        except Exception as e:
            if not _is_oom(e) or attempt >= retries:
                raise
            log.warning(
                "on-chip OOM (%s); shrinking streamed plan and retrying",
                type(e).__name__,
            )
            if not _shrink_streamed_plan(fwd, extra, fold_group):
                raise
            gc.collect()


def _plan_backward_passes(
    F_total, yB, per_facet_acc, per_facet_rows, fold_group, budget,
    fwd_min=3.3e9, reserve=1.2e9, n_facet_env=0, n_row_env=0,
):
    """Facet x output-row-slab partition plan for the sampled backward.

    Delegates to the unified plan compiler
    (`swiftly_tpu.plan.compiler.plan_backward_passes`, where the
    partition heuristic moved verbatim) — this wrapper keeps the
    historical bench entry point the 128k tests and operator docs name.
    Returns ``(parts, resident_bytes)`` exactly as before.
    """
    from swiftly_tpu.plan import plan_backward_passes

    return plan_backward_passes(
        F_total, yB, per_facet_acc, per_facet_rows, fold_group, budget,
        fwd_min=fwd_min, reserve=reserve,
        n_facet_env=n_facet_env, n_row_env=n_row_env,
    )


def _numpy_baseline_from_parts(params, sources, reps=3):
    """Extrapolate the numpy forward wall-clock from sampled sub-ops.

    At streamed-mode scales (32k+) a full numpy forward pass takes hours
    on one core, so time its three cost centres on small samples and
    scale linearly in op COUNTS (never in config size): facet preparation
    per column block, per-column extraction+preparation, and per-subgrid
    summation/finish.

    Each centre is warmed once (cold first calls carry FFT planning and
    allocator noise — the r4 estimates spread 4x run-to-run) and then
    timed `reps` times; returns ``(low, high)`` totals built from the
    per-centre min / median. Callers report the bracket and use its low
    end for vs_baseline (under-, never over-stating the speedup).
    """
    from swiftly_tpu import (
        SwiftlyConfig,
        make_facet,
        make_full_facet_cover,
        make_full_subgrid_cover,
    )
    from swiftly_tpu.ops import numpy_backend as npk
    from swiftly_tpu.ops.core import prepare_facet_math
    from swiftly_tpu.parallel import batched

    config = SwiftlyConfig(backend="numpy", **params)
    core = config.core
    fcs = make_full_facet_cover(config)
    sgs = make_full_subgrid_cover(config)
    n_facets, yB = len(fcs), fcs[0].size
    m, yN = core.xM_yN_size, core.yN_size
    col_offs0 = sorted({sg.off0 for sg in sgs})

    def sample(fn, scale):
        fn()  # warm: FFT plans, allocator, import side effects
        ts = []
        for _ in range(reps):
            t0 = time.time()
            fn()
            ts.append(time.time() - t0)
        ts.sort()
        return ts[0] * scale, ts[len(ts) // 2] * scale

    facet = make_facet(config.image_size, fcs[0], sources)
    blk = min(256, yB)
    prep_lo, prep_hi = sample(
        lambda: prepare_facet_math(
            npk, core._Fb, yN, facet[:, :blk], fcs[0].off0, 0
        ),
        (yB / blk) * n_facets,
    )

    BF_F = np.zeros((yN, yB), dtype=complex)

    def col_op():
        col = core.extract_from_facet(BF_F, col_offs0[0], 0)
        core.prepare_facet(col, fcs[0].off1, 1)

    col_lo, col_hi = sample(col_op, n_facets * len(col_offs0))

    NMBF_BFs = np.zeros((n_facets, m, yN), dtype=complex)
    offs0 = [fc.off0 for fc in fcs]
    offs1 = [fc.off1 for fc in fcs]
    sg = sgs[0]
    sg_lo, sg_hi = sample(
        lambda: batched.subgrid_from_columns_batch(
            core, NMBF_BFs, offs0, offs1, sg.off0, sg.off1, sg.size,
            (np.ones(sg.size), np.ones(sg.size)),
        ),
        len(sgs),
    )
    return prep_lo + col_lo + sg_lo, prep_hi + col_hi + sg_hi


# Coarse on-chip wall-clock guesses per size class, seconds — the
# projected-cost skip in main() only needs the ORDER OF MAGNITUDE
# (r4/r5 measured: 4k legs ~1-3 s + baseline, 32k streamed ~18 s,
# 32k round trip ~38 s, 64k round trip ~650 s + compiles). Roundtrips
# roughly double the leg; compiles/baselines are folded into the guess.
_LEG_COST_GUESS_S = {
    "1k": 30, "2k": 40, "4k": 60, "8k": 90, "16k": 120,
    "32k": 240, "64k": 900, "128k": 700,
}


def _leg_cost_guess_s(name, mode):
    """Projected wall for one leg (config size class x mode)."""
    base = _LEG_COST_GUESS_S.get(name.split("[")[0], 300)
    return base * (2 if "roundtrip" in mode else 1)


def _cover_kwargs(facet_configs, subgrid_configs):
    """The cover-shape arguments every flops-model call takes."""
    n_cols = len({sg.off0 for sg in subgrid_configs})
    return dict(
        n_facets=len(facet_configs),
        facet_size=facet_configs[0].size,
        n_columns=n_cols,
        subgrids_per_column=len(subgrid_configs) // n_cols,
        subgrid_size=subgrid_configs[0].size,
    )


def _flop_fields(config, facet_configs, subgrid_configs, mode, elapsed,
                 real_facets=False, finish_passes=1, colpass=None):
    """Analytic FLOP count -> tflops / mfu_pct fields.

    `colpass` is the column-pass body the forward executor actually ran
    (its `last_plan["colpass"]` — slab plans resolve from facet_group,
    not the full stack), so the FLOP shape matches the executed program.
    """
    from swiftly_tpu.utils.flops import (
        forward_batched_flops,
        forward_sampled_flops,
        peak_tflops,
    )

    from swiftly_tpu.utils.flops import backward_batched_flops

    core = config.core
    kwargs = _cover_kwargs(facet_configs, subgrid_configs)
    if mode == "streamed":
        flops = forward_sampled_flops(
            core, real_facets=real_facets, finish_passes=finish_passes,
            colpass=colpass, **kwargs,
        )
    elif mode == "roundtrip-streamed":
        from swiftly_tpu.utils.flops import backward_sampled_flops

        flops = forward_sampled_flops(
            core, real_facets=real_facets, finish_passes=finish_passes,
            colpass=colpass, **kwargs,
        ) + backward_sampled_flops(core, **kwargs)
    elif mode == "roundtrip":
        flops = forward_batched_flops(core, **kwargs) + backward_batched_flops(
            core, **kwargs
        )
    else:
        flops = forward_batched_flops(core, **kwargs)
    fields = {"tflops": round(flops / elapsed / 1e12, 2)}
    peak = peak_tflops()
    if peak:
        fields["mfu_pct"] = round(100 * flops / elapsed / 1e12 / peak, 1)
    return fields


def run_one(config_name, mode):
    import jax
    import jax.numpy as jnp

    from swiftly_tpu import SWIFT_CONFIGS, check_subgrid
    from swiftly_tpu.obs import Heartbeat, metrics
    from swiftly_tpu.obs import trace as otrace

    if metrics.enabled():
        metrics.reset()  # one telemetry export per configuration record
    # the leg's root span: everything below (build, warmup, timed pass,
    # baseline) nests under it, so trace_report's critical path covers
    # the whole leg wall. Entered/exited explicitly — the body is not
    # reindented under a `with` — and `leg_wall_s` brackets the span so
    # the artifact's trace block can be checked against it.
    otrace.adopt(0)  # legs are roots, even after a failed leg's leak
    leg_span = otrace.span("bench.leg", cat="bench",
                           config=config_name, mode=mode)
    t_leg0 = time.perf_counter()
    leg_span.__enter__()
    sparse_fov = None
    if mode.endswith("-sparse"):
        # circular-FoV sparse facet cover, composable with the streamed
        # modes (reference scripts/demo_sparse_facet.py:34-181)
        sparse_fov = float(os.environ.get("BENCH_SPARSE_FOV", "0.6"))
        mode = mode[: -len("-sparse")]
    if mode not in ("batched", "roundtrip", "streamed",
                    "roundtrip-streamed", "streamed-partial"):
        raise ValueError(
            f"Unknown bench mode {mode!r} (batched|roundtrip|streamed|"
            "roundtrip-streamed|streamed-partial[-sparse])"
        )

    def force(arr):
        """Force completion via an 8-byte checksum pull (see
        run_streamed; whether block_until_ready alone suffices on the
        chip is a measurement still to be made)."""
        return float(np.asarray(jnp.sum(arr)))

    params = dict(SWIFT_CONFIGS[config_name])
    params.setdefault("fov", 1.0)
    platform = jax.devices()[0].platform
    dtype = jax.numpy.float32

    # --- accelerated run (planar backend) --------------------------------
    streamed_mode = mode in (
        "streamed", "roundtrip-streamed", "streamed-partial"
    )
    config, fwd, facet_configs, subgrid_configs, sources = _build(
        "planar", params, dtype, streamed=streamed_mode,
        sparse_fov=sparse_fov,
    )
    extra = {}
    finish_passes = 1
    real_facets = getattr(fwd, "_facets_real", False)
    mode_label = mode if not sparse_fov else f"{mode}-sparse"
    partial_scale = None
    if sparse_fov:
        extra["sparse_cover"] = {
            "fov_fraction": sparse_fov,
            "n_facets": len(facet_configs),
            "n_facets_dense": (
                -(-config.image_size // config.max_facet_size)
            ) ** 2,
        }

    if mode == "streamed-partial":
        # measured PARTIAL cover: the first BENCH_PARTIAL_COLS subgrid
        # columns through the real full-size (e.g. yN=65536) programs —
        # the measured anchor for estimate_large_config's extrapolation
        # at scales (128k) where a full cover is hours of chip time.
        # Clearly labelled: `partial` records what fraction ran.
        all_offs = sorted({sg.off0 for sg in subgrid_configs})
        n_part = max(1, int(os.environ.get("BENCH_PARTIAL_COLS", "1")))
        n_part = min(n_part, len(all_offs))
        keep = set(all_offs[:n_part])
        n_subgrids_full = len(subgrid_configs)
        subgrid_configs = [sg for sg in subgrid_configs if sg.off0 in keep]
        if fwd.col_group is None:
            fwd.col_group = n_part
        extra["partial"] = {
            "n_columns": n_part,
            "n_columns_full": len(all_offs),
            "n_subgrids_full": n_subgrids_full,
        }
        partial_scale = len(all_offs) / n_part
        mode = "streamed"  # identical execution path from here on

    if mode == "streamed":
        import jax.numpy as jnp

        sample_map, oracle_dev = _oracle_sample_stack(
            config, subgrid_configs, sources
        )
        # the resident oracle stack shrinks the budget the auto-sizers see
        fwd.hbm_headroom = int(oracle_dev.nbytes)

        def run_streamed():
            """Full cover via sampled-DFT column groups; outputs consumed
            on device (device->host bandwidth is not part of the
            transform) and verified on device against the uploaded
            oracle samples.

            Completion is forced through a device-side checksum that
            depends on EVERY column's output, then one 8-byte pull —
            blocking on the last output alone under-reports on runtimes
            whose block_until_ready does not imply whole-queue completion
            (whether the chip's does is still to be measured). Records
            the dispatch-loop vs final-drain split (`stream_s` /
            `drain_s`) so artifacts separate streaming from the
            completion tail."""
            acc = None
            max_rms2 = jnp.zeros((), dtype=jnp.float32)
            t0 = time.time()
            hb = Heartbeat(
                len(subgrid_configs), label=f"{config_name} subgrids",
                interval_s=float(os.environ.get("BENCH_HEARTBEAT_S", "30")),
                log=log,
            )
            for items, out in fwd.stream_columns(
                subgrid_configs, device_arrays=True
            ):
                s = jnp.sum(out)
                acc = s if acc is None else acc + s
                for srow, (i, sgc) in enumerate(items):
                    k = sample_map.get(i)
                    if k is not None:
                        max_rms2 = jnp.maximum(
                            max_rms2,
                            _rms2_device(
                                config.core, out[srow], oracle_dev[k]
                            ),
                        )
                hb.update(len(items))
            hb.finish()
            t1 = time.time()
            float(np.asarray(acc))
            extra["stream_s"] = round(t1 - t0, 2)
            extra["drain_s"] = round(time.time() - t1, 2)
            return float(np.asarray(max_rms2)) ** 0.5

        log.info("streamed: warmup pass (compile + facet upload)")
        t0 = time.time()
        warm_rms = _oom_soft(run_streamed, fwd, extra)
        t_cold = time.time() - t0
        log.info("streamed: warmup done in %.1fs; timed pass", t_cold)
        max_cfg = float(os.environ.get("BENCH_MAX_CONFIG_S", "1800"))
        if os.environ.get("BENCH_SKIP_WARM_PASS") or t_cold > max_cfg:
            # report the cold pass (incl. compiles) rather than paying a
            # second full pass that would starve the configs after this
            # one; flagged honestly
            rms, elapsed = warm_rms, t_cold
            extra["includes_compile"] = True
        else:
            retries_before = extra.get("oom_retries", 0)
            t0 = time.time()
            rms = _oom_soft(run_streamed, fwd, extra)
            elapsed = time.time() - t0
            if extra.get("oom_retries", 0) > retries_before:
                # the timed pass OOM'd and re-ran a shrunk plan: the
                # number includes the failed attempt + its recompiles
                extra["includes_compile"] = True
        log.info("streamed: timed %.1fs", elapsed)
        extra["n_rms_samples"] = len(sample_map)
        extra["rms_sample_pct"] = round(
            100 * len(sample_map) / len(subgrid_configs), 2
        )
        plan = fwd.last_plan or {}
        extra["facets_real"] = fwd._facets_real
        extra["plan"] = plan
        # compiled-plan block for the forward leg too: the same model
        # prices what the executor's sizers chose, so plan coverage is
        # not limited to the roundtrip legs
        from swiftly_tpu.plan import PlanInputs, compile_plan
        from swiftly_tpu.plan import hbm_budget_bytes as _hbm_budget_env

        extra["plan_compiled"] = compile_plan(
            PlanInputs.from_cover(
                config, facet_configs, subgrid_configs,
                hbm_budget=_hbm_budget_env(),
                real_facets=fwd._facets_real,
            ),
            mode="streamed",
        ).artifact_block()
    elif mode == "roundtrip-streamed":
        import jax.numpy as jnp

        from swiftly_tpu.parallel import StreamedBackward

        fold_group = [int(os.environ.get("BENCH_FOLD_GROUP", "2"))]

        # the backward's image-space accumulator + its pending row buffer
        # share the chip with the forward: reserve them out of the budget
        # the forward's auto-sizers see (at 32k this tips the forward into
        # facet-slab streaming, which is the point — the accumulator is
        # the bigger resident and the facets re-stream around it)
        core = config.core
        yB = facet_configs[0].size
        per_el = np.dtype(core.dtype).itemsize * (
            2 if core.backend == "planar" else 1
        )
        F_total = len(facet_configs)
        per_facet_acc = yB * yB * per_el
        per_facet_rows = core.xM_yN_size * yB * per_el

        # Facet x row-slab partitioned backward: the 64k+ accumulator
        # (34 GiB at 64k) cannot fit 16 GiB HBM whole, and ONE 128k
        # facet's accumulator (16.2 GiB) is itself past HBM — but the
        # backward column pass and the adjoint fold both scale with the
        # facets (and the fold's output rows) in the program, so P
        # passes over facet subsets x row slabs do the SAME total
        # backward work. The subgrid stream every pass consumes is
        # persisted ONCE by the spill cache (utils.spill), so the
        # forward runs once and passes 2..P are cache-fed — before the
        # cache, each pass replayed the full forward (~8 x 73 s of the
        # 64k round trip's 703 s).
        from swiftly_tpu.plan import PlanInputs, compile_plan
        from swiftly_tpu.plan import hbm_budget_bytes as _hbm_budget_env
        from swiftly_tpu.plan.model import (
            DEFAULT_FWD_MIN_BYTES,
            DEFAULT_RESERVE_BYTES,
        )

        # the one SWIFTLY_HBM_BUDGET parse (plan.hbm_budget_bytes) —
        # bench used to read the env var next to the streamed
        # executors' own copy
        budget = _hbm_budget_env()
        fwd_min = DEFAULT_FWD_MIN_BYTES  # measured: the 32k roundtrip
        # fwd plan (G=3, slab_depth=2) streams green inside this
        reserve = DEFAULT_RESERVE_BYTES  # fold row-blocks +
        # donation-copy slack
        plan_inputs = PlanInputs.from_cover(
            config, facet_configs, subgrid_configs, hbm_budget=budget,
            real_facets=getattr(fwd, "_facets_real", False),
        )
        # measured-feedback autotune: BENCH_PLAN_HISTORY names artifact
        # globs whose per-stage telemetry refits the model's throughput
        # coefficients (plan.autotune); unset -> static defaults, and
        # the compiled plan is provably the old heuristics' plan
        plan_history = os.environ.get("BENCH_PLAN_HISTORY") or None
        plan_state = {"plan": None}

        def _make_plan():
            # re-planned per run: _oom_soft may have shrunk fold_group
            # (after an OOM the shrunk value is binding — history-based
            # reselection must not grow it back)
            cplan = compile_plan(
                plan_inputs.replace(fold_group=fold_group[0]),
                history=(
                    plan_history.split(",")
                    if plan_history and not extra.get("oom_retries")
                    else None
                ),
                fwd_min=fwd_min, reserve=reserve,
                n_facet_env=int(
                    os.environ.get("BENCH_BWD_FACET_PASSES", "0")
                ),
                n_row_env=int(
                    os.environ.get("BENCH_BWD_ROW_SLABS", "0")
                ),
                allow_spill=os.environ.get("BENCH_SPILL", "1") != "0",
                feed_env=int(
                    os.environ.get("BENCH_BWD_FEED_GROUP", "0")
                ),
            )
            fold_group[0] = cplan.backward.fold_group
            plan_state["plan"] = cplan
            extra["plan_compiled"] = cplan.artifact_block()
            return cplan.backward.parts, cplan.backward.resident_bytes

        def _verify_part(facets_dev, i0, i1, r0, r1):
            """Device-side RMS of reproduced facet (row-slab) [i0:i1) x
            [r0:r1) vs the round trip's own inputs; returns per-facet
            mean |res|^2 over the slab."""
            n = i1 - i0
            Rs = r1 - r0
            if fwd._dev_facets is not None and fwd._facets_real:
                ref = fwd._dev_facets[0]
                res_re = facets_dev[:n, :, :, 0] - ref[i0:i1, r0:r1]
                res_im = facets_dev[:n, :, :, 1]
                return jnp.mean(
                    res_re * res_re + res_im * res_im, axis=(1, 2)
                )
            if getattr(fwd, "_facets_sparse", False):
                # grouped sparse forward: synthesise each reference
                # plane on device (no multi-GB re-upload), in ROW CHUNKS
                # — at 64k the full [yB, yB] ref + residual transients
                # (~6 GiB) next to the live accumulator OOM'd the
                # verification step. Out-of-chunk pixels drop out of the
                # scatter (mode="drop"); each chunk's scalar is pulled
                # before the next dispatch (async dispatch would put all
                # chunks' transients live at once). Row slabs reuse the
                # same program with slab-shifted pixel rows (off-slab
                # rows land outside [0, Rs) and drop).
                yB_full = facets_dev.shape[2]
                n_ch = max(1, int(Rs * yB_full * 12 / 1.2e9))
                while Rs % n_ch:
                    n_ch += 1
                Cr = Rs // n_ch
                chunk_rms2 = _chunk_rms2_fn(Cr, yB_full)
                rms2s = []
                for i in range(i0, i1):
                    _, r, c, v = fwd._sparse_pixels(i, i + 1)
                    r = (r - r0).astype(np.int32)  # slab-relative rows
                    total = 0.0
                    for ci in range(n_ch):
                        total += float(
                            np.asarray(
                                chunk_rms2(
                                    facets_dev, jnp.int32(i - i0), r, c,
                                    v, jnp.int32(ci * Cr),
                                )
                            )
                        )
                    rms2s.append(total / (Rs * yB_full))
                return jnp.asarray(rms2s)
            # re-upload per-facet references (grouped forward or
            # complex facets: no resident copy to compare against)
            rms2s = []
            for i in range(i0, i1):
                host_ref = (
                    fwd._facet_data[i]
                    if not fwd._facets_real
                    else np.stack(
                        [fwd._facet_data[i],
                         np.zeros_like(fwd._facet_data[i])],
                        axis=-1,
                    )
                )
                ref = jnp.asarray(host_ref[r0:r1])
                rms2s.append(
                    _rms2_device(config.core, facets_dev[i - i0], ref)
                )
            return jnp.stack(rms2s)

        def run_roundtrip_streamed():
            """StreamedForward -> sampled-residency StreamedBackward,
            entirely on device: forward columns feed the backward's
            adjoint-einsum accumulator, the finished facets are compared
            on device with the round trip's own input facets, and one
            scalar pull forces completion of the whole graph. When the
            accumulator exceeds HBM the backward runs in facet-subset x
            row-slab passes (same total backward work); the subgrid
            stream is persisted ONCE by the spill cache and the passes
            run under the plan's FEED-ONCE/FOLD-MANY schedule
            (`feed_backward_passes`): `feed_group` passes share each
            pass over the stream, so the whole partitioned round trip
            costs 1 forward + (n_feeds - 1) cache-fed feeds instead of
            1 + (n_passes - 1) (counter-asserted via `fwd.passes`; the
            h2d collapse shows in `spill.h2d` bytes). A stream too
            large for the cache budget falls back to forward replay per
            FEED — exact, and the schedule shrinks even that cost."""
            from swiftly_tpu.parallel import feed_backward_passes

            parts, resident = _make_plan()
            cplan = plan_state["plan"]
            feed_q = min(cplan.backward.feed_group, len(parts))
            # the feed's shared accumulators all sit on the chip during
            # the fill feed: the forward's sizers must leave room for
            # every pass in the largest feed chunk, not just one
            fwd.hbm_headroom = int(feed_q * resident + reserve)
            extra["bwd_plan"] = {
                "n_passes": len(parts),
                "n_facet_passes": len({(p[0], p[1]) for p in parts}),
                "n_row_slabs": len({(p[2], p[3]) for p in parts}),
                "feed_group": feed_q,
                "n_feeds": cplan.backward.n_feeds,
            }
            # the spill policy (cache budget, RAM/disk/replay) is the
            # compiled plan's third output — SpillCache no longer prices
            # the stream for itself on this path
            spill = (
                cplan.spill.make_cache() if cplan.spill.use_spill
                else None
            )
            passes0 = feeds0 = h2d0 = 0
            if metrics.enabled():
                exp0 = metrics.export()
                passes0 = (exp0.get("counters") or {}).get(
                    "fwd.passes", 0
                )
                feeds0 = (exp0.get("counters") or {}).get(
                    "bwd.feed_groups", 0
                )
                h2d0 = (
                    (exp0.get("stages") or {}).get("spill.h2d") or {}
                ).get("bytes", 0)
            max_rms2 = 0.0
            extra["pass_s"] = []
            hb = Heartbeat(
                len(subgrid_configs) * len(parts),
                label=f"{config_name} roundtrip subgrids",
                interval_s=float(os.environ.get("BENCH_HEARTBEAT_S", "30")),
                log=log,
            )
            from swiftly_tpu.obs import trace as otrace

            chunks = [
                parts[c0 : c0 + feed_q]
                for c0 in range(0, len(parts), feed_q)
            ]
            for kfeed, chunk in enumerate(chunks):
                t_pass = time.time()
                # the hierarchy's pass level: leg → PASS (one shared
                # feed of feed_group facet x row-slab parts) → feed
                # group → column group → stage
                pass_span = otrace.span(
                    "bwd.pass", cat="bench", feed=kfeed,
                    parts=[list(p) for p in chunk],
                )
                pass_span.__enter__()
                bwds = [
                    StreamedBackward(
                        config, list(facet_configs[i0:i1]),
                        residency="sampled", fold_group=fold_group[0],
                        row_slab=(
                            (r0, r1) if (r0, r1) != (0, yB) else None
                        ),
                    )
                    for i0, i1, r0, r1 in chunk
                ]
                # feed-once/fold-many: ONE pass over the (cached)
                # stream serves every backward in the chunk — group
                # feeding inside (one vmapped column pass + one fold
                # per forward column group per pass); feed 1 records
                # the stream, later feeds are cache-fed
                feed_backward_passes(
                    fwd, subgrid_configs, bwds, spill=spill,
                    progress=hb.update, feed_index=kfeed,
                )
                for bwd, (i0, i1, r0, r1) in zip(bwds, chunk):
                    facets_dev = bwd.finish_device()
                    rms2 = _verify_part(facets_dev, i0, i1, r0, r1)
                    max_rms2 = max(
                        max_rms2, float(np.asarray(jnp.max(rms2)))
                    )
                    del facets_dev
                del bwds
                pass_span.__exit__(None, None, None)
                extra["pass_s"].append(round(time.time() - t_pass, 1))
                if len(chunks) > 1:
                    log.info(
                        "roundtrip feed %d/%d (%d pass(es)) done",
                        kfeed + 1, len(chunks), len(chunk),
                    )
            if spill is not None:
                extra["spill"] = spill.stats()
            if metrics.enabled():
                exp1 = metrics.export()
                extra["forward_passes"] = (
                    exp1.get("counters") or {}
                ).get("fwd.passes", 0) - passes0
                # this run's feed-schedule execution, as deltas (the
                # warmup run shares the registry): feeds run and the
                # cache-fed h2d bytes the schedule actually moved
                extra["feed_groups"] = (
                    exp1.get("counters") or {}
                ).get("bwd.feed_groups", 0) - feeds0
                extra["spill_h2d_bytes"] = (
                    (exp1.get("stages") or {}).get("spill.h2d") or {}
                ).get("bytes", 0) - h2d0
            return max_rms2 ** 0.5

        t0 = time.time()
        warm_rms = _oom_soft(
            run_roundtrip_streamed, fwd, extra, fold_group
        )  # warmup: compile both directions
        t_cold = time.time() - t0
        max_cfg = float(os.environ.get("BENCH_MAX_CONFIG_S", "1800"))
        if os.environ.get("BENCH_SKIP_WARM_PASS") or t_cold > max_cfg:
            rms, elapsed = warm_rms, t_cold
            extra["includes_compile"] = True
        else:
            retries_before = extra.get("oom_retries", 0)
            t0 = time.time()
            rms = _oom_soft(
                run_roundtrip_streamed, fwd, extra, fold_group
            )
            elapsed = time.time() - t0
            if extra.get("oom_retries", 0) > retries_before:
                extra["includes_compile"] = True
        extra["n_rms_samples"] = len(facet_configs)
        extra["rms_check"] = "all facets, device-side vs input facets"
        extra["facets_real"] = fwd._facets_real
        extra["fold_group"] = fold_group[0]
        plan = fwd.last_plan or {}
        extra["plan"] = plan
    elif mode == "roundtrip":
        from swiftly_tpu import backward_all, check_facet

        def run_roundtrip():
            subgrids = fwd.all_subgrids(subgrid_configs)
            facets = backward_all(
                config, facet_configs,
                [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)],
            )
            force(facets)
            return facets

        run_roundtrip()  # warmup: compile both fused programs
        t0 = time.time()
        facets = run_roundtrip()
        elapsed = time.time() - t0
        rms = max(
            check_facet(
                config.image_size, fc,
                config.core.as_complex(np.asarray(facets[i])), sources,
            )
            for i, fc in enumerate(facet_configs)
        )
    else:
        # Warmup: compile + run the fused whole-cover program once
        force(fwd.all_subgrids(subgrid_configs))

        # Timed: ONE dispatch (fused scan over columns), ONE host sync —
        # the transform's real device wall-clock, not per-subgrid dispatch
        # latency.
        t0 = time.time()
        results = fwd.all_subgrids(subgrid_configs)
        force(results)
        elapsed = time.time() - t0

        # RMS vs oracle on a few sample subgrids
        rms = max(
            check_subgrid(
                config.image_size, sg, config.core.as_complex(results[i]),
                sources,
            )
            for i, sg in list(enumerate(subgrid_configs))[
                :: max(1, len(subgrid_configs) // 4)
            ]
        )

    # --- numpy reference baseline ----------------------------------------
    log.info("numpy baseline measurement")
    baseline_estimated = streamed_mode
    env_baseline = os.environ.get("BENCH_NUMPY_BASELINE_S")
    if baseline_estimated and env_baseline:
        baseline_source = "operator"
    elif baseline_estimated:
        baseline_source = "estimated"
    else:
        baseline_source = "measured"
    def _estimator_scale():
        """The mode/cover rescale the parts estimator needs to compare
        like with like (shared by the estimated path and the operator-
        supplied provenance check)."""
        scale = 1.0
        if sparse_fov:
            # the parts estimator times the DENSE facet cover; every
            # cost centre scales ~linearly with facet count, so rescale
            # to the sparse cover's
            sc = extra["sparse_cover"]
            scale *= sc["n_facets"] / sc["n_facets_dense"]
        if partial_scale:
            # compare like with like: the numpy estimate covers the full
            # cover, the measured run only 1/partial_scale of its columns
            scale /= partial_scale
        if mode == "roundtrip-streamed":
            # extrapolate the backward leg by the analytic FLOP ratio of
            # the two directions (their op sequences are duals with the
            # same matmul-FFT shapes); flagged baseline_estimated
            from swiftly_tpu.utils.flops import (
                backward_batched_flops as _bb,
                forward_batched_flops as _fb,
            )

            kw = _cover_kwargs(facet_configs, subgrid_configs)
            core = config.core
            scale *= 1.0 + _bb(core, **kw) / _fb(core, **kw)
        return scale

    if baseline_estimated and env_baseline:
        # operator-supplied (e.g. from a prior run of the same config).
        # Provenance is ENFORCED at record time: the estimator bracket
        # is measured anyway (minutes of host time at 64k — the price
        # of an auditable artifact) and recorded NEXT TO the operator
        # figure; a >1.5x disagreement with the bracket warns loudly
        # and stamps `baseline_disagreement` (round-5 flagship
        # artifacts carried hand-typed 600.0/7000.0 baselines ~3.6x off
        # the same round's rehearsal — structurally silent until here).
        numpy_total = float(env_baseline)
        if partial_scale:
            # the supplied figure covers the full cover; the measured
            # run only 1/partial_scale of its columns
            numpy_total /= partial_scale
        try:
            est_lo, est_hi = _numpy_baseline_from_parts(params, sources)
        except Exception:
            log.warning(
                "estimator bracket failed; operator baseline recorded "
                "UNCHECKED", exc_info=True,
            )
        else:
            scale = _estimator_scale()
            est_lo *= scale
            est_hi *= scale
            extra["numpy_baseline_bracket_s"] = [
                round(est_lo, 2), round(est_hi, 2)
            ]
            if numpy_total < est_lo / 1.5 or numpy_total > est_hi * 1.5:
                factor = max(
                    est_lo / max(numpy_total, 1e-9),
                    numpy_total / max(est_hi, 1e-9),
                )
                extra["baseline_disagreement"] = round(factor, 2)
                log.warning(
                    "operator-supplied numpy baseline %.1f s disagrees "
                    "%.1fx with the measured estimator bracket "
                    "[%.1f, %.1f] s — recording both; vs_baseline uses "
                    "the OPERATOR figure, audit it against the bracket",
                    numpy_total, factor, est_lo, est_hi,
                )
    elif baseline_estimated:
        numpy_total, numpy_hi = _numpy_baseline_from_parts(params, sources)
        scale = _estimator_scale()
        numpy_total *= scale
        numpy_hi *= scale
        # vs_baseline uses the LOW end (min-of-reps): under-, never
        # over-states the speedup; the bracket records the spread
        extra["numpy_baseline_bracket_s"] = [
            round(numpy_total, 2), round(numpy_hi, 2)
        ]
    else:
        # Warm one subgrid first so the one-time facet preparation is
        # excluded from the sample, as the planar run's warmup does. Then
        # time ONE FULL FRESH COLUMN: its first subgrid pays the column
        # extraction, the rest share it — the same amortisation the real
        # full-cover run has, so per-subgrid cost is estimated fairly
        # (sampling consecutive subgrids of an already-warm column would
        # exclude extraction entirely; sampling one subgrid per column
        # would charge it S times over).
        cfg_np, fwd_np, fc_np, sg_np, _ = _build("numpy", params)
        fwd_np.get_subgrid_task(sg_np[0])
        col1 = [sg for sg in sg_np if sg.off0 != sg_np[0].off0]
        if col1:
            column = [sg for sg in col1 if sg.off0 == col1[0].off0]
        else:
            # single-column cover: reuse the (already warm) only column —
            # extraction cost is then excluded, a conservative estimate
            column = sg_np[1:] or sg_np
        t0 = time.time()
        tasks_np = [(sg, fwd_np.get_subgrid_task(sg)) for sg in column]
        numpy_total = (time.time() - t0) / len(column) * len(sg_np)
        if mode == "roundtrip":
            from swiftly_tpu import SwiftlyBackward

            n_cols = len({sg.off0 for sg in sg_np})
            bwd_np = SwiftlyBackward(cfg_np, fc_np)
            t0 = time.time()
            bwd_np.add_new_subgrid_tasks(tasks_np)
            numpy_total += (time.time() - t0) / len(column) * len(sg_np)
            # finish() = ONE column fold (a full cover pays K of those)
            # + the final per-facet finishes (paid once); isolate the
            # fold by timing an empty finish (identical final shapes)
            t0 = time.time()
            bwd_np.finish()
            t_fin = time.time() - t0
            bwd_empty = SwiftlyBackward(cfg_np, fc_np)
            t0 = time.time()
            bwd_empty.finish()
            t_fin_empty = time.time() - t0
            t_fold = max(0.0, t_fin - t_fin_empty)
            numpy_total += t_fold * n_cols + t_fin_empty

    leg_span.__exit__(None, None, None)
    leg_wall_s = time.perf_counter() - t_leg0
    if "plan_compiled" in extra:
        # close the loop: the stamped plan carries predicted vs MEASURED
        # wall, which is what bench_compare's mispricing flag and the
        # autotune history read back (sig-fig rounding — a decimal
        # round zeroed sub-0.1 ms smoke legs and dropped the ratio)
        from swiftly_tpu.plan import stamp_measured_wall

        stamp_measured_wall(extra["plan_compiled"], elapsed)
    direction = (
        "forward+backward round-trip"
        if mode in ("roundtrip", "roundtrip-streamed")
        else "forward facet->subgrid"
    )
    if partial_scale:
        extra["extrapolated_full_cover_s"] = round(
            elapsed * partial_scale, 1
        )
    if streamed_mode:
        from swiftly_tpu.utils.profiling import probe_hbm_bytes

        probed = probe_hbm_bytes()
        if probed:
            extra["hbm_probe_gib"] = round(probed / 2**30, 2)
    from swiftly_tpu.obs import run_manifest

    result = {
        "metric": f"{config_name} {direction} wall-clock "
                  f"({len(subgrid_configs)} subgrids, planar f32, "
                  f"{mode_label}, {platform})",
        "value": round(elapsed, 4),
        "unit": "s",
        "vs_baseline": round(numpy_total / elapsed, 2),
        "rms_vs_dft_oracle": float(f"{rms:.3e}"),
        "numpy_baseline_s": round(numpy_total, 2),
        "baseline_estimated": baseline_estimated,
        "baseline_source": baseline_source,
        "n_subgrids": len(subgrid_configs),
    }
    result.update(extra)
    result.update(
        _flop_fields(
            config, facet_configs, subgrid_configs, mode, elapsed,
            real_facets=real_facets, finish_passes=finish_passes,
            colpass=(extra.get("plan") or {}).get("colpass"),
        )
    )
    # provenance: every record is self-describing (device, git SHA, env
    # knobs, config hash, baseline pedigree) — VERDICT r5's unauditable-
    # artifact findings are structurally impossible with the stamp
    result["manifest"] = run_manifest(
        baseline_source=baseline_source,
        params={"config": config_name, "mode": mode_label, **params},
    )
    if metrics.enabled():
        result["telemetry"] = metrics.export()
        if "plan_compiled" in result:
            _stamp_plan_accuracy(result)
    if otrace.enabled():
        from swiftly_tpu.obs import summarize_trace

        summary = summarize_trace(
            otrace.export(), root_id=getattr(leg_span, "id", None)
        )
        summary["leg_wall_s"] = round(leg_wall_s, 6)
        result["trace"] = summary
    return result


def _trace_path_from_argv(default="BENCH_trace.json"):
    """The Chrome-trace output path for this invocation, or None.

    ``--trace [PATH]`` (PATH optional — defaults to ``BENCH_trace.json``
    next to the other artifacts) turns the span tracer on for the run;
    ``SWIFTLY_TRACE=1`` + ``SWIFTLY_TRACE_PATH`` are the env twins the
    manifest records.
    """
    if "--trace" in sys.argv:
        i = sys.argv.index("--trace")
        nxt = sys.argv[i + 1] if i + 1 < len(sys.argv) else None
        if nxt and not nxt.startswith("--"):
            return nxt
        return os.environ.get("SWIFTLY_TRACE_PATH") or default
    from swiftly_tpu.obs import trace as otrace

    if otrace.enabled():  # SWIFTLY_TRACE=1 at process start
        return otrace.path() or os.environ.get(
            "SWIFTLY_TRACE_PATH"
        ) or default
    return None


def _maybe_enable_trace():
    """Enable the span tracer when ``--trace``/``SWIFTLY_TRACE`` asks
    for it; returns the output path (None = tracing off)."""
    path = _trace_path_from_argv()
    if path:
        from swiftly_tpu.obs import trace as otrace

        otrace.enable(path)
    return path


def _stamp_plan_accuracy(record, dump_path=None):
    """Close the plan-accuracy loop for one leg: join the stamped
    ``plan_compiled`` block against the leg's telemetry into a
    ``plan_accuracy`` block (obs.ledger), append it to the persisted
    calibration history (``SWIFTLY_CALIBRATION_HISTORY``; ``0``
    disables), and — when CALIBRATED stages mispriced beyond the
    threshold — land ``plan.mispriced`` flight-recorder events plus a
    post-mortem dump. Returns the block (also stamped into the
    record)."""
    from swiftly_tpu.obs import ledger as oledger

    block = oledger.plan_accuracy_block(
        record.get("plan_compiled"),
        record.get("telemetry"),
        manifest=record.get("manifest"),
    )
    record["plan_accuracy"] = block
    try:
        oledger.append_history(block)
    except OSError as exc:
        log.warning("calibration history append failed: %s", exc)
    threshold = float(os.environ.get("BENCH_PLAN_THRESHOLD", "2.0"))
    mispriced = oledger.record_mispricing(
        block, threshold=threshold,
        dump_path=dump_path or os.environ.get(
            "BENCH_PLAN_PM_OUT", "BENCH_plan_postmortem.jsonl"
        ),
    )
    if mispriced:
        log.warning(
            "calibrated plan mispriced beyond x%g: %s", threshold,
            ", ".join(f"{n} (x{r:g})" for n, r in mispriced),
        )
    return block


def _maybe_enable_recorder():
    """Flight recorder ON by default for drills (``SWIFTLY_RECORDER=0``
    opts out); returns the recorder module when recording, else None.
    The ring is reset so the post-mortem window is this drill's, not a
    previous leg's."""
    if os.environ.get("SWIFTLY_RECORDER", "1") in ("", "0"):
        return None
    from swiftly_tpu.obs import recorder as orecorder

    orecorder.reset()
    orecorder.enable()
    return orecorder


def _zipf_workload(subgrid_configs, n_requests, seed, zipf_s=1.1):
    """A synthetic serving trace: requests zipf-distributed over
    subgrid COLUMNS (a shuffled popularity ranking, p ∝ 1/rank^s),
    uniform within a column — the ragged-demand shape the coalescing
    scheduler exists for (a few hot columns coalesce into dense
    batches; the tail arrives as singletons).

    :return: (requested configs list, the hottest column's off0)
    """
    rng = np.random.default_rng(seed)
    cols = sorted({sg.off0 for sg in subgrid_configs})
    by_col = {}
    for sg in subgrid_configs:
        by_col.setdefault(sg.off0, []).append(sg)
    order = rng.permutation(len(cols))
    ranks = np.empty(len(cols), dtype=int)
    ranks[order] = np.arange(len(cols))
    p = 1.0 / (ranks + 1.0) ** zipf_s
    p /= p.sum()
    picks = rng.choice(len(cols), size=n_requests, p=p)
    reqs = []
    for c in picks:
        col = by_col[cols[c]]
        reqs.append(col[rng.integers(len(col))])
    return reqs, cols[int(np.argmax(p))]


def serve_bench(smoke_mode=False):
    """`bench.py --serve [--smoke]`: the on-demand serving leg.

    Replays a zipf-over-columns workload through
    `swiftly_tpu.serve.SubgridService` (bounded admission queue →
    locality-aware coalescing scheduler → stacked column programs) and
    stamps the latency-SLO block into a BENCH-style artifact:
    p50/p99 latency, throughput, shed rate, coalesce-hit rate, retry/
    quarantine counts — the harness every future PR regresses serving
    tail latency against.

    The leg is also the serving fault drill: one burst overflows the
    admission queue (sheds recorded, clients get structured rejects),
    a cache feed seeded from the hottest column serves hits until a
    FORCED EVICTION makes later lookups fall back to recomputation, a
    fault injector fails one coalesced batch (its requests retry singly
    to success), and one POISONED request (malformed mask) is
    quarantined without wedging the column behind it. Every served
    result is verified BIT-IDENTICAL against per-request
    `get_subgrid_task` on a fresh forward.

    With ``--smoke`` the leg validates the artifact schema
    (`obs.validate_serve_artifact`) plus the drill outcomes and exits
    nonzero on any problem — wired into tier-1 via
    tests/test_bench_smoke.py.
    """
    import jax

    from swiftly_tpu import api as _api
    from swiftly_tpu.obs import metrics, run_manifest, validate_serve_artifact
    from swiftly_tpu.models import SWIFT_CONFIGS
    from swiftly_tpu.serve import (
        AdmissionQueue,
        CoalescingScheduler,
        SubgridService,
    )
    from swiftly_tpu.models.config import SubgridConfig
    from swiftly_tpu.parallel.streamed import CachedColumnFeed
    from swiftly_tpu.utils import enable_compilation_cache
    from swiftly_tpu.utils.spill import SpillCache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    out_path = os.environ.get("BENCH_SERVE_OUT", "BENCH_serve.json")
    if smoke_mode:
        os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
        metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get("BENCH_SERVE_CONFIG", "1k[1]-n512-256")
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "276"))
    seed = int(os.environ.get("BENCH_SERVE_SEED", "1234"))
    zipf_s = float(os.environ.get("BENCH_SERVE_ZIPF_S", "1.1"))
    max_depth = int(os.environ.get("BENCH_SERVE_DEPTH", "64"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    slo_ms = float(os.environ.get("BENCH_SERVE_SLO_MS", "30000"))

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    dtype = jax.numpy.float32
    platform = jax.devices()[0].platform
    config, fwd, facet_configs, subgrid_configs, sources = _build(
        "planar", params, dtype
    )
    workload, hot_off0 = _zipf_workload(
        subgrid_configs, n_requests, seed, zipf_s
    )

    # cache feed seeded from the hottest column, recorded through the
    # SAME stacked program the batcher uses — feed hits therefore stay
    # bit-identical to per-request compute. Mid-run the cache is
    # force-evicted: later hot-column lookups raise and the service
    # falls back to recomputation (the spill-replay degrade contract).
    hot_col = [sg for sg in subgrid_configs if sg.off0 == hot_off0]
    stacked = fwd.get_subgrid_tasks(hot_col)
    spill = SpillCache(budget_bytes=2**30)
    spill.begin_fill(tag=("serve-seed", name, len(hot_col)))
    spill.put(
        [list(enumerate(hot_col))],
        np.stack([np.asarray(r) for r in stacked])[None],
    )
    spill.end_fill()
    feed = CachedColumnFeed(spill)

    inject_state = {"armed": 0, "fired": 0}

    def injector(reqs, attempt):
        if attempt == 0 and inject_state["armed"] > 0:
            inject_state["armed"] -= 1
            inject_state["fired"] += 1
            raise RuntimeError("injected transient device failure")

    service = SubgridService(
        fwd,
        queue=AdmissionQueue(max_depth=max_depth),
        scheduler=CoalescingScheduler(
            max_batch=max_batch, urgency_s=0.05
        ),
        cache_feed=feed,
        max_retries=2,
        slo_ms=slo_ms,
        fault_injector=injector,
    )

    if not smoke_mode:
        # move the bucket-shape compiles off the latency path: the
        # power-of-two batch buckets plus the single-request program
        b = 1
        while b <= min(max_batch, len(hot_col) * 2):
            fwd.get_subgrid_tasks([hot_col[0]] * b)
            b *= 2
        fwd.get_subgrid_task(hot_col[0])

    rng = np.random.default_rng(seed + 1)
    tracked = []
    # burst 0 intentionally overflows the admission queue (depth
    # max_depth against a 1.5x burst): sheds are part of the drill
    bursts = [workload[: int(max_depth * 1.5)]]
    rest = workload[int(max_depth * 1.5):]
    burst_n = int(os.environ.get("BENCH_SERVE_BURST", "20"))
    bursts += [
        rest[i : i + burst_n] for i in range(0, len(rest), burst_n)
    ]
    poisoned = SubgridConfig(
        hot_off0, hot_col[0].off1, hot_col[0].size,
        np.ones(hot_col[0].size + 3), None,
    )
    from swiftly_tpu.obs import trace as otrace

    serve_span = otrace.span("bench.serve", cat="bench", config=name)
    t0 = time.time()
    serve_span.__enter__()
    for k, burst in enumerate(bursts):
        if k == 2:
            spill.reset()  # forced eviction: feed index now dangles
        if k == 3:
            inject_state["armed"] = 1  # fail the next coalesced batch
        for sg in burst:
            tracked.append(
                (
                    sg,
                    service.submit(
                        sg,
                        priority=int(rng.integers(0, 4)),
                        deadline_s=(
                            None if rng.integers(0, 7) else 120.0
                        ),
                    ),
                )
            )
        if k == 3:
            tracked.append((poisoned, service.submit(poisoned)))
        while service.pump_once():
            pass
    serve_span.__exit__(None, None, None)
    wall = time.time() - t0

    # bit-identity audit: every served result vs per-request
    # get_subgrid_task on a FRESH forward (fresh LRU, fresh queue)
    _config2, fwd_ref, _fc2, _sg2, _src2 = _build("planar", params, dtype)
    ref_cache = {}
    checked = mismatches = 0
    for sg, req in tracked:
        res = req.result
        if res is None or not res.ok:
            continue
        key = (sg.off0, sg.off1)
        if key not in ref_cache:
            ref_cache[key] = np.asarray(fwd_ref.get_subgrid_task(sg))
        checked += 1
        if not np.array_equal(np.asarray(res.data), ref_cache[key]):
            mismatches += 1

    stats = service.stats()
    n_cols = len({sg.off0 for sg in subgrid_configs})
    record = {
        "metric": (
            f"{name} on-demand subgrid serving "
            f"({stats['n_requests']} zipf requests over {n_cols} "
            f"columns, planar f32, {platform})"
        ),
        "value": round(wall, 4),
        "unit": "s",
        "throughput_rps": round(stats["n_served"] / wall, 2) if wall else 0.0,
        **stats,
        "bit_identical": {"checked": checked, "mismatches": mismatches},
        "fault_drill": {
            "forced_evictions": feed.evicted,
            "injected_failures": inject_state["fired"],
            "poisoned_quarantined": stats["n_quarantined"],
            "queue_drained": len(service.queue) == 0,
        },
        "cache_feed": {
            "indexed": len(feed),
            "hits": feed.hits,
            "misses": feed.misses,
            "evicted": feed.evicted,
        },
        "zipf": {"s": zipf_s, "n_columns": n_cols, "seed": seed},
        "includes_compile": smoke_mode,
        "n_subgrids_cover": len(subgrid_configs),
        "dispatch_path": _api.last_dispatch_path(),
        "manifest": run_manifest(
            params={"config": name, "mode": "serve", **params},
        ),
    }
    if metrics.enabled():
        record["telemetry"] = metrics.export()
    if trace_path:
        from swiftly_tpu.obs import summarize_trace

        summary = summarize_trace(
            otrace.export(), root_id=getattr(serve_span, "id", None)
        )
        summary["leg_wall_s"] = round(wall, 6)
        record["trace"] = summary
        otrace.save(trace_path)
        otrace.disable()

    problems = validate_serve_artifact(record)
    if smoke_mode:
        # drill outcomes: schema alone is not proof the paths ran
        if stats["n_served"] < 200:
            problems.append(f"served {stats['n_served']} < 200 requests")
        if mismatches or checked < stats["n_served"]:
            problems.append(
                f"bit-identity audit failed: {mismatches} mismatches, "
                f"{checked}/{stats['n_served']} checked"
            )
        if not stats["shed_rate"] > 0:
            problems.append("overload burst shed nothing (shed_rate == 0)")
        if not stats["coalesce_hit_rate"] > 0:
            problems.append("no coalesced requests (hit_rate == 0)")
        if not stats["cache_hits"]:
            problems.append("cache feed served no hits")
        if not stats["cache_fallbacks"]:
            problems.append(
                "forced eviction produced no cache->compute fallback"
            )
        if not inject_state["fired"] or not stats["retries"]:
            problems.append(
                f"injected failure did not exercise the retry path "
                f"(fired={inject_state['fired']}, "
                f"retries={stats['retries']})"
            )
        if stats["n_quarantined"] != 1:
            problems.append(
                f"expected exactly 1 quarantined (poisoned) request, "
                f"got {stats['n_quarantined']}"
            )
        if len(service.queue) != 0:
            problems.append(f"queue wedged: {len(service.queue)} pending")
        telemetry = record.get("telemetry") or {}
        t_stages = telemetry.get("stages") or {}
        if not {"serve.batch", "serve.request"} <= set(t_stages):
            problems.append(
                f"missing serve stages in telemetry: {sorted(t_stages)}"
            )
        elif "p50_s" not in t_stages["serve.request"]:
            problems.append("serve.request stage missing p50_s")
        # request journeys: every served request's queue/compute/
        # transfer segments must SUM to its end-to-end latency (they
        # are contiguous timestamp diffs — the p99 decomposition
        # contract), and the stats block aggregates them
        n_journeys = n_bad = 0
        for _sg, req in tracked:
            res = req.result
            if res is None or not res.ok:
                continue
            if not res.journey:
                n_bad += 1
                continue
            n_journeys += 1
            total = sum(res.journey.values())
            if abs(total - res.latency_s) > 1e-6 + 1e-4 * res.latency_s:
                n_bad += 1
        if not n_journeys or n_bad:
            problems.append(
                f"journey decomposition failed: {n_journeys} journeys, "
                f"{n_bad} missing/not summing to end-to-end latency"
            )
        if not stats.get("journey"):
            problems.append("stats missing journey decomposition block")
        if trace_path:
            from swiftly_tpu.obs import validate_trace_artifact

            problems.extend(validate_trace_artifact(record))
            tr_j = (record.get("trace") or {}).get("journeys") or {}
            if not tr_j.get("n_requests"):
                problems.append("trace holds no serve.journey spans")
        gm = (telemetry.get("gauges_max") or {})
        if "serve.queue_depth_peak" not in gm:
            problems.append(
                "gauges_max missing serve.queue_depth_peak watermark"
            )
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    if smoke_mode:
        metrics.disable()
        print(
            json.dumps(
                {
                    "serve_smoke": "ok" if not problems else "failed",
                    "config": name,
                    "artifact": out_path,
                    "n_served": stats["n_served"],
                    "p99_ms": stats["p99_ms"],
                    "shed_rate": stats["shed_rate"],
                    "coalesce_hit_rate": stats["coalesce_hit_rate"],
                    "problems": problems,
                }
            ),
            flush=True,
        )
        return 0 if not problems else 1
    print(json.dumps(record), flush=True)
    return 0 if not problems else 1


def _lat_quantile_ms(latencies_s, q):
    """Latency quantile in ms over a list of seconds-samples."""
    if not latencies_s:
        return 0.0
    lat = sorted(latencies_s)
    return round(lat[min(len(lat) - 1, int(q * len(lat)))] * 1e3, 3)


def _vis_build(params, kernel, dtype):
    """Forward + cover for the visibility leg.

    Differs from `_build` in one load-bearing way: the sky model is
    band-limited into the degrid kernel's accuracy band and GRID-
    CORRECTED (`vis.kernel.VisKernel.correct_sources`) before facets
    are built, so degridded samples approximate the TRUE visibilities
    of the returned RAW sources — the direct-DFT oracle the leg audits
    against (`vis.oracle.vis_oracle`).
    """
    from swiftly_tpu import (
        SwiftlyConfig,
        SwiftlyForward,
        make_facet,
        make_full_facet_cover,
        make_full_subgrid_cover,
    )

    config = SwiftlyConfig(backend="planar", dtype=dtype, **params)
    N = config.image_size
    maxc = max(
        max(abs(a), abs(b)) for a, b in _BENCH_SOURCE_FRACTIONS
    )
    # 0.9 of the band edge: the kernel fit's error grows toward the
    # band boundary, so the margin keeps the measured oracle RMS well
    # inside DEGRID_TOLERANCE instead of brushing it
    scale = 0.9 * kernel.band / 2.0 / maxc
    raw = [
        (w, int(x * scale), int(y * scale))
        for (w, x, y) in _bench_sources(N)
    ]
    corrected = kernel.correct_sources(raw, N)
    facet_configs = make_full_facet_cover(config)
    tasks = [
        (fc, make_facet(N, fc, corrected)) for fc in facet_configs
    ]
    fwd = SwiftlyForward(config, tasks, lru_forward=2, queue_size=64)
    return config, fwd, facet_configs, make_full_subgrid_cover(config), raw


def _vis_zipf_uv(subgrid_configs, n_samples, seed, zipf_s, margin, N):
    """Zipf-over-(u, v) sample workload: columns ranked zipf (shuffled
    popularity, p ∝ 1/rank^s), samples uniform inside a column subgrid's
    interior (``margin`` pixels in from the span edge, so the kernel
    footprint lands in-cover), plus a 10% uniform-over-the-grid tail
    whose off-cover samples exercise the structured shed path.

    :return: ([n, 2] uv array, hottest column's off0)
    """
    rng = np.random.default_rng(seed)
    cols = sorted({sg.off0 for sg in subgrid_configs})
    by_col = {}
    for sg in subgrid_configs:
        by_col.setdefault(sg.off0, []).append(sg)
    order = rng.permutation(len(cols))
    ranks = np.empty(len(cols), dtype=int)
    ranks[order] = np.arange(len(cols))
    p = 1.0 / (ranks + 1.0) ** zipf_s
    p /= p.sum()
    n_spread = n_samples // 10
    n_zipf = n_samples - n_spread
    uv = np.empty((n_samples, 2))
    picks = rng.choice(len(cols), size=n_zipf, p=p)
    for i, c in enumerate(picks):
        col = by_col[cols[c]]
        sg = col[rng.integers(len(col))]
        half = sg.size / 2.0 - margin
        uv[i] = (
            sg.off0 + rng.uniform(-half, half),
            sg.off1 + rng.uniform(-half, half),
        )
    uv[n_zipf:] = rng.uniform(0, N, size=(n_spread, 2))
    return uv, cols[int(np.argmax(p))]


def vis_bench(smoke_mode=False):
    """`bench.py --vis [--smoke]`: the visibility-serving leg.

    Replays a zipf-over-(u, v) workload through
    `swiftly_tpu.vis.VisibilityService` (sample batches split by owning
    subgrid, coalesced by column through the serve admission/scheduling
    machinery, answered by one degrid dispatch per touched subgrid off
    cache-fed or computed rows) and stamps the ``vis`` artifact block:
    latency quantiles, shed/coalesce/cache rates, served-sample
    throughput — AUDITED for accuracy, not just speed: every served
    sample is compared against the direct-DFT oracle
    (`vis.oracle.vis_oracle`, rel RMS within the kernel's stamped
    tolerance), the degrid/grid adjoint dot-product identity is
    asserted, and the gridded batch round-trips into
    `parallel.streamed.StreamedBackward.add_subgrid_group`.

    Drills folded into the replay: an admission-queue overload burst
    (structured "depth" sheds), a FORCED spill eviction (later hot-
    column lookups fall back to recomputation), a boundary-straddling
    batch shed with ``outside_cover``, and a facet update after which
    the version-pinned `vis.VisGridder` REFUSES stale-era batches and
    the service serves compute-path only (the dropped feed's rows
    belong to the superseded stack). Served cache-path samples are
    verified BIT-IDENTICAL against direct `vis.degrid.degrid_batch` on
    rows from a fresh forward. A small `serve.SubgridService` burst on
    the same forward anchors the throughput contract: served samples/s
    must be >= 10x the subgrid-serving request rate (the whole point
    of serving samples instead of rows).

    With ``--smoke`` the leg validates the artifact schema
    (`obs.validate_vis_artifact`) plus the drill outcomes and exits
    nonzero on any problem — wired into tier-1 via
    tests/test_bench_smoke.py.
    """
    import jax

    from swiftly_tpu import api as _api
    from swiftly_tpu.models import SWIFT_CONFIGS
    from swiftly_tpu.obs import metrics, run_manifest, validate_vis_artifact
    from swiftly_tpu.parallel import StreamedBackward
    from swiftly_tpu.parallel.streamed import CachedColumnFeed
    from swiftly_tpu.plan import price_vis
    from swiftly_tpu.serve import (
        AdmissionQueue,
        CoalescingScheduler,
        SubgridService,
    )
    from swiftly_tpu.utils import enable_compilation_cache
    from swiftly_tpu.utils.spill import SpillCache
    from swiftly_tpu.vis import (
        ADJOINT_TOLERANCE,
        VisGridder,
        VisibilityService,
        degrid_batch,
        grid_batch,
        vis_kernel,
        vis_oracle,
    )

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    out_path = os.environ.get("BENCH_VIS_OUT", "BENCH_vis.json")
    if smoke_mode:
        os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
        metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get("BENCH_VIS_CONFIG", "")
    n_samples = int(os.environ.get("BENCH_VIS_SAMPLES", "2000"))
    seed = int(os.environ.get("BENCH_VIS_SEED", "1234"))
    zipf_s = float(os.environ.get("BENCH_VIS_ZIPF_S", "1.1"))
    max_depth = int(os.environ.get("BENCH_VIS_DEPTH", "64"))
    max_batch = int(os.environ.get("BENCH_VIS_MAX_BATCH", "16"))
    slo_ms = float(os.environ.get("BENCH_VIS_SLO_MS", "30000"))
    n_serve = int(os.environ.get("BENCH_VIS_SERVE_REQUESTS", "24"))

    if name:
        params = dict(SWIFT_CONFIGS[name])
        params.setdefault("fov", 1.0)
    else:
        # smoke-scale geometry (the tests' known-good small set: real
        # PSWF margin between yB and yN, so served rows carry signal)
        name = "vis-n256"
        params = dict(W=8.0, fov=1.0, N=256, yB_size=96, yN_size=128,
                      xA_size=56, xM_size=64)
    kernel = vis_kernel()
    platform = jax.devices()[0].platform
    config, fwd, facet_configs, subgrid_configs, sources = _vis_build(
        params, kernel, jax.numpy.float32
    )
    N = config.image_size
    uv_all, hot_off0 = _vis_zipf_uv(
        subgrid_configs, n_samples, seed, zipf_s,
        kernel.support + 1, N,
    )
    cols_sorted = sorted({sg.off0 for sg in subgrid_configs})
    hot_col = [sg for sg in subgrid_configs if sg.off0 == hot_off0]

    # cache feed seeded from the hottest column through the SAME
    # per-subgrid program the compute fallback uses — feed hits stay
    # bit-identical to fallback recompute. Mid-run the spill is
    # force-evicted: later hot-column lookups raise and the service
    # falls back to recomputation (the spill-replay degrade contract).
    hot_rows = [np.asarray(fwd.get_subgrid_task(sg)) for sg in hot_col]
    spill = SpillCache(budget_bytes=2**30)
    spill.begin_fill(tag=("vis-seed", name, len(hot_col)))
    spill.put([list(enumerate(hot_col))], np.stack(hot_rows)[None])
    spill.end_fill()
    feed = CachedColumnFeed(spill)

    service = VisibilityService(
        fwd,
        subgrid_configs=subgrid_configs,
        kernel=kernel,
        cache_feed=feed,
        queue=AdmissionQueue(max_depth=max_depth),
        scheduler=CoalescingScheduler(
            max_batch=max_batch, urgency_s=0.05
        ),
        slo_ms=slo_ms,
    )

    from swiftly_tpu.obs import trace as otrace

    rng = np.random.default_rng(seed + 1)
    burst = max(16, n_samples // 12)
    bursts = [
        uv_all[i : i + burst] for i in range(0, len(uv_all), burst)
    ]
    # in-cover point on the hottest subgrid: the overload drill's
    # repeated target (same owning subgrid -> coalesced singles)
    hot_pt = np.array(
        [[hot_col[0].off0 + 0.3, hot_col[0].off1 + 0.3]]
    )
    # a kernel footprint straddling the border between the first two
    # columns can be answered by neither side's row: structured shed
    border = (cols_sorted[0] + cols_sorted[1]) / 2.0
    uv_outside = np.array(
        [[border + 0.25, hot_off0], [border - 0.25, hot_off0]]
    )

    tracked = []
    vis_span = otrace.span("bench.vis", cat="bench", config=name)
    t0 = time.time()
    vis_span.__enter__()
    # overload drill: 1.5x the admission depth as single-sample
    # submissions with no pump between them — past max_depth they shed
    # with the queue's structured "depth" reason; the admitted ones
    # coalesce (one subgrid) into max_batch-sized degrid dispatches
    for _ in range(int(max_depth * 1.5)):
        tracked.append((hot_pt, service.submit(hot_pt)))
    while service.pump_once():
        pass
    outside_handle = None
    pending = 0
    for k, b in enumerate(bursts):
        if k == 1:
            outside_handle = service.serve(uv_outside)
        if k == 3:
            spill.reset()  # forced eviction: feed index now dangles
        tracked.append(
            (b, service.submit(b, priority=int(rng.integers(0, 4))))
        )
        pending += 1
        # drain every second burst so concurrent batches overlap on the
        # hot columns (the coalescing the scheduler exists for)
        if pending >= 2 or k == len(bursts) - 1:
            while service.pump_once():
                pass
            pending = 0
    vis_span.__exit__(None, None, None)
    wall = time.time() - t0
    stats_run = service.stats()

    # accuracy audit: every served sample vs the direct-DFT oracle of
    # the RAW (band-limited, uncorrected) sky model
    served_uv, served_vis = [], []
    for uv_b, h in tracked:
        m = np.isfinite(h.data)
        if m.any():
            served_uv.append(np.atleast_2d(uv_b)[m])
            served_vis.append(h.data[m])
    served_uv = np.concatenate(served_uv)
    served_vis = np.concatenate(served_vis)
    oracle = vis_oracle(sources, served_uv, N)
    degrid_rms = float(
        np.sqrt(np.mean(np.abs(served_vis - oracle) ** 2))
        / max(np.sqrt(np.mean(np.abs(oracle) ** 2)), 1e-30)
    )

    # bit-identity audit: served samples vs direct degrid_batch on rows
    # from a FRESH forward (fresh LRU/queue; per-lane einsum
    # independence makes batch shape irrelevant to the bits)
    _c2, fwd_ref, _fc2, _sg2, _src2 = _vis_build(
        params, kernel, jax.numpy.float32
    )
    ref_rows = {}
    checked = mismatches = 0
    for uv_b, h in tracked:
        owners, _shed = service.cover.map_samples(np.atleast_2d(uv_b))
        for key, entry in owners.items():
            got = h.data[entry["idx"]]
            m = np.isfinite(got)
            if not m.any():
                continue
            if key not in ref_rows:
                ref_rows[key] = np.asarray(
                    fwd_ref.get_subgrid_task(service.cover.config(*key))
                )
            ref = degrid_batch(
                ref_rows[key], entry["iu0"], entry["iv0"],
                kernel.weights(entry["fu"], dtype=np.float64),
                kernel.weights(entry["fv"], dtype=np.float64),
            )
            checked += int(m.sum())
            mismatches += int(np.sum(got[m] != ref[m]))

    # adjoint audit: < degrid(G), y > == < G, grid(y) > over a fresh
    # in-cover batch (the dot-product identity pinning grid as the
    # exact adjoint; float32 accumulation noise only)
    rng_adj = np.random.default_rng(seed + 5)
    half = hot_col[0].size / 2.0 - kernel.support - 1
    uv_adj = np.stack(
        [
            hot_off0 + rng_adj.uniform(-half, half, size=64),
            hot_col[0].off1 + rng_adj.uniform(-half, half, size=64),
        ],
        axis=1,
    )
    owners_adj, _ = service.cover.map_samples(uv_adj)
    lhs = rhs = 0.0 + 0.0j
    for key, entry in owners_adj.items():
        sg = service.cover.config(*key)
        row = ref_rows.get(key)
        if row is None:
            row = np.asarray(fwd_ref.get_subgrid_task(sg))
        plane = row[..., 0] + 1j * row[..., 1]
        cu = kernel.weights(entry["fu"], dtype=np.float64)
        cv = kernel.weights(entry["fv"], dtype=np.float64)
        d = degrid_batch(row, entry["iu0"], entry["iv0"], cu, cv)
        y = (
            rng_adj.normal(size=d.size)
            + 1j * rng_adj.normal(size=d.size)
        )
        ar, ai = grid_batch(
            sg.size, entry["iu0"], entry["iv0"], cu, cv, y
        )
        lhs += np.vdot(d, y)
        rhs += np.vdot(plane, ar + 1j * ai)
    adjoint_rel = float(abs(lhs - rhs) / max(abs(lhs), 1e-30))

    # gridding round-trip: accumulate every served sample through the
    # version-pinned gridder and ingest the emitted columns into the
    # backward's add_subgrid_group form (residency="sampled")
    gridder = VisGridder(
        service.cover, kernel,
        stream_version=service.stream_version,
        version_of=lambda: service.stream_version,
    )
    gridder.add_batch(served_uv, served_vis)
    col_sg_lists, stack = gridder.emit(planar=True)
    bwd = StreamedBackward(config, facet_configs, residency="sampled")
    bwd.add_subgrid_group(col_sg_lists, jax.numpy.asarray(stack))
    ingested = True

    # facet-update drill: version gates must hold — the pinned gridder
    # refuses the next batch outright, the dropped feed's rows are
    # unreachable (hits frozen), and post-update serving is compute-path
    pre_update_hits = service.stats()["cache_hits"]
    service.post_facet_update()
    stale_refused = False
    try:
        gridder.add_batch(served_uv[:4], served_vis[:4])
    except LookupError:
        stale_refused = True
    post_handle = service.serve(hot_pt)
    post_compute_only = all(
        r.result is not None and r.result.ok
        and r.result.path == "compute"
        for r in post_handle.children
    )
    post_hits_delta = service.stats()["cache_hits"] - pre_update_hits

    # throughput anchor: a subgrid-serving burst on the SAME forward —
    # the rate a row-granular client would get; served samples/s must
    # beat it 10x or visibility serving has no reason to exist
    serve_reqs, _hot2 = _zipf_workload(
        subgrid_configs, n_serve, seed + 7, zipf_s
    )
    serve_svc = SubgridService(
        fwd,
        queue=AdmissionQueue(max_depth=max_depth),
        scheduler=CoalescingScheduler(max_batch=1, urgency_s=0.05),
    )
    t1 = time.time()
    serve_tracked = [serve_svc.submit(sg) for sg in serve_reqs]
    while serve_svc.pump_once():
        pass
    serve_wall = time.time() - t1
    serve_stats = serve_svc.stats()
    serve_rps = (
        serve_stats["n_served"] / serve_wall if serve_wall else 0.0
    )
    samples_per_s = (
        stats_run["n_served_samples"] / wall if wall else 0.0
    )
    serve_ratio = samples_per_s / serve_rps if serve_rps else 0.0

    stats = service.stats()
    n_cols = len(cols_sorted)
    hit_rate = stats["cache_hits"] / max(1, stats["n_batches"])
    plan = price_vis(
        n_samples=stats["n_samples"],
        subgrid_size=config.max_subgrid_size,
        support=kernel.support,
        cache_hit_rate=hit_rate,
        include_grid=True,
    )
    vis_block = {
        **stats,
        "throughput_ksamples_s": round(samples_per_s / 1e3, 4),
        "degrid_rms": degrid_rms,
        "kernel": kernel.as_dict(),
        "adjoint": {
            "rel_err": adjoint_rel,
            "tolerance": ADJOINT_TOLERANCE,
        },
        "grid": {
            "n_gridded": gridder.n_gridded,
            "n_shed": gridder.n_shed,
            "batches": gridder.batches,
            "columns": len(col_sg_lists),
            "ingested": ingested,
            "stale_refused": stale_refused,
        },
        "serve_baseline": {
            "n_requests": n_serve,
            "n_served": serve_stats["n_served"],
            "wall_s": round(serve_wall, 4),
            "rps": round(serve_rps, 3),
            "samples_per_s": round(samples_per_s, 2),
            "ratio": round(serve_ratio, 2),
        },
        "version_gate": {
            "facet_updates": stats["facet_updates"],
            "gridder_refused": stale_refused,
            "post_update_cache_hits_delta": post_hits_delta,
            "post_update_compute_only": post_compute_only,
        },
        "plan": plan.as_dict(),
    }
    record = {
        "metric": (
            f"{name} visibility serving ({stats['n_samples']} zipf "
            f"(u,v) samples over {n_cols} columns, planar f32, "
            f"{platform})"
        ),
        "value": round(wall, 4),
        "unit": "s",
        "throughput_rps": round(stats["n_served"] / wall, 2) if wall else 0.0,
        "vis": vis_block,
        "bit_identical": {"checked": checked, "mismatches": mismatches},
        "cache_feed": {
            "indexed": len(feed),
            "hits": feed.hits,
            "misses": feed.misses,
            "evicted": feed.evicted,
        },
        "zipf": {"s": zipf_s, "n_columns": n_cols, "seed": seed},
        "includes_compile": True,
        "n_subgrids_cover": len(subgrid_configs),
        "dispatch_path": _api.last_dispatch_path(),
        "plan_compiled": {
            "predicted": {"stages": plan.as_dict()["predicted"]},
            "coeffs_source": plan.coeffs_source,
            "config": name,
            "mode": "vis",
        },
        "manifest": run_manifest(
            params={"config": name, "mode": "vis", **params},
        ),
    }
    if metrics.enabled():
        record["telemetry"] = metrics.export()
        _stamp_plan_accuracy(record)
    if trace_path:
        from swiftly_tpu.obs import summarize_trace

        summary = summarize_trace(
            otrace.export(), root_id=getattr(vis_span, "id", None)
        )
        summary["leg_wall_s"] = round(wall, 6)
        record["trace"] = summary
        otrace.save(trace_path)
        otrace.disable()

    problems = validate_vis_artifact(record)
    if smoke_mode:
        # drill outcomes: schema alone is not proof the paths ran
        total = stats["n_samples"]
        if stats["n_served_samples"] < 0.5 * total:
            problems.append(
                f"served {stats['n_served_samples']}/{total} samples "
                "(< 50%)"
            )
        if not checked or mismatches:
            problems.append(
                f"bit-identity audit failed: {mismatches} mismatches, "
                f"{checked} checked"
            )
        if not stats["shed_reasons"].get("outside_cover"):
            problems.append("no outside_cover sheds (spread tail + "
                            "boundary drill both missed)")
        if outside_handle is None or outside_handle.status != "shed" \
                or outside_handle.shed_reason != "outside_cover":
            problems.append(
                "boundary-straddling batch was not shed outside_cover "
                f"(got {outside_handle!r})"
            )
        if not stats["shed_reasons"].get("depth"):
            problems.append(
                "overload burst shed nothing with the 'depth' reason"
            )
        if not stats["cache_hits"]:
            problems.append("cache feed served no hits")
        if not stats["cache_fallbacks"]:
            problems.append(
                "forced eviction produced no cache->compute fallback"
            )
        if not stats["coalesce_hit_rate"] > 0:
            problems.append("no coalesced sample slices (hit_rate == 0)")
        if serve_ratio < 10.0:
            problems.append(
                f"served-sample throughput only {serve_ratio:.1f}x the "
                "subgrid-serving request rate (contract: >= 10x)"
            )
        if not stale_refused:
            problems.append(
                "stale-pinned gridder accepted a post-update batch"
            )
        if post_hits_delta or not post_compute_only:
            problems.append(
                f"post-facet-update serving touched the dropped feed "
                f"(hits delta {post_hits_delta}, compute_only="
                f"{post_compute_only})"
            )
        if len(service.queue) != 0:
            problems.append(f"queue wedged: {len(service.queue)} pending")
        telemetry = record.get("telemetry") or {}
        t_stages = telemetry.get("stages") or {}
        if not {"vis.degrid", "vis.row_fetch", "vis.grid"} <= set(t_stages):
            problems.append(
                f"missing vis stages in telemetry: {sorted(t_stages)}"
            )
        if "vis.queue_depth_peak" not in (
            telemetry.get("gauges_max") or {}
        ):
            problems.append(
                "gauges_max missing vis.queue_depth_peak watermark"
            )
        if not stats.get("journey"):
            problems.append("stats missing journey decomposition block")
        if trace_path:
            from swiftly_tpu.obs import validate_trace_artifact

            problems.extend(validate_trace_artifact(record))
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    if smoke_mode:
        metrics.disable()
        print(
            json.dumps(
                {
                    "vis_smoke": "ok" if not problems else "failed",
                    "config": name,
                    "artifact": out_path,
                    "n_served_samples": stats["n_served_samples"],
                    "p99_ms": stats["p99_ms"],
                    "shed_rate": stats["shed_rate"],
                    "degrid_rms": round(degrid_rms, 6),
                    "adjoint_rel_err": round(adjoint_rel, 9),
                    "serve_ratio": round(serve_ratio, 2),
                    "throughput_ksamples_s": round(
                        samples_per_s / 1e3, 4
                    ),
                    "problems": problems,
                }
            ),
            flush=True,
        )
        return 0 if not problems else 1
    print(json.dumps(record), flush=True)
    return 0 if not problems else 1


def fleet_bench(smoke_mode=False):
    """`bench.py --fleet [--smoke]`: the self-healing serve-fleet drill.

    Runs ``BENCH_FLEET_REPLICAS`` (default 3) `SubgridService` replicas
    — threads, one prepared forward each, one simulated chip per
    replica — behind the `swiftly_tpu.serve.ServeFleet` rendezvous
    column router with health leases and per-replica circuit breakers,
    and replays the SAME zipf-over-columns workload through four
    phases:

    1. **before** — a clean window; its p99 is the recovery baseline;
    2. **kill** — the same workload submitted as a burst, then a
       deterministic ``fleet.replica.kill`` fault (`WorkerKilled` in a
       replica pump — simulated chip death) lands mid-stream: the
       victim's lease misses beats → suspect → probe fails → revoked;
       its breaker trips open; its queued + in-flight requests fail
       over to the survivors with the backoff ladder (laggards past
       the p99 budget are hedged). ZERO requests may be lost;
    3. **after** — the victim is restored (fresh pump over its warm
       forward); the breaker goes half-open, probe requests close it,
       and the window's p99 must recover to <= 1.5x the *before* p99;
    4. **overload** — injected ``fleet.route`` faults are survived by
       the route retry, then the brownout ladder is drilled with a
       forced queue-share signal: rung 1 sheds priority-0 submissions
       with a structured ``retry_after_s``, rung 2 degrades every
       replica to per-request dispatch, then hysteresis steps back
       down. (The signal is forced so the drill is deterministic; the
       organic signal path is pinned by tests/test_fleet.py.)
    5. **autoscale** — a sustained zipf burst under a forced-high
       journey signal must scale the fleet out through the
       `serve.FleetAutoscaler` (each newcomer serves a `cache` fabric
       feed VIEW — an L1 over the one resident stream, never a copy),
       then a forced-low signal drains the extras back through the
       zero-loss retire path; a final clean window pins p99 where the
       *before* phase left it.

    The whole fleet serves ONE recorded subgrid stream through the
    shared cache fabric (`cache.SharedStreamTier` over the
    `delta.IncrementalForward` recording): per-replica hot-row L1s
    (sized by `plan.price_cache_tier`'s break-even) over a single
    versioned spill-backed L2 — the artifact's ``cache`` block asserts
    exactly one resident stream copy and a >= 10x QPS-equivalent over
    the timed single-service compute baseline.

    Since the control tower (PR 15) the drill also exercises the fleet
    observability plane: every replica, the cache fabric, the
    autoscaler and the fleet itself register as tower sources; the
    flight recorder is ON by default (``SWIFTLY_RECORDER=0`` opts out)
    and the kill's post-mortem bundle is stamped + dumped next to the
    artifact; two declarative SLOs ride the supervisor tick and the
    forced brownout ladder must open (then close) the burn-rate alert.
    The artifact's ``fleet_telemetry`` and ``alerts`` blocks are
    validated by `obs.validate_fleet_telemetry_artifact` /
    `obs.validate_alerts_artifact`.

    Every served result is audited BIT-IDENTICAL against per-request
    `get_subgrid_task` on a fresh forward — failover, hedging and the
    cache fabric must never change an answer. The artifact's ``fleet``
    and ``cache`` blocks (validated by `obs.validate_fleet_artifact`)
    record per-replica QPS, the failover/hedge/brownout/autoscale
    counters, fabric hit/miss/dedup stats, the victim's full breaker
    cycle and the p99 before/during/after windows; with ``--smoke``
    the drill outcomes are asserted and the leg exits nonzero on any
    problem (wired into tier-1 via tests/test_bench_smoke.py).
    """
    import jax

    from swiftly_tpu import (
        SwiftlyConfig,
        SwiftlyForward,
        make_facet,
        make_full_facet_cover,
        make_full_subgrid_cover,
    )
    from swiftly_tpu.models import SWIFT_CONFIGS
    from swiftly_tpu.obs import (
        metrics,
        run_manifest,
        validate_fleet_artifact,
    )
    from swiftly_tpu.resilience import FaultPlan, faults
    from swiftly_tpu.serve import (
        AdmissionQueue,
        CoalescingScheduler,
        FleetAutoscaler,
        ServeFleet,
        SubgridService,
    )
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    orecorder = _maybe_enable_recorder()
    out_path = os.environ.get("BENCH_FLEET_OUT", "BENCH_fleet.json")
    if smoke_mode:
        os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
        metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get("BENCH_FLEET_CONFIG", "1k[1]-n512-256")
    n_replicas = int(os.environ.get("BENCH_FLEET_REPLICAS", "3"))
    per_phase = int(os.environ.get("BENCH_FLEET_PHASE_REQUESTS", "72"))
    seed = int(os.environ.get("BENCH_FLEET_SEED", "1234"))
    zipf_s = float(os.environ.get("BENCH_FLEET_ZIPF_S", "1.1"))
    max_depth = int(os.environ.get("BENCH_FLEET_DEPTH", "256"))
    max_batch = int(os.environ.get("BENCH_FLEET_MAX_BATCH", "16"))

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    dtype = jax.numpy.float32
    platform = jax.devices()[0].platform
    config = SwiftlyConfig(backend="planar", dtype=dtype, **params)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    sources = _bench_sources(config.image_size)
    # ONE facet data set, N independent prepared forwards (replica =
    # simulated chip: own facet upload, own column LRU, own queue); the
    # in-process + persistent XLA caches make the repeat compiles cheap
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, sources))
        for fc in facet_configs
    ]

    def replica_factory(rid, feed):
        fwd = SwiftlyForward(
            config, facet_tasks, lru_forward=2, queue_size=64
        )
        return SubgridService(
            fwd,
            queue=AdmissionQueue(max_depth=max_depth),
            scheduler=CoalescingScheduler(max_batch=max_batch),
            max_retries=2,
            cache_feed=feed,
        )

    # admission costing from the unified plan compiler: the fleet's
    # per-request / per-column byte model is the compiled plan's serve
    # block (no cap here — the drill's phases must admit everything;
    # the pricing lands in the artifact's admission stats)
    from swiftly_tpu.plan import PlanInputs, compile_plan, price_cache_tier

    plan_inputs = PlanInputs.from_cover(
        config, facet_configs, subgrid_configs, max_batch=max_batch,
    )
    fleet_plan = compile_plan(plan_inputs, mode="streamed")

    # ONE recorded stream for the whole fleet: record the subgrid
    # stream once through the incremental engine, then front it with
    # the shared cache fabric — each replica gets a hot-row L1 VIEW
    # over the single resident spill-backed L2, sized by the plan
    # compiler's priced break-even
    from swiftly_tpu.delta import IncrementalForward
    from swiftly_tpu.utils.spill import SpillCache, spill_budget_bytes

    engine = IncrementalForward(
        config, facet_tasks,
        SpillCache(budget_bytes=spill_budget_bytes()),
    )
    engine.record(subgrid_configs)
    l1_env = int(os.environ.get("BENCH_FLEET_L1_ROWS", "0"))
    cache_plan = price_cache_tier(
        plan_inputs, replicas=n_replicas,
        l1_rows=l1_env or None, zipf_s=zipf_s,
    )
    fabric = engine.fabric(l1_rows=cache_plan.l1_rows)

    fleet = ServeFleet(
        replica_factory, n_replicas,
        lease_interval_s=0.02, miss_suspect=3, miss_revoke=6,
        breaker_threshold=3, breaker_reopen_s=0.3,
        breaker_max_reopen_s=4.0, half_open_probes=2,
        hedge_min_s=0.05,
        # brownout is drilled explicitly in the overload phase; an
        # impossible share keeps it out of the kill/recovery windows
        brownout_share=2.0, brownout_min_depth=8,
        brownout_escalate_s=0.1,
        failover_backoff_s=0.01, seed=seed,
        request_bytes=fleet_plan.serve.request_bytes,
        column_bytes=fleet_plan.serve.column_bytes,
        fabric=fabric, drain_timeout_s=20.0,
    )
    # declarative SLOs on the control tower: the forced brownout ladder
    # in the overload phase must OPEN the burn-rate alert (fast AND
    # slow windows burning) and the step-down must CLOSE it — the alert
    # lifecycle is a drill outcome, asserted under --smoke. The shed
    # SLO stays quiet (the drill sheds a dozen of hundreds): one alert
    # that fires and one that doesn't is the schema's smoke test.
    from swiftly_tpu.obs import SLO

    fleet.tower.set_slos([
        # windows sized to the drill: the ladder holds rung >= 1 for
        # brownout_escalate_s (0.1s) before rung 2, so a 0.2s slow
        # window is >= half-breached by the time rung 2 lands
        SLO("brownout_engaged", "fleet.brownout_level", 0.5,
            direction="above", fast_s=0.05, slow_s=0.2, burn=0.4),
        SLO("shed_storm", "fleet.shed_rate", 0.5,
            direction="above", fast_s=0.5, slow_s=2.0, burn=0.5),
    ])

    # one shared workload per phase (same seed -> identical request
    # multiset), so the before/during/after p99 windows are comparable
    workload, hot_off0 = _zipf_workload(
        subgrid_configs, per_phase, seed, zipf_s
    )
    # move the bucket-shape compiles AND the per-replica lazy facet
    # preparation off every phase's latency path (each replica's
    # forward prepares its facet stack on first dispatch — unwarmed,
    # that lands in the *before* window and poisons the p99 baseline)
    hot_col = [sg for sg in subgrid_configs if sg.off0 == hot_off0]
    for replica in fleet.replicas.values():
        warm_fwd = replica.service.fwd
        b = 1
        while b <= max_batch:
            warm_fwd.get_subgrid_tasks([hot_col[0]] * b)
            b *= 2
        warm_fwd.get_subgrid_task(hot_col[0])

    # single-service compute baseline: one replica-shaped service with
    # NO cache feed, timed over a slice of the same zipf workload — the
    # honest denominator for the fabric's QPS-equivalence claim
    solo = replica_factory(-1, None)
    solo.serve(workload[:2], priority=1)  # warm its dispatch path
    solo_n = min(24, len(workload))
    t_solo = time.time()
    solo_reqs = solo.serve(workload[:solo_n], priority=1)
    solo_wall = time.time() - t_solo
    solo_ok = sum(
        1 for r in solo_reqs if r.result is not None and r.result.ok
    )
    single_service_qps = (solo_ok / solo_wall) if solo_wall else 0.0

    from swiftly_tpu.obs import trace as otrace

    fleet_span = otrace.span("bench.fleet", cat="bench", config=name)
    t0 = time.time()
    fleet_span.__enter__()
    fleet.start()
    tracked = []

    def run_phase(label, drain_timeout=180.0):
        phase = []
        for sg in workload:
            fr = fleet.submit(sg, priority=1)
            phase.append((sg, fr))
            tracked.append((sg, fr))
        if not fleet.drain(timeout=drain_timeout):
            log.error("phase %s did not drain", label)
        oks = [
            fr.result.latency_s
            for _sg, fr in phase
            if fr.result is not None and fr.result.ok
        ]
        return phase, oks

    # -- phase 1: the clean baseline window -------------------------------
    _phase_a, lat_before = run_phase("before")
    p99_before = _lat_quantile_ms(lat_before, 0.99)

    # -- phase 2: kill mid-workload ---------------------------------------
    # burst FIRST so every replica holds queued work, THEN arm the
    # deterministic kill: the 4th fleet.replica.kill site call after
    # install (every replica pump iterates the shared site) raises
    # WorkerKilled in whichever pump reaches it — the drill is
    # victim-agnostic by design (any of the N must fail over cleanly,
    # with its queued + in-flight burst share stranded mid-serve)
    kill_plan = FaultPlan(
        [{"site": "fleet.replica.kill", "kind": "kill", "at": 3}],
        seed=seed,
    )
    phase_b = []
    for sg in workload:
        fr = fleet.submit(sg, priority=1)
        phase_b.append((sg, fr))
        tracked.append((sg, fr))
    with faults.active(kill_plan):
        if not fleet.drain(timeout=300.0):
            log.error("kill phase did not drain")
    lat_during = [
        fr.result.latency_s
        for _sg, fr in phase_b
        if fr.result is not None and fr.result.ok
    ]
    p99_during = _lat_quantile_ms(lat_during, 0.99)
    victims = [
        rid for rid, r in fleet.replicas.items() if r.dead
    ]
    victim = victims[0] if victims else None
    # the fabric makes the kill window cache-fast: the burst drains in
    # tens of milliseconds, well inside the monitor's miss_revoke
    # horizon — wait for DETECTION (missed heartbeats -> revocation,
    # which trips the breaker) before restoring, or the drill restores
    # a victim the health plane never got to condemn
    if victim is not None:
        deadline = time.time() + 10.0
        while (
            not fleet.replica(victim).lease.revoked
            and time.time() < deadline
        ):
            time.sleep(0.005)
    # the black box earns its keep HERE: snapshot the recorder window
    # while the kill's event tail (fault injection, replica death,
    # lease revocation, breaker trip, failovers) is the recent past
    kill_post_mortem = (
        orecorder.post_mortem(
            "WorkerKilled", reason=f"replica {victim} killed mid-burst"
        )
        if orecorder is not None else None
    )

    # -- phase 3: restore + recovery window -------------------------------
    if victim is not None:
        fleet.restore_replica(victim)
    _phase_c, lat_after = run_phase("after")
    p99_after = _lat_quantile_ms(lat_after, 0.99)
    # drive the victim's breaker through half-open probes to closed:
    # keep offering its preferred columns until the cycle completes
    if victim is not None:
        victim_cols = [
            sg for sg in subgrid_configs
            if fleet.preferred_replica(sg.off0) == victim
        ] or hot_col
        deadline = time.time() + 10.0
        i = 0
        while (
            fleet.replica(victim).breaker.state != "closed"
            and time.time() < deadline
        ):
            sg = victim_cols[i % len(victim_cols)]
            i += 1
            fr = fleet.submit(sg, priority=1)
            tracked.append((sg, fr))
            fleet.drain(timeout=30.0)
            time.sleep(0.02)

    # -- phase 4: overload — route faults + the brownout ladder -----------
    route_plan = FaultPlan(
        [{"site": "fleet.route", "kind": "ioerror", "every": 3,
          "times": 4}],
        seed=seed,
    )
    with faults.active(route_plan):
        for sg in workload[:24]:
            fr = fleet.submit(sg, priority=1)
            tracked.append((sg, fr))
        fleet.drain(timeout=60.0)
    # brownout: force the journey queue-share signal (deterministic
    # drill of the LADDER; the organic signal path is unit-tested) and
    # shed a priority-0 burst at the door
    fleet.queue_share = lambda window=256: 0.95  # instance override
    fleet.brownout_min_depth = 0
    fleet.brownout_share = 0.5
    deadline = time.time() + 5.0
    while fleet.brownout_level < 1 and time.time() < deadline:
        time.sleep(0.005)
    brownout_shed = [
        fleet.submit(sg, priority=0) for sg in workload[:12]
    ]
    while fleet.brownout_level < 2 and time.time() < deadline:
        time.sleep(0.005)
    level_max = fleet.brownout_level
    per_request_dispatch = all(
        r.service.scheduler.max_batch == 1
        for r in fleet.replicas.values()
    ) if level_max >= 2 else False
    # restore the organic signal AND the impossible threshold so the
    # step-down path is deterministic (hysteresis walks 2 -> 1 -> 0)
    del fleet.queue_share
    fleet.brownout_share = 2.0
    fleet.brownout_min_depth = 8
    deadline = time.time() + 5.0
    while fleet.brownout_level > 0 and time.time() < deadline:
        time.sleep(0.005)
    batch_restored = all(
        r.service.scheduler.max_batch == max_batch
        for r in fleet.replicas.values()
    )

    # -- phase 5: sustained zipf + autoscaler (scale out, drain back) -----
    # the elastic drill: a sustained burst under a forced-high journey
    # signal must scale the fleet out (each newcomer is a fabric feed
    # VIEW — an L1, not a stream copy), then a forced-low signal must
    # drain the extra replicas back through the zero-loss path. The
    # signals are forced for determinism, exactly like the brownout
    # rungs above; the organic paths are pinned by tests/test_fleet.py.
    fleet.drain(timeout=60.0)
    scaler = FleetAutoscaler(
        fleet, min_replicas=n_replicas, max_replicas=n_replicas + 2,
        up_share=0.55, down_share=0.15, min_queue_depth=2,
        hold_ticks=2, cooldown_s=0.2,
    )
    fleet.autoscaler = scaler
    fleet.queue_share = lambda window=256: 0.9  # instance override
    as_phase = []
    t_as = time.time()
    for _rep in range(3):
        for sg in workload:
            fr = fleet.submit(sg, priority=1)
            as_phase.append((sg, fr))
            tracked.append((sg, fr))
    deadline = time.time() + 15.0
    while (
        fleet._counts["scale_outs"] < 1 and time.time() < deadline
    ):
        time.sleep(0.005)
    if not fleet.drain(timeout=120.0):
        log.error("autoscale phase did not drain")
    as_wall = time.time() - t_as
    # drain back: forced-low signal, empty queue -> the autoscaler
    # retires the newcomers one cooldown at a time
    fleet.queue_share = lambda window=256: 0.0
    deadline = time.time() + 20.0
    while (
        len(fleet.replicas) > n_replicas and time.time() < deadline
    ):
        time.sleep(0.01)
    del fleet.queue_share
    as_ok = sum(
        1 for _sg, fr in as_phase
        if fr.result is not None and fr.result.ok
    )
    autoscale_phase_rps = (as_ok / as_wall) if as_wall else 0.0
    # post-churn clean window: the same request multiset as the
    # *before* phase — elastic churn must leave p99 where it found it
    _phase_e, lat_elastic = run_phase("elastic_after")
    p99_elastic = _lat_quantile_ms(lat_elastic, 0.99)

    fleet.drain(timeout=60.0)
    wall = time.time() - t0
    stats = fleet.stats(wall_s=wall)
    # tower blocks BEFORE stop(): the replica sources are still
    # registered, so the fleet totals cover every serving source
    fleet_telemetry = fleet.tower.fleet_telemetry()
    alerts_block = fleet.tower.alerts_block()
    fleet.stop()
    fleet_span.__exit__(None, None, None)

    # -- bit-identity audit: every served result vs a FRESH deterministic
    # reference for ITS serving path — failover/hedging/dedup must never
    # change answers. Cache-path rows come from the recorded stream
    # (the streamed column-group program), compute-path results from the
    # stacked per-request program; the two differ in reduction order at
    # float noise, so each path is audited BIT-identical against its own
    # freshly re-run program, and a cross-program allclose guard catches
    # wrong-row serving (an index/L1 mix-up is an O(1) relative error,
    # not an O(1e-10) reduction-order one)
    fwd_ref = SwiftlyForward(config, facet_tasks, lru_forward=2,
                             queue_size=64)
    ref_engine = IncrementalForward(
        config, facet_tasks,
        SpillCache(budget_bytes=spill_budget_bytes()),
    )
    ref_engine.record(subgrid_configs)
    stream_ref = ref_engine.feed()
    ref_cache = {}
    checked = mismatches = cross_mismatches = 0
    for sg, fr in tracked:
        res = fr.result
        if res is None or not res.ok:
            continue
        key = (sg.off0, sg.off1)
        if key not in ref_cache:
            srow = stream_ref.lookup(sg)
            ref_cache[key] = (
                np.asarray(fwd_ref.get_subgrid_task(sg)),
                None if srow is None else np.asarray(srow),
            )
        compute_ref, cache_ref = ref_cache[key]
        expected = (
            cache_ref
            if res.path == "cache" and cache_ref is not None
            else compute_ref
        )
        got = np.asarray(res.data)
        checked += 1
        if not np.array_equal(got, expected):
            mismatches += 1
        if not np.allclose(got, compute_ref, rtol=1e-4, atol=1e-8):
            cross_mismatches += 1

    n_ok = sum(
        1 for _sg, fr in tracked
        if fr.result is not None and fr.result.ok
    )
    zero_lost = n_ok == len(tracked)
    victim_cycle = (
        [t["to"] for t in stats["breakers"][str(victim)]["transitions"]]
        if victim is not None else []
    )
    n_cols = len({sg.off0 for sg in subgrid_configs})
    shed_hints = [
        r.result.retry_after_s
        for r in brownout_shed
        if r.result is not None and r.result.retry_after_s is not None
    ]
    cache_stats = fabric.stats()
    qps_ratio = (
        autoscale_phase_rps / single_service_qps
        if single_service_qps else 0.0
    )
    record = {
        "metric": (
            f"{name} self-healing serve fleet "
            f"({len(tracked)} zipf requests over {n_cols} columns, "
            f"{n_replicas} replicas + cache fabric, kill+restore+"
            f"autoscale drill, planar f32, {platform})"
        ),
        "value": round(wall, 4),
        "unit": "s",
        "throughput_rps": (
            round(stats["served"] / wall, 2) if wall else 0.0
        ),
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "n_requests": stats["requests"],
        "n_served": stats["served"],
        "n_shed": stats["shed"],
        "bit_identical": {
            "checked": checked,
            "mismatches": mismatches,
            "cross_program_mismatches": cross_mismatches,
        },
        "fleet": {
            "n_replicas": n_replicas,
            "victim": victim,
            "replica_deaths": len(victims),
            "restores": stats["restores"],
            "failovers": stats["failovers"],
            "reroutes": stats["reroutes"],
            "hedges": stats["hedges"],
            "hedge_wins": stats["hedge_wins"],
            "route_faults": stats["route_faults"],
            "zero_lost": zero_lost,
            "p99_before_ms": p99_before,
            "p99_during_ms": p99_during,
            "p99_after_ms": p99_after,
            "p99_recovery_ratio": (
                round(p99_after / p99_before, 3) if p99_before else None
            ),
            "breaker_cycle": victim_cycle,
            "admission": stats["admission"],
            "breakers": stats["breakers"],
            "health_transitions": stats["health"]["transitions"],
            "zombie_beats": stats["health"]["zombie_beats"],
            "brownout": {
                **stats["brownout"],
                "level_max": level_max,
                "per_request_dispatch": per_request_dispatch,
                "batch_restored": batch_restored,
                "retry_after_hints": [
                    round(h, 4) for h in shed_hints[:8]
                ],
            },
            "per_replica": stats["per_replica"],
            "stream_copies": stats["stream_copies"],
            "scale_outs": stats["scale_outs"],
            "drains": stats["drains"],
            "retired": stats["retired"],
            "autoscale": stats.get("autoscale"),
            "p99_elastic_ms": p99_elastic,
        },
        "cache": {
            **cache_stats,
            "plan": {
                "l1_rows": cache_plan.l1_rows,
                "break_even_l1_rows": cache_plan.break_even_l1_rows,
                "expected_wall_s": round(cache_plan.expected_wall_s, 9),
                "coeffs_source": cache_plan.coeffs_source,
            },
            "single_service_qps": round(single_service_qps, 2),
            "autoscale_phase_rps": round(autoscale_phase_rps, 2),
            "qps_equivalent_ratio": round(qps_ratio, 2),
        },
        "zipf": {"s": zipf_s, "n_columns": n_cols, "seed": seed},
        "fleet_telemetry": fleet_telemetry,
        "alerts": alerts_block,
        "n_subgrids_cover": len(subgrid_configs),
        "manifest": run_manifest(
            params={"config": name, "mode": "fleet", **params},
        ),
    }
    if orecorder is not None:
        pm_path = os.path.splitext(out_path)[0] + "_postmortem.jsonl"
        orecorder.dump(
            pm_path, "WorkerKilled",
            reason=f"replica {victim} killed mid-burst",
        )
        record["post_mortem"] = dict(
            kill_post_mortem
            or orecorder.post_mortem("drill_complete")
        )
        record["post_mortem"]["dump_path"] = pm_path
    if metrics.enabled():
        record["telemetry"] = metrics.export()
    if trace_path:
        from swiftly_tpu.obs import summarize_trace

        summary = summarize_trace(
            otrace.export(), root_id=getattr(fleet_span, "id", None)
        )
        summary["leg_wall_s"] = round(wall, 6)
        record["trace"] = summary
        otrace.save(trace_path)
        otrace.disable()

    from swiftly_tpu.obs import (
        validate_alerts_artifact,
        validate_fleet_telemetry_artifact,
    )

    problems = validate_fleet_artifact(record)
    problems.extend(validate_fleet_telemetry_artifact(record))
    problems.extend(validate_alerts_artifact(record))
    if smoke_mode:
        # drill outcomes: the schema passing is not proof the fleet
        # actually healed
        if len(victims) != 1:
            problems.append(
                f"expected exactly 1 replica death, got {victims}"
            )
        if not zero_lost:
            problems.append(
                f"lost requests: {len(tracked) - n_ok} of "
                f"{len(tracked)} not served"
            )
        if mismatches or checked != n_ok:
            problems.append(
                f"bit-identity audit failed: {mismatches} mismatches, "
                f"{checked}/{n_ok} checked"
            )
        if cross_mismatches:
            problems.append(
                f"cross-program audit failed: {cross_mismatches} "
                "cache-path results diverge from per-request compute "
                "beyond reduction-order noise (wrong-row serving)"
            )
        if stats["failovers"] < 1:
            problems.append("the kill produced no failover")
        for state in ("open", "half_open", "closed"):
            if state not in victim_cycle:
                problems.append(
                    f"victim breaker never reached {state!r} "
                    f"(cycle: {victim_cycle})"
                )
        if p99_before and p99_after > 1.5 * p99_before:
            problems.append(
                f"p99 did not recover: {p99_after}ms after vs "
                f"{p99_before}ms before (> 1.5x)"
            )
        if not any(
            h["owner"] == victim and h["to"] == "revoked"
            for h in stats["health"]["transitions"]
        ):
            problems.append("victim lease was never revoked")
        if stats["route_faults"] < 1:
            problems.append(
                "injected fleet.route faults never fired/retried"
            )
        if stats["brownout"]["sheds"] < 1 or not shed_hints:
            problems.append(
                "brownout rung 1 shed nothing (or sheds carried no "
                "retry_after_s hint)"
            )
        if level_max < 2 or not per_request_dispatch:
            problems.append(
                f"brownout never reached per-request dispatch "
                f"(level_max={level_max})"
            )
        if not batch_restored:
            problems.append(
                "brownout recovery did not restore max_batch"
            )
        # cache fabric + autoscale drill outcomes
        if cache_stats["resident_stream_copies"] != 1:
            problems.append(
                f"fabric reports {cache_stats['resident_stream_copies']}"
                " resident stream copies, not 1"
            )
        if stats["stream_copies"] != 1:
            problems.append(
                f"fleet reports stream_copies={stats['stream_copies']}"
                " with a fabric attached"
            )
        if len(fleet.replicas) < 3:
            problems.append(
                f"fleet ended with {len(fleet.replicas)} replicas "
                "(need >= 3 sharing the one resident stream)"
            )
        if cache_stats["hit_ratio"] < 0.5:
            problems.append(
                f"fabric hit_ratio {cache_stats['hit_ratio']} < 0.5: "
                "the drill should serve mostly from the shared cache"
            )
        if stats["scale_outs"] < 1:
            problems.append(
                "autoscaler never scaled out under the sustained burst"
            )
        if stats["drains"] < 1:
            problems.append(
                "autoscaler never drained the scaled-out replica back"
            )
        if len(fleet.replicas) != n_replicas:
            problems.append(
                f"fleet did not drain back to {n_replicas} replicas "
                f"(has {len(fleet.replicas)})"
            )
        if qps_ratio < 10.0:
            problems.append(
                f"autoscale-phase throughput is only {qps_ratio:.1f}x "
                "the single-service compute QPS (need >= 10x)"
            )
        if p99_before and p99_elastic > 1.5 * p99_before:
            problems.append(
                f"p99 not held through elastic churn: {p99_elastic}ms "
                f"vs {p99_before}ms before (> 1.5x)"
            )
        # control-tower drill outcomes: the forced ladder must have
        # burned the brownout SLO open and the step-down closed it,
        # and the kill's post-mortem must tell the failure story
        if alerts_block["opened"] < 1:
            problems.append(
                "SLO burn-rate alert never opened under the forced "
                f"brownout ladder: {alerts_block}"
            )
        if alerts_block["open"]:
            problems.append(
                f"alerts still open at drill end: {alerts_block['open']}"
            )
        if not any(
            e["slo"] == "brownout_engaged" for e in alerts_block["events"]
        ):
            problems.append(
                "the brownout_engaged SLO never appears in the alert "
                f"event log: {alerts_block['events']}"
            )
        if orecorder is not None:
            pm_kinds = record["post_mortem"]["by_kind"]
            pm_names = [
                e["name"] for e in record["post_mortem"]["events"]
            ]
            if not any(
                n.startswith("fault.injected.fleet.replica.kill")
                for n in pm_names
            ):
                problems.append(
                    "kill post-mortem tail missing the injected "
                    f"fleet.replica.kill fault: {pm_names}"
                )
            if "fleet.replica_death" not in pm_names:
                problems.append(
                    "kill post-mortem tail missing the replica death "
                    f"event: {pm_names}"
                )
            for kind in ("fault", "fleet", "lease"):
                if not pm_kinds.get(kind):
                    problems.append(
                        f"kill post-mortem recorded no {kind!r} "
                        f"events: {pm_kinds}"
                    )
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    if smoke_mode:
        metrics.disable()
        print(
            json.dumps(
                {
                    "fleet_smoke": "ok" if not problems else "failed",
                    "config": name,
                    "artifact": out_path,
                    "n_served": stats["served"],
                    "victim": victim,
                    "failovers": stats["failovers"],
                    "p99_before_ms": p99_before,
                    "p99_after_ms": p99_after,
                    "breaker_cycle": victim_cycle,
                    "stream_copies": stats["stream_copies"],
                    "hit_ratio": cache_stats["hit_ratio"],
                    "scale_outs": stats["scale_outs"],
                    "drains": stats["drains"],
                    "qps_equivalent_ratio": round(qps_ratio, 2),
                    "alerts_opened": alerts_block["opened"],
                    "alerts_open": len(alerts_block["open"]),
                    "recorder_events": (
                        record["post_mortem"]["n_events"]
                        if orecorder is not None else 0
                    ),
                    "problems": problems,
                }
            ),
            flush=True,
        )
        return 0 if not problems else 1
    print(json.dumps(record), flush=True)
    return 0 if not problems else 1


def procfleet_bench(smoke_mode=False):
    """`bench.py --procfleet [--smoke]`: the process-fleet SIGKILL drill.

    Runs ``BENCH_PROCFLEET_WORKERS`` (default 3, 2 under ``--smoke``)
    replicas as REAL OS processes behind `serve.ProcessFleet` — each a
    spawned worker hosting a `SubgridService` over its own prepared
    forward, speaking `serve.ipc`'s versioned length-prefixed frames,
    serving the parent's recorded stream through the shared spill
    directory (`SpillCache.export_manifest` → `SharedSpillReader` under
    the unchanged `CachedColumnFeed` gates) — and lands two REAL
    ``SIGKILL -9``s:

    1. **before** — a clean zipf window; its p99 is the baseline.
    2. **kill** — the same workload as a burst; mid-burst the hot
       column's preferred worker is SIGKILLed. Its silent socket misses
       lease beats → suspect → revoked; the breaker trips open; queued
       + in-flight requests fail over to the survivors. ZERO requests
       may be lost, and ``failover_ms`` (revocation → last failed-over
       request served) is the artifact's headline value.
    3. **restart** — the supervisor restarts the victim with capped
       backoff; its breaker is NOT reset — victim-preferred traffic
       drives the half-open probe path until the cycle reads
       open → half_open → closed; a clean window pins p99 recovery.
    4. **mid-L2-read kill** — a ``CONTROL`` frame arms a dwell inside
       the second victim's next `SharedSpillReader.get_row` (the worker
       announces the held mmap read via a flag file), and the SIGKILL
       lands INSIDE that window: the failed-over row re-served by a
       survivor must be bit-identical — entry files are immutable and
       renamed into place, so a worker killed mid-read can never leave
       a torn row for a survivor to observe.

    Before any of that, fleet start exercises startup hygiene against
    fabricated wreckage of a "crashed" previous run: a stale socket
    file is swept and a live decoy worker process (cmdline-marker
    matched, never pid alone) is reaped.

    Every served result is audited BIT-IDENTICAL against its serving
    path's reference — cache rows vs the parent's own recorded stream
    (the exact bytes the workers mmap), compute results vs per-request
    `get_subgrid_task` on a fresh forward — plus a cross-program
    allclose guard against wrong-row serving.

    The distributed observability plane runs throughout: every worker
    ships cumulative TELEMETRY frames on the heartbeat cadence into a
    `ControlTower` (``fleet_telemetry`` totals sum exactly across
    processes, surviving the deaths through the retired-generation
    ledger), traces its half of every request so
    `ProcessFleet.merged_trace` emits ONE timeline across all pids
    (clocks aligned via the HELLO offset estimates, ±rtt/2), and
    persists its flight-recorder ring as a crash-safe black box — the
    artifact's post-mortem shows each SIGKILL victim's OWN last events
    (the L2 dwell it held, the request in flight), exhumed by the
    supervisor. The artifact's ``procfleet`` block is validated by
    `obs.validate_procfleet_artifact`; with ``--smoke`` the drill
    outcomes are asserted and the leg exits nonzero on any problem
    (wired into tier-1 via tests/test_bench_smoke.py).
    """
    import signal
    import subprocess
    import tempfile

    import jax

    from swiftly_tpu import (
        SwiftlyConfig,
        SwiftlyForward,
        make_facet,
        make_full_facet_cover,
        make_full_subgrid_cover,
    )
    from swiftly_tpu.delta import IncrementalForward
    from swiftly_tpu.models import SWIFT_CONFIGS
    from swiftly_tpu.obs import (
        ControlTower,
        metrics,
        run_manifest,
        validate_procfleet_artifact,
    )
    from swiftly_tpu.obs import trace as otrace
    from swiftly_tpu.serve import ProcessFleet, make_worker_spec
    from swiftly_tpu.serve.fleet import _rendezvous_score
    from swiftly_tpu.utils import enable_compilation_cache
    from swiftly_tpu.utils.spill import SpillCache, spill_budget_bytes

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    if not otrace.enabled():
        # the merged cross-process timeline needs the router's tracer
        # live even when --trace didn't ask for an export on disk
        otrace.enable()
    orecorder = _maybe_enable_recorder()
    out_path = os.environ.get("BENCH_PROCFLEET_OUT", "BENCH_procfleet.json")
    if smoke_mode:
        os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
        metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get("BENCH_PROCFLEET_CONFIG", "1k[1]-n512-256")
    n_workers = int(os.environ.get(
        "BENCH_PROCFLEET_WORKERS", "2" if smoke_mode else "3"))
    per_phase = int(os.environ.get(
        "BENCH_PROCFLEET_PHASE_REQUESTS", "16" if smoke_mode else "48"))
    seed = int(os.environ.get("BENCH_PROCFLEET_SEED", "1234"))
    zipf_s = float(os.environ.get("BENCH_PROCFLEET_ZIPF_S", "1.1"))
    max_depth = int(os.environ.get("BENCH_PROCFLEET_DEPTH", "256"))
    max_batch = int(os.environ.get("BENCH_PROCFLEET_MAX_BATCH", "16"))
    dwell_s = float(os.environ.get("BENCH_PROCFLEET_DWELL_S", "1.5"))

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    platform = jax.devices()[0].platform
    config = SwiftlyConfig(backend="planar", dtype=jax.numpy.float32,
                           **params)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    sources = _bench_sources(config.image_size)
    facet_tasks = [
        (fc, make_facet(config.image_size, fc, sources))
        for fc in facet_configs
    ]

    # ONE recorded stream in the parent; its exported manifest is the
    # cross-process L2 every worker serves through the spill directory
    # (disk-backed: export_manifest forces every entry to its atomic
    # on-disk form for the workers to mmap)
    spill = SpillCache(budget_bytes=spill_budget_bytes(),
                       spill_dir=tempfile.gettempdir())
    engine = IncrementalForward(config, facet_tasks, spill)
    engine.record(subgrid_configs)

    spec = make_worker_spec(
        params, sources, max_depth=max_depth, max_batch=max_batch,
    )

    # fabricate the wreckage of a "crashed" previous fleet so start()'s
    # hygiene sweep has something real to clean: a run dir owned by a
    # dead pid holding a stale socket file and a pidfile pointing at a
    # LIVE decoy process whose cmdline carries the worker marker — the
    # sweep must remove the socket and SIGKILL the decoy (marker match,
    # never pid alone)
    run_root = os.path.join(
        tempfile.gettempdir(), f"swiftly_procfleet_bench_{os.getpid()}")
    stale_dir = os.path.join(run_root, "run-stale-crashed")
    os.makedirs(stale_dir, exist_ok=True)
    open(os.path.join(stale_dir, "worker-0.g1.sock"), "w").close()
    with open(os.path.join(stale_dir, "fleet.pid"), "w") as fh:
        fh.write("999999")  # long-dead owner pid
    decoy = subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(600)",
         "swiftly_tpu.serve.procfleet", "--worker"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    # wait for the exec: until then /proc/<pid>/cmdline still shows THIS
    # process's argv and the sweep would (rightly) refuse to signal it
    from swiftly_tpu.serve.procfleet import _cmdline_matches

    decoy_deadline = time.monotonic() + 10.0
    while (not _cmdline_matches(decoy.pid)
           and time.monotonic() < decoy_deadline):
        time.sleep(0.01)
    with open(os.path.join(stale_dir, "worker-0.pid"), "w") as fh:
        fh.write(str(decoy.pid))

    fleet = ProcessFleet(
        spec, n_workers, stream_spill=spill, run_root=run_root,
        lease_interval_s=0.02, miss_suspect=3, miss_revoke=6,
        breaker_threshold=3, breaker_reopen_s=0.3,
        breaker_max_reopen_s=4.0, half_open_probes=2,
        restart_backoff_s=0.2, restart_backoff_max_s=2.0,
        boot_deadline_s=240.0, worker_trace=True,
    )
    # the distributed observability plane: per-worker TELEMETRY
    # sources + fleet signals/SLOs under one control tower, ticked by
    # the fleet's own supervisor
    tower = ControlTower()
    fleet.register_tower(tower)

    workload, hot_off0 = _zipf_workload(
        subgrid_configs, per_phase, seed, zipf_s
    )

    fleet_span = otrace.span("bench.procfleet", cat="bench", config=name)
    t0 = time.time()
    fleet_span.__enter__()
    tracked = []
    try:
        fleet.start()
        # the decoy must be dead (it is our child: reap the zombie)
        try:
            decoy.wait(timeout=10.0)
            decoy_reaped = True
        except Exception:
            decoy_reaped = False
        orphans = {
            "orphans_reaped": fleet.counts["orphans_reaped"],
            "stale_sockets_swept": fleet.counts["stale_sockets_swept"],
            "decoy_reaped": decoy_reaped,
        }

        def run_phase(label, drain_timeout=120.0):
            phase = []
            for sg in workload:
                fr = fleet.submit(sg, priority=1)
                phase.append((sg, fr))
                tracked.append((sg, fr))
            if not fleet.drain(timeout_s=drain_timeout):
                log.error("phase %s did not drain", label)
            oks = [
                fr.result.latency_s
                for _sg, fr in phase
                if fr.result is not None and fr.result.ok
            ]
            return phase, oks

        # -- phase 1: clean baseline window -------------------------------
        _phase_a, lat_before = run_phase("before")
        p99_before = _lat_quantile_ms(lat_before, 0.99)

        # -- phase 2: SIGKILL -9 mid-burst --------------------------------
        # the victim is the hot column's preferred worker, so the burst's
        # head is queued/in-flight ON the victim when the kill lands
        victim = max(
            range(n_workers), key=lambda r: _rendezvous_score(hot_off0, r))
        phase_b = []
        burst_head = max(2, len(workload) // 3)
        for sg in workload[:burst_head]:
            fr = fleet.submit(sg, priority=1)
            phase_b.append((sg, fr))
            tracked.append((sg, fr))
        killed_pid = fleet.kill_worker(victim, signal.SIGKILL)
        for sg in workload[burst_head:]:
            fr = fleet.submit(sg, priority=1)
            phase_b.append((sg, fr))
            tracked.append((sg, fr))
        if not fleet.drain(timeout_s=120.0):
            log.error("kill phase did not drain")
        lat_during = [
            fr.result.latency_s
            for _sg, fr in phase_b
            if fr.result is not None and fr.result.ok
        ]
        p99_during = _lat_quantile_ms(lat_during, 0.99)
        # wait for DETECTION: the silent socket must miss enough beats
        # for the lease to revoke (trips the breaker, stamps the death)
        deadline = time.time() + 15.0
        while time.time() < deadline:
            w = fleet.worker(victim)
            if w.lease is not None and w.lease.revoked or w.dead:
                break
            time.sleep(0.005)
        # wait for EXHUMATION: _on_revoked digs up the victim's black
        # box and folds its tail into the parent's recorder — the dump
        # below must show the victim's own story, not just the silence
        deadline = time.time() + 10.0
        while (time.time() < deadline
               and fleet.counts["blackbox_exhumed"] < 1):
            time.sleep(0.005)
        kill_post_mortem = (
            orecorder.post_mortem(
                "WorkerSIGKILLed",
                reason=f"worker {victim} pid {killed_pid} killed -9",
            )
            if orecorder is not None else None
        )

        # -- phase 3: supervised restart + half-open → closed -------------
        deadline = time.time() + 60.0
        while time.time() < deadline:
            w = fleet.worker(victim)
            if w.ready and not w.dead and w.generation >= 2:
                break
            time.sleep(0.01)
        victim_cols = [
            sg for sg in subgrid_configs
            if max(range(n_workers),
                   key=lambda r: _rendezvous_score(sg.off0, r)) == victim
        ] or list(subgrid_configs)
        deadline = time.time() + 20.0
        i = 0
        while (
            fleet.worker(victim).breaker.state != "closed"
            and time.time() < deadline
        ):
            sg = victim_cols[i % len(victim_cols)]
            i += 1
            fr = fleet.submit(sg, priority=1)
            tracked.append((sg, fr))
            fleet.drain(timeout_s=30.0)
            time.sleep(0.02)
        _phase_c, lat_after = run_phase("after")
        p99_after = _lat_quantile_ms(lat_after, 0.99)

        # -- phase 4: SIGKILL while the victim holds an L2 read -----------
        fleet.drain(timeout_s=60.0)
        fleet.wait_ready(60.0)
        victim2 = next(
            r for r in range(n_workers) if r != victim)
        col2 = next(
            sg for sg in subgrid_configs
            if max(range(n_workers),
                   key=lambda r: _rendezvous_score(sg.off0, r)) == victim2)
        flag = fleet.dwell_flag_path(victim2)
        try:
            os.unlink(flag)
        except OSError:
            pass
        fleet.set_control(victim2, dwell_l2_s=dwell_s)
        time.sleep(0.05)  # let the worker ack the CONTROL frame
        fr2 = fleet.submit(col2, priority=1)
        tracked.append((col2, fr2))
        killed_mid_read = False
        deadline = time.time() + 30.0
        while time.time() < deadline:
            if os.path.exists(flag):
                # the worker is INSIDE get_row with the row mmapped
                fleet.kill_worker(victim2, signal.SIGKILL)
                killed_mid_read = True
                break
            time.sleep(0.002)
        if not fleet.drain(timeout_s=60.0):
            log.error("mid-L2-read kill phase did not drain")
        res2 = fr2.result
        row_ref = engine.feed().lookup(col2)
        row_bit_identical = bool(
            res2 is not None and res2.ok and row_ref is not None
            and np.array_equal(np.asarray(res2.data), np.asarray(row_ref))
        )
        mid_l2_kill = {
            "killed_mid_read": killed_mid_read,
            "row_bit_identical": row_bit_identical,
            "dwell_s": dwell_s,
            "victim": victim2,
            "served_by_path": None if res2 is None else res2.path,
        }
        # wait for the SECOND exhumation (victim2's black box holds
        # the dwell + in-flight request the kill interrupted), then
        # capture the post-mortem that must show them
        deadline = time.time() + 15.0
        while (time.time() < deadline
               and fleet.counts["blackbox_exhumed"] < 2):
            time.sleep(0.005)
        final_post_mortem = (
            orecorder.post_mortem(
                "WorkerSIGKILLedMidL2Read",
                reason=f"worker {victim2} killed -9 inside an L2 read",
            )
            if orecorder is not None else None
        )
        # let victim2's restart land so stop() drains a whole fleet
        deadline = time.time() + 60.0
        while time.time() < deadline:
            w2 = fleet.worker(victim2)
            if w2.ready and not w2.dead:
                break
            time.sleep(0.01)

        fleet.drain(timeout_s=60.0)
        wall = time.time() - t0
        stats = fleet.stats(wall_s=wall)
        lost = fleet.lost_requests()
        fleet_telemetry = tower.fleet_telemetry()
        alerts_block = tower.alerts_block()
        # merge the fleet's timelines while the run dir still exists
        # (workers atomically publish on the heartbeat cadence,
        # throttled to one save per 0.5s — give the tail one beat)
        time.sleep(0.6)
        try:
            merged = fleet.merged_trace()
        except Exception:
            log.exception("cross-process trace merge failed")
            merged = None
    finally:
        try:
            fleet.stop(drain=True)
        except Exception:
            log.exception("fleet stop failed")
        if decoy.poll() is None:  # hygiene sweep failed: don't leak it
            decoy.kill()
            decoy.wait(timeout=5.0)
        import shutil as _shutil

        _shutil.rmtree(run_root, ignore_errors=True)
    fleet_span.__exit__(None, None, None)

    # -- bit-identity audit: every served result vs ITS path's fresh
    # reference. Cache rows must equal the parent's own recorded stream
    # (the workers mmap those exact bytes through the exported
    # manifest); compute results must equal per-request
    # get_subgrid_task on a fresh forward; the cross-program allclose
    # guard catches wrong-row serving either way.
    fwd_ref = SwiftlyForward(config, facet_tasks, lru_forward=2,
                             queue_size=64)
    stream_ref = engine.feed()
    ref_cache = {}
    checked = mismatches = cross_mismatches = 0
    for sg, fr in tracked:
        res = fr.result
        if res is None or not res.ok:
            continue
        key = (sg.off0, sg.off1)
        if key not in ref_cache:
            srow = stream_ref.lookup(sg)
            ref_cache[key] = (
                np.asarray(fwd_ref.get_subgrid_task(sg)),
                None if srow is None else np.asarray(srow),
            )
        compute_ref, cache_ref = ref_cache[key]
        expected = (
            cache_ref
            if res.path == "cache" and cache_ref is not None
            else compute_ref
        )
        got = np.asarray(res.data)
        checked += 1
        if not np.array_equal(got, expected):
            mismatches += 1
        if not np.allclose(got, compute_ref, rtol=1e-4, atol=1e-8):
            cross_mismatches += 1

    n_ok = sum(
        1 for _sg, fr in tracked
        if fr.result is not None and fr.result.ok
    )
    victim_cycle = [
        t["to"] for t in stats["breakers"][victim]["transitions"]
    ]

    # -- distributed observability plane: trace merge + black box -----
    merged_path = None
    trace_merge = None
    if merged is not None:
        merged_path = (
            os.path.splitext(out_path)[0] + "_merged_trace.json")
        with open(merged_path, "w") as fh:
            json.dump(merged, fh)
        meta = merged.get("otherData") or {}
        router_pid = os.getpid()
        cross_requests = sum(
            1 for ev in merged.get("traceEvents") or []
            if isinstance(ev, dict) and ev.get("ph") == "X"
            and (ev.get("args") or {}).get("xpid") == router_pid
        )
        trace_merge = {
            "n_processes": meta.get("n_processes"),
            "pids": meta.get("pids"),
            "n_spans": meta.get("n_spans"),
            "clock_offsets": meta.get("clock_offsets"),
            "cross_process_requests": cross_requests,
            "merged_trace_path": merged_path,
        }

    def _victim_event(pm, rid, name):
        """Did the rid's OWN `name` event (exhumed from its black box,
        `[worker-<rid> ...]`-prefixed) reach this post-mortem tail?"""
        return any(
            isinstance(e, dict) and e.get("name") == name
            and f"[worker-{rid} " in str(e.get("detail", ""))
            for e in ((pm or {}).get("events") or [])
        )

    victim_events_in_pm = bool(
        _victim_event(final_post_mortem, victim2, "proc.l2_dwell")
        or _victim_event(kill_post_mortem, victim, "proc.request")
    )
    n_cols = len({sg.off0 for sg in subgrid_configs})
    failover_ms = stats["failover_ms"]
    record = {
        "metric": (
            f"{name} process-fleet SIGKILL drill "
            f"({len(tracked)} zipf requests over {n_cols} columns, "
            f"{n_workers} worker processes, kill+restart+mid-L2-read "
            f"kill, planar f32, {platform})"
        ),
        "value": round(wall, 4),
        "unit": "s",
        "throughput_rps": (
            round(stats["served"] / wall, 2) if wall else 0.0
        ),
        "p50_ms": stats["p50_ms"],
        "p99_ms": stats["p99_ms"],
        "n_requests": stats["requests"],
        "n_served": stats["served"],
        "n_shed": stats["shed"],
        "bit_identical": {
            "checked": checked,
            "mismatches": mismatches,
            "cross_program_mismatches": cross_mismatches,
        },
        "procfleet": {
            "n_workers": n_workers,
            "victim": victim,
            "victim_pid": killed_pid,
            "worker_deaths": stats["worker_deaths"],
            "restarts": stats["restarts"],
            "failovers": stats["failovers"],
            "reroutes": stats["reroutes"],
            "lost_requests": lost,
            "failover_ms": failover_ms,
            "failover_episodes": stats["failover_episodes"],
            "p99_before_ms": p99_before,
            "p99_during_ms": p99_during,
            "p99_after_ms": p99_after,
            "p99_recovery_ratio": (
                round(p99_after / p99_before, 3) if p99_before else None
            ),
            "breaker_cycle": victim_cycle,
            "breakers": {
                str(rid): b for rid, b in stats["breakers"].items()
            },
            "health_transitions": stats["health"]["transitions"],
            "per_worker": stats["per_worker"],
            "orphans": orphans,
            "mid_l2_kill": mid_l2_kill,
            "wire": {
                "heartbeats": stats["heartbeats"],
            },
            "telemetry": stats["telemetry"],
            "clock_offsets": stats["clock_offsets"],
            "trace_merge": trace_merge,
            "black_box": {
                **stats["black_box"],
                "victim_events_in_post_mortem": victim_events_in_pm,
            },
        },
        "fleet_telemetry": fleet_telemetry,
        "alerts": alerts_block,
        "zipf": {"s": zipf_s, "n_columns": n_cols, "seed": seed},
        "n_subgrids_cover": len(subgrid_configs),
        "manifest": run_manifest(
            params={"config": name, "mode": "procfleet", **params},
        ),
    }
    if orecorder is not None:
        pm_path = os.path.splitext(out_path)[0] + "_postmortem.jsonl"
        orecorder.dump(
            pm_path, "WorkerSIGKILLed",
            reason=f"worker {victim} pid {killed_pid} killed -9",
        )
        record["post_mortem"] = dict(
            final_post_mortem
            or kill_post_mortem
            or orecorder.post_mortem("drill_complete")
        )
        record["post_mortem"]["dump_path"] = pm_path
    if metrics.enabled():
        record["telemetry"] = metrics.export()
    if trace_path:
        from swiftly_tpu.obs import summarize_trace

        summary = summarize_trace(
            otrace.export(), root_id=getattr(fleet_span, "id", None)
        )
        summary["leg_wall_s"] = round(wall, 6)
        record["trace"] = summary
        otrace.save(trace_path)
        otrace.disable()

    problems = validate_procfleet_artifact(record)
    if smoke_mode:
        # drill outcomes: schema passing is not proof the fleet survived
        if lost != 0:
            problems.append(f"lost requests: {lost}")
        if n_ok != len(tracked):
            problems.append(
                f"{len(tracked) - n_ok} of {len(tracked)} requests "
                "not served ok"
            )
        if mismatches or checked != n_ok:
            problems.append(
                f"bit-identity audit failed: {mismatches} mismatches, "
                f"{checked}/{n_ok} checked"
            )
        if cross_mismatches:
            problems.append(
                f"cross-program audit failed: {cross_mismatches} "
                "results diverge from per-request compute beyond "
                "reduction-order noise (wrong-row serving)"
            )
        if stats["worker_deaths"] < 2:
            problems.append(
                f"expected 2 real worker deaths (mid-burst + mid-L2-"
                f"read), got {stats['worker_deaths']}"
            )
        if stats["restarts"] < 1:
            problems.append("supervisor never restarted a dead worker")
        if stats["failovers"] < 1:
            problems.append("the SIGKILL produced no failover")
        for state in ("open", "half_open", "closed"):
            if state not in victim_cycle:
                problems.append(
                    f"victim breaker never reached {state!r} "
                    f"(cycle: {victim_cycle})"
                )
        if not any(
            h["owner"] == victim and h["to"] == "revoked"
            for h in stats["health"]["transitions"]
        ):
            problems.append("victim lease was never revoked")
        if not killed_mid_read:
            problems.append(
                "the dwell flag never appeared: the second kill did "
                "not land inside an L2 read"
            )
        if not row_bit_identical:
            problems.append(
                "the mid-L2-read kill's failed-over row is not "
                "bit-identical to the recorded stream"
            )
        if orphans["orphans_reaped"] < 1 or not orphans["decoy_reaped"]:
            problems.append(
                f"startup hygiene did not reap the decoy orphan: "
                f"{orphans}"
            )
        if orphans["stale_sockets_swept"] < 1:
            problems.append(
                "startup hygiene did not sweep the stale socket"
            )
        if stats["heartbeats"] < 10:
            problems.append(
                f"suspiciously few heartbeats on the wire: "
                f"{stats['heartbeats']}"
            )
        if p99_before and p99_after > 3.0 * p99_before:
            problems.append(
                f"p99 did not recover: {p99_after}ms after vs "
                f"{p99_before}ms before (> 3x)"
            )
        # observability-plane outcomes: the victim's OWN story must
        # survive the kill, and one timeline must span the fleet
        if not _victim_event(final_post_mortem, victim2,
                             "proc.l2_dwell"):
            problems.append(
                "the mid-L2-read victim's own proc.l2_dwell event "
                "never reached the parent's post-mortem (black box "
                "lost the dwell)"
            )
        if not _victim_event(final_post_mortem, victim2,
                             "proc.request"):
            problems.append(
                "the mid-L2-read victim's in-flight proc.request "
                "never reached the parent's post-mortem"
            )
        if trace_merge is None:
            problems.append("cross-process trace merge produced "
                            "nothing")
        else:
            if (trace_merge["n_processes"] or 0) < 2:
                problems.append(
                    f"merged timeline spans "
                    f"{trace_merge['n_processes']!r} process(es), "
                    "expected >= 2"
                )
            if trace_merge["cross_process_requests"] < 1:
                problems.append(
                    "no request span crossed a process boundary in "
                    "the merged timeline"
                )
        if len(stats["clock_offsets"]) < n_workers:
            problems.append(
                f"clock offsets estimated for only "
                f"{len(stats['clock_offsets'])} of {n_workers} workers"
            )
        cov = stats["telemetry"]["coverage"]
        if not isinstance(cov, (int, float)) or cov < 0.5:
            problems.append(
                f"telemetry coverage {cov!r}: TELEMETRY frames "
                "vouch for less than half the workers' live time"
            )
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    if smoke_mode:
        metrics.disable()
        print(
            json.dumps(
                {
                    "procfleet_smoke": "ok" if not problems else "failed",
                    "config": name,
                    "artifact": out_path,
                    "n_served": stats["served"],
                    "lost_requests": lost,
                    "victim": victim,
                    "failover_ms": failover_ms,
                    "worker_deaths": stats["worker_deaths"],
                    "restarts": stats["restarts"],
                    "breaker_cycle": victim_cycle,
                    "killed_mid_read": killed_mid_read,
                    "row_bit_identical": row_bit_identical,
                    "orphans_reaped": orphans["orphans_reaped"],
                    "stale_sockets_swept": orphans["stale_sockets_swept"],
                    "heartbeats": stats["heartbeats"],
                    "telemetry_frames": stats["telemetry"]["frames"],
                    "telemetry_coverage": stats["telemetry"]["coverage"],
                    "blackbox_exhumed": stats["blackbox_exhumed"],
                    "merged_processes": (
                        None if trace_merge is None
                        else trace_merge["n_processes"]),
                    "cross_process_requests": (
                        None if trace_merge is None
                        else trace_merge["cross_process_requests"]),
                    "problems": problems,
                }
            ),
            flush=True,
        )
        return 0 if not problems else 1
    print(json.dumps(record), flush=True)
    return 0 if not problems else 1


def _ensure_mesh_devices():
    """Device count for the mesh legs, called before any other JAX use.

    A virtual CPU mesh of ``BENCH_MESH_DEVICES`` (default 8) devices is
    built only when the CPU platform was asked for explicitly; otherwise
    the real devices are used as they are, so on a chip host the mesh
    legs never move to the CPU."""
    import __graft_entry__ as ge

    if ge._cpu_requested():
        ge._ensure_devices(int(os.environ.get("BENCH_MESH_DEVICES", "8")))
    import jax

    return len(jax.devices())


def mesh_bench(smoke_mode=False):
    """`bench.py --mesh [--smoke]`: the mesh-streamed engine leg.

    Runs the SAME spill-cached, facet-partitioned streamed round trip
    twice — once on the single-chip engine, once on the mesh-streamed
    engine (`swiftly_tpu.mesh`) with the facet stack sharded over every
    device — and stamps a ``mesh`` artifact block: the executed layout
    (shards, padding), the plan's ICI collective bytes, scaling
    efficiency vs single-chip, the reduction-order match audit
    (per-facet math is identical; only the forward collective's
    facet-sum order differs — asserted within BENCH_MESH_TOL, default
    5e-5 relative, docs/multichip.md), and an HLO audit showing the
    facet-axis collective in the lowered streamed column pass: the
    all-reduce under psum, the 2(n-1) collective-permute pipeline under
    SWIFTLY_MESH_COLLECTIVE=ring. The executed collective is stamped
    in the artifact and must MATCH the planned one
    (``plan_compiled.mesh.collective``); under ring the leg also times
    a psum baseline on the same geometry and records the ring-vs-psum
    wall ratio. Both paths are warmed (compile + first dispatch)
    before timing — BENCH_MESH_WARM=0 restores the cold wall. The
    compiled plan's `MeshLayout` is consumed by the engine, so the
    stamped ``plan_compiled.mesh.status`` is ``"bound"``. Validated by
    `obs.validate_mesh_artifact`.

    With ``JAX_PLATFORMS=cpu`` the leg builds a virtual CPU mesh of
    ``BENCH_MESH_DEVICES`` (default 8) devices; elsewhere it runs on
    every real device present. ``BENCH_MESH_CONFIG`` sets the config.
    """
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    n_av = _ensure_mesh_devices()  # before any other jax use
    problems = []
    if n_av < 2:
        print(
            json.dumps(
                {
                    "mesh_smoke" if smoke_mode else "mesh": "failed",
                    "problems": [
                        f"mesh leg needs >= 2 devices, found {n_av}; "
                        "for a virtual CPU mesh set JAX_PLATFORMS=cpu"
                    ],
                }
            ),
            flush=True,
        )
        return 1
    from swiftly_tpu.obs import (
        metrics,
        run_manifest,
        validate_mesh_artifact,
        validate_plan_accuracy_artifact,
        validate_plan_artifact,
    )

    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    out_path = os.environ.get("BENCH_MESH_OUT", "BENCH_mesh.json")
    metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
    name = os.environ.get(
        "BENCH_MESH_CONFIG",
        "1k[1]-n512-256" if smoke_mode else "4k[1]-n2k-512",
    )
    import re

    import jax
    import jax.numpy as jnp

    from swiftly_tpu import SWIFT_CONFIGS
    from swiftly_tpu.mesh import (
        MeshStreamedBackward,
        MeshStreamedForward,
        make_facet_mesh,
    )
    from swiftly_tpu.parallel import StreamedBackward
    from swiftly_tpu.plan import PlanInputs, compile_plan
    from swiftly_tpu.utils.spill import SpillCache

    platform = jax.devices()[0].platform
    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    config, fwd, facet_configs, subgrid_configs, _sources = _build(
        "planar", params, jnp.float32, streamed=True
    )
    F = len(facet_configs)
    half = max(1, F // 2)
    subsets = [(0, half), (half, F)] if F > 1 else [(0, F)]
    fold_group = int(os.environ.get("BENCH_FOLD_GROUP", "2"))

    def _passes_counter():
        return (metrics.export().get("counters") or {}).get(
            "fwd.passes", 0
        )

    # feed-once/fold-many parity: the mesh backward consumes the SAME
    # schedule helper as the single-chip leg (one shared feed per chunk
    # of `feed_group` facet-subset passes). Default 1 keeps the
    # cache-fed feed exercised under sharding (a single shared feed
    # would never re-read the cache).
    feed_group_env = max(
        1, int(os.environ.get("BENCH_BWD_FEED_GROUP", "1"))
    )

    def roundtrip(fwd_exec, make_bwd):
        """Spill-cached facet-partitioned round trip: ONE forward pass
        records the stream, every later facet-subset FEED is cache-fed
        (identical shape to `run_one`'s roundtrip-streamed leg,
        including the feed-once/fold-many schedule)."""
        from swiftly_tpu.parallel import feed_backward_passes

        spill = SpillCache(budget_bytes=2e9)
        parts = []
        t0 = time.time()
        for kfeed, c0 in enumerate(
            range(0, len(subsets), feed_group_env)
        ):
            chunk = subsets[c0 : c0 + feed_group_env]
            bwds = [make_bwd(i0, i1) for i0, i1 in chunk]
            feed_backward_passes(
                fwd_exec, subgrid_configs, bwds, spill=spill,
                feed_index=kfeed,
            )
            parts.extend(np.asarray(bwd.finish()) for bwd in bwds)
        wall = time.time() - t0
        return np.concatenate(parts, axis=0), wall, spill

    # warm both engines before timing: the first round trip carries
    # compile + first-dispatch cost, which used to land inside the
    # mesh wall and skew scaling_efficiency (BENCH_MESH_WARM=0 keeps
    # the cold wall for compile-cost studies)
    warm = os.environ.get("BENCH_MESH_WARM", "1") != "0"

    def measured_roundtrip(fwd_exec, make_bwd):
        if warm:
            roundtrip(fwd_exec, make_bwd)
        p0 = _passes_counter()
        out, wall, spill = roundtrip(fwd_exec, make_bwd)
        return out, wall, spill, _passes_counter() - p0

    # -- single-chip reference (the engine every prior PR measured) ------
    log.info("mesh leg: single-chip reference round trip (%s)", name)
    ref, wall_single, _spill1, single_passes = measured_roundtrip(
        fwd,
        lambda i0, i1: StreamedBackward(
            config, list(facet_configs[i0:i1]), residency="sampled",
            fold_group=fold_group,
        ),
    )

    # -- mesh-streamed run: the compiled layout, bound by the engine -----
    n_shards = min(n_av, F)
    plan = compile_plan(
        PlanInputs.from_cover(
            config, facet_configs, subgrid_configs, n_devices=n_shards,
            real_facets=getattr(fwd, "_facets_real", False),
            fold_group=fold_group,
        ),
        mode="roundtrip-streamed",
    )
    mesh = make_facet_mesh(n_devices=plan.mesh.facet_shards)
    facet_tasks = list(zip(facet_configs, fwd._facet_data))
    mfwd = MeshStreamedForward(
        config, facet_tasks, layout=plan.mesh, mesh=mesh
    )
    executed_collective = getattr(mfwd, "collective", "psum")
    planned_collective = getattr(plan.mesh, "collective", "psum")
    if executed_collective != planned_collective:
        problems.append(
            f"executed collective {executed_collective!r} != planned "
            f"{planned_collective!r} (plan_compiled.mesh.collective) — "
            "the env changed between compile and run"
        )
    log.info(
        "mesh leg: mesh-streamed round trip over %d shard(s) (%s)",
        mfwd.facet_shards, executed_collective,
    )

    def _mesh_bwd(i0, i1):
        return MeshStreamedBackward(
            config, list(facet_configs[i0:i1]), mesh=mesh,
            fold_group=fold_group,
        )

    got, wall_mesh, spill2, mesh_passes = measured_roundtrip(
        mfwd, _mesh_bwd
    )
    if mesh_passes != 1:
        problems.append(
            f"mesh round trip ran {mesh_passes} forward pass(es); the "
            "spill-cached plan must run exactly 1 (later passes "
            "cache-fed under sharding)"
        )

    # -- reduction-order match audit -------------------------------------
    scale = float(np.max(np.abs(ref))) or 1.0
    max_abs = float(np.max(np.abs(got - ref)))
    rms = float(np.sqrt(np.mean((got - ref) ** 2)))
    tol = float(os.environ.get("BENCH_MESH_TOL", "5e-5")) * scale
    if not max_abs <= tol:
        problems.append(
            f"mesh facets diverge from single-chip by {max_abs:.3e} "
            f"(> reduction-order tolerance {tol:.3e})"
        )

    # -- HLO audit: the facet-axis collective in the streamed stage ------
    from swiftly_tpu.parallel.streamed import _column_pass_fwd_sharded

    core = config.core
    xA = params["xA_size"]
    F_probe = mfwd.facet_shards
    colfn = _column_pass_fwd_sharded(core, mesh, xA)
    probe = (
        jnp.zeros(
            (F_probe, core.xM_yN_size, params["yB_size"], 2),
            dtype=core.dtype,
        ),
        jnp.zeros(F_probe, dtype=int),
        jnp.zeros(F_probe, dtype=int),
        jnp.zeros((3, 2), dtype=int),
        jnp.ones((3, xA), dtype=core.dtype),
        jnp.ones((3, xA), dtype=core.dtype),
    )
    hlo = colfn.lower(*probe).compile().as_text()
    n_all_reduce = len(re.findall(r"all-reduce(?:-start)?\(", hlo))
    n_permute = len(
        re.findall(r"collective-permute(?:-start)?\(", hlo)
    )
    if executed_collective == "ring":
        if not n_permute:
            problems.append(
                "ring collective requested but no collective-permute "
                "in the lowered streamed column pass (likely HLO "
                "text-format drift — see "
                "__graft_entry__.dryrun_multichip)"
            )
    elif not n_all_reduce:
        problems.append(
            "no all-reduce in the lowered streamed column pass (likely "
            "HLO text-format drift — see __graft_entry__.dryrun_multichip)"
        )

    # -- ring-vs-psum baseline: same geometry, blocking collective -------
    # Recorded whenever ring executed: the overlap claim is a RATIO
    # claim, so the artifact carries the psum wall it beat (or didn't —
    # CPU-simulated permutes share one memory bus, so the ratio is a
    # trend anchor there, meaningful on real ICI like the SE itself).
    collective_baseline = None
    if executed_collective == "ring":
        log.info("mesh leg: psum baseline round trip (same geometry)")
        prev_env = os.environ.get("SWIFTLY_MESH_COLLECTIVE")
        os.environ["SWIFTLY_MESH_COLLECTIVE"] = "psum"
        try:
            _, wall_psum, _, _ = measured_roundtrip(mfwd, _mesh_bwd)
        finally:
            if prev_env is None:
                del os.environ["SWIFTLY_MESH_COLLECTIVE"]
            else:
                os.environ["SWIFTLY_MESH_COLLECTIVE"] = prev_env
        collective_baseline = {
            "collective": "psum",
            "mesh_wall_s": round(wall_psum, 4),
            "scaling_efficiency": round(
                (wall_single / wall_psum) / mfwd.facet_shards, 4
            ),
            # > 1.0 = ring round trip beat the blocking psum
            "ring_vs_psum": round(wall_psum / wall_mesh, 4),
        }

    mesh_block = {
        "n_devices": int(n_av),
        "facet_shards": int(mfwd.facet_shards),
        "n_facets": F,
        "padded_facets": int(mfwd.stack.n_total),
        "collective_bytes": int(plan.mesh.collective_bytes_total),
        "single_chip_wall_s": round(wall_single, 4),
        "mesh_wall_s": round(wall_mesh, 4),
        # speedup per shard: 1.0 = linear scaling (CPU-simulated meshes
        # sit far below 1 — the number is the sentinel's trend anchor,
        # meaningful on real ICI)
        "scaling_efficiency": round(
            (wall_single / wall_mesh) / mfwd.facet_shards, 4
        ),
        "collective": executed_collective,
        "match": {
            "max_abs_diff": max_abs,
            "rms_diff": rms,
            "tolerance": tol,
            "within_tolerance": bool(max_abs <= tol),
            "bit_identical": bool(max_abs == 0.0),
        },
        "hlo": {
            "all_reduce": n_all_reduce,
            "collective_permute": n_permute,
            "stage": "fwd.column_pass",
        },
        "spill": spill2.stats(),
        "forward_passes": mesh_passes,
    }
    if collective_baseline is not None:
        mesh_block["collective_baseline"] = collective_baseline
    record = {
        "metric": f"{name} mesh-streamed round-trip wall-clock "
                  f"({len(subgrid_configs)} subgrids, planar f32, "
                  f"mesh-streamed, {platform})",
        "value": round(wall_mesh, 4),
        "unit": "s",
        "n_subgrids": len(subgrid_configs),
        "single_chip_wall_s": round(wall_single, 4),
        "single_chip_forward_passes": single_passes,
        "mesh": mesh_block,
        # the engine bound the layout above, so the stamped status is
        # "bound" — the acceptance contract validate_mesh_artifact checks
        "plan_compiled": plan.artifact_block(measured_wall_s=wall_mesh),
    }
    record["manifest"] = run_manifest(
        baseline_source=None,
        params={"config": name, "mode": "mesh-streamed", **params},
    )
    record["telemetry"] = metrics.export()
    # per-stage predicted-vs-measured reconciliation — the mesh leg is
    # where the plan's collective pricing (mesh.psum / mesh.ring_step)
    # meets its measured stage
    _stamp_plan_accuracy(record)
    problems.extend(validate_plan_accuracy_artifact(record))
    if trace_path:
        from swiftly_tpu.obs import summarize_trace
        from swiftly_tpu.obs import trace as otrace

        record["trace"] = summarize_trace(otrace.export())
        otrace.save(trace_path)
        otrace.disable()
    problems.extend(validate_mesh_artifact(record))
    problems.extend(validate_plan_artifact(record))
    import json as _json

    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    metrics.disable()
    print(
        json.dumps(
            {
                "mesh_smoke" if smoke_mode else "mesh": (
                    "ok" if not problems else "failed"
                ),
                "config": name,
                "artifact": out_path,
                "facet_shards": mesh_block["facet_shards"],
                "collective": executed_collective,
                "scaling_efficiency": mesh_block["scaling_efficiency"],
                **(
                    {"ring_vs_psum": collective_baseline["ring_vs_psum"]}
                    if collective_baseline
                    else {}
                ),
                "max_abs_diff": max_abs,
                "all_reduce": n_all_reduce,
                "collective_permute": n_permute,
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


def _delta_mutate(tasks, idxs, scale):
    """A content-bearing mutation of the facets at ``idxs``: scale the
    sparse descriptor's pixel values (a sky-model amplitude change —
    the K-of-J update the incremental engine exists for)."""
    from swiftly_tpu.ops.oracle import SparseRealFacet

    out = list(tasks)
    for i in idxs:
        fc, f = tasks[i]
        out[i] = (
            fc,
            SparseRealFacet(
                f.size, f.rows, f.cols,
                np.asarray(f.vals) * np.float32(scale),
            ),
        )
    return out


def delta_bench(smoke_mode=False):
    """`bench.py --delta [--smoke]`: the incremental re-transform leg.

    Records the full subgrid stream once (`delta.IncrementalForward`),
    then mutates K of the J facets (BENCH_DELTA_K, default "1,3") and
    times the incremental update — delta stream restricted to the K
    changed facets, cached stream patched in place — against the timed
    full re-record. Asserts: the engine took the PATCH path (its
    `plan.plan_delta` pricing agrees), the patched stream matches a
    fresh full recompute of the new stack within the documented f32
    sum-reorder tolerance (BENCH_DELTA_TOL, default 1e-4 relative —
    docs/incremental.md), and `SWIFTLY_DELTA_EXACT`-style updates
    (``exact=True``) are BIT-identical to the fresh recompute. Stamps a
    ``delta`` artifact block {changed_facets, patched_columns,
    speedup_vs_full, max_abs_diff, plan, match, exact} validated by
    `obs.validate_delta_artifact`; `scripts/delta_drill.py` is the
    operator entry.
    """
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    from swiftly_tpu.obs import (
        metrics,
        run_manifest,
        validate_delta_artifact,
    )

    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    out_path = os.environ.get("BENCH_DELTA_OUT", "BENCH_delta.json")
    metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
    name = os.environ.get(
        "BENCH_DELTA_CONFIG",
        "1k[1]-n512-256" if smoke_mode else "4k[1]-n2k-512",
    )
    import jax
    import jax.numpy as jnp

    from swiftly_tpu import (
        SWIFT_CONFIGS,
        SwiftlyConfig,
        make_full_facet_cover,
        make_full_subgrid_cover,
        make_sparse_facet,
    )
    from swiftly_tpu.delta import FacetDeltaLedger, IncrementalForward
    from swiftly_tpu.parallel import StreamedForward
    from swiftly_tpu.utils.spill import SpillCache

    platform = jax.devices()[0].platform
    problems = []
    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    config = SwiftlyConfig(backend="planar", dtype=jnp.float32, **params)
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    sources = _bench_sources(config.image_size)
    facet_tasks = [
        (fc, make_sparse_facet(config.image_size, fc, sources,
                               dtype=np.float32))
        for fc in facet_configs
    ]
    F = len(facet_configs)
    # only content-bearing facets make a real delta (scaling an empty
    # descriptor is content-identical and the ledger rightly ignores it)
    content = [
        j for j, (_, f) in enumerate(facet_tasks)
        if np.asarray(f.vals).size
    ]
    if not content:
        problems.append("no facet carries source pixels; nothing to mutate")
    ks = sorted({
        max(1, min(int(k), max(1, F - 1), len(content)))
        for k in os.environ.get("BENCH_DELTA_K", "1,3").split(",")
    })

    from swiftly_tpu.utils.spill import spill_budget_bytes

    spill = SpillCache(budget_bytes=spill_budget_bytes())
    engine = IncrementalForward(
        config, facet_tasks, spill, ledger=FacetDeltaLedger()
    )
    log.info("delta leg: warmup record (%s, %d facets)", name, F)
    engine.record(subgrid_configs)  # compile + layout warmup
    log.info("delta leg: timed full record")
    t0 = time.time()
    engine.record(subgrid_configs)
    wall_full = time.time() - t0

    def fresh_reference(tasks):
        """A fresh full stream of ``tasks`` into its own cache — the
        ground truth the patched stream is audited against."""
        ref = SpillCache(budget_bytes=spill_budget_bytes())
        rfwd = StreamedForward(config, tasks, residency="device")
        for _ in rfwd.stream_column_groups(subgrid_configs, spill=ref):
            pass
        return ref

    def audit(ref):
        mx = sc = 0.0
        for k in range(len(spill)):
            a = np.asarray(spill.get(k))
            b = np.asarray(ref.get(k))
            mx = max(mx, float(np.max(np.abs(a - b))))
            sc = max(sc, float(np.max(np.abs(b))))
        return mx, sc or 1.0

    legs = []
    scale_step = 1.5
    # under SWIFTLY_DELTA_EXACT=1 (delta_drill --exact) every update
    # replays by contract, and the audit tightens to bit-identity
    exact_env = os.environ.get("SWIFTLY_DELTA_EXACT") == "1"
    for kk in ks:
        idxs = content[:kk]
        # warm update: compiles the K-facet delta pass (a fresh
        # StreamedForward per update shares the lru-cached jits)
        scale_step += 0.25
        engine.update(_delta_mutate(engine.facet_tasks, idxs, scale_step))
        scale_step += 0.25
        tasks2 = _delta_mutate(engine.facet_tasks, idxs, scale_step)
        t0 = time.time()
        report = engine.update(tasks2)
        wall_patch = time.time() - t0
        if exact_env:
            if report["mode"] != "replay":
                problems.append(
                    f"K={kk} exact-mode update took mode "
                    f"{report['mode']!r}; SWIFTLY_DELTA_EXACT=1 must "
                    "force the full replay"
                )
        elif report["mode"] != "patch":
            problems.append(
                f"K={kk} update took mode {report['mode']!r} "
                f"(reason {report['reason']!r}); the drill must "
                "exercise the patch path"
            )
        mx, sc = audit(fresh_reference(engine.facet_tasks))
        tol = (
            0.0
            if exact_env
            else float(os.environ.get("BENCH_DELTA_TOL", "1e-4")) * sc
        )
        if not mx <= tol:
            problems.append(
                f"K={kk} patched stream diverges from fresh recompute "
                f"by {mx:.3e} (> f32 sum-reorder tolerance {tol:.3e})"
            )
        legs.append(
            {
                "k": kk,
                "changed_facets": list(report["changed_facets"]),
                "patched_columns": report["patched_columns"],
                "patched_entries": report["patched_entries"],
                "patch_wall_s": round(wall_patch, 4),
                "full_wall_s": round(wall_full, 4),
                "speedup_vs_full": round(wall_full / wall_patch, 2),
                "match": {
                    "max_abs_diff": mx,
                    "tolerance": tol,
                    "within_tolerance": bool(mx <= tol),
                    "bit_identical": bool(mx == 0.0),
                },
                "stream_version": report["stream_version"],
                "plan": report["plan"],
            }
        )
        log.info(
            "delta leg: K=%d patch %.3fs vs full %.3fs (%.1fx), "
            "max|diff| %.3e", kk, wall_patch, wall_full,
            wall_full / wall_patch, mx,
        )

    # exactness escape hatch: an exact update re-records and must be
    # BIT-identical to an independent fresh stream of the same stack
    exact_block = None
    if os.environ.get("BENCH_DELTA_EXACT_CHECK", "1") == "1" and content:
        tasks3 = _delta_mutate(engine.facet_tasks, content[:1], 0.8)
        rep3 = engine.update(tasks3, exact=True)
        ref3 = fresh_reference(engine.facet_tasks)
        bit = all(
            np.array_equal(
                np.asarray(spill.get(k)), np.asarray(ref3.get(k))
            )
            for k in range(len(spill))
        )
        exact_block = {"mode": rep3["mode"], "bit_identical": bool(bit)}
        if rep3["mode"] != "replay" or not bit:
            problems.append(
                f"exact update must replay bit-identically, got "
                f"{exact_block}"
            )

    head = legs[0] if legs else {}
    delta_block = {
        "n_facets": F,
        "changed_facets": head.get("changed_facets", []),
        "patched_columns": head.get("patched_columns", 0),
        "patched_entries": head.get("patched_entries", 0),
        "speedup_vs_full": head.get("speedup_vs_full", 0.0),
        "max_abs_diff": (head.get("match") or {}).get("max_abs_diff"),
        "match": head.get("match"),
        "plan": head.get("plan"),
        "exact": exact_block,
        "exact_mode": exact_env,
        "legs": legs,
        "spill": spill.stats(),
    }
    record = {
        "metric": f"{name} incremental K-facet update wall-clock "
                  f"({len(subgrid_configs)} subgrids, planar f32, "
                  f"delta, {platform})",
        "value": head.get("patch_wall_s", 0.0),
        "unit": "s",
        "n_subgrids": len(subgrid_configs),
        "full_record_wall_s": round(wall_full, 4),
        "delta": delta_block,
    }
    record["manifest"] = run_manifest(
        baseline_source=None,
        params={"config": name, "mode": "delta", **params},
    )
    record["telemetry"] = metrics.export()
    if trace_path:
        from swiftly_tpu.obs import summarize_trace
        from swiftly_tpu.obs import trace as otrace

        record["trace"] = summarize_trace(otrace.export())
        otrace.save(trace_path)
        otrace.disable()
    problems.extend(validate_delta_artifact(record))
    import json as _json

    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    metrics.disable()
    print(
        json.dumps(
            {
                "delta_smoke" if smoke_mode else "delta": (
                    "ok" if not problems else "failed"
                ),
                "config": name,
                "artifact": out_path,
                "speedup_vs_full": delta_block["speedup_vs_full"],
                "patched_columns": delta_block["patched_columns"],
                "max_abs_diff": delta_block["max_abs_diff"],
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


# Relative-RMS error budgets asserted by `bench.py --precision` — the
# code twin of the table in docs/accuracy.md ("Precision error budget").
# Relative RMS = abs RMS x N^2 (the unit-source scaling of accuracy.md;
# the bench's multi-source model with amplitudes up to 2.75 and a
# max-over-samples RMS measures ~2e-5 at the `highest` f32 floor).
# Budgets carry ~15x headroom over the measured floor so they trip on a
# real precision regression (`high`'s bf16x3 passes sit ~63x above the
# floor on TPU; a LOST `highest` flag therefore lands near ~1.3e-3,
# well past the 3e-4 budget) but never on run-to-run noise. On CPU both
# settings execute true f32 matmuls and land at the `highest` floor.
PRECISION_RMS_BUDGET_REL = {
    "highest": 3e-4,
    "high": 3e-2,
    "default": 3e-2,
}


def precision_child():
    """`bench.py --precision-child`: one precision setting, one process.

    `SWIFTLY_PRECISION` is baked into the lowered programs at TRACE
    time (ops.planar_backend), so each setting must run in its own
    interpreter — the parent (`precision_bench`) sets the env and
    spawns this, which streams the forward cover once warm + once
    timed and prints a single JSON line with the wall and the
    max-over-samples RMS vs the direct-DFT oracle.
    """
    import jax
    import jax.numpy as jnp

    from swiftly_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    name = os.environ.get("BENCH_PRECISION_CONFIG", "1k[1]-n512-256")
    from swiftly_tpu import SWIFT_CONFIGS

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    config, fwd, facet_configs, subgrid_configs, sources = _build(
        "planar", params, jnp.float32, streamed=True
    )
    sample_map, oracle_dev = _oracle_sample_stack(
        config, subgrid_configs, sources
    )

    def run_pass():
        max_rms2 = jnp.zeros((), dtype=jnp.float32)
        acc = None
        for items, out in fwd.stream_columns(
            subgrid_configs, device_arrays=True
        ):
            s = jnp.sum(out)
            acc = s if acc is None else acc + s
            for srow, (i, _sgc) in enumerate(items):
                k = sample_map.get(i)
                if k is not None:
                    max_rms2 = jnp.maximum(
                        max_rms2,
                        _rms2_device(config.core, out[srow], oracle_dev[k]),
                    )
        float(np.asarray(acc))
        return float(np.asarray(max_rms2)) ** 0.5

    run_pass()  # warm: compile + facet upload
    t0 = time.time()
    rms = run_pass()
    wall = time.time() - t0
    print(
        json.dumps(
            {
                "precision": os.environ.get(
                    "SWIFTLY_PRECISION", "highest"
                ).lower(),
                "config": name,
                "wall_s": round(wall, 4),
                "rms_vs_dft_oracle": float(f"{rms:.3e}"),
                "n_subgrids": len(subgrid_configs),
                "platform": jax.devices()[0].platform,
            }
        ),
        flush=True,
    )
    return 0


def precision_bench(smoke_mode=False):
    """`bench.py --precision [--smoke]`: the mixed-precision leg.

    Runs the streamed forward under each `SWIFTLY_PRECISION` setting
    (BENCH_PRECISION_SETTINGS, default "highest,high") in a SUBPROCESS
    each — the knob is baked in at trace time — and asserts every
    measured RMS against the explicit error budget table
    (`PRECISION_RMS_BUDGET_REL`, documented in docs/accuracy.md).
    The artifact's headline wall and ``rms_vs_dft_oracle`` come from
    the ``highest`` leg so `scripts/bench_compare.py` tracks both
    (wall and RMS lower-is-better).
    """
    import subprocess

    from swiftly_tpu.obs import run_manifest, validate_artifact

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    name = os.environ.get(
        "BENCH_PRECISION_CONFIG",
        "1k[1]-n512-256" if smoke_mode else "4k[1]-n2k-512",
    )
    out_path = os.environ.get(
        "BENCH_PRECISION_OUT", "BENCH_precision.json"
    )
    settings = [
        s.strip().lower()
        for s in os.environ.get(
            "BENCH_PRECISION_SETTINGS", "highest,high"
        ).split(",")
        if s.strip()
    ]
    from swiftly_tpu import SWIFT_CONFIGS

    params = dict(SWIFT_CONFIGS[name])
    n_img = params["N"]
    problems = []
    legs = []
    for setting in settings:
        budget_rel = PRECISION_RMS_BUDGET_REL.get(setting)
        if budget_rel is None:
            problems.append(
                f"no error budget for SWIFTLY_PRECISION={setting!r} "
                "(docs/accuracy.md table)"
            )
            continue
        env = dict(os.environ)
        env["SWIFTLY_PRECISION"] = setting
        env["BENCH_PRECISION_CONFIG"] = name
        log.info("precision leg: %s (subprocess)", setting)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--precision-child"],
            capture_output=True, text=True, env=env,
            timeout=float(os.environ.get("BENCH_PRECISION_TIMEOUT_S",
                                         "600")),
        )
        line = (proc.stdout.strip().splitlines() or [""])[-1]
        try:
            child = json.loads(line)
        except ValueError:
            problems.append(
                f"precision child {setting!r} emitted no JSON "
                f"(rc={proc.returncode}): "
                f"{(proc.stderr or '').strip()[-300:]}"
            )
            continue
        rel = child["rms_vs_dft_oracle"] * n_img * n_img
        leg = {
            **child,
            "rms_relative": float(f"{rel:.3e}"),
            "budget_relative": budget_rel,
            "within_budget": bool(rel <= budget_rel),
        }
        legs.append(leg)
        if not leg["within_budget"]:
            problems.append(
                f"SWIFTLY_PRECISION={setting}: relative RMS {rel:.3e} "
                f"over the documented budget {budget_rel:.1e} "
                "(docs/accuracy.md)"
            )
    head = next(
        (l for l in legs if l["precision"] == "highest"),
        legs[0] if legs else None,
    )
    if head is None:
        problems.append("no precision leg produced a measurement")
        head = {"wall_s": 0.0, "rms_vs_dft_oracle": 0.0, "platform": "?"}
    record = {
        "metric": f"{name} forward facet->subgrid wall-clock "
                  f"(SWIFTLY_PRECISION={head.get('precision', '?')}, "
                  f"planar f32, streamed, {head['platform']})",
        "value": head["wall_s"],
        "unit": "s",
        "rms_vs_dft_oracle": head["rms_vs_dft_oracle"],
        "precision": {
            "budget_relative": PRECISION_RMS_BUDGET_REL,
            "legs": legs,
        },
    }
    record["manifest"] = run_manifest(
        baseline_source=None,
        params={"config": name, "mode": "precision", **params},
    )
    problems.extend(validate_artifact(record, require_baseline=False))
    import json as _json

    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    print(
        json.dumps(
            {
                "precision_smoke" if smoke_mode else "precision": (
                    "ok" if not problems else "failed"
                ),
                "config": name,
                "artifact": out_path,
                "legs": [
                    {
                        "precision": l["precision"],
                        "wall_s": l["wall_s"],
                        "rms_relative": l["rms_relative"],
                        "within_budget": l["within_budget"],
                    }
                    for l in legs
                ],
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


def smoke():
    """Fast schema-validation leg (`bench.py --smoke`, wired into the
    tier-1 tests): run the 1k round trip with telemetry ON, write the
    BENCH-style artifact plus the JSONL event log, and validate what was
    emitted — full run manifest present, `baseline_source` set, >= 6
    distinct engine stage names, per-stage wall/MFU summary. Schema
    drift fails HERE, in seconds on CPU, not months later in an
    unauditable artifact."""
    from swiftly_tpu.obs import metrics, validate_artifact
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    out_path = os.environ.get("BENCH_SMOKE_OUT", "BENCH_smoke.json")
    jsonl_path = os.environ.get(
        "SWIFTLY_METRICS_JSONL", out_path + "l"
    )
    # placeholder roofline so the MFU arithmetic is exercised on CPU
    # (recorded in the manifest's env capture; a real run sets a
    # measured value or runs on a device with a published peak)
    os.environ.setdefault("SWIFTLY_PEAK_TFLOPS", "1.0")
    # force a 2-pass facet-partitioned backward so the spill-cache path
    # (fill + cache-fed pass) and its artifact fields are exercised on
    # CPU — the single-pass plan would never touch the cache. Feed
    # group pinned to 1 (per-pass feeding) for the same reason: CPU's
    # unlimited budget would share ONE feed across both passes and the
    # cache-fed h2d path (prefetch hits, spill.h2d) would never run
    os.environ.setdefault("BENCH_BWD_FACET_PASSES", "2")
    os.environ.setdefault("BENCH_BWD_FEED_GROUP", "1")
    # calibration history lands next to the smoke artifact unless the
    # operator pointed SWIFTLY_CALIBRATION_HISTORY elsewhere (0 = off)
    os.environ.setdefault(
        "SWIFTLY_CALIBRATION_HISTORY",
        os.path.join(
            os.path.dirname(os.path.abspath(out_path)),
            "BENCH_calibration.jsonl",
        ),
    )
    metrics.enable(jsonl_path)
    name = os.environ.get("BENCH_SMOKE_CONFIG", "1k[1]-n512-256")
    record = run_one(name, "roundtrip-streamed")
    problems = validate_artifact(record)
    telemetry = record.get("telemetry") or {}
    stages = telemetry.get("stages") or {}
    engine_stages = {
        s for s in stages if s.startswith(("fwd.", "bwd."))
    }
    if len(engine_stages) < 6:
        problems.append(
            f"expected >= 6 engine stage names, got {sorted(engine_stages)}"
        )
    for s, entry in stages.items():
        for field in ("count", "total_s", "mean_s", "p99_s"):
            if field not in entry:
                problems.append(f"stage {s} missing {field}")
    if not (telemetry.get("total") or {}).get("mfu_pct"):
        problems.append("telemetry total missing mfu_pct")
    # spill-cache schema: the 2-pass backward must have filled the cache
    # on pass 1 and fed pass 2 from it — exactly ONE forward pass
    # (the tentpole's cost model, counter-asserted), spill stats in the
    # artifact, and prefetch hits recorded
    spill_block = record.get("spill") or {}
    if not spill_block:
        problems.append("roundtrip-streamed artifact missing spill stats")
    else:
        for field in ("entries", "complete", "ram_bytes", "writes"):
            if field not in spill_block:
                problems.append(f"spill stats missing {field}")
        if not spill_block.get("complete"):
            problems.append(f"spill cache incomplete: {spill_block}")
    counters = telemetry.get("counters") or {}
    if record.get("forward_passes") != 1:
        problems.append(
            "cache-fed round trip must execute exactly 1 forward pass, "
            f"got forward_passes={record.get('forward_passes')} "
            f"(fwd.passes counter={counters.get('fwd.passes')})"
        )
    if not counters.get("spill.prefetch_hits"):
        problems.append(
            f"no spill prefetch hits in counters {sorted(counters)}"
        )
    # unified-plan schema: every roundtrip-streamed artifact now stamps
    # the compiled plan (inputs hash, pass grid, spill policy, predicted
    # vs measured wall) — drift fails here, on CPU, in seconds
    from swiftly_tpu.obs import validate_plan_artifact

    problems.extend(validate_plan_artifact(record))
    pc = record.get("plan_compiled") or {}
    bwd_plan = record.get("bwd_plan") or {}
    if (pc.get("backward") or {}).get("n_passes") != bwd_plan.get(
        "n_passes"
    ):
        problems.append(
            f"compiled plan n_passes {pc.get('backward')} disagrees "
            f"with the executed bwd_plan {bwd_plan}"
        )
    if "measured_wall_s" not in pc:
        problems.append("plan_compiled missing measured_wall_s")
    # colpass pedigree: the compiled plan resolves the same forward
    # column-pass body the executor binds (env + platform at both
    # sites), so a silent divergence — e.g. a plan priced for pallas
    # while the stream ran einsum — fails here, on CPU, in seconds
    executed_colpass = (record.get("plan") or {}).get("colpass")
    planned_colpass = (pc.get("forward") or {}).get("colpass")
    if executed_colpass != planned_colpass:
        problems.append(
            f"executed plan.colpass {executed_colpass!r} != compiled "
            f"plan_compiled.forward.colpass {planned_colpass!r}"
        )
    if not (pc.get("forward") or {}).get("colpass_candidates"):
        problems.append(
            "plan_compiled.forward missing the ranked "
            "colpass_candidates table"
        )
    # feed-once/fold-many schema: the executed schedule must match the
    # compiled one, the shared-feed stage must have been recorded, and
    # the h2d byte collapse must be exactly what the schedule promises
    # ((n_feeds - 1) x the recorded stream) — asserted from telemetry,
    # not inferred
    if (pc.get("backward") or {}).get("feed_group") != bwd_plan.get(
        "feed_group"
    ):
        problems.append(
            f"compiled plan feed_group {pc.get('backward')} disagrees "
            f"with the executed bwd_plan {bwd_plan}"
        )
    n_feeds = bwd_plan.get("n_feeds") or 0
    if record.get("feed_groups") != n_feeds:
        problems.append(
            f"executed feed_groups {record.get('feed_groups')} != "
            f"planned n_feeds {n_feeds}"
        )
    if "bwd.feed_group" not in stages:
        problems.append("telemetry missing the bwd.feed_group stage")
    # plan-accuracy ledger schema: every smoke run stamps the per-stage
    # predicted-vs-measured reconciliation, and the join must cover at
    # least 80% of the plan-priced stage wall — uncovered stages are
    # listed by name, so a timer falling out of the mapping fails HERE
    from swiftly_tpu.obs import validate_plan_accuracy_artifact

    problems.extend(validate_plan_accuracy_artifact(record))
    pa = record.get("plan_accuracy") or {}
    coverage = pa.get("coverage")
    if not isinstance(coverage, (int, float)) or coverage < 0.8:
        problems.append(
            f"plan_accuracy coverage {coverage!r} < 0.8 of plan-priced "
            f"stage wall (uncovered: {pa.get('uncovered')})"
        )
    stream_bytes = (record.get("spill") or {}).get("ram_bytes", 0) + (
        record.get("spill") or {}
    ).get("disk_bytes", 0)
    if stream_bytes and n_feeds:
        want = (n_feeds - 1) * stream_bytes
        if record.get("spill_h2d_bytes") != want:
            problems.append(
                f"spill.h2d moved {record.get('spill_h2d_bytes')} "
                f"bytes; the feed schedule promises (n_feeds-1) x "
                f"stream = {want}"
            )
    import json as _json

    with open(jsonl_path) as fh:
        jsonl_stages = {
            r["name"]
            for r in map(_json.loads, fh)
            if r.get("kind") == "stage"
        }
    if len({s for s in jsonl_stages if s.startswith(("fwd.", "bwd."))}) < 6:
        problems.append(
            f"JSONL event log has stage names {sorted(jsonl_stages)}, "
            "expected >= 6 engine stages"
        )
    if trace_path:
        problems.extend(_check_smoke_trace(record, trace_path))
    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    metrics.disable()
    print(
        json.dumps(
            {
                "smoke": "ok" if not problems else "failed",
                "config": name,
                "artifact": out_path,
                "jsonl": jsonl_path,
                "trace": trace_path,
                "n_engine_stages": len(engine_stages),
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


def _check_smoke_trace(record, trace_path):
    """Save + validate the smoke leg's timeline: structurally valid
    Chrome trace JSON (Perfetto-loadable), a trace block whose schema
    passes `validate_trace_artifact`, a critical path rooted at
    `bench.leg` whose wall matches the measured leg wall within 5%,
    and the engine stage vocabulary present as spans."""
    from swiftly_tpu.obs import report as oreport
    from swiftly_tpu.obs import trace as otrace
    from swiftly_tpu.obs import validate_trace_artifact

    problems = list(validate_trace_artifact(record))
    otrace.save(trace_path)
    otrace.disable()
    trace = oreport.load_trace(trace_path)
    problems += [
        f"trace file: {p}" for p in oreport.validate_trace_events(trace)
    ]
    tr = record.get("trace") or {}
    wall, leg_wall = tr.get("wall_s"), tr.get("leg_wall_s")
    if not wall or not leg_wall or abs(wall - leg_wall) > 0.05 * leg_wall:
        problems.append(
            f"critical-path root wall {wall} != measured leg wall "
            f"{leg_wall} within 5%"
        )
    if (tr.get("critical_path") or [{}])[0].get("name") != "bench.leg":
        problems.append(
            f"critical path does not start at bench.leg: "
            f"{tr.get('critical_path')}"
        )
    span_names = {
        s["name"] for s in oreport.build_tree(trace).values()
    }
    want = {"bench.leg", "fwd.column_group", "bwd.sampled_fold",
            "spill.write", "spill.read"}
    if not want <= span_names:
        problems.append(
            f"trace missing engine spans {sorted(want - span_names)}"
        )
    return problems


def run_chaos_drill(config_name, fault_plan=None, fold_group=2,
                    col_group=2):
    """The kill-and-resume chaos drill (`bench.py --chaos`, also driven
    by scripts/chaos_drill.py).

    1. Run a facet-partitioned sampled streamed backward UNDISTURBED
       (pass 1 records the subgrid stream into the spill cache, pass 2
       is cache-fed) — the reference facets, computed with NO fault
       plan installed (the clean path must stay hook-free).
    2. Re-run under an injected fault schedule: transient spill-read
       and h2d/d2h transfer IOErrors (the retry layer must absorb
       them), per-group checkpoint autosave, a bit-flipped newest
       checkpoint generation (restore must fall back a generation), and
       a worker death mid-pass-2 (`WorkerKilled` tears through every
       isolation layer).
    3. RESUME: fresh backward, restore from the surviving generation,
       skip the processed groups, finish.
    4. Assert the chaos run's facets are BIT-IDENTICAL to the
       undisturbed run's, and stamp the resilience block (faults
       injected/survived, retries, degradations, resume count) into a
       BENCH-style artifact validated by `obs.validate_resilience_artifact`.

    Bit-identity holds because every fold is deterministic and the
    ledger/autosave tick lands at column-GROUP boundaries only: the
    resumed feed re-dispatches exactly the fold programs the killed run
    would have, on a CRC-verified bit-exact accumulator.
    """
    import shutil
    import tempfile

    import jax.numpy as jnp

    from swiftly_tpu import SWIFT_CONFIGS
    from swiftly_tpu.obs import metrics
    from swiftly_tpu.parallel import StreamedBackward
    from swiftly_tpu.resilience import (
        FaultPlan,
        WorkerKilled,
        degrade,
        faults,
    )
    from swiftly_tpu.utils.checkpoint import (
        checkpoint_generations,
        restore_streamed_backward_state,
    )
    from swiftly_tpu.utils.spill import SpillCache

    params = dict(SWIFT_CONFIGS[config_name])
    params.setdefault("fov", 1.0)
    config, fwd, facet_configs, subgrid_configs, _sources = _build(
        "planar", params, jnp.float32, streamed=True
    )
    # deterministic column-group count: the fault schedule is indexed by
    # site call number, so the drill pins the group size instead of
    # letting the auto-sizer pick per-host values
    fwd.col_group = col_group
    n_cols = len({sg.off0 for sg in subgrid_configs})
    n_groups = -(-n_cols // col_group)
    if n_groups < 3:
        raise ValueError(
            f"chaos drill needs >= 3 column groups for its schedule "
            f"(kill after 2 autosaves); {config_name} with "
            f"col_group={col_group} has {n_groups}"
        )
    F = len(facet_configs)
    half = max(1, F // 2)
    subsets = [(0, half), (half, F)] if F > 1 else [(0, F)]

    work_dir = tempfile.mkdtemp(prefix="chaos_drill_")
    ck_paths = [
        os.path.join(work_dir, f"ck_pass{i}.npz")
        for i in range(len(subsets))
    ]

    def feed(bwd, spill, skip=()):
        skip = set(skip)
        for per_col, group in fwd.stream_column_groups(
            subgrid_configs, spill=spill
        ):
            keys = [
                (sg.off0, sg.off1) for col in per_col for _, sg in col
            ]
            if skip and all(k in skip for k in keys):
                continue
            bwd.add_subgrid_group(
                [[sg for _, sg in col] for col in per_col], group
            )

    def run_passes(spill, autosave=False, resume=False):
        outs = []
        for idx, (i0, i1) in enumerate(subsets):
            bwd = StreamedBackward(
                config, list(facet_configs[i0:i1]),
                residency="sampled", fold_group=fold_group,
            )
            skip = ()
            if resume and checkpoint_generations(ck_paths[idx]):
                skip = restore_streamed_backward_state(
                    ck_paths[idx], bwd
                )
            if autosave:
                bwd.enable_autosave(ck_paths[idx], every_subgrids=1)
            feed(bwd, spill, skip)
            outs.append(np.asarray(bwd.finish_device()))
        return np.concatenate(outs, axis=0)

    try:
        # --- undisturbed reference (clean path: no plan installed) ----
        assert faults.current() is None
        t0 = time.time()
        spill_ref = SpillCache()
        ref = run_passes(spill_ref)
        clean_s = time.time() - t0

        # --- the fault schedule --------------------------------------
        # bwd.feed is called once per group per pass; the kill lands on
        # pass 2's third group, after two autosaved generations — so the
        # corrupted newest generation has a good predecessor to fall
        # back to.
        kill_at = n_groups + 2
        if fault_plan is None:
            fault_plan = FaultPlan(
                faults=[
                    {"site": "spill.read", "kind": "ioerror", "at": 1},
                    {"site": "transfer.d2h", "kind": "ioerror", "at": 1},
                    {"site": "transfer.h2d", "kind": "ioerror", "at": 2},
                    {"site": "checkpoint.restore", "kind": "corrupt",
                     "at": 0},
                    {"site": "bwd.feed", "kind": "kill", "at": kill_at},
                ],
                seed=int(os.environ.get("BENCH_CHAOS_SEED", "20260804")),
            )
        degrade.reset()
        counters0 = dict(
            (metrics.export().get("counters") or {})
        ) if metrics.enabled() else {}

        # --- chaos run: fault schedule + kill + resume ---------------
        t0 = time.time()
        spill_chaos = SpillCache()
        resumes = 0
        got = None
        from swiftly_tpu.obs import recorder as orecorder

        with faults.active(fault_plan):
            try:
                got = run_passes(spill_chaos, autosave=True)
            except WorkerKilled as exc:
                log.warning("chaos drill: %s; resuming from checkpoint",
                            exc)
                orecorder.record(
                    "drill", "chaos.worker_killed", str(exc)
                )
                resumes += 1
                got = run_passes(
                    spill_chaos, autosave=True, resume=True
                )
        chaos_s = time.time() - t0
        # snapshot the black box while the kill -> fallback -> resume
        # story is the recent past (the drill stamps it; --smoke
        # asserts the tail actually tells it)
        post_mortem = (
            orecorder.post_mortem(
                "WorkerKilled",
                reason=f"bwd.feed kill at call {kill_at}, "
                       f"resumed {resumes}x",
            )
            if orecorder.enabled() else None
        )

        bit_identical = bool(
            got.shape == ref.shape and np.array_equal(got, ref)
        )
        counters = dict(
            (metrics.export().get("counters") or {})
        ) if metrics.enabled() else {}

        def delta(name):
            return counters.get(name, 0) - counters0.get(name, 0)

        pstats = fault_plan.stats()
        resilience = {
            "plan": fault_plan.spec(),
            "faults_injected": pstats["by_site"],
            "faults_injected_total": pstats["total"],
            "faults_by_kind": pstats["by_kind"],
            # the drill finished and verified: every injected fault was
            # survived (retried past, degraded around, or resumed over)
            "faults_survived": pstats["total"] if bit_identical else 0,
            "retries": delta("retry.attempts"),
            "retries_recovered": delta("retry.recovered"),
            "degradations": degrade.events(),
            "resume_count": resumes,
            "checkpoint_fallbacks": delta("ckpt.fallbacks"),
            "checkpoint_autosaves": delta("ckpt.autosaves"),
            "checkpoint_saves": delta("ckpt.saves"),
            "kill_site": "bwd.feed",
            "kill_at_call": kill_at,
            "bit_identical": bit_identical,
        }
        record = {
            "metric": f"chaos-drill {config_name}",
            "value": round(chaos_s, 2),
            "unit": "s",
            "config": config_name,
            "n_subgrids": len(subgrid_configs),
            "n_groups": n_groups,
            "n_passes": len(subsets),
            "clean_run": {
                "elapsed_s": round(clean_s, 2),
                "fault_plan_installed": False,
            },
            "resilience": resilience,
            "spill": spill_chaos.stats(),
        }
        if post_mortem is not None:
            record["post_mortem"] = post_mortem
        return record
    finally:
        faults.uninstall()
        shutil.rmtree(work_dir, ignore_errors=True)


def chaos(smoke_mode=False):
    """`bench.py --chaos [--smoke]`: run the kill-and-resume chaos
    drill, stamp the resilience artifact, and validate its schema.

    ``--smoke`` runs the 1k drill (the tier-1 wiring via
    tests/test_bench_smoke.py); the full drill defaults to the 4k
    config (slow-marked in the tests). ``SWIFTLY_FAULT_PLAN`` replaces
    the built-in schedule; ``BENCH_CHAOS_CONFIG`` the config.
    """
    from swiftly_tpu.obs import (
        metrics,
        run_manifest,
        validate_resilience_artifact,
    )
    from swiftly_tpu.resilience import plan_from_env
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    orecorder = _maybe_enable_recorder()
    out_path = os.environ.get("BENCH_CHAOS_OUT", "BENCH_chaos.json")
    metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get(
        "BENCH_CHAOS_CONFIG",
        "1k[1]-n512-256" if smoke_mode else "4k[1]-n2k-512",
    )
    from swiftly_tpu import SWIFT_CONFIGS

    record = run_chaos_drill(
        name,
        fault_plan=plan_from_env(),
        fold_group=int(os.environ.get("BENCH_CHAOS_FOLD_GROUP", "2")),
        col_group=int(os.environ.get("BENCH_CHAOS_COL_GROUP", "2")),
    )
    record["manifest"] = run_manifest(
        baseline_source=None, params=dict(SWIFT_CONFIGS[name])
    )
    record["telemetry"] = metrics.export()
    if trace_path:
        # a chaos-drill trace shows WHERE the run degraded: the fault
        # injections and ladder steps land as instant events among the
        # pass/group/stage spans
        from swiftly_tpu.obs import summarize_trace
        from swiftly_tpu.obs import trace as otrace

        record["trace"] = summarize_trace(otrace.export())
        otrace.save(trace_path)
        otrace.disable()
    problems = validate_resilience_artifact(record)
    res = record["resilience"]
    # the drill's own invariants, beyond the schema: the schedule must
    # actually have exercised every resilience layer
    if res["retries"] < 1 or res["retries_recovered"] < 1:
        problems.append(
            f"no transient fault was retried+recovered: {res}"
        )
    if res["checkpoint_fallbacks"] < 1:
        problems.append(
            "the corrupted checkpoint generation was never fallen "
            f"back from: {res}"
        )
    if not any(
        d["site"] == "checkpoint" for d in res["degradations"]
    ):
        problems.append(
            f"degradation trail missing the checkpoint fallback: "
            f"{res['degradations']}"
        )
    if orecorder is not None:
        pm_path = os.path.splitext(out_path)[0] + "_postmortem.jsonl"
        orecorder.dump(
            pm_path, "WorkerKilled",
            reason=record.get("post_mortem", {}).get("reason"),
        )
        if "post_mortem" in record:
            record["post_mortem"]["dump_path"] = pm_path
        # the post-mortem must TELL the drill's story: the injected
        # kill and the degradation ladder it forced
        pm_names = [
            e["name"]
            for e in record.get("post_mortem", {}).get("events", [])
        ]
        if not any(
            n.startswith("fault.injected.bwd.feed") for n in pm_names
        ):
            problems.append(
                "chaos post-mortem tail missing the injected bwd.feed "
                f"kill: {pm_names}"
            )
        if not any(n.startswith("degrade.") for n in pm_names):
            problems.append(
                "chaos post-mortem tail missing the degradation "
                f"ladder steps: {pm_names}"
            )
        if "chaos.worker_killed" not in pm_names:
            problems.append(
                "chaos post-mortem tail missing the drill's "
                f"worker-killed marker: {pm_names}"
            )
    import json as _json

    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    metrics.disable()
    print(
        json.dumps(
            {
                "chaos": "ok" if not problems else "failed",
                "config": name,
                "artifact": out_path,
                "bit_identical": res["bit_identical"],
                "faults_injected": res["faults_injected_total"],
                "resume_count": res["resume_count"],
                "recorder_events": (
                    record.get("post_mortem", {}).get("n_events", 0)
                ),
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


def run_mesh_chaos_drill(config_name, fault_plan=None, col_group=2,
                         fold_group=2, max_cols=0):
    """The elastic mesh recovery drill (`bench.py --mesh --chaos`, also
    driven by scripts/mesh_drill.py --chaos).

    1. Run the facet-partitioned mesh-streamed round trip UNDISTURBED
       over N virtual shards (pass 1 records the subgrid stream into
       the spill cache, pass 2 is cache-fed) — the reference facets,
       with NO fault plan installed.
    2. Watchdog phase: re-run the recording briefly with an injected
       collective latency (``mesh.psum``, or ``mesh.ring_step`` when
       SWIFTLY_MESH_COLLECTIVE=ring schedules the pipeline) and a small
       ``SWIFTLY_COLLECTIVE_TIMEOUT_S`` — the stalled collective must
       surface as a caught `CollectiveStalledError` (the silent-hang
       class converted to a detected failure), then is discarded.
    3. Chaos run: fresh spill, fault schedule installed — transient
       spill-read/h2d IOErrors (retried), a ``mesh.feed`` latency
       blip, a bit-flipped newest checkpoint generation (restore must
       fall back a generation DURING migration), and one of the N
       shards killed mid-pass-2 (``mesh.shard_loss`` on a CACHE-FED
       pass — the recorded stream bytes are fixed, so recovery can be
       exact). `mesh.recovery.run_elastic_pass` walks the ladder:
       re-plan on N-1 survivors (priced by `plan.plan_mesh_layout`),
       rebuild the engines, migrate the last autosave across layouts,
       resume at the autosave group boundary.
    4. Assert the recovered facets BIT-IDENTICAL to the undisturbed
       mesh run (backward folds are shard-local per-facet — identical
       math on any layout) and stamp the ``mesh.recovery`` +
       ``resilience`` artifact blocks, including
       ``recovery_overhead`` (disturbed/undisturbed wall ratio — the
       scripts/bench_compare.py sentinel).
    """
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from swiftly_tpu import SWIFT_CONFIGS
    from swiftly_tpu.mesh import (
        MeshStreamedBackward,
        MeshStreamedForward,
        make_facet_mesh,
        run_elastic_pass,
    )
    from swiftly_tpu.obs import metrics
    from swiftly_tpu.plan import PlanInputs, compile_plan
    from swiftly_tpu.resilience import (
        CollectiveStalledError,
        FaultPlan,
        degrade,
        faults,
    )
    from swiftly_tpu.utils.spill import SpillCache

    n_av = len(jax.devices())
    n_req = int(os.environ.get("BENCH_MESH_DEVICES", "0")) or n_av
    params = dict(SWIFT_CONFIGS[config_name])
    params.setdefault("fov", 1.0)
    config, fwd, facet_configs, subgrid_configs, _sources = _build(
        "planar", params, jnp.float32, streamed=True
    )
    if max_cols:
        # smoke budget: stream only the first `max_cols` columns — the
        # recovery mechanics (and the bit-identity contract, taken over
        # the SAME truncated set on both runs) are column-count-blind
        keep = set(sorted({sg.off0 for sg in subgrid_configs})[:max_cols])
        subgrid_configs = [
            sg for sg in subgrid_configs if sg.off0 in keep
        ]
    F = len(facet_configs)
    n_shards = min(n_req, n_av, F)
    if n_shards < 3:
        raise ValueError(
            f"mesh chaos drill needs >= 3 facet shards (one dies, >= 2 "
            f"survive a real collective); have {n_shards}"
        )
    inputs = PlanInputs.from_cover(
        config, facet_configs, subgrid_configs, n_devices=n_shards,
        real_facets=getattr(fwd, "_facets_real", False),
        fold_group=fold_group,
    )
    plan = compile_plan(inputs, mode="roundtrip-streamed")
    mesh = make_facet_mesh(n_devices=plan.mesh.facet_shards)
    facet_tasks = list(zip(facet_configs, fwd._facet_data))
    mfwd = MeshStreamedForward(
        config, facet_tasks, layout=plan.mesh, mesh=mesh
    )
    # deterministic column-group count: the fault schedule is indexed
    # by site call number (same discipline as run_chaos_drill)
    mfwd.col_group = col_group
    n_cols = len({sg.off0 for sg in subgrid_configs})
    n_groups = -(-n_cols // col_group)
    if n_groups < 3:
        raise ValueError(
            f"mesh chaos drill needs >= 3 column groups (kill after 2 "
            f"autosaves); {config_name} with col_group={col_group} has "
            f"{n_groups}"
        )
    half = max(1, F // 2)
    subsets = [(0, half), (half, F)] if F > 1 else [(0, F)]

    work_dir = tempfile.mkdtemp(prefix="mesh_chaos_")
    ck_paths = [
        os.path.join(work_dir, f"ck_pass{i}.npz")
        for i in range(len(subsets))
    ]

    def make_bwd(i0, i1, on_mesh):
        return MeshStreamedBackward(
            config, list(facet_configs[i0:i1]), mesh=on_mesh,
            fold_group=fold_group,
        )

    try:
        # --- undisturbed mesh reference (clean path, no plan) --------
        assert faults.current() is None
        t0 = time.time()
        spill_ref = SpillCache(budget_bytes=2e9)
        parts = []
        for i0, i1 in subsets:
            bwd = make_bwd(i0, i1, mesh)
            for per_col, group in mfwd.stream_column_groups(
                subgrid_configs, spill=spill_ref
            ):
                bwd.add_subgrid_group(
                    [[sg for _, sg in col] for col in per_col], group
                )
            parts.append(np.asarray(bwd.finish()))
        ref = np.concatenate(parts, axis=0)
        clean_s = time.time() - t0

        # --- watchdog phase: a stalled collective is a DETECTED loss --
        # the fault site tracks the scheduled collective: mesh.psum
        # under the default, mesh.ring_step when
        # SWIFTLY_MESH_COLLECTIVE=ring pipelines the reduction
        wd_timeout = float(
            os.environ.get("BENCH_MESH_WATCHDOG_S", "0.15")
        )
        stall_site = (
            "mesh.ring_step"
            if getattr(mfwd, "collective", "psum") == "ring"
            else "mesh.psum"
        )
        stall_plan = FaultPlan(
            faults=[
                {"site": stall_site, "kind": "latency", "at": 0,
                 "delay_s": wd_timeout * 4},
            ]
        )
        stalls_detected = 0
        prev_knob = os.environ.get("SWIFTLY_COLLECTIVE_TIMEOUT_S")
        os.environ["SWIFTLY_COLLECTIVE_TIMEOUT_S"] = str(wd_timeout)
        try:
            with faults.active(stall_plan):
                try:
                    for _pc, _g in mfwd.stream_column_groups(
                        subgrid_configs, spill=SpillCache(budget_bytes=2e9)
                    ):
                        pass  # aborted by the first group's stalled sync
                except CollectiveStalledError:
                    stalls_detected = 1
        finally:
            if prev_knob is None:
                os.environ.pop("SWIFTLY_COLLECTIVE_TIMEOUT_S", None)
            else:
                os.environ["SWIFTLY_COLLECTIVE_TIMEOUT_S"] = prev_knob

        # --- the fault schedule --------------------------------------
        # mesh.shard_loss fires once per yielded group; pass 1 (the
        # recording) burns calls 0..n_groups-1, so call n_groups+2
        # lands before pass-2's THIRD group — a CACHE-FED pass with two
        # autosaved generations behind it (the newest gets bit-flipped,
        # so generation fallback must compose with layout migration).
        kill_at = n_groups + 2
        if fault_plan is None:
            fault_plan = FaultPlan(
                faults=[
                    {"site": "spill.read", "kind": "ioerror", "at": 1},
                    {"site": "transfer.h2d", "kind": "ioerror", "at": 2},
                    {"site": "mesh.feed", "kind": "latency", "at": 0,
                     "delay_s": 0.01},
                    {"site": "checkpoint.restore", "kind": "corrupt",
                     "at": 0},
                    {"site": "mesh.shard_loss", "kind": "shard_loss",
                     "at": kill_at},
                ],
                seed=int(os.environ.get("BENCH_CHAOS_SEED", "20260804")),
            )
        degrade.reset()
        counters0 = dict(
            (metrics.export().get("counters") or {})
        ) if metrics.enabled() else {}

        # --- chaos run: elastic passes under the schedule ------------
        t0 = time.time()
        spill_chaos = SpillCache(budget_bytes=2e9)
        parts = []
        reports = []
        fwd_cur = mfwd
        with faults.active(fault_plan):
            for idx, (i0, i1) in enumerate(subsets):
                bwd = make_bwd(i0, i1, fwd_cur.mesh)
                fwd_cur, bwd, rep = run_elastic_pass(
                    fwd_cur, bwd, subgrid_configs, spill_chaos,
                    ck_paths[idx], plan_inputs=inputs,
                    max_recoveries=1,
                )
                reports.append(rep)
                parts.append(np.asarray(bwd.finish()))
        got = np.concatenate(parts, axis=0)
        chaos_s = time.time() - t0
        # snapshot the black box while the shard loss -> re-plan ->
        # migrate -> resume ladder is the recent past
        from swiftly_tpu.obs import recorder as orecorder

        post_mortem = (
            orecorder.post_mortem(
                "ShardLostError",
                reason=f"mesh.shard_loss at call {kill_at}",
            )
            if orecorder.enabled() else None
        )

        bit_identical = bool(
            got.shape == ref.shape and np.array_equal(got, ref)
        )
        counters = dict(
            (metrics.export().get("counters") or {})
        ) if metrics.enabled() else {}

        def delta(name):
            return counters.get(name, 0) - counters0.get(name, 0)

        recoveries = [i for r in reports for i in r["recoveries"]]
        last = recoveries[-1] if recoveries else {}
        recovery_block = {
            "events": len(recoveries),
            "recoveries": recoveries,
            "shards_before": int(n_shards),
            "shards_after": int(reports[-1]["shards_after"]),
            "replanned": last.get("replanned"),
            "migrated": bool(
                any(i["migrated"] for i in recoveries)
            ),
            "subgrids_migrated": int(last.get("subgrids_migrated", 0)),
            "watchdog": {
                "timeout_s": wd_timeout,
                "stalls_detected": stalls_detected,
                "stall_site": stall_site,
                "stall_plan": stall_plan.stats(),
            },
            "kill_site": "mesh.shard_loss",
            "kill_at_call": kill_at,
            "migrations": delta("ckpt.migrations"),
            "checkpoint_fallbacks": delta("ckpt.fallbacks"),
            "checkpoint_autosaves": delta("ckpt.autosaves"),
            "recovery_wall_s": round(
                sum(r["recovery_wall_s"] for r in reports), 4
            ),
            # disturbed/undisturbed wall ratio: the time-to-recover
            # sentinel scripts/bench_compare.py trends (lower = better)
            "recovery_overhead": round(chaos_s / clean_s, 4),
            "bit_identical": bit_identical,
        }
        pstats = fault_plan.stats()
        resilience = {
            "plan": fault_plan.spec(),
            "faults_injected": pstats["by_site"],
            "faults_injected_total": pstats["total"],
            "faults_by_kind": pstats["by_kind"],
            "faults_survived": pstats["total"] if bit_identical else 0,
            "retries": delta("retry.attempts"),
            "retries_recovered": delta("retry.recovered"),
            "degradations": degrade.events(),
            "resume_count": len(recoveries),
            "checkpoint_fallbacks": delta("ckpt.fallbacks"),
            "checkpoint_autosaves": delta("ckpt.autosaves"),
            "checkpoint_saves": delta("ckpt.saves"),
            "kill_site": "mesh.shard_loss",
            "kill_at_call": kill_at,
            "bit_identical": bit_identical,
        }
        mesh_block = {
            "n_devices": int(n_av),
            "facet_shards": int(n_shards),
            "n_facets": F,
            "padded_facets": int(mfwd.stack.n_total),
            "collective_bytes": int(plan.mesh.collective_bytes_total),
            "clean_wall_s": round(clean_s, 4),
            "chaos_wall_s": round(chaos_s, 4),
            # the chaos drill's match audit IS the bit-identity
            # contract: zero tolerance, the recovered stream must equal
            # the undisturbed mesh run byte for byte
            "match": {
                "max_abs_diff": float(np.max(np.abs(got - ref))),
                "tolerance": 0.0,
                "within_tolerance": bit_identical,
                "bit_identical": bit_identical,
            },
            "spill": spill_chaos.stats(),
            "recovery": recovery_block,
        }
        platform = jax.devices()[0].platform
        record = {
            "metric": f"{config_name} mesh chaos drill wall-clock "
                      f"({n_shards} shards kill one mid-stream, "
                      f"planar f32, mesh-chaos, {platform})",
            "value": round(chaos_s, 2),
            "unit": "s",
            "config": config_name,
            "n_subgrids": len(subgrid_configs),
            "n_groups": n_groups,
            "n_passes": len(subsets),
            "clean_run": {
                "elapsed_s": round(clean_s, 2),
                "fault_plan_installed": False,
            },
            "mesh": mesh_block,
            "resilience": resilience,
            "plan_compiled": plan.artifact_block(
                measured_wall_s=chaos_s
            ),
        }
        if post_mortem is not None:
            record["post_mortem"] = post_mortem
        return record
    finally:
        faults.uninstall()
        shutil.rmtree(work_dir, ignore_errors=True)


def mesh_chaos(smoke_mode=False):
    """`bench.py --mesh --chaos [--smoke]`: the elastic mesh recovery
    drill — kill one of N virtual shards mid-stream, re-plan the layout
    on the survivors, migrate the checkpoint across layouts, resume,
    and validate the stamped ``mesh.recovery`` + ``resilience`` blocks.

    ``--smoke`` runs the 1k drill (tier-1 wiring via
    tests/test_bench_smoke.py); the full drill defaults to the 4k
    config (slow-marked in the tests). ``SWIFTLY_FAULT_PLAN`` replaces
    the built-in schedule; ``BENCH_MESH_CHAOS_CONFIG`` the config;
    ``BENCH_MESH_DEVICES`` the shard count.
    """
    from swiftly_tpu.utils import enable_compilation_cache

    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    n_av = _ensure_mesh_devices()  # before any other jax use
    key = "mesh_chaos_smoke" if smoke_mode else "mesh_chaos"
    if n_av < 3:
        print(
            json.dumps(
                {
                    key: "failed",
                    "problems": [
                        f"mesh chaos drill needs >= 3 devices, found "
                        f"{n_av}; for a virtual CPU mesh set "
                        "JAX_PLATFORMS=cpu"
                    ],
                }
            ),
            flush=True,
        )
        return 1
    from swiftly_tpu.obs import (
        metrics,
        run_manifest,
        validate_mesh_artifact,
        validate_plan_artifact,
        validate_resilience_artifact,
    )
    from swiftly_tpu.resilience import plan_from_env

    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    orecorder = _maybe_enable_recorder()
    out_path = os.environ.get(
        "BENCH_MESH_CHAOS_OUT", "BENCH_mesh_chaos.json"
    )
    metrics.enable(os.environ.get("SWIFTLY_METRICS_JSONL") or None)
    name = os.environ.get(
        "BENCH_MESH_CHAOS_CONFIG",
        "1k[1]-n512-256" if smoke_mode else "4k[1]-n2k-512",
    )
    from swiftly_tpu import SWIFT_CONFIGS

    record = run_mesh_chaos_drill(
        name,
        fault_plan=plan_from_env(),
        col_group=int(
            os.environ.get(
                "BENCH_CHAOS_COL_GROUP", "1" if smoke_mode else "2"
            )
        ),
        fold_group=int(os.environ.get("BENCH_CHAOS_FOLD_GROUP", "2")),
        max_cols=int(
            os.environ.get(
                "BENCH_MESH_CHAOS_COLS", "3" if smoke_mode else "0"
            )
        ),
    )
    record["manifest"] = run_manifest(
        baseline_source=None, params=dict(SWIFT_CONFIGS[name])
    )
    record["telemetry"] = metrics.export()
    if record.get("plan_compiled"):
        _stamp_plan_accuracy(
            record,
            dump_path=os.path.splitext(out_path)[0]
            + "_plan_postmortem.jsonl",
        )
    if trace_path:
        from swiftly_tpu.obs import summarize_trace
        from swiftly_tpu.obs import trace as otrace

        record["trace"] = summarize_trace(otrace.export())
        otrace.save(trace_path)
        otrace.disable()
    problems = validate_mesh_artifact(record)
    problems.extend(validate_resilience_artifact(record))
    problems.extend(validate_plan_artifact(record))
    rec = record["mesh"]["recovery"]
    # the drill's own invariants, beyond the schema: every rung of the
    # elastic ladder must actually have been walked
    if rec["watchdog"]["stalls_detected"] < 1:
        problems.append(
            "the stalled collective was never detected by the "
            f"watchdog: {rec['watchdog']}"
        )
    if rec["checkpoint_fallbacks"] < 1:
        problems.append(
            "the corrupted checkpoint generation was never fallen "
            "back from during migration (fallback must compose with "
            f"layout migration): {rec}"
        )
    if rec["migrations"] < 1:
        problems.append(
            f"no checkpoint crossed a layout boundary: {rec}"
        )
    res = record["resilience"]
    if res["retries"] < 1 or res["retries_recovered"] < 1:
        problems.append(
            f"no transient fault was retried+recovered: {res}"
        )
    if orecorder is not None:
        pm_path = os.path.splitext(out_path)[0] + "_postmortem.jsonl"
        orecorder.dump(
            pm_path, "ShardLostError",
            reason=record.get("post_mortem", {}).get("reason"),
        )
        if "post_mortem" in record:
            record["post_mortem"]["dump_path"] = pm_path
        # the post-mortem must tell the elastic ladder's story: the
        # injected shard loss and every recovery rung behind it
        pm_names = [
            e["name"]
            for e in record.get("post_mortem", {}).get("events", [])
        ]
        if not any(
            n.startswith("fault.injected.mesh.shard_loss")
            for n in pm_names
        ):
            problems.append(
                "mesh post-mortem tail missing the injected "
                f"shard loss: {pm_names}"
            )
        for step in ("mesh.recovery.detected", "mesh.recovery.replanned",
                     "mesh.recovery.resumed"):
            if step not in pm_names:
                problems.append(
                    f"mesh post-mortem tail missing the {step} "
                    f"ladder step: {pm_names}"
                )
    import json as _json

    with open(out_path, "w") as fh:
        _json.dump(record, fh, indent=2)
    metrics.disable()
    print(
        json.dumps(
            {
                key: "ok" if not problems else "failed",
                "config": name,
                "artifact": out_path,
                "bit_identical": rec["bit_identical"],
                "shards": (
                    f"{rec['shards_before']}->{rec['shards_after']}"
                ),
                "recovery_overhead": rec["recovery_overhead"],
                "stalls_detected": rec["watchdog"]["stalls_detected"],
                "recorder_events": (
                    record.get("post_mortem", {}).get("n_events", 0)
                ),
                "problems": problems,
            }
        ),
        flush=True,
    )
    return 0 if not problems else 1


def main():
    import signal

    from swiftly_tpu.obs import PartialArtifactWriter
    from swiftly_tpu.utils import enable_compilation_cache

    if "--vis" in sys.argv:
        sys.exit(vis_bench(smoke_mode="--smoke" in sys.argv))
    if "--serve" in sys.argv:
        sys.exit(serve_bench(smoke_mode="--smoke" in sys.argv))
    if "--procfleet" in sys.argv:
        sys.exit(procfleet_bench(smoke_mode="--smoke" in sys.argv))
    if "--fleet" in sys.argv:
        sys.exit(fleet_bench(smoke_mode="--smoke" in sys.argv))
    if "--mesh" in sys.argv and "--chaos" in sys.argv:
        sys.exit(mesh_chaos(smoke_mode="--smoke" in sys.argv))
    if "--chaos" in sys.argv:
        sys.exit(chaos(smoke_mode="--smoke" in sys.argv))
    if "--mesh" in sys.argv:
        sys.exit(mesh_bench(smoke_mode="--smoke" in sys.argv))
    if "--precision-child" in sys.argv:
        sys.exit(precision_child())
    if "--precision" in sys.argv:
        sys.exit(precision_bench(smoke_mode="--smoke" in sys.argv))
    if "--delta" in sys.argv:
        sys.exit(delta_bench(smoke_mode="--smoke" in sys.argv))
    if "--smoke" in sys.argv:
        sys.exit(smoke())

    # progress visibility for the hour-scale configs: BENCH_LOGLEVEL=INFO
    # streams per-phase and per-sweep lines to stderr
    logging.basicConfig(
        level=os.environ.get("BENCH_LOGLEVEL", "WARNING"),
        format="%(asctime)s %(name)s: %(message)s",
        stream=sys.stderr,
    )
    enable_compilation_cache()
    trace_path = _maybe_enable_trace()
    # incremental per-leg flush: a killed run (BENCH_r05 died at rc=124)
    # still leaves every FINISHED leg's full record on disk, plus a
    # "started" marker naming the leg it died in. BENCH_PARTIAL_PATH=""
    # disables.
    partial = PartialArtifactWriter(
        os.environ.get("BENCH_PARTIAL_PATH", "BENCH_partial.jsonl")
    )

    legacy = os.environ.get("BENCH_CONFIG")
    if legacy:
        entries = [(legacy, os.environ.get("BENCH_MODE", "batched"))]
    else:
        # Default legs sized for the 870 s driver window (BENCH_r05 ran
        # the old 8-leg list incl. two 64k legs and died at rc=124 with
        # nothing on stdout): smoke-scale 1k round trip, the 4k fused
        # legs, 32k streamed + sparse, and the 32k round trip as the
        # headline. The 64k/128k flagship legs run via an explicit
        # BENCH_CONFIGS with a matching BENCH_TIME_BUDGET_S.
        spec = os.environ.get(
            "BENCH_CONFIGS",
            "1k[1]-n512-256:roundtrip-streamed,"
            "4k[1]-n2k-512:batched,4k[1]-n2k-512:roundtrip,"
            "32k[1]-n16k-512:streamed,"
            "32k[1]-n16k-512:streamed-sparse,"
            "32k[1]-n16k-512:roundtrip-streamed",
        )
        entries = []
        for item in spec.split(","):
            name, _, mode = item.strip().partition(":")
            entries.append((name, mode or "batched"))

    # The LAST listed entry is the headline metric — but it RUNS FIRST so
    # a slow or failing earlier config can never starve it of the driver
    # window (BENCH_r03 died with the headline unmeasured), and its line
    # is re-printed at the end so the headline is the last stdout line.
    t_start = time.time()
    budget_s = float(os.environ.get("BENCH_TIME_BUDGET_S", "870"))
    state = {"headline_line": None}

    def _on_term(signum, frame):  # pragma: no cover - signal path
        # driver timeout: make the headline (if measured) the last line
        if state["headline_line"]:
            print(state["headline_line"], flush=True)
            os._exit(0)
        os._exit(1)

    signal.signal(signal.SIGTERM, _on_term)

    order = [len(entries) - 1] + list(range(len(entries) - 1))
    ok = {}
    for pos in order:
        name, mode = entries[pos]
        is_headline = pos == len(entries) - 1
        elapsed = time.time() - t_start
        # Two skip rules for non-headline legs: the old high-water mark
        # (elapsed > 0.75 * budget), and a PROJECTED overrun — starting
        # a leg whose size-class cost guess does not fit the remaining
        # window is how BENCH_r05 overran 870 s with legs already in
        # hand. A guess can only skip, never kill: headline runs first
        # and unconditionally.
        skip_reason = None
        if budget_s and not is_headline:
            if elapsed > 0.75 * budget_s:
                skip_reason = "time budget"
            elif elapsed + _leg_cost_guess_s(name, mode) > 0.95 * budget_s:
                skip_reason = "time budget (projected leg cost)"
        if skip_reason:
            skip_record = {
                "metric": f"{name} ({mode})",
                "skipped": skip_reason,
                "elapsed_s": round(elapsed, 1),
            }
            print(json.dumps(skip_record), flush=True)
            partial.append(skip_record)
            continue
        partial.append(
            {"leg": name, "mode": mode, "status": "started",
             "elapsed_s": round(elapsed, 1)}
        )
        try:
            record = run_one(name, mode)
            line = json.dumps(record)
            print(line, flush=True)
            partial.append(record)
            if is_headline:
                state["headline_line"] = line
            ok[pos] = True
        except Exception:  # pragma: no cover - report and move on
            ok[pos] = False
            traceback.print_exc(file=sys.stderr)
            fail_record = {"metric": f"{name} ({mode})", "error": "failed"}
            print(json.dumps(fail_record), flush=True)
            partial.append(fail_record)
    if trace_path:
        from swiftly_tpu.obs import trace as otrace

        otrace.save(trace_path)
    if state["headline_line"]:
        print(state["headline_line"], flush=True)
    sys.exit(0 if ok.get(len(entries) - 1) else 1)


if __name__ == "__main__":
    main()
