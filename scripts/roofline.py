"""Per-stage roofline of the streamed (sampled-DFT) forward on real TPU.

Times each pipeline stage IN ISOLATION with genuine completion pulls
(8-byte checksums; whether block_until_ready alone is completion on
the chip is a measurement still to be made), then prints one JSON
line per stage with measured TF/s, the fraction of the
`Precision.HIGHEST` matmul ceiling, and the effective HBM bandwidth
where a stage is memory/latency-bound rather than
MXU-bound. This is the committed evidence for where the wall-clock of
`bench.py`'s streamed mode goes (VERDICT r3 weak #4: MFU progress must
be measured, not asserted).

Stages (32k default):
  dispatch   - an empty-ish jitted op + checksum pull: the runtime's
               per-dispatch latency floor (pure overhead, 0 FLOPs)
  synth      - sparse facet-slab synthesis (scatter into zeros)
  sampled    - the sampled-DFT facet pass einsum for one column group
  column     - the group column pass (prepare + per-subgrid matmuls),
               body per resolve_colpass (einsum / fused pallas / fft)
  column-*   - on planar backends, the OTHER matrix body (einsum vs
               pallas) timed at the same geometry: the committed
               evidence row behind the plan's colpass_candidates table
  finish     - the group finish (crop iFFTs + masks)

Usage: python scripts/roofline.py [--config 32k[1]-n16k-512] [--G 8]
       [--reps 5]
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="32k[1]-n16k-512")
    ap.add_argument("--G", type=int, default=8, help="column group size")
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--bwd", action="store_true",
                    help="also time the backward stages (group column "
                    "pass + adjoint sampled fold, fold_group=2)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from swiftly_tpu import (
        SWIFT_CONFIGS,
        SwiftlyConfig,
        make_full_facet_cover,
        make_full_subgrid_cover,
        make_sparse_facet,
    )
    from swiftly_tpu.api import _subgrid_masks
    from swiftly_tpu.parallel import StreamedForward
    from swiftly_tpu.parallel.streamed import (
        _column_group_finish_j,
        _column_group_step_j,
        _facet_pass_sampled_j,
        _synth_slab_j,
        sampled_row_indices,
    )
    from swiftly_tpu.utils import enable_compilation_cache
    from swiftly_tpu.utils.flops import fft_flops, peak_tflops

    enable_compilation_cache()
    params = dict(SWIFT_CONFIGS[args.config])
    params.setdefault("fov", 1.0)
    config = SwiftlyConfig(backend="planar", dtype=jnp.float32, **params)
    core = config.core
    fcs = make_full_facet_cover(config)
    sgs = make_full_subgrid_cover(config)
    sources = [(1.0, 1, 0)]
    fwd = StreamedForward(
        config,
        [(fc, make_sparse_facet(config.image_size, fc, sources))
         for fc in fcs],
        residency="device",
    )
    F, yB = len(fcs), fcs[0].size
    m, xM, yN = core.xM_yN_size, core.xM_size, core.yN_size
    xA = sgs[0].size
    col_offs0 = sorted({sg.off0 for sg in sgs})
    G, chunk = args.G, args.chunk
    n_chunks = G // chunk
    grp = col_offs0[:G]
    by_col = {}
    for sg in sgs:
        by_col.setdefault(sg.off0, []).append(sg)
    S = len(by_col[grp[0]])
    peak = peak_tflops() or float("nan")

    def pull(x):
        return float(np.asarray(jnp.sum(x)))

    def timed(fn, *a, reps=args.reps):
        out = fn(*a)
        pull(out)  # compile + warm
        t0 = time.time()
        for _ in range(reps):
            out = fn(*a)
            pull(out)
        return (time.time() - t0) / reps, out

    def emit(stage, dt, flops, bytes_touched=None, note=""):
        rec = {
            "stage": stage,
            "seconds": round(dt, 5),
            "gflops": round(flops / 1e9, 2),
            "tflops_per_s": round(flops / dt / 1e12, 2),
            "pct_of_matmul_peak": round(100 * flops / dt / 1e12 / peak, 1),
        }
        if bytes_touched is not None:
            rec["effective_GBps"] = round(bytes_touched / dt / 1e9, 1)
        if note:
            rec["note"] = note
        print(json.dumps(rec), flush=True)
        return rec

    # -- dispatch latency floor ------------------------------------------
    tiny = jnp.ones((8, 128), jnp.float32)
    addj = jax.jit(lambda x: x + 1.0)
    dt, _ = timed(addj, tiny, reps=10)
    emit("dispatch", dt, 0.0,
         note="per-dispatch + 8-byte pull latency floor; every streamed "
              "stage pays this at least once")
    t_lat = dt

    # -- sparse slab synthesis -------------------------------------------
    synth = _synth_slab_j(core, 1, yB)
    px = fwd._sparse_pixels(0, 1)
    dt, slab = timed(synth, *px)
    emit("synth", dt, 0.0, bytes_touched=slab.nbytes,
         note="scatter into zeros; replaces a multi-GB h2d upload")

    # -- sampled facet pass ----------------------------------------------
    krows = jnp.asarray(sampled_row_indices(core, grp))
    e0 = jnp.asarray(
        (np.asarray(fwd.stack.offs0) - yB // 2).astype(np.int32)
    )
    samfn = _facet_pass_sampled_j(core, True)
    fn9 = _synth_slab_j(core, fwd.stack.n_total, yB)
    stack = fn9(*fwd._sparse_pixels(0, fwd.stack.n_total))
    dt_sampled, buf = timed(samfn, stack, e0, krows)
    flops = 4 * G * m * yB * F * yB + 6 * F * G * m * yB
    emit("sampled", dt_sampled, flops,
         bytes_touched=stack.nbytes + buf.nbytes,
         note=f"[{G * m},{yB}]x[{F},{yB},{yB}] real einsum pair")

    # -- column pass (no finish) -----------------------------------------
    sg_offs_g = [[(sg.off0, sg.off1) for sg in by_col[o]] for o in grp]
    rdt = core._Fb.dtype
    ms = [[_subgrid_masks(sg) for sg in by_col[o]] for o in grp]
    so_c = jnp.asarray(sg_offs_g).reshape(n_chunks, chunk, S, 2)
    m0_c = jnp.asarray(
        np.asarray([[mk[0] for mk in row] for row in ms]), rdt
    ).reshape(n_chunks, chunk, S, -1)
    m1_c = jnp.asarray(
        np.asarray([[mk[1] for mk in row] for row in ms]), rdt
    ).reshape(n_chunks, chunk, S, -1)
    from swiftly_tpu.utils.flops import resolve_colpass

    colpass = resolve_colpass(core, F)
    foffs0 = jnp.asarray(np.asarray(fwd.stack.offs0))
    foffs1 = jnp.asarray(np.asarray(fwd.stack.offs1))
    if colpass in ("einsum", "pallas"):
        # time the kernel the resident executor actually runs: the group
        # column pass (sequential columns, finish folded into the
        # operators) — the slab step at full F with a chunk-wide vmap is
        # a shape the einsum executor never chooses (it would OOM)
        from swiftly_tpu.parallel.streamed import _column_pass_fwd_group_j

        prep_flops = G * F * (fft_flops(yN, m) + 6 * m * yN)  # prep1
        einsum_col_flops = (
            prep_flops
            + G * F * 8 * xM * m * yN  # H = A0 @ NMBF_BF
            + G * S * 8 * xM * xM * F * m  # stage-2 contraction
        )
        # fused kernel: gather commutes past stage 1, no hoisted H —
        # per subgrid 8*xM*m*(m+xM)*F triple product + the crop iFFTs
        pallas_col_flops = prep_flops + G * S * (
            8 * xM * m * (m + xM) * F + 4 * xA * xA
        )
        col_notes = {
            "einsum": f"prepare + operator einsums (K={F * m}) incl. "
                      f"crop for {G} columns x {S} subgrids "
                      f"(all {F} facets)",
            "pallas": f"fused Pallas colpass (prepare + gather + "
                      f"triple product, K={F * m}) incl. crop for "
                      f"{G} columns x {S} subgrids (all {F} facets)",
        }
        gcolfn = _column_pass_fwd_group_j(core, xA)
        so_g = so_c.reshape(G, S, 2)
        m0_g = m0_c.reshape(G, S, -1)
        m1_g = m1_c.reshape(G, S, -1)

        def run_col(buf):
            return gcolfn(buf, foffs0, foffs1, so_g, m0_g, m1_g)

        dt_column, out = timed(run_col, buf)
        col_flops = (
            einsum_col_flops if colpass == "einsum" else pallas_col_flops
        )
        emit("column", dt_column, col_flops,
             bytes_touched=buf.nbytes + out.nbytes,
             note=col_notes[colpass])

        # paired row: the OTHER matrix body at the exact same geometry,
        # so a single roofline run carries the einsum-vs-pallas evidence
        # the plan's ranked colpass_candidates table is refit against.
        # Skipped when the other body is pallas on a CPU backend without
        # SWIFTLY_PALLAS_INTERPRET=1: pallas_call only lowers natively on
        # TPU, and an interpret-mode timing is not roofline evidence
        from swiftly_tpu.ops.pallas_kernels import pallas_interpret

        _other_is_pallas = colpass == "einsum"
        _can_run_other = not _other_is_pallas or (
            jax.default_backend() != "cpu" or pallas_interpret()
        )
        if getattr(core, "backend", "") == "planar" and _can_run_other:
            from swiftly_tpu.parallel.streamed import (
                _colpass_einsum_body,
                _colpass_operators,
                _colpass_pallas_body,
            )

            other = "pallas" if colpass == "einsum" else "einsum"
            body = (
                _colpass_pallas_body
                if other == "pallas"
                else _colpass_einsum_body
            )
            ops_cmp = _colpass_operators(core, foffs0, foffs1)

            @jax.jit
            def run_other(buf):
                NMBF_g = jnp.moveaxis(
                    buf.reshape((F, G, m) + buf.shape[2:]), 1, 0
                )

                def per_col(xs):
                    NMBF, so, mk0, mk1 = xs
                    return body(
                        core, xA, ops_cmp, NMBF, foffs1, so, mk0, mk1
                    )

                return jax.lax.map(
                    per_col, (NMBF_g, so_g, m0_g, m1_g)
                )

            dt_other, out_other = timed(run_other, buf)
            emit(f"column-{other}", dt_other,
                 einsum_col_flops if other == "einsum"
                 else pallas_col_flops,
                 bytes_touched=buf.nbytes + out_other.nbytes,
                 note=col_notes[other] + " [comparison row: body not "
                      "selected by resolve_colpass on this platform]")
        dt_fin = 0.0  # folded into the matrix-body operators (crop+masks
        # happen inside the column stage above) — no separate stage
    else:
        stepfn = _column_group_step_j(core, xA, chunk, colpass)

        def run_step(buf):
            acc = jnp.zeros(
                (n_chunks, chunk, S, xM, xM, 2), dtype=np.float32
            )
            return stepfn(acc, buf, foffs0, foffs1, so_c)

        dt_column, acc = timed(run_step, buf)
        col_flops = G * F * (fft_flops(yN, m) + 6 * m * yN) + G * S * F * (
            fft_flops(m, m) + 6 * m * m + fft_flops(m, xM) + 6 * xM * m
        ) + G * S * 2 * (F - 1) * xM * xM
        emit("column", dt_column, col_flops,
             bytes_touched=buf.nbytes + acc.nbytes,
             note=f"prepare + per-subgrid small matmuls for {G} columns "
                  f"x {S} subgrids (all {F} facets)")

        # -- finish -------------------------------------------------------
        finfn = _column_group_finish_j(core, xA, colpass)

        def run_fin(acc):
            return finfn(acc, so_c, m0_c, m1_c)

        # acc is donated by finfn: rebuild each rep inside the timed fn
        def fin_fresh(_):
            a = jnp.zeros(
                (n_chunks, chunk, S, xM, xM, 2), dtype=np.float32
            )
            return run_fin(a)

        dt_fin, fin = timed(fin_fresh, 0)
        fin_flops = G * S * (
            fft_flops(xM, xM) + fft_flops(xM, xA) + 4 * xA * xA
        )
        emit("finish", dt_fin, fin_flops, bytes_touched=fin.nbytes,
             note="once per group since r4 (was once per slab)")

    # Full-cover bracketing from the per-group stage sum. Each timed
    # stage already embeds one dispatch+pull (~t_lat), so the
    # compute-only lower bound subtracts those; the serial upper bound
    # adds the generator's own per-group pulls. The real pipeline
    # overlaps dispatch with compute, so the measurement should land
    # between the bounds.
    n_groups = -(-len(col_offs0) // G)
    per_group = dt_sampled + dt_column + dt_fin
    # each timed stage embeds one dispatch+pull; the matrix bodies
    # (einsum/pallas) have two stages per group (sampled +
    # column-with-crop), fft mode three
    n_stages = 2 if colpass in ("einsum", "pallas") else 3
    lo = n_groups * (per_group - n_stages * t_lat)
    hi = n_groups * (per_group + 2 * t_lat)
    print(json.dumps({
        "stage": "model",
        "full_cover_lower_s": round(lo, 2),
        "full_cover_upper_s": round(hi, 2),
        "note": f"{len(col_offs0)} columns in {n_groups} groups of {G}; "
                "the measured full-cover wall-clock (bench.py) should "
                "fall inside this bracket",
    }), flush=True)

    if not args.bwd:
        return

    # -- backward stages (the round trip's other half) --------------------
    # free every forward-stage device buffer first: the fold's donated
    # [F, yB, yB, 2] accumulator is 9.1 GiB at 32k and must not share
    # HBM with the forward's group buffer / partials
    buf = out = acc = fin = slab = None  # noqa: F841 - releases buffers

    from swiftly_tpu.parallel.streamed import (
        _bwd_sampled_fold_j,
        _column_pass_bwd_group_j,
    )
    from swiftly_tpu.utils.flops import resolve_colpass_bwd

    # reuse the forward executor's facet stack (same fcs -> same
    # offsets as foffs0 above) and its real dtype
    rdt = core._Fb.dtype
    m1 = jnp.asarray(np.asarray(fwd.stack.masks1, rdt))
    Gb = 2  # the bench's fold_group default
    rng = np.random.default_rng(3)
    sgs_dev = jnp.asarray(
        rng.standard_normal((Gb, S, xA, xA, 2)), jnp.float32
    )
    so_b = jnp.asarray(
        [[(sg.off0, sg.off1) for sg in by_col[o]] for o in grp[:Gb]]
    )
    bcol = _column_pass_bwd_group_j(core, yB)
    dt_bcol, rows_g = timed(
        bcol, sgs_dev, so_b, foffs0, foffs1, m1
    )
    bwd_mode = resolve_colpass_bwd(core, F)
    col_fin = F * (fft_flops(yN, m) + 6 * m * yB)
    if bwd_mode == "einsum":
        # the einsum body's FLOP shape (matches
        # utils.flops.backward_sampled_flops): two K=xM complex einsums
        # per (subgrid, facet) + the scatter-add — NOT the fft-chain
        # formulas, which would describe a different algorithm than the
        # one timed
        per_sg = F * 8 * (m * xM * xM + m * m * xM) + F * 2 * m * yN
        bcol_flops = Gb * (S * per_sg + col_fin)
    else:
        prep = fft_flops(xM, xA) + fft_flops(xM, xM)
        extract = F * (
            fft_flops(m, m) + 6 * m * xM + fft_flops(m, m) + 6 * m * m
        )
        bcol_flops = Gb * (S * (prep + extract) + col_fin)
    emit("bwd-column", dt_bcol, bcol_flops,
         bytes_touched=sgs_dev.nbytes + rows_g.nbytes,
         note=f"{Gb}-column backward group pass ({bwd_mode} body): "
              f"prepare + per-facet extract + axis-1 finish")

    # adjoint sampled fold: rows [Gb, F, m, yB] -> [F, Gb*m, yB] with
    # the PRODUCTION layout (moveaxis before the reshape — a plain
    # reshape would scramble the facet/column association the krows
    # indices assume)
    rows = jnp.moveaxis(rows_g, 0, 1).reshape(
        (F, Gb * m) + rows_g.shape[3:]
    )
    krows_b = jnp.asarray(sampled_row_indices(core, grp[:Gb]))
    e0 = jnp.asarray(
        (np.asarray(fwd.stack.offs0) - yB // 2).astype(np.int32)
    )
    foldfn = _bwd_sampled_fold_j(core)

    def run_fold(_):
        # the fold donates its accumulator (rebuild per rep); return
        # only a checksum so the 9.1 GiB result never outlives the rep
        a = jnp.zeros((F, yB, yB, 2), jnp.float32)
        r = foldfn(a, rows, e0, krows_b)
        s = jnp.sum(r)
        del a, r
        return s

    dt_fold, _ = timed(run_fold, 0)
    R = Gb * m
    fold_flops = 8 * R * yB * F * yB + 6 * F * R * yB
    emit("bwd-fold", dt_fold, fold_flops,
         bytes_touched=rows.nbytes + 2 * F * yB * yB * 4 * 2,
         note=f"adjoint sampled einsum, K={R} rows -> [F, yB, yB] "
              "image accumulator (includes the zeros rebuild)")
    n_folds = -(-len(col_offs0) // Gb)
    print(json.dumps({
        "stage": "bwd-model",
        "full_cover_lower_s": round(
            n_folds * (dt_bcol + dt_fold - 2 * t_lat), 2
        ),
        "full_cover_upper_s": round(
            n_folds * (dt_bcol + dt_fold + 2 * t_lat), 2
        ),
        "note": f"{len(col_offs0)} columns in {n_folds} fold groups of "
                f"{Gb}; the round trip adds this to the forward model "
                "above (plus the final facet finish)",
    }), flush=True)


if __name__ == "__main__":
    main()
