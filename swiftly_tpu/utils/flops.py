"""Analytic FLOP accounting for the planar matmul-FFT pipeline.

Every compute op in the planar backend is an einsum (or elementwise op) of
statically known shape, so the FLOP count of a whole transform is exact —
no sampling or hardware counters needed. The bench reports effective
TFLOP/s and % of the chip's published peak alongside the wall-clock, which
turns `vs_baseline` (a soft single-core-numpy yardstick) into a hard
hardware-utilisation number.

Conventions: one multiply-add = 2 FLOPs; counts follow the default "4mul"
complex-product algorithm (4 real matmuls per complex matmul,
`planar_backend._cmatmul`); elementwise twiddle/phase/window multiplies are
included (6 FLOPs per complex point) but are <1% of any total.
"""

from __future__ import annotations

import os

from ..ops.planar_backend import _DIRECT_MAX, _factor

__all__ = [
    "bwd_column_pass_flops",
    "bwd_fold_flops",
    "colpass_mode",
    "column_pass_flops",
    "fft_flops",
    "forward_batched_flops",
    "forward_sampled_flops",
    "backward_batched_flops",
    "backward_sampled_flops",
    "peak_tflops",
    "resolve_colpass",
    "resolve_colpass_bwd",
    "sampled_facet_pass_flops",
]


def fft_flops(n: int, batch: int) -> int:
    """FLOPs of one planar matmul (i)FFT of size n over `batch` rows.

    Direct (n <= 1024): 4 real [batch, n] x [n, n] matmuls.
    Factored n = n1*n2: two matmul rounds (8*batch*n*(n1+n2)) plus the
    elementwise twiddle (6 per complex point).
    """
    if n <= _DIRECT_MAX:
        return 8 * batch * n * n
    n1, n2 = _factor(n)
    return 8 * batch * n * (n1 + n2) + 6 * batch * n


def colpass_mode() -> str:
    """The streamed column-pass body (einsum|fft|pallas|auto, default
    auto) — the single parser of SWIFTLY_COLPASS, shared with
    `parallel.streamed` so the FLOP shape can never silently diverge
    from the executed algorithm. Read at trace/report time."""
    mode = os.environ.get("SWIFTLY_COLPASS", "auto")
    if mode not in ("einsum", "fft", "pallas", "auto"):
        raise ValueError(
            f"SWIFTLY_COLPASS must be einsum|fft|pallas|auto, got {mode!r}"
        )
    return mode


def _pallas_colpass_available(core) -> bool:
    """The fused Pallas column pass needs the planar backend (it
    contracts split real/imaginary planes)."""
    return getattr(core, "backend", "") == "planar"


def _on_tpu() -> bool:
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover - no backend at all
        return False


# Minimum stage-2 contraction depth (facets_in_program * m) for "auto"
# to pick the einsum FORWARD body. Measured on v5e through the round-5
# runtime (now gone): despite ~2x the chain's matmul FLOPs, the
# einsum body won at every measured forward shape — resident 32k
# (K = 9*256: 14.6 -> 12.2 s) AND facet-slab 64k (K = 1*256:
# 66.7 -> 61.7 s) — so "auto" currently resolves einsum everywhere;
# the threshold stays as the tuning point should a shallower shape
# regress.
_COLPASS_MIN_K = 0


def resolve_colpass(core, n_facets_in_program: int) -> str:
    """The column-pass body a program with `n_facets_in_program` stacked
    facets runs: the explicit SWIFTLY_COLPASS setting, or — under
    "auto" — the fused Pallas kernel on TPU (planar backend; Mosaic
    keeps the accumulator tile in VMEM across the whole K = F*m
    contraction, beating the einsum chain at every measured forward
    shape) falling back to the measured contraction-depth heuristic
    between einsum and fft elsewhere. An explicit ``pallas`` request on
    a non-planar backend degrades to einsum (there are no split planes
    to feed the kernel)."""
    mode = colpass_mode()
    if mode == "pallas":
        return "pallas" if _pallas_colpass_available(core) else "einsum"
    if mode != "auto":
        return mode
    if _pallas_colpass_available(core) and _on_tpu():
        return "pallas"
    if n_facets_in_program * core.xM_yN_size >= _COLPASS_MIN_K:
        return "einsum"
    return "fft"


def resolve_colpass_bwd(core, n_facets_in_program: int) -> str:
    """Backward column-pass body: SWIFTLY_COLPASS_BWD if set (einsum|
    fft|pallas), else the same fused Pallas kernel the forward resolves
    to on TPU (``reduce_f=False`` — per-facet Z products), einsum
    elsewhere — re-measured on v5e r5 (32k round trip, fg=2): 41.8 s
    einsum vs 48.3 s fft chain. The r4 measurement had einsum LOSING
    (80.4 vs 66.3 s), but that predated the one-shot
    `_bwd_scatter_rows` accumulator and the rebalanced Sb blocks; with
    those, the adjoint einsums' K=xM MXU contractions beat the
    per-(subgrid, facet) fft chains despite ~2x the FLOPs."""
    mode = os.environ.get("SWIFTLY_COLPASS_BWD", "")
    if mode:
        if mode not in ("einsum", "fft", "pallas"):
            raise ValueError(
                f"SWIFTLY_COLPASS_BWD must be einsum|fft|pallas, got {mode!r}"
            )
        if mode == "pallas" and not _pallas_colpass_available(core):
            return "einsum"
        return mode
    if _pallas_colpass_available(core) and _on_tpu():
        return "pallas"
    return "einsum"


def _per_subgrid_flops(
    core, subgrid_size: int, n_facets: int, colpass: str = "fft"
) -> int:
    """FLOPs to turn one column's NMBF_BFs into one finished subgrid.

    ``colpass="fft"`` (the batched path, and SWIFTLY_COLPASS=fft): per
    facet, add_to_subgrid axis 0 (fft size m over m rows) and axis 1
    (fft size m over xM rows) plus the Fn windows; then one
    finish_subgrid (ifft size xM over xM rows, crop, ifft size xM over
    xA rows, crop).

    ``colpass="einsum"``: one complex [xM, F*m] x [F*m, xM] stage-2
    contraction (4 real matmuls) — the facet reduction and the finish
    iFFTs are inside it / its operators, and the finish is a crop +
    masks. The per-program operator build (~F*(m^3 + 2*xM*m^2) complex
    ops, <0.5% of any cover) is excluded — understating, never
    overstating, the achieved TFLOP/s.

    ``colpass="pallas"``: the fused kernel runs the prepare matmul PER
    SUBGRID (dot #1 of the triple product A0 @ Xn @ B1): per facet a
    complex [xM, m] x [m, m] then [xM, m] x [m, xM] — so the hoisted
    per-column H contraction of the einsum shape moves here, at the
    gathered m-column width instead of the full yN width.
    """
    m, xM = core.xM_yN_size, core.xM_size
    if colpass == "einsum":
        return 8 * xM * xM * n_facets * m + 4 * subgrid_size**2
    if colpass == "pallas":
        return 8 * xM * m * (m + xM) * n_facets + 4 * subgrid_size**2
    per_facet = (
        fft_flops(m, m) + 6 * m * m  # axis 0 fft + Fn window
        + fft_flops(m, xM) + 6 * xM * m  # axis 1 fft + Fn window
    )
    finish = fft_flops(xM, xM) + fft_flops(xM, subgrid_size)
    # facet-sum (2 adds per complex point per facet) + masks
    reduce_mask = 2 * (n_facets - 1) * xM * xM + 4 * subgrid_size**2
    return n_facets * per_facet + finish + reduce_mask


def _column_prepare_flops(core, n_facets: int, colpass: str = "fft") -> int:
    """Axis-1 preparation of one column's rows: per facet, Fb window +
    ifft size yN over m rows; the einsum column pass adds its hoisted
    H = A0 @ NMBF_BF contraction ([xM, m] x [m, yN] complex per facet,
    shared by all the column's subgrids). The pallas body has NO hoisted
    term — its prepare matmul fuses into the per-subgrid triple product
    (counted in `_per_subgrid_flops`)."""
    m, yN = core.xM_yN_size, core.yN_size
    base = n_facets * (fft_flops(yN, m) + 6 * m * yN)
    if colpass == "einsum":
        base += n_facets * 8 * core.xM_size * m * yN
    return base


# -- per-stage counts (the obs instrumentation's attribution unit) ----------
#
# The whole-cover totals below are SUMS of these stage counts, so the
# per-stage MFU the metrics registry reports and the artifact-level
# tflops/mfu_pct the bench reports can never diverge: one formula per
# stage, used by both.


def sampled_facet_pass_flops(
    core, n_facets: int, facet_size: int, n_rows: int,
    real_facets: bool = False,
) -> int:
    """FLOPs of ONE sampled-DFT facet-pass einsum extracting `n_rows`
    contribution rows from `n_facets` resident facets (the forward's
    per-column-group dispatch; `n_rows` = G*m). ``real_facets`` halves
    the matmuls (the zero imaginary plane's einsums are skipped)."""
    yB = facet_size
    mm = 4 if real_facets else 8
    return mm * n_rows * yB * (n_facets * yB) + 6 * n_facets * n_rows * yB


def column_pass_flops(
    core, n_facets: int, n_subgrids: int, subgrid_size: int,
    colpass: str = "fft",
) -> int:
    """FLOPs of ONE forward column pass: axis-1 preparation plus the
    summation/finish of the column's `n_subgrids` subgrids, for the body
    (`colpass`) the executor actually runs."""
    return _column_prepare_flops(core, n_facets, colpass) + (
        n_subgrids * _per_subgrid_flops(core, subgrid_size, n_facets, colpass)
    )


def bwd_column_pass_flops(
    core, n_facets: int, n_subgrids: int, facet_size: int,
    subgrid_size: int, colpass: str = "einsum",
) -> int:
    """FLOPs of ONE backward column pass (subgrid column -> NAF_BMNAF
    rows): per-subgrid prepare/extract plus the per-column axis-1
    finish, for the executed body."""
    m, xM, yN = core.xM_yN_size, core.xM_size, core.yN_size
    if colpass in ("einsum", "pallas"):
        # two K=xM complex einsums per (subgrid, facet) plus the
        # scatter-add into the [F, m, yN] accumulator; the fused pallas
        # body runs the same contractions (as one grid program), so the
        # FLOP shape is identical
        per_sg = n_facets * 8 * (m * xM * xM + m * m * xM)
        per_sg += n_facets * 2 * m * yN
    else:
        # fft body: prepare (two ffts) + per-facet extraction
        per_sg = fft_flops(xM, subgrid_size) + fft_flops(xM, xM)
        per_sg += n_facets * (
            fft_flops(m, m) + 6 * m * xM + fft_flops(m, m) + 6 * m * m
        )
    col_fin = n_facets * (fft_flops(yN, m) + 6 * m * facet_size)
    return n_subgrids * per_sg + col_fin


def bwd_fold_flops(core, n_facets: int, facet_size: int, n_rows: int) -> int:
    """FLOPs of ONE adjoint sampled-DFT fold of `n_rows` concatenated
    column rows into the [F, yB, yB] image accumulator (the backward's
    per-fold-group dispatch; `n_rows` = P*m)."""
    yB = facet_size
    return 8 * n_rows * yB * (n_facets * yB) + 6 * n_facets * n_rows * yB


def forward_batched_flops(
    core, n_facets: int, facet_size: int, n_columns: int,
    subgrids_per_column: int, subgrid_size: int,
) -> int:
    """Total FLOPs of the batched whole-cover forward transform.

    prepare_facets (once) + per-column extraction/preparation + per-subgrid
    summation/finish — the exact op sequence of
    `parallel.batched.forward_all_batch`.
    """
    yN = core.yN_size
    prepare = n_facets * (fft_flops(yN, facet_size) + 6 * facet_size * yN)
    columns = n_columns * _column_prepare_flops(core, n_facets)
    subgrids = (
        n_columns
        * subgrids_per_column
        * _per_subgrid_flops(core, subgrid_size, n_facets)
    )
    return prepare + columns + subgrids


def forward_sampled_flops(
    core, n_facets: int, facet_size: int, n_columns: int,
    subgrids_per_column: int, subgrid_size: int,
    real_facets: bool = False, finish_passes: int = 1,
    colpass: str | None = None,
) -> int:
    """Total FLOPs of the streamed device-resident (sampled-DFT) forward.

    Facet pass: one [R, yB] x [F*yB, yB] complex matmul with R = C*m
    sampled rows, plus the per-facet diagonal phase; column pass: same as
    the batched path's per-column work.

    ``real_facets``: the facets' imaginary plane is identically zero, so
    the sampled matmul is 2 real matmuls instead of 4 — HALF the facet
    pass FLOPs (honest accounting: work skipped is not work done).
    ``finish_passes``: the facet-slab-streamed path finishes each subgrid
    once per slab and sums (linearity) — count the repeats.
    """
    yB = facet_size
    m, xM = core.xM_yN_size, core.xM_size
    if colpass is None:
        colpass = resolve_colpass(core, n_facets)
    R = n_columns * m
    facet_pass = sampled_facet_pass_flops(
        core, n_facets, yB, R, real_facets=real_facets
    )
    columns = n_columns * _column_prepare_flops(core, n_facets, colpass)
    subgrids = (
        n_columns
        * subgrids_per_column
        * _per_subgrid_flops(core, subgrid_size, n_facets, colpass)
    )
    if colpass in ("einsum", "pallas"):
        extra_finish = 0  # slab finish is a crop: no repeated iFFT passes
    else:
        extra_finish = (
            (finish_passes - 1)
            * n_columns
            * subgrids_per_column
            * (fft_flops(xM, xM) + fft_flops(xM, subgrid_size)
               + 4 * subgrid_size**2)
        )
    return facet_pass + columns + subgrids + extra_finish


def backward_sampled_flops(
    core, n_facets: int, facet_size: int, n_columns: int,
    subgrids_per_column: int, subgrid_size: int,
    colpass: str | None = None,
) -> int:
    """Total FLOPs of the streamed sampled-residency backward transform.

    Column stage per subgrid (prepare + per-facet extract) and per-column
    axis-1 finish as in the batched path; the axis-0 facet pass is the
    adjoint sampled einsum: [R, yB_i]^T x [F, R, yB_j] over all R =
    n_columns*m rows, plus conjugate phases and the Fb weighting.
    """
    m = core.xM_yN_size
    yB = facet_size
    if colpass is None:
        colpass = resolve_colpass_bwd(core, n_facets)
    columns = n_columns * bwd_column_pass_flops(
        core, n_facets, subgrids_per_column, yB, subgrid_size, colpass
    )
    fold = bwd_fold_flops(core, n_facets, yB, n_columns * m)
    finish_mask = 2 * n_facets * yB * yB
    return columns + fold + finish_mask


def backward_batched_flops(
    core, n_facets: int, facet_size: int, n_columns: int,
    subgrids_per_column: int, subgrid_size: int,
) -> int:
    """Total FLOPs of the batched whole-cover backward transform.

    Per subgrid: prepare_subgrid (two ffts) + per-facet extraction (two
    iffts + Fn windows); per column: per-facet axis-1 finish
    (fft size yN over m rows) + Fb window; finish: per-facet axis-0
    finish (fft size yN over yB rows).
    """
    m, xM, yN = core.xM_yN_size, core.xM_size, core.yN_size
    prep = fft_flops(xM, subgrid_size) + fft_flops(xM, xM)
    extract = n_facets * (
        fft_flops(m, m) + 6 * m * xM + fft_flops(m, m) + 6 * m * m
    )
    per_sg = prep + extract
    col_fin = n_facets * (
        fft_flops(yN, m) + 6 * m * facet_size
    )
    facet_fin = n_facets * (
        fft_flops(yN, facet_size) + 6 * facet_size * yN
    )
    return (
        n_columns * subgrids_per_column * per_sg
        + n_columns * col_fin
        + facet_fin
    )


# Published peak dense-matmul throughput, TFLOP/s. The planar pipeline runs
# f32 einsums at Precision.HIGHEST (bf16x3/f32 accumulate on the MXU), so
# the honest utilisation ceiling on TPU is the bf16 MXU peak divided by the
# 3 bf16 passes HIGHEST costs; published bf16 peaks below.
_PEAKS_BF16 = {
    "TPU v5 lite": 197.0,  # v5e
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v4": 275.0,
    "TPU v6e": 918.0,
}


def peak_tflops(device=None) -> float | None:
    """Peak f32-HIGHEST matmul TFLOP/s for the current device.

    None on the CPU, which has no MFU. An accelerator whose
    ``device_kind`` is not in the table raises: an MFU against a guessed
    peak is no number at all. SWIFTLY_PEAK_TFLOPS overrides the table
    (e.g. with a measured matmul roofline).
    """
    env = os.environ.get("SWIFTLY_PEAK_TFLOPS")
    if env:
        return float(env)
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = str(device.device_kind)
    for name, bf16 in _PEAKS_BF16.items():
        if name.lower() in kind.lower():
            return bf16 / 3.0  # HIGHEST = 3 bf16 MXU passes
    raise ValueError(
        f"no peak TFLOP/s known for device_kind {kind!r}; add it to "
        "utils.flops._PEAKS_BF16 or set SWIFTLY_PEAK_TFLOPS"
    )
