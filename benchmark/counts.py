"""Least work of each stage of the SwiFTly transform, from shapes alone.

A roofline share is the least time the chip could take for a stage's
work, divided by the stage's device time. The work here is what the
SwiFTly algorithm itself must do, whatever body implements a stage:
every transform is counted as a radix-2 FFT, ``5 n log2 n`` FLOPs per
complex line of length ``n``, and window multiplies at 6 FLOPs per
complex point. The bytes are only what a stage must write to memory
that no fusion can keep on chip: the finished subgrids, the facet
accumulator once a pass, and what the backward reads of the forward's
subgrids. The program's bodies (matrix DFTs, Pallas kernels, fused
steps) do more FLOPs and move more bytes than this, so no body reads
over 100%, and the same stage reads the same work whatever runs it.

Geometry (all from a configuration file): ``N`` image size, ``yB``
facet size, ``yN`` padded facet size, ``xA`` subgrid size, ``xM``
padded subgrid size, ``m = xM * yN / N`` contribution size, ``F`` facets
that hold data, ``C`` subgrid columns of the cover, ``S`` subgrids per
column. Complex float32 is 8 bytes.

Every function returns ``(flops, bytes)`` for ONE subgrid column, the
unit the harness counts; work done once a pass is spread evenly over
the pass's ``C`` columns.
"""

from __future__ import annotations

import math

COMPLEX_BYTES = 8


def fft(n):
    """FLOPs of one complex radix-2 FFT of length ``n``."""
    return 5 * n * math.log2(n)


def geometry(config, n_facets, n_columns, per_column):
    """The shape record every count takes, from a configuration dict."""
    N = int(config["N"])
    return {
        "N": N,
        "yB": int(config["yB_size"]),
        "yN": int(config["yN_size"]),
        "xA": int(config["xA_size"]),
        "xM": int(config["xM_size"]),
        "m": int(config["xM_size"]) * int(config["yN_size"]) // N,
        "F": int(n_facets),
        "C": int(n_columns),
        "S": int(per_column),
    }


def fwd_facet_pass(g):
    """Axis-0 preparation of every facet column (window, pad to yN,
    FFT) once a pass, and the windowed extraction of the column's ``m``
    rows from each facet."""
    flops = g["F"] * g["yB"] * fft(g["yN"]) / g["C"]
    flops += 6 * g["F"] * g["m"] * g["yB"]
    return flops, 0.0


def fwd_column_pass(g):
    """Axis-1 preparation of the column's rows (FFT of yN per row and
    facet), two length-m transforms per row of each (subgrid, facet)
    contribution, and the finish of each subgrid (inverse FFT of xM
    over xM then xA lines); writes the column's finished subgrids."""
    F, m, S, xM, xA = g["F"], g["m"], g["S"], g["xM"], g["xA"]
    flops = F * m * fft(g["yN"])
    flops += S * (F * 2 * m * fft(m) + (xM + xA) * fft(xM))
    return flops, S * xA * xA * COMPLEX_BYTES


def bwd_column_pass(g):
    """The adjoint of the forward column pass: each subgrid prepared
    (FFT of xM over xA then xM lines), two length-m transforms per
    (subgrid, facet), and the axis-1 finish of the column's rows; reads
    the column's subgrids."""
    F, m, S, xM, xA = g["F"], g["m"], g["S"], g["xM"], g["xA"]
    flops = S * ((xA + xM) * fft(xM) + F * 2 * m * fft(m))
    flops += F * m * fft(g["yN"])
    return flops, S * xA * xA * COMPLEX_BYTES


def bwd_fold(g):
    """The adjoint of the facet pass: the column's rows windowed into
    the facets, and the axis-0 transform of every facet column once a
    pass; writes the facet accumulator once a pass."""
    F, yB = g["F"], g["yB"]
    flops = F * yB * fft(g["yN"]) / g["C"] + 6 * F * g["m"] * yB
    return flops, F * yB * yB * COMPLEX_BYTES / g["C"]


STAGES = {
    "fwd_facet_pass": fwd_facet_pass,
    "fwd_column_pass": fwd_column_pass,
    "bwd_column_pass": bwd_column_pass,
    "bwd_fold": bwd_fold,
}


def least_seconds(flops, nbytes, peak):
    """``(seconds, bound)``: the least time for ``flops`` and ``nbytes``
    on one chip of ``peak`` (a `peaks.json` entry), and which of the two
    bounds it."""
    t_flops = flops / (peak["bf16_tflops"] * 1e12)
    t_bytes = nbytes / (peak["hbm_gbytes_per_s"] * 1e9)
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
