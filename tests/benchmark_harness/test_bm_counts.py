"""`benchmark/counts.py` against FLOPs and bytes worked out by hand at
the catalogue's 1k[1]-n512-512: N=1024, yB=352, yN=512, xA=448,
xM=512, so m=256; 9 facets, 3 columns of 3 subgrids.

fft(512) = 5*512*9 = 23040 and fft(256) = 5*256*8 = 10240.
"""

import json

import bm_helpers
import pytest

from benchmark import counts

G = counts.geometry(dict(bm_helpers.TINY), n_facets=9, n_columns=3,
                    per_column=3)


def test_geometry():
    assert G == {"N": 1024, "yB": 352, "yN": 512, "xA": 448, "xM": 512,
                 "m": 256, "F": 9, "C": 3, "S": 3}


def test_fft_count():
    assert counts.fft(512) == 23040
    assert counts.fft(256) == 10240


def test_forward_facet_pass():
    # 9*352*23040/3 + 6*9*256*352 = 24330240 + 4866048
    assert counts.fwd_facet_pass(G) == (29196288, 0.0)


def test_forward_column_pass():
    # rows: 9*256*23040 = 53084160
    # subgrids: 3*(9*2*256*10240 + (512+448)*23040) = 207912960
    # bytes: 3 finished subgrids of 448^2 complex64 = 3*448*448*8
    assert counts.fwd_column_pass(G) == (260997120, 4816896)


def test_backward_column_pass_is_the_adjoint_work():
    assert counts.bwd_column_pass(G) == (260997120, 4816896)


def test_backward_fold():
    # flops as the facet pass; bytes: the 9 facet accumulators of
    # 352^2 complex64 written once a pass, a third of it per column
    assert counts.bwd_fold(G) == (29196288, 2973696)


def test_least_seconds_takes_the_larger_bound():
    peak = json.loads((bm_helpers.ROOT / "benchmark" / "peaks.json")
                      .read_text())["TPU v5 lite"]
    t, bound = counts.least_seconds(1.97e12, 0, peak)
    assert (t, bound) == (pytest.approx(0.01), "flops")
    t, bound = counts.least_seconds(1.0, 8.19e9, peak)
    assert (t, bound) == (pytest.approx(0.01), "bytes")


def test_every_stage_is_counted_by_one_function():
    assert set(counts.STAGES) == {"fwd_facet_pass", "fwd_column_pass",
                                  "bwd_column_pass", "bwd_fold"}
