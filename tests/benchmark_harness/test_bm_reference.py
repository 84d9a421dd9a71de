"""The benchmark's plain reference against the program's own oracle
(``swiftly_tpu.ops.oracle``) and covers at 1k, and the seeded sky."""

import bm_helpers
import numpy as np
import pytest

from benchmark import reference

N, YB, XA = 1024, 352, 448
SKY = {"n_sources": 8, "amp0": 1.0, "amp_step": 0.25, "margin_frac": 0.125}


@pytest.fixture(scope="module")
def program_config():
    from swiftly_tpu import SwiftlyConfig

    t = bm_helpers.TINY
    return SwiftlyConfig(W=t["W"], fov=1, N=N, yB_size=YB,
                         yN_size=t["yN_size"], xA_size=XA,
                         xM_size=t["xM_size"], backend="numpy")


def test_covers_match_the_program(program_config):
    from swiftly_tpu import make_full_facet_cover, make_full_subgrid_cover

    for size, cover in ((XA, make_full_subgrid_cover(program_config)),
                        (YB, make_full_facet_cover(program_config))):
        masks = reference.cover_masks(N, size)
        assert sorted({c.off0 for c in cover}) == sorted(masks)
        for c in cover:
            np.testing.assert_array_equal(c.mask0, masks[c.off0])
            np.testing.assert_array_equal(c.mask1, masks[c.off1])


def test_subgrids_match_the_oracle():
    from swiftly_tpu.ops.oracle import make_subgrid_from_sources

    sources = reference.draw_sky(N, YB, SKY, 5)
    masks = reference.cover_masks(N, XA)
    for off0, off1 in [(0, 0), (448, 896), (896, 448)]:
        want = make_subgrid_from_sources(
            sources, N, XA, [off0, off1], [masks[off0], masks[off1]])
        got = reference.subgrid(N, XA, sources, off0, off1)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-13)
        assert reference.relative_error(got, want) < 1e-10


def test_facet_pixels_match_the_oracle():
    from swiftly_tpu.ops.oracle import make_facet_from_sources

    sources = reference.draw_sky(N, YB, SKY, 6)
    masks = reference.cover_masks(N, YB)
    for (off0, off1), (r, c, v) in reference.facet_pixels(
            N, YB, sources).items():
        dense = np.zeros((YB, YB))
        np.add.at(dense, (r, c), v)
        want = make_facet_from_sources(
            sources, N, YB, [off0, off1], [masks[off0], masks[off1]])
        np.testing.assert_array_equal(dense, want.real)


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 3 * 2**40 + 1])
def test_sky_is_seeded_and_one_pixel_a_facet(seed):
    a = reference.draw_sky(N, YB, SKY, seed)
    assert a == reference.draw_sky(N, YB, SKY, seed)
    assert len(a) == 8
    assert sorted(s[0] for s in a) == [1.0 + 0.25 * k for k in range(8)]
    pixels = reference.facet_pixels(N, YB, a)
    assert max(len(r) for r, _, _ in pixels.values()) == 1
    assert sum(len(r) for r, _, _ in pixels.values()) == 8
    assert a != reference.draw_sky(N, YB, SKY, seed + 1)


@pytest.mark.parametrize("N_, yB", [(32768, 11264), (65536, 22528)])
def test_sky_fits_the_benchmark_sizes(N_, yB):
    pixels = reference.facet_pixels(
        N_, yB, reference.draw_sky(N_, yB, SKY, 2**31 + 9))
    assert len(pixels) == 9
    assert max(len(r) for r, _, _ in pixels.values()) == 1
