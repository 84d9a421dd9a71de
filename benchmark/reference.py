"""Plain reference of the benchmark's deployments, in numpy.

It imports nothing of the program under test and takes nothing that the
program made. It gives:

* the full facet and subgrid covers of a configuration: offsets and 0/1
  ownership masks, with borders at the midpoints between neighbouring
  offsets, wrapping at the image edge (the upstream project's
  ``make_full_cover_config``);
* the seeded point-source sky of a configuration's ``assumed.sky``;
* each facet of that sky as its few non-zero pixels: a point source is
  one pixel, scaled by the facet's masks;
* each subgrid of that sky by the direct Fourier sum, in float64.

A facet at offset ``off`` holds the image pixels ``[off - yB//2,
off - yB//2 + yB)`` (centre-relative, modulo N); a subgrid at offset
``off`` holds the grid cells ``[off - xA//2, off - xA//2 + xA)``.
"""

from __future__ import annotations

import math

import numpy as np


def cover_offsets(N, size):
    """Offsets of the full 1D cover of ``N`` pixels by chunks of
    ``size``: multiples of ``size``."""
    return size * np.arange(math.ceil(N / size))


def cover_masks(N, size):
    """``{offset: 0/1 mask of length size}`` of the full 1D cover."""
    offs = cover_offsets(N, size)
    nxt = np.concatenate([offs[1:], [N + offs[0]]])
    border = (offs + nxt) // 2
    half = size // 2
    masks = {}
    for i, off in enumerate(offs):
        left = (border[i - 1] - off + half) % N
        right = border[i] - off + half
        mask = np.zeros(size)
        mask[int(left):int(right)] = 1.0
        masks[int(off)] = mask
    return masks


def owned_interval(N, size, index):
    """Image coordinates ``[lo, hi)`` that cover chunk ``index`` owns
    (``lo`` may be negative for the chunk that wraps)."""
    offs = cover_offsets(N, size)
    nxt = np.concatenate([offs[1:], [N + offs[0]]])
    border = (offs + nxt) // 2
    lo = border[index - 1] - (N if index == 0 else 0)
    return int(lo), int(border[index])


def draw_sky(N, yB, sky, seed):
    """The seeded point-source sky: ``[(amplitude, x0, x1), ...]``.

    ``sky`` is a configuration's ``assumed.sky``: ``n_sources`` sources
    with the amplitudes ``amp0 + amp_step * k``, each placed in its own
    facet of the full facet cover (the facets drawn from the seed) at a
    uniform position at least ``margin_frac * yB`` inside the part that
    facet owns. So every seed gives each facet at most one pixel, and
    the program gets inputs of one shape whatever the seed.
    """
    rng = np.random.default_rng(int(seed))
    n_axis = len(cover_offsets(N, yB))
    n = int(sky["n_sources"])
    if n > n_axis * n_axis:
        raise ValueError(f"{n} sources for {n_axis ** 2} facets")
    cells = rng.permutation(n_axis * n_axis)[:n]
    amps = sky["amp0"] + sky["amp_step"] * rng.permutation(n)
    margin = int(sky["margin_frac"] * yB)
    sources = []
    for amp, cell in zip(amps, cells):
        coords = []
        for index in divmod(int(cell), n_axis):
            lo, hi = owned_interval(N, yB, index)
            x = int(rng.integers(lo + margin, hi - margin))
            coords.append((x + N // 2) % N - N // 2)
        sources.append((float(amp), coords[0], coords[1]))
    return sources


def facet_pixels(N, yB, sources):
    """``{(off0, off1): (rows, cols, vals)}`` of every facet of the full
    cover: the sources that fall inside it, scaled by its masks."""
    masks = cover_masks(N, yB)
    out = {}
    for off0 in masks:
        for off1 in masks:
            rows, cols, vals = [], [], []
            for amp, x0, x1 in sources:
                r = (x0 - (off0 - yB // 2)) % N
                c = (x1 - (off1 - yB // 2)) % N
                if r < yB and c < yB:
                    rows.append(r)
                    cols.append(c)
                    vals.append(amp * masks[off0][r] * masks[off1][c])
            out[(off0, off1)] = (
                np.asarray(rows, np.int64),
                np.asarray(cols, np.int64),
                np.asarray(vals, np.float64),
            )
    return out


def subgrid(N, xA, sources, off0, off1):
    """Subgrid ``(off0, off1)`` of the full cover by the direct Fourier
    sum of ``sources``, masked, as complex128 ``[xA, xA]``."""
    masks = cover_masks(N, xA)
    u = np.arange(off0 - xA // 2, off0 - xA // 2 + xA)
    v = np.arange(off1 - xA // 2, off1 - xA // 2 + xA)
    out = np.zeros((xA, xA), complex)
    for amp, x0, x1 in sources:
        p0 = np.exp(2j * np.pi * ((u * x0) % N) / N)
        p1 = np.exp(2j * np.pi * ((v * x1) % N) / N)
        out += (amp / N**2) * np.outer(p0, p1)
    return out * np.outer(masks[off0], masks[off1])


def relative_error(got, want):
    """``||got - want|| / ||want||`` (L2 over all elements)."""
    got = np.asarray(got, complex)
    want = np.asarray(want, complex)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))
