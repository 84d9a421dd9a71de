"""The comparison that decides ``correct``.

What the window produced is held against the plain reference
(`reference`), each number against its limit from
``benchmark/limits/<workload>.json``:

* ``subgrid_err``: over the subgrids drawn from the seed (one from each
  column of the cover), the largest ``||got - ref|| / ||ref||`` against
  the direct Fourier sum of the sky;
* ``facet_err`` (round trip): over every facet of the last pass, the
  largest ``||got - ref|| / ||sky||``, where ``ref`` is the facet's
  source pixels and ``||sky||`` the norm of all of them; computed on
  the device, a block of rows at a time, in float32;
* ``missing``: columns of the run's share (the whole cover where the
  configuration states none) whose drawn subgrid the window never
  produced (limit 0).
"""

from __future__ import annotations

import functools

import numpy as np

from . import reference


@functools.lru_cache(maxsize=None)
def _residual_fn(rows, yB):
    """Sum of ``|facet - ref|^2`` over rows ``[j0, j0 + rows)`` of facet
    ``f`` of a stack ``[F, yB, yB, 2]``, the reference scattered from
    its pixels into the block (pixels outside it are dropped)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def fn(stack, f, r, c, v, j0):
        z = jnp.int32(0)
        block = jax.lax.dynamic_slice(
            stack, (f, j0, z, z), (1, rows, yB, 2))[0]
        inside = (r >= j0) & (r < j0 + rows)
        rr = jnp.where(inside, r - j0, rows)  # out of range: dropped
        ref = jnp.zeros((rows, yB), block.dtype).at[rr, c].add(
            v, mode="drop")
        re = block[..., 0] - ref
        im = block[..., 1]
        return jnp.sum(re * re + im * im)

    return fn


def facet_errors(facets, facet_configs, pixels, yB, n_pix=1):
    """``||got - ref||`` of each facet of a device stack, in the order
    of ``facet_configs``."""
    import jax.numpy as jnp

    n_blocks = 1
    while yB * yB * 8 / n_blocks > 2.5e8 or yB % n_blocks:
        n_blocks += 1
    rows = yB // n_blocks
    fn = _residual_fn(rows, yB)
    out = []
    for f, fc in enumerate(facet_configs):
        r, c, v = pixels[(fc.off0, fc.off1)]
        pr = np.zeros(n_pix, np.int32)
        pc = np.zeros(n_pix, np.int32)
        pv = np.zeros(n_pix, np.float32)
        pr[: len(r)], pc[: len(c)], pv[: len(v)] = r, c, v
        total = 0.0
        for b in range(n_blocks):
            total += float(fn(facets, jnp.int32(f), pr, pc, pv,
                              jnp.int32(b * rows)))
        out.append(total ** 0.5)
    return out


def compare(op, limits):
    """``{"correct", "attempted", "failed", "checks"}`` for a finished
    window of `drive.Operation` ``op``."""
    errs = []
    for (off0, off1), got in op.samples.items():
        want = reference.subgrid(op.N, op.xA, op.sources, off0, off1)
        errs.append(reference.relative_error(got, want))
    missing = len(set(op.col_offs) - {off0 for off0, _ in op.samples})
    checks = {
        "subgrid_err": {"value": max(errs) if errs else float("inf"),
                        "limit": limits["subgrid_err"]},
    }
    failed = sum(e > limits["subgrid_err"] for e in errs)
    attempted = len(errs)
    facets = getattr(op, "facets", None)
    if facets is not None:
        sky = float(np.sqrt(sum(a * a for a, _, _ in op.sources)))
        ferrs = [e / sky for e in facet_errors(
            facets, op.facet_configs, op.pixels, op.yB)]
        op.facets = None  # the device's largest array, no longer needed
        checks["facet_err"] = {"value": max(ferrs),
                               "limit": limits["facet_err"]}
        failed += sum(e > limits["facet_err"] for e in ferrs)
        attempted += len(ferrs)
    checks["missing"] = {"value": missing, "limit": 0}
    failed += max(missing, 0)
    correct = all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()
    )
    return {"correct": bool(correct), "attempted": attempted + max(missing, 0),
            "failed": int(failed), "checks": checks}
