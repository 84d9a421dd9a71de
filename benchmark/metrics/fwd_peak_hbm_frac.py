"""`peak_hbm_frac` in the forward cells, which report `fwd_subgrid_rate`:
the same reading, under a name of its own because a per-layer metric
names the one end-to-end metric it moves."""

from benchmark.metrics.peak_hbm_frac import read  # noqa: F401
