"""Roofline share of the column passes, forward and backward (layer:
kernels, the column-pass bodies of ``ops.pallas_kernels`` and their
einsum and fft siblings).

Device time of the operations under ``fwd.column_pass``,
``fwd.slab_step`` (the facet-slab plan's column step: the column pass
of one facet slab into the group's sums), ``fwd.group_finish`` and
``bwd.column_pass``, against the least time of `counts`'
``fwd_column_pass`` and ``bwd_column_pass`` over the columns the span
ran, collectives left out."""

SCOPES = ["fwd.column_pass", "fwd.slab_step", "fwd.group_finish",
          "bwd.column_pass"]


def read(reading):
    return reading.roofline(SCOPES, ["fwd_column_pass", "bwd_column_pass"])
