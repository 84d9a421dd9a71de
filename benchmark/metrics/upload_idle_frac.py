"""Share of the traced span in which the device ran nothing while the
host was dispatching a staged facet slab to the device (the program's
``fwd.slab_upload`` span) or pulling the checksum of the slab two back
(``fwd.drain``): in the slab stream a drain finds the device idle only
while an upload it depends on has not landed. Averaged over the cell's
chips (layer: host stream)."""

from benchmark.metrics.staging_idle_frac import idle_inside

SPANS = ("fwd.slab_upload", "fwd.drain")


def read(reading):
    return idle_inside(reading, SPANS)
