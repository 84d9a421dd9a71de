"""Share of the traced span in which the device ran nothing while the
host was staging a facet slab: blocked on the prefetch thread's copy
(the program's ``fwd.slab_wait`` span) or copying the slab itself
(``fwd.slab_stage``). Averaged over the cell's chips (layer: host
stream)."""

from benchmark import trace

SPANS = ("fwd.slab_wait", "fwd.slab_stage")


def idle_inside(reading, names):
    """Percent of the traced span in which a device was idle while the
    host was inside one of the spans ``names``, averaged over the
    devices; None where the trace has no device plane or no such
    span."""
    lo, hi = reading.lo, reading.hi
    inside = trace.union(trace.clip(
        [(s.start, s.end) for s in reading.host if s.name in names],
        lo, hi))
    if not reading.devices or not inside or hi <= lo:
        return None
    idle = 0.0
    for ops in reading.devices.values():
        gaps = trace.gaps(ops, lo, hi)
        idle += sum(trace.length(trace.clip(gaps, a, b)) for a, b in inside)
    return 100.0 * idle / len(reading.devices) / (hi - lo)


def read(reading):
    return idle_inside(reading, SPANS)
