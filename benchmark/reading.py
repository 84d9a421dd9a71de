"""What a traced run hands each per-layer metric's reader: the reduced
trace of the traced span, the work counted inside it, and the device's
memory after the window."""

from __future__ import annotations

from . import counts, trace
from .drive import STAGE_UNITS, Tracer


class Reading:
    def __init__(self, devices, host, config, peak, op, units, mem_peak,
                 mem_limit):
        ids = {d.id for d in op.devices}
        mine = {k: v for k, v in devices.items() if k in ids and v}
        self.devices = mine or {k: v for k, v in devices.items() if v}
        self.host = host
        self.lo, self.hi = trace.span_of(host, Tracer.SPAN)
        self.peak = peak
        self.units = dict(units)
        self.mem_peak = mem_peak
        self.mem_limit = mem_limit
        self.geometry = counts.geometry(
            config, len(op.facet_configs), len(op.col_offs), op.per_column)

    @property
    def span_s(self):
        return self.hi - self.lo

    def busy_s(self):
        """Busy seconds of the span, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(trace.length(trace.busy(ops, self.lo, self.hi))
                   for ops in self.devices.values()) / len(self.devices)

    def seconds(self, scopes):
        """Device seconds under ``scopes``, summed over the devices."""
        return sum(trace.scope_seconds(ops, scopes, self.lo, self.hi)
                   for ops in self.devices.values())

    def work(self, stages):
        """``(flops, bytes)`` of ``stages`` over the columns of the
        span."""
        flops = nbytes = 0.0
        for s in stages:
            f, b = counts.STAGES[s](self.geometry)
            n = self.units[STAGE_UNITS[s]]
            flops += f * n
            nbytes += b * n
        return flops, nbytes

    def roofline(self, scopes, stages):
        """Least time of ``stages``' work over the device time under
        ``scopes``, in %; None where the span holds neither."""
        t = self.seconds(scopes)
        flops, nbytes = self.work(stages)
        if t <= 0 or flops <= 0:
            return None
        least, _ = counts.least_seconds(flops, nbytes, self.peak)
        return 100.0 * least / t

    def collectives(self):
        """Whether any collective ran in the span."""
        return any(trace.is_collective(o)
                   for ops in self.devices.values() for o in ops
                   if self.lo <= o.start < self.hi)

    def exposed_collective_s(self):
        """Seconds with only a collective running, averaged over the
        devices."""
        return sum(trace.exposed_collective(ops, self.lo, self.hi)
                   for ops in self.devices.values()) / len(self.devices)

    def breakdown(self):
        return {
            "device_ops": trace.top_ops(self.devices, self.lo, self.hi),
            "idle_gaps": trace.idle_gaps(
                self.devices,
                [s for s in self.host if s.name != Tracer.SPAN],
                self.lo, self.hi),
        }
