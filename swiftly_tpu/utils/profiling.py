"""Profiling and memory instrumentation.

The reference relies on Dask's performance_report + MemorySampler + worker
transfer logs (scripts/utils.py:166-231, demo_api.py:125-148). TPU
equivalents:

* `trace(dir)` — context manager writing a jax.profiler trace (viewable in
  Perfetto/TensorBoard) covering the wrapped region.
* `device_memory_stats()` — per-device live/peak byte counts.
* `MemorySampler` — periodic device-memory sampling into rows you can dump
  to CSV.
* `collective_bytes_forward/backward` — analytic transfer accounting: on a
  facet-sharded mesh the bytes moved per subgrid are exactly computable
  from the contribution size, replacing post-hoc Dask transfer-log
  scraping.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time

import numpy as np

logger = logging.getLogger(__name__)

# one-shot flag: warn the first time every device reports empty stats so
# operators know the memory artifacts they are writing carry no data
# (e.g. the CPU backend).
_warned_empty_stats = False

__all__ = [
    "MemorySampler",
    "collective_bytes_backward",
    "collective_bytes_forward",
    "column_collective_bytes",
    "device_memory_stats",
    "probe_hbm_bytes",
    "trace",
]

# probe_hbm_bytes result cache: None = not probed yet; 0 = probed, nothing
# measurable; >0 = usable HBM bytes
_probed_hbm = None


def probe_hbm_bytes(device=None):
    """USABLE accelerator-memory bytes for budget sizing (margins already
    applied — callers subtract their own residents, not another safety
    factor).

    90% of `memory_stats()["bytes_limit"]`. Returns None on CPU or
    where the runtime reports no limit (callers fall back to their own
    default). Result cached per process; SWIFTLY_HBM_PROBE=0 disables.
    """
    import os

    global _probed_hbm
    if os.environ.get("SWIFTLY_HBM_PROBE", "1") == "0":
        return None
    if _probed_hbm is not None:
        return _probed_hbm or None
    import jax

    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        limit = (device.memory_stats() or {}).get("bytes_limit", 0)
    except Exception:  # pragma: no cover - backend-specific
        limit = 0
    _probed_hbm = int(0.9 * limit)  # reported TOTAL -> usable
    return _probed_hbm or None


@contextlib.contextmanager
def trace(log_dir=None):
    """Write a jax.profiler trace for the enclosed region (no-op if
    log_dir is None)."""
    if log_dir is None:
        yield
        return
    import jax

    jax.profiler.start_trace(str(log_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_memory_stats() -> dict:
    """Per-device memory statistics (bytes_in_use, peak_bytes_in_use, ...).

    Returns an empty dict per device on backends that don't expose stats
    (e.g. CPU)."""
    import jax

    stats = {}
    for dev in jax.devices():
        try:
            stats[str(dev)] = dev.memory_stats() or {}
        except Exception:  # pragma: no cover - backend-specific
            stats[str(dev)] = {}
    global _warned_empty_stats
    if not _warned_empty_stats and not any(stats.values()):
        _warned_empty_stats = True
        logger.warning(
            "memory_stats() is empty on every device (%s) — memory "
            "reports/CSVs from this run will contain only zeros",
            ", ".join(stats) or "no devices",
        )
    return stats


class MemorySampler:
    """Samples device memory on a background thread.

    Usage::

        sampler = MemorySampler(interval=0.5)
        with sampler.sample():
            ... work ...
        rows = sampler.rows   # [(t, device, bytes_in_use), ...]
        sampler.to_csv("mem.csv")
    """

    def __init__(self, interval: float = 0.5):
        self.interval = interval
        self.rows = []
        self._stop = threading.Event()
        self._thread = None

    def _loop(self):
        t0 = time.time()
        while not self._stop.is_set():
            for dev, stats in device_memory_stats().items():
                self.rows.append(
                    (time.time() - t0, dev, stats.get("bytes_in_use", 0))
                )
            self._stop.wait(self.interval)

    @contextlib.contextmanager
    def sample(self):
        self.rows = []
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        try:
            yield self
        finally:
            self._stop.set()
            self._thread.join()

    def to_csv(self, path):
        with open(path, "w") as fh:
            fh.write("t_seconds,device,bytes_in_use\n")
            for t, dev, b in self.rows:
                fh.write(f"{t:.3f},{dev},{b}\n")

    def to_html(self, path, title="device memory"):
        """Self-contained HTML report: an inline-SVG memory timeline per
        device (the analogue of the reference demo's Dask
        performance-report HTML, reference demo_api.py:127-133)."""
        import html as _html

        title = _html.escape(str(title))
        by_dev = {}
        for t, dev, b in self.rows:
            by_dev.setdefault(str(dev), []).append((t, b))
        t_max = max((t for t, _, _ in self.rows), default=1.0) or 1.0
        b_max = max((b for _, _, b in self.rows), default=1) or 1
        W, H, PAD = 800, 240, 40
        # legend column to the right of the plot so labels never overlap
        # the curves, however many devices there are
        LEG = 180
        colors = ["#1f77b4", "#d62728", "#2ca02c", "#9467bd", "#ff7f0e"]
        parts = [
            "<!DOCTYPE html><html><head><meta charset='utf-8'>"
            f"<title>{title}</title></head><body>"
            f"<h2>{title}</h2>"
            f"<p>peak {b_max / 2**30:.2f} GiB over {t_max:.1f} s</p>"
            f"<svg width='{W + LEG}' height='{H}' "
            "style='background:#fafafa;border:1px solid #ccc'>"
        ]
        for i, (dev, pts) in enumerate(sorted(by_dev.items())):
            coords = [
                (
                    PAD + (W - 2 * PAD) * t / t_max,
                    H - PAD - (H - 2 * PAD) * b / b_max,
                )
                for t, b in pts
            ]
            c = colors[i % len(colors)]
            if len(coords) == 1:
                # a one-point polyline renders nothing: draw a dot
                x, y = coords[0]
                parts.append(
                    f"<circle cx='{x:.1f}' cy='{y:.1f}' r='3' "
                    f"fill='{c}'/>"
                )
            else:
                poly = " ".join(f"{x:.1f},{y:.1f}" for x, y in coords)
                parts.append(
                    f"<polyline points='{poly}' fill='none' stroke='{c}' "
                    f"stroke-width='1.5'/>"
                )
            parts.append(
                f"<text x='{W + 8}' y='{16 + 14 * i}' fill='{c}' "
                f"font-size='12'>{_html.escape(dev)}</text>"
            )
        parts.append(
            f"<text x='{PAD}' y='{H - 8}' font-size='11'>0 s</text>"
            f"<text x='{W - PAD - 30}' y='{H - 8}' font-size='11'>"
            f"{t_max:.0f} s</text>"
            f"<text x='2' y='{PAD}' font-size='11'>"
            f"{b_max / 2**30:.1f} GiB</text>"
            "</svg></body></html>"
        )
        with open(path, "w") as fh:
            fh.write("".join(parts))


def _itemsize(dtype, planar: bool) -> int:
    size = np.dtype(dtype).itemsize
    return 2 * size if planar else size


def collective_bytes_forward(
    xM_size: int, n_devices: int, dtype=np.float32, planar: bool = True,
) -> int:
    """Bytes crossing the mesh per forward subgrid (analytic).

    Each device contributes a partial padded subgrid [xM, xM]; a ring
    all-reduce over d devices moves 2*(d-1) buffers in total.
    """
    buf = xM_size * xM_size * _itemsize(dtype, planar)
    return int(buf * 2 * (n_devices - 1))


def collective_bytes_backward(
    xA_size: int, n_devices: int, dtype=np.float32, planar: bool = True,
) -> int:
    """Bytes crossing the mesh per backward subgrid (analytic).

    The subgrid [xA, xA] is broadcast to every device holding facets;
    accumulators stay device-local (no further collectives).
    """
    buf = xA_size * xA_size * _itemsize(dtype, planar)
    return int(buf * (n_devices - 1))


def column_collective_bytes(
    core, n_devices: int, n_subgrids: int, direction: str = "forward",
    subgrid_size: int | None = None,
) -> int:
    """Analytic wire bytes of ONE streamed column's collectives — the
    per-stage transfer attribution the obs instrumentation stamps on
    mesh column passes (zero off-mesh, so single-device stages carry no
    phantom traffic).

    Forward: one psum of the column's [S, xM, xM] partials (ring
    all-reduce accounting, `collective_bytes_forward` per subgrid).
    Backward: the column's subgrids broadcast to every facet shard
    (`collective_bytes_backward`; requires `subgrid_size`).
    """
    if n_devices <= 1:
        return 0
    planar = core.backend == "planar"
    if direction == "forward":
        per = collective_bytes_forward(
            core.xM_size, n_devices, core.dtype, planar
        )
    elif direction == "backward":
        if subgrid_size is None:
            raise ValueError("backward direction requires subgrid_size")
        per = collective_bytes_backward(
            subgrid_size, n_devices, core.dtype, planar
        )
    else:
        raise ValueError(f"direction must be forward|backward, got {direction!r}")
    return per * n_subgrids
