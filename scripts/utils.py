"""Shared CLI and instrumentation helpers for the demo scripts.

Parity: reference scripts/utils.py (CLI with @file argument support,
human-readable sizes, transfer accounting) — re-based on JAX device/memory
introspection instead of Dask worker logs.
"""

from __future__ import annotations

import argparse
import sys

__all__ = ["cli_parser", "human_readable_size"]


def human_readable_size(size: float, decimal_places: int = 3) -> str:
    """Format a byte count with binary units."""
    for unit in ["B", "KiB", "MiB", "GiB", "TiB", "PiB"]:
        if size < 1024 or unit == "PiB":
            break
        size /= 1024
    return f"{size:.{decimal_places}f} {unit}"


def _mesh_devices_arg(value: str) -> str:
    """Validate --mesh_devices at parse time: an integer or 'all'."""
    if value != "all":
        try:
            int(value)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"expected an integer or 'all', got {value!r}"
            ) from None
    return value


def cli_parser(description: str) -> argparse.ArgumentParser:
    """Common demo CLI. Supports @file argument files (one arg per line)."""
    parser = argparse.ArgumentParser(
        description=description,
        fromfile_prefix_chars="@",
    )
    parser.add_argument(
        "--swift_config",
        type=str,
        default="1k[1]-n512-256",
        help="comma-separated catalogue key(s), see swiftly_tpu.SWIFT_CONFIGS",
    )
    parser.add_argument(
        "--backend",
        type=str,
        default="jax",
        choices=["jax", "planar", "numpy", "native"],
        help="numerical backend",
    )
    parser.add_argument(
        "--precision",
        type=str,
        default="f64",
        choices=["f32", "f64"],
        help="working precision (f64 enables x64)",
    )
    parser.add_argument(
        "--source_number",
        type=int,
        default=10,
        help="number of random point sources in the test image",
    )
    parser.add_argument(
        "--queue_size", type=int, default=20, help="in-flight work cap"
    )
    parser.add_argument(
        "--lru_forward", type=int, default=1, help="forward column cache size"
    )
    parser.add_argument(
        "--lru_backward", type=int, default=1,
        help="backward column accumulator count",
    )
    parser.add_argument(
        "--execution",
        type=str,
        default="batched",
        choices=["batched", "fused", "streamed", "streamed-device"],
        help="execution strategy: 'batched' streams subgrid-by-subgrid "
             "with prepared facets device-resident; 'fused' runs the "
             "whole cover as ONE forward program and ONE backward "
             "program (fastest when everything fits HBM); 'streamed' "
             "buffers column intermediates in host RAM (out-of-core); "
             "'streamed-device' keeps raw facets resident and computes "
             "column groups by sampled DFT (large N on one chip, no "
             "host round-trip)",
    )
    parser.add_argument(
        "--col_group",
        type=int,
        default=0,
        help="streamed-device: columns per sampled-DFT group "
             "(0 = auto-size from the HBM budget)",
    )
    parser.add_argument(
        "--mesh_devices",
        type=_mesh_devices_arg,
        default="0",
        help="shard facets over this many devices "
             "(0 = single device, 'all' = every visible device)",
    )
    parser.add_argument(
        "--multihost",
        action="store_true",
        help="initialise jax.distributed for a multi-host pod slice "
             "(run the same command on every host)",
    )
    parser.add_argument(
        "--checkpoint",
        type=str,
        default=None,
        help="streamed executions: snapshot the backward accumulators to "
             "this directory every --checkpoint_every columns and "
             "auto-resume from an existing snapshot (long 32k+ runs "
             "survive preemption)",
    )
    parser.add_argument(
        "--checkpoint_every",
        type=int,
        default=8,
        help="columns between checkpoint snapshots",
    )
    parser.add_argument(
        "--profile_dir",
        type=str,
        default=None,
        help="write a jax.profiler trace to this directory",
    )
    parser.add_argument(
        "--artifact_dir",
        type=str,
        default=None,
        help="write per-run artifacts here: device-memory samples CSV, "
             "analytic collective-transfer bytes, and a summary JSON "
             "(parity with the reference demo's performance report / "
             "memory CSV / transfer txt)",
    )
    parser.add_argument(
        "--metrics",
        action="store_true",
        help="enable the per-stage metrics registry (swiftly_tpu.obs): "
             "host stage timers, per-stage FLOPs/MFU, and a telemetry "
             "block in the summary artifact (equivalent to "
             "SWIFTLY_METRICS=1)",
    )
    parser.add_argument(
        "--metrics_jsonl",
        type=str,
        default=None,
        help="also append per-stage telemetry events to this JSONL file "
             "(implies --metrics; equivalent to SWIFTLY_METRICS_JSONL)",
    )
    parser.add_argument(
        "--trace",
        type=str,
        default=None,
        metavar="PATH",
        help="record a hierarchical span timeline (swiftly_tpu.obs."
             "trace) and write Perfetto-loadable Chrome trace-event "
             "JSON to PATH at exit (equivalent to SWIFTLY_TRACE=1 + "
             "SWIFTLY_TRACE_PATH; inspect with scripts/trace_report.py)",
    )
    return parser


def enable_observability(args):
    """Turn on the metrics registry and/or span tracer the CLI asked
    for; returns the trace path (None = tracing off). The demos call
    this once after parse_args — one switchboard, identical knobs."""
    if getattr(args, "metrics", False) or getattr(args, "metrics_jsonl", None):
        from swiftly_tpu.obs import metrics

        metrics.enable(args.metrics_jsonl or None)
    trace_path = getattr(args, "trace", None)
    if trace_path:
        from swiftly_tpu.obs import trace

        trace.enable(trace_path)
    return trace_path


def setup_jax(args):
    """Apply precision/platform settings before first device use.

    Every backend runs on the default platform: a v5e runs complex64
    matmuls and `jnp.fft` (probed on the chip, PR 21). float64 has no
    TPU support, so ``--precision f64`` pins the CPU, and says so.
    """
    import jax

    from swiftly_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    if getattr(args, "multihost", False):
        from swiftly_tpu.parallel.mesh import initialize_multihost

        initialize_multihost()
    if args.precision == "f64":
        print("--precision f64: running on the CPU (no float64 on TPU)",
              file=sys.stderr)
        jax.config.update("jax_enable_x64", True)
        jax.config.update("jax_platforms", "cpu")
    return jax


def resolve_mesh(mesh_devices: str):
    """Build the facet mesh described by the --mesh_devices argument."""
    from swiftly_tpu.parallel.mesh import make_facet_mesh

    if mesh_devices == "all":
        return make_facet_mesh()
    n = int(mesh_devices)
    return make_facet_mesh(n_devices=n) if n else None


def make_sources(rng, count, image_size, fov=1.0):
    """Random integer point sources within the field of view."""
    lim = int(image_size // 2 * min(fov, 1.0)) - 1
    return [
        (float(rng.integers(1, 100)),
         int(rng.integers(-lim, lim)),
         int(rng.integers(-lim, lim)))
        for _ in range(count)
    ]
