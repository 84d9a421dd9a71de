"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell is found by its name:

* ``BENCHMARK.json`` ``configs[].file``: the configuration's sizes;
* ``benchmark/traffic/<traffic>.json``: the mix `drive` runs;
* ``benchmark/metrics/<metric>.py``: a reader with ``read(reading)``;
* ``benchmark/limits/<workload>.json``: the limits `correct` holds the
  cell's compared numbers to.

The run: refuse anything but enough TPU chips; set up (sky from the
seed, the program's inputs, every program the window runs compiled or
loaded from ``<checkout>/.jax_cache``); measure for ``--seconds``; read
the device's peak memory; with ``--trace 1`` reduce the traced span;
free the program's state; compare the window's answers with the plain
reference; print the checks on standard error and the result as the
last line of standard output.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "benchmark"

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is not as the harness needs."""


def _name(kind, value):
    if not isinstance(value, str) or not NAME.match(value):
        raise SpecError(f"bad {kind} name {value!r}")
    return value


def load_spec(root=ROOT):
    """``BENCHMARK.json``, with every name and unit checked."""
    spec = json.loads((Path(root) / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        _name("config", c["name"])
        for key in c["reduced"]:
            _name("reduced key", key)
    for w in spec["workloads"]:
        _name("workload", w["name"])
        _name("config", w["config"])
        _name("traffic", w["traffic"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        _name("metric", m["name"])
        if not UNIT.match(m["unit"]):
            raise SpecError(f"bad unit {m['unit']!r} of {m['name']}")
    return spec


def _reader(path):
    spec = importlib.util.spec_from_file_location(
        f"benchmark_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec, workload, root=ROOT):
    """Everything the run of ``workload`` needs, found by name."""
    root = Path(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
        .read_text())
    limits = json.loads(
        (root / "benchmark" / "limits" / f"{workload}.json").read_text())
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "limits": limits,
        "end_to_end": [m for m in spec["end_to_end"]
                       if _applies(m, workload)],
        "per_layer": per_layer,
        "readers": {
            m["name"]: _reader(root / "benchmark" / "metrics"
                               / f"{m['name']}.py")
            for m in per_layer
        },
    }


def device_stamp(n_chips):
    """The result's device record; exits non-zero, before anything is
    printed, unless JAX finds at least ``n_chips`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < n_chips:
        raise SystemExit(
            f"benchmark: needs {n_chips} TPU chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": int(n_chips)}


class CompileClock:
    """Counts JAX's tracing and compiling events, so the run can say how
    many happened inside the window (there should be none)."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.count += 1


def process_age():
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def configure(config):
    """Set what the configuration states before the program traces
    anything. The precision is the configuration's guarantee: it is
    set, not read from the environment."""
    os.environ["SWIFTLY_PRECISION"] = config["precision"]


def use_cache(cache_dir=None):
    """JAX's persistent compilation cache at a fixed path in the
    checkout, every program in it, so only a checkout's first run of a
    cell compiles."""
    import jax

    cache_dir = str(cache_dir or ROOT / ".jax_cache")
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicting cache keeps access-time files beside each
    # entry, and one it finds without them fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)


def memory(devices):
    """``(peak bytes on the fullest chip, that chip's limit)``."""
    peak, limit = 0, 0
    for d in devices:
        st = d.memory_stats() or {}
        p = int(st.get("peak_bytes_in_use", 0))
        if p >= peak:
            peak, limit = p, int(st.get("bytes_limit", 0))
    return peak, limit


def peak_of(kind):
    """The `peaks.json` entry of a device kind; an unknown kind is an
    error, never a default."""
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise SpecError(f"no peaks.json entry for {kind!r}")
    return peaks[kind]


def run(res, seed, seconds, trace, device, setup_t0=None):
    """One run of a resolved cell; returns the result line's dict.
    ``setup_t0`` (a ``time.perf_counter()`` reading) stands in for the
    process start where the run is not a process of its own."""
    from . import check, drive
    from . import trace as trace_mod
    from .reading import Reading

    cell, config, traffic = res["cell"], res["config"], res["traffic"]
    peak_table = peak_of(device["kind"]) if trace else None
    clock = CompileClock()
    op = drive.OPERATIONS[traffic["operation"]](config, cell["chips"],
                                                traffic)
    op.load(seed)
    op.build()
    op.warm()
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        tracer = drive.Tracer(tdir, op.devices)
        if trace:
            tracer.barrier()  # compiles the drain before the window
        setup_s = process_age() if setup_t0 is None else (
            time.perf_counter() - setup_t0)
        c0 = clock.count
        subgrids, window_s = op.window(seconds, tracer)
        window_compiles = clock.count - c0
        peak, limit = memory(op.devices)
        reading = None
        if trace:
            if tracer.state != "done":
                raise RuntimeError(
                    "the window ended before the traced span began")
            devices, host = trace_mod.read_xplane(
                trace_mod.find_profile(tdir))
            reading = Reading(devices, host, config, peak_table, op,
                              tracer.units, peak, limit)
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    op.free()
    verdict = check.compare(op, res["limits"])
    metrics = {}
    if trace:
        for m in res["per_layer"]:
            v = res["readers"][m["name"]](reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        # one rate under two names, each bounded by the spread of its own
        # cells: the round trip's host slab stream spreads its runs far
        # wider than the forwards from device-resident facets do
        rate = subgrids / window_s
        e2e = {"subgrid_rate": rate, "fwd_subgrid_rate": rate,
               "setup_s": setup_s}
        for m in res["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=peak)
    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": dev,
    }
    if reading is not None:
        dev["busy_s"] = reading.busy_s()
        dev["window_s"] = reading.span_s
        result["breakdown"] = reading.breakdown()
    result["run"] = {"subgrids": subgrids, "window_s": window_s,
                     "window_compiles": window_compiles,
                     "columns": op.columns, "facet_input": op.facet_input,
                     "plan": getattr(op, "plan", {})}
    result["checks"] = verdict["checks"]  # last, as the contract asks
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    res = resolve(load_spec(), args.workload)
    device = device_stamp(res["cell"]["chips"])
    configure(res["config"])
    use_cache()
    result = run(res, args.seed, args.seconds, bool(args.trace), device)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0

