#!/usr/bin/env python3
"""Bring-up smoke: the main path once on the chip, at sizes users run.

One process owns the chip and starts no child that touches JAX. Without
options (one chip) it runs, in order:

  (a) ``4k[1]-n2k-512`` through ``SwiftlyForward.all_subgrids`` and
      ``backward_all`` (planar f32), checked against the direct-DFT
      oracle (``make_subgrid_from_sources`` / ``make_facet_from_sources``);
  (b) ``32k[1]-n16k-512``, the headline's shapes: a full-cover
      ``StreamedForward(residency="device")`` feeding one
      ``StreamedBackward(residency="sampled")``; the forward is checked
      on sampled subgrids against the oracle, every facet after the
      round trip against its input;
  (c) a few ``serve.SubgridService`` requests on (a)'s prepared facets,
      bit-for-bit against ``get_subgrid_task``.

``--chips 4`` runs only the facet-mesh round trip at ``64k[1]-n32k-1k``
through ``MeshStreamedForward``/``MeshStreamedBackward`` with the default
collective: the 9 facets, padded to 12, stay resident three to a chip,
and one forward pass over the full cover feeds a backward that
accumulates facets 0-2, one per chip (the resident stack plus all nine
accumulators would need ~18 GB per chip; the cut is printed on an
earlier line). It checks the same oracle samples and the accumulated
facets, and prints ``bytes_in_use`` per device.

RMS gates use ``bench.PRECISION_RMS_BUDGET_REL["highest"]``: a subgrid
RMS is made relative by N² (docs/accuracy.md), a facet RMS by the
largest source amplitude. Each phase prints one JSON line with its wall
time, the compile time inside it, its RMS, ``peak_bytes_in_use`` and the
column-pass/fold bodies the ``auto`` rules picked. Any failed check, any
retry or degradation step the path records, or a platform other than
``tpu`` exits non-zero without the final line, which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

PHASE_A_CONFIG = "4k[1]-n2k-512"
PHASE_B_CONFIG = "32k[1]-n16k-512"
MESH_CONFIG = "64k[1]-n32k-1k"
# The 64k backward on four chips accumulates one facet per chip next to
# the resident stack (~11 GB in use per chip, PR 21's chip run); all
# nine facets take three such passes, each re-running the forward,
# which PR 21's chip budget did not cover.
MESH_BWD_FACETS = 3
SERVE_REQUESTS = 8


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def device_stamp(n_chips=1):
    """The device record of the last line; exits non-zero unless JAX
    found at least ``n_chips`` TPU devices. Runs before anything else
    touches the repo, so a CPU-only host or a bare directory prints
    nothing that looks like a result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}"
        )
    if len(devs) < n_chips:
        raise SystemExit(
            f"chip_smoke: needs {n_chips} TPU chips, JAX found {len(devs)}"
        )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own
    duration events), so each phase reports compile apart from wall."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration


def _complex(planar):
    planar = np.asarray(planar)
    return planar[..., 0] + 1j * planar[..., 1]


def _memory(devices):
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append({
            "device": d.id,
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        })
    return out


def _bodies(core, n_facets, meshed=False):
    """The bodies the ``auto`` rules resolve for a program holding
    ``n_facets`` facets on this backend."""
    from swiftly_tpu.parallel.streamed import (
        resolve_fold_kernel,
        resolve_fold_mode,
        select_fold_body,
    )
    from swiftly_tpu.utils.flops import resolve_colpass, resolve_colpass_bwd

    mode = resolve_fold_mode()
    return {
        "colpass_fwd": resolve_colpass(core, n_facets),
        "colpass_bwd": resolve_colpass_bwd(core, n_facets),
        # the fold body of a call of 1 and of 2 columns
        "fold": [
            select_fold_body(mode, core.yN_size, g * core.xM_yN_size, meshed)
            for g in (1, 2)
        ],
        "fold_kernel": resolve_fold_kernel(core, meshed=meshed),
    }


def _recorded_degradations():
    """Retries and degradation steps the path recorded."""
    from swiftly_tpu.obs import metrics
    from swiftly_tpu.resilience import degrade

    counters = metrics.export().get("counters") or {}
    bad = {
        k: v for k, v in counters.items()
        if v and k.startswith(("retry.", "degrade.", "spill.fallback"))
    }
    if degrade.events():
        bad["degrade.events"] = degrade.events()
    return bad


def _gate(record):
    """Fail the phase on a recorded degradation or an RMS over budget."""
    import bench

    budget = bench.PRECISION_RMS_BUDGET_REL["highest"]
    record["budget_rel"] = budget
    bad = _recorded_degradations()
    if bad:
        record["degradations"] = bad
        raise SmokeFailure(f"phase {record['phase']}: degraded: {bad}")
    for k in ("subgrid_rms_rel", "facet_rms_rel"):
        if k in record and not record[k] <= budget:
            raise SmokeFailure(
                f"phase {record['phase']}: {k} {record[k]:.3e} over the "
                f"highest-precision budget {budget:.1e}"
            )


def _facet_rms(stack, f, sparse):
    """RMS of round-tripped planar facet ``f`` of a single-device stack
    [n, yB, yB, 2] against its sparse input facet, on device in row
    chunks, in place (bench's own reduction)."""
    import jax.numpy as jnp

    import bench

    yB = int(stack.shape[1])
    n_ch = max(1, int(yB * yB * 12 / 1.2e9))
    while yB % n_ch:
        n_ch += 1
    rows = yB // n_ch
    fn = bench._chunk_rms2_fn(rows, yB)
    total = 0.0
    for ci in range(n_ch):
        total += float(
            fn(stack, jnp.int32(f), sparse.rows, sparse.cols,
               sparse.vals.astype(np.float32), jnp.int32(ci * rows))
        )
    return (total / (yB * yB)) ** 0.5


def _sample_rms2(core, per_col, group, sample_map, oracle_dev, acc):
    """Fold the column group's oracle-sampled subgrids into the running
    device-side max |residual|² (``acc``)."""
    import jax.numpy as jnp

    import bench

    for c, col in enumerate(per_col):
        for s, (i, _) in enumerate(col):
            k = sample_map.get(i)
            if k is not None:
                acc = jnp.maximum(
                    acc, bench._rms2_device(core, group[c, s], oracle_dev[k])
                )
    return acc


def _params(name):
    from swiftly_tpu import SWIFT_CONFIGS

    params = dict(SWIFT_CONFIGS[name])
    params.setdefault("fov", 1.0)
    return params


def phase_batched(name=PHASE_A_CONFIG):
    """(a): whole-cover forward and backward through the fused API."""
    import jax.numpy as jnp

    import bench
    from swiftly_tpu import backward_all, check_facet, check_subgrid

    config, fwd, facet_configs, subgrid_configs, sources = bench._build(
        "planar", _params(name), jnp.float32
    )
    N = config.image_size
    amp = max(s[0] for s in sources)
    subgrids = fwd.all_subgrids(subgrid_configs)
    facets = backward_all(
        config, facet_configs,
        [(sg, subgrids[i]) for i, sg in enumerate(subgrid_configs)],
    )
    sg_host, f_host = np.asarray(subgrids), np.asarray(facets)
    rms_sg = max(
        check_subgrid(N, sg, _complex(sg_host[i]), sources)
        for i, sg in enumerate(subgrid_configs)
    )
    rms_f = max(
        check_facet(N, fc, _complex(f_host[i]), sources)
        for i, fc in enumerate(facet_configs)
    )
    record = {
        "phase": "a", "config": name, "n_subgrids": len(subgrid_configs),
        "n_facets": len(facet_configs),
        "subgrid_rms_rel": rms_sg * N * N,
        "facet_rms_rel": rms_f / amp,
    }
    return record, (fwd, subgrid_configs)


def phase_streamed(name=PHASE_B_CONFIG):
    """(b): full-cover streamed forward feeding the sampled backward."""
    import jax.numpy as jnp

    import bench
    from swiftly_tpu import make_sparse_facet
    from swiftly_tpu.parallel import StreamedBackward
    from swiftly_tpu.plan import PlanInputs, compile_plan
    from swiftly_tpu.plan.model import DEFAULT_RESERVE_BYTES

    config, fwd, facet_configs, subgrid_configs, sources = bench._build(
        "planar", _params(name), jnp.float32, streamed=True
    )
    N = config.image_size
    core = config.core
    sample_map, oracle_dev = bench._oracle_sample_stack(
        config, subgrid_configs, sources
    )
    # the backward's accumulator shares the chip with the forward: the
    # plan prices it, and the forward's sizers see what is left
    cplan = compile_plan(PlanInputs.from_cover(
        config, facet_configs, subgrid_configs,
        real_facets=fwd._facets_real, fold_group=2,
    ))
    if len(cplan.backward.parts) != 1:
        raise SmokeFailure(
            f"phase b: the plan partitions the backward into "
            f"{len(cplan.backward.parts)} passes; this smoke feeds one"
        )
    fwd.hbm_headroom = int(
        oracle_dev.nbytes + cplan.backward.resident_bytes
        + DEFAULT_RESERVE_BYTES
    )
    bwd = StreamedBackward(
        config, facet_configs, residency="sampled",
        fold_group=cplan.backward.fold_group,
    )
    max_rms2 = jnp.zeros((), jnp.float32)
    for per_col, group in fwd.stream_column_groups(subgrid_configs):
        max_rms2 = _sample_rms2(
            core, per_col, group, sample_map, oracle_dev, max_rms2
        )
        bwd.add_subgrid_group([[sg for _, sg in col] for col in per_col],
                              group)
    facets = bwd.finish_device()
    amp = max(s[0] for s in sources)
    rms_f = max(
        _facet_rms(facets, i, make_sparse_facet(N, fc, sources,
                                                dtype=np.float32))
        for i, fc in enumerate(facet_configs)
    )
    record = {
        "phase": "b", "config": name, "n_subgrids": len(subgrid_configs),
        "n_facets": len(facet_configs), "n_oracle_samples": len(sample_map),
        "subgrid_rms_rel": float(max_rms2) ** 0.5 * N * N,
        "facet_rms_rel": rms_f / amp,
        "fwd_plan": {k: (fwd.last_plan or {}).get(k)
                     for k in ("mode", "col_group", "facet_group",
                               "colpass")},
        "bodies": _bodies(core, len(facet_configs)),
    }
    return record


def phase_serve(fwd, subgrid_configs, n=SERVE_REQUESTS):
    """(c): requests through the serve tier, bit-for-bit against the
    forward's own per-subgrid path."""
    from swiftly_tpu.serve import SubgridService

    step = max(1, len(subgrid_configs) // n)
    picks = list(subgrid_configs[::step])[:n]
    svc = SubgridService(fwd)
    reqs = [svc.submit(sg) for sg in picks]
    while svc.pump_once():
        pass
    mismatched = 0
    for sg, req in zip(picks, reqs):
        req.wait()
        if not (req.result is not None and req.result.ok):
            raise SmokeFailure(f"phase c: request not served: {req.result}")
        ref = np.asarray(fwd.get_subgrid_task(sg))
        mismatched += int(not np.array_equal(np.asarray(req.result.data),
                                             ref))
    if mismatched:
        raise SmokeFailure(
            f"phase c: {mismatched} of {len(picks)} served subgrids differ "
            "from get_subgrid_task"
        )
    stats = svc.stats()
    # retries, OOM batch splits and shedding the service recorded
    bad = {k: stats[k] for k in ("retries", "batch_failures",
                                 "batch_splits", "quarantined", "shed",
                                 "expired") if stats.get(k)}
    if bad:
        raise SmokeFailure(f"phase c: degraded: {bad}")
    return {"phase": "c", "n_requests": len(picks),
            "bit_mismatches": mismatched}


def phase_mesh(name=MESH_CONFIG, n_chips=4, n_bwd_facets=MESH_BWD_FACETS):
    """The facet-mesh round trip: the whole facet stack resident and
    sharded, one forward pass over the full cover feeding a backward
    that accumulates the first ``n_bwd_facets`` facets."""
    import jax
    import jax.numpy as jnp

    import bench
    from swiftly_tpu import (
        SwiftlyConfig,
        make_full_facet_cover,
        make_full_subgrid_cover,
        make_sparse_facet,
    )
    from swiftly_tpu.mesh import MeshStreamedBackward, MeshStreamedForward
    from swiftly_tpu.parallel.mesh import make_facet_mesh
    from swiftly_tpu.plan.model import DEFAULT_RESERVE_BYTES

    config = SwiftlyConfig(backend="planar", dtype=jnp.float32,
                           **_params(name))
    N = config.image_size
    core = config.core
    facet_configs = make_full_facet_cover(config)
    subgrid_configs = make_full_subgrid_cover(config)
    sources = bench._bench_sources(N)
    amp = max(s[0] for s in sources)
    sparse = [make_sparse_facet(N, fc, sources, dtype=np.float32)
              for fc in facet_configs]
    mesh = make_facet_mesh(n_devices=n_chips)
    devices = list(mesh.devices.flat)
    sample_map, oracle_dev = bench._oracle_sample_stack(
        config, subgrid_configs, sources
    )
    fwd = MeshStreamedForward(config, list(zip(facet_configs, sparse)),
                              mesh=mesh)
    n_bwd = min(n_bwd_facets, len(facet_configs))
    yB = facet_configs[0].size
    fwd.hbm_headroom = int(
        oracle_dev.nbytes + -(-n_bwd // n_chips) * yB * yB * 8
        + DEFAULT_RESERVE_BYTES
    )
    print(json.dumps({
        "phase": "mesh", "cut": (
            f"the backward accumulates facets 0-{n_bwd - 1} of "
            f"{len(facet_configs)} over the full cover; all 9 need 3 "
            "such passes, each re-running the forward"
        ),
    }), flush=True)
    bwd = MeshStreamedBackward(config, list(facet_configs[:n_bwd]),
                               mesh=mesh, fold_group=2)
    max_rms2 = jnp.zeros((), jnp.float32)
    for per_col, group in fwd.stream_column_groups(subgrid_configs):
        max_rms2 = _sample_rms2(
            core, per_col, group, sample_map, oracle_dev, max_rms2
        )
        bwd.add_subgrid_group([[sg for _, sg in col] for col in per_col],
                              group)
    facets = bwd.finish_device()
    memory = _memory(devices)
    rms_f = 0.0
    for shard in facets.addressable_shards:
        start = shard.index[0].start or 0
        for f in range(shard.data.shape[0]):
            if start + f < n_bwd:
                rms_f = max(rms_f, _facet_rms(shard.data, f,
                                              sparse[start + f]))
    record = {
        "phase": "mesh", "config": name, "n_chips": n_chips,
        "n_subgrids": len(subgrid_configs),
        "n_facets": len(facet_configs),
        "padded_facets": int(fwd.stack.n_total),
        "bwd_facets": n_bwd, "bwd_padded_facets": int(bwd.stack.n_total),
        "n_oracle_samples": len(sample_map),
        "subgrid_rms_rel": float(max_rms2) ** 0.5 * N * N,
        "facet_rms_rel": rms_f / amp,
        "collective": fwd.collective,
        "fwd_plan": {k: (fwd.last_plan or {}).get(k)
                     for k in ("mode", "col_group", "colpass")},
        "bodies": _bodies(core, fwd.stack.n_total // n_chips, meshed=True),
        "memory": memory,
        "platform": jax.devices()[0].platform,
    }
    idle = [m["device"] for m in memory if not m["bytes_in_use"]]
    if idle:
        raise SmokeFailure(f"mesh: devices {idle} hold no bytes")
    return record


def _run_phase(clock, fn, *args):
    import jax

    from swiftly_tpu.obs import metrics

    metrics.reset()
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args)
    record, extra = out if isinstance(out, tuple) else (out, None)
    record["wall_s"] = time.perf_counter() - t0
    record["compile_s"] = clock.seconds - c0
    record["peak_bytes_in_use"] = _memory(jax.devices()[:1])[0][
        "peak_bytes_in_use"]
    _gate(record)
    print(json.dumps(record, default=str), flush=True)
    return extra


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 64k facet-mesh round trip")
    args = ap.parse_args(argv)
    device = device_stamp(args.chips)

    from swiftly_tpu.obs import metrics
    from swiftly_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    metrics.enable()
    clock = _CompileClock()
    try:
        if args.chips == 4:
            _run_phase(clock, phase_mesh, MESH_CONFIG, args.chips)
        else:
            fwd, subgrid_configs = _run_phase(clock, phase_batched)
            _run_phase(clock, phase_streamed)
            _run_phase(clock, phase_serve, fwd, subgrid_configs)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
