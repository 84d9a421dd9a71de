"""Device-mesh construction and multi-host initialisation.

The execution fabric of the framework: where the reference distributes
tasks over a Dask scheduler/worker cluster (api.py:133-147), the TPU build
lays facets out over a `jax.sharding.Mesh` axis and lets XLA insert the
collectives (psum over ICI within a slice, DCN across slices).
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

FACET_AXIS = "facet"

COLLECTIVES = ("psum", "ring")

__all__ = [
    "COLLECTIVES",
    "FACET_AXIS",
    "bootstrap_from_env",
    "facet_sharding",
    "mesh_size",
    "initialize_multihost",
    "place_facet_sharded",
    "make_facet_mesh",
    "pad_to_shards",
    "replicated_sharding",
    "resolve_collective",
]


def resolve_collective(n_shards: int | None = None) -> str:
    """The facet-axis reduction schedule a sharded column pass runs.

    ``SWIFTLY_MESH_COLLECTIVE`` ∈ {psum, ring, auto} (default auto):

    - ``psum`` — one blocking ``lax.psum`` per column group; XLA lowers
      it to its own all-reduce.  Deterministic tree order, the exactness
      reference.
    - ``ring`` — reduce-scatter + all-gather built from 2(n−1)
      ``lax.ppermute`` chunk rotations, so each step moves 1/n of the
      buffer and the schedule interleaves with neighbouring compute
      instead of serializing after it.  Same result up to reduction
      order (documented tolerance in docs/multichip.md).
    - ``auto`` — psum.  The conservative default: the ring only wins
      when its measured ``mesh.ring_step`` rate says so, and that
      decision lives in the plan compiler (calibrated-coefficient gated,
      like the colpass candidates); the engine follows the plan by
      exporting the choice through this env knob, not by guessing here.

    Read at CALL time (not trace time) so one process can bench psum and
    ring back to back; the sharded kernel caches key on the resolved
    value.  A one-shard "mesh" always degrades to psum — there is no
    ring of one.
    """
    mode = os.environ.get("SWIFTLY_MESH_COLLECTIVE", "auto")
    if mode not in ("psum", "ring", "auto"):
        raise ValueError(
            f"SWIFTLY_MESH_COLLECTIVE must be psum|ring|auto, got {mode!r}"
        )
    if n_shards is not None and n_shards <= 1:
        return "psum"
    if mode == "auto":
        return "psum"
    return mode


def make_facet_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1D mesh over the facet stack axis.

    :param n_devices: number of devices to use (default: all available)
    :param devices: explicit device list (overrides n_devices)
    """
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            if len(devices) < n_devices:
                raise ValueError(
                    f"Requested a {n_devices}-device mesh but only "
                    f"{len(devices)} devices are available"
                )
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (FACET_AXIS,))


def facet_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding that splits the leading (facet-stack) axis over the mesh."""
    return NamedSharding(mesh, PartitionSpec(FACET_AXIS))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully replicated sharding on the mesh."""
    return NamedSharding(mesh, PartitionSpec())


def mesh_size(mesh) -> int:
    """Device count of a (possibly absent) mesh."""
    return 1 if mesh is None else mesh.devices.size


def varying(x, axis_name: str):
    """Tag `x` as varying over a shard_map axis.

    shard_map tracks which values vary per shard; a `jnp.zeros` scan
    carry created inside the mapped body starts out unvarying and fails
    the carry-type check once the scan body mixes in shard-varying data.
    """
    return jax.lax.pcast(x, (axis_name,), to="varying")


def pad_to_shards(n: int, n_shards: int) -> int:
    """Facet count padded up to a multiple of the mesh size.

    Zero-padded facets contribute zeros to every linear accumulation, so
    padding is exact (not approximate)."""
    return ((n + n_shards - 1) // n_shards) * n_shards


def place_facet_sharded(arr, mesh: Mesh, facet_axis: int = 0):
    """Place the GLOBAL array `arr` facet-sharded over the mesh,
    multihost-safely.

    Single-process: a plain `device_put` with the facet sharding. On a
    multi-host pod slice (jax.process_count() > 1) a global device_put
    would address devices this process cannot reach; instead each
    process materialises only its addressable shards of the global host
    array (`jax.make_array_from_callback` slices them out), so no
    cross-host transfer of the stack ever happens.
    """
    arr = np.asarray(arr)
    spec = [None] * arr.ndim
    spec[facet_axis] = FACET_AXIS
    sharding = NamedSharding(mesh, PartitionSpec(*spec))
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def initialize_multihost(coordinator=None, num_processes=None, process_id=None):
    """Initialise JAX distributed runtime for multi-host (pod-slice) runs.

    On TPU pods with standard orchestration all arguments are discovered
    automatically; arguments are for manual (e.g. GPU/CPU cluster) setups.
    Safe to call once per process before any device use.
    """
    kwargs = {}
    if coordinator is not None:
        kwargs["coordinator_address"] = coordinator
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def bootstrap_from_env():
    """Env-driven `jax.distributed` bootstrap — the process-spanning
    mesh's entry point (docs/multichip.md "Multi-process bootstrap").

    Reads ``SWIFTLY_COORDINATOR`` (host:port of process 0's
    coordinator), ``SWIFTLY_NUM_PROCESSES`` and ``SWIFTLY_PROCESS_ID``
    and calls `initialize_multihost` with whatever is set. With NONE of
    them set this is a no-op returning ``None`` — single-process runs
    (and TPU pods whose orchestrator auto-discovers all three) need no
    environment at all. Returns the resolved
    ``{coordinator, num_processes, process_id}`` dict when a bootstrap
    happened, so callers can log what they joined.

    Must run before any device use in the process;
    ``__graft_entry__.dryrun_distributed`` drives a real 2-process
    CPU bootstrap through exactly this path.
    """
    coordinator = os.environ.get("SWIFTLY_COORDINATOR") or None
    num_processes = os.environ.get("SWIFTLY_NUM_PROCESSES") or None
    process_id = os.environ.get("SWIFTLY_PROCESS_ID") or None
    if coordinator is None and num_processes is None and process_id is None:
        return None
    if num_processes is not None:
        num_processes = int(num_processes)
    if process_id is not None:
        process_id = int(process_id)
    initialize_multihost(
        coordinator=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return {
        "coordinator": coordinator,
        "num_processes": num_processes,
        "process_id": process_id,
    }
