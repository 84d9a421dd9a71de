"""The one traffic generator: drives the program under test through its
public entry points, as a traffic mix's data file describes.

A mix (``benchmark/traffic/<name>.json``) names its ``operation``:

* ``roundtrip``: whole passes back to back. Each pass streams the cover
  through ``StreamedForward(residency="device").stream_column_groups``
  into a fresh ``StreamedBackward(residency="sampled")`` and ends with
  ``finish_device``. A pass counts when it has finished.
* ``forward``: the cover streamed through ``stream_column_groups``
  (``MeshStreamedForward`` on a cell of several chips), over and over.
  A column group counts when its device work has been synced.

The traced span of a ``--trace 1`` run (`Tracer`) starts at a group
boundary after ``Tracer.SKIP_GROUPS`` groups and closes at the first
boundary ``Tracer.SECONDS`` later, with the device drained at both
ends, so the span holds whole column groups and their work alone.

A configuration file may state, as data:

* ``"columns": {"first": i, "count": n}``: the run streams only a
  contiguous share of the cover's columns (in the order of ``off0``),
  the part one chip holds of a deployment whose columns are divided
  over more chips. The ``forward`` mix streams the share over and over;
  the ``roundtrip`` mix refuses one, since a share folds part of every
  facet, which neither ``facet_err`` nor the reference computes.
* ``"facet_input"``: ``"dense"`` (the default) or ``"components"``.

The program gets only inputs made here from the seed, each facet
holding the sky's pixels from `reference`: with ``"dense"`` a dense
real float32 plane on the host, as an image is; with ``"components"``
the program's own point-component facet (`SparseRealFacet`), the input
of a predict from a component list, which the program keeps sparse or
densifies by its own rule. From each column one subgrid, drawn from
the seed, is copied out of the timed path for the comparison.
"""

from __future__ import annotations

import time

import numpy as np

from . import reference

STAGE_UNITS = {
    # stage of `counts` -> which column count of the span it scales with
    "fwd_facet_pass": "fwd_columns",
    "fwd_column_pass": "fwd_columns",
    "bwd_column_pass": "bwd_columns",
    "bwd_fold": "bwd_columns",
}


def plan_record(plan):
    """The program's plan of the window, as plain JSON values."""
    return {k: v if isinstance(v, (str, int, float, bool)) else str(v)
            for k, v in (plan or {}).items()}


def annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def program_config(config, mesh=None):
    """The program's `SwiftlyConfig` for a configuration file's dict."""
    import jax.numpy as jnp

    from swiftly_tpu import SwiftlyConfig

    if config["backend"] != "planar" or config["dtype"] != "float32":
        raise ValueError("the harness runs the planar float32 backend")
    return SwiftlyConfig(
        W=config["W"], fov=config["fov"], N=config["N"],
        yB_size=config["yB_size"], yN_size=config["yN_size"],
        xA_size=config["xA_size"], xM_size=config["xM_size"],
        backend="planar", dtype=jnp.float32, mesh=mesh,
    )


class Tracer:
    """Starts and stops the profiler at group boundaries of the window
    and counts the columns of work dispatched inside the traced span."""

    SPAN = "bench/traced_span"
    SKIP_GROUPS = 1  # the first group of the window is not traced
    SECONDS = 4.0  # the span closes at the first boundary this late

    def __init__(self, directory, devices):
        self.directory = directory
        self.devices = devices
        self.groups = 0
        self.state = "waiting" if directory else "off"
        self.units = {"fwd_columns": 0, "bwd_columns": 0}
        self._t0 = None
        self._span = None

    def barrier(self):
        """Wait until every operation enqueued on each device has run
        (each device runs its queue in order)."""
        import jax

        for d in self.devices:
            jax.device_put(np.float32(0), d).__add__(1).block_until_ready()

    def boundary(self, fwd_columns=0, bwd_columns=0):
        """Called after each column group's work has been dispatched."""
        if self.state == "tracing":
            self.units["fwd_columns"] += fwd_columns
            self.units["bwd_columns"] += bwd_columns
        self.groups += 1
        if self.state == "waiting" and self.groups > self.SKIP_GROUPS:
            import jax

            self.barrier()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self._span = annotate(self.SPAN)
            self._span.__enter__()
            self._t0 = time.perf_counter()
            self.state = "tracing"
        elif (self.state == "tracing"
              and time.perf_counter() - self._t0 >= self.SECONDS):
            self.stop()

    def stop(self):
        if self.state != "tracing":
            return
        import jax

        self.barrier()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


FACET_INPUTS = ("dense", "components")


def column_share(config, n_columns):
    """``(first, count)`` of the configuration's ``columns`` share of a
    cover of ``n_columns`` columns; the whole cover where it states
    none."""
    share = config.get("columns")
    if share is None:
        return 0, n_columns
    first, count = int(share["first"]), int(share["count"])
    if first < 0 or count < 1 or first + count > n_columns:
        raise ValueError(
            f"columns {share} is not a share of the cover's {n_columns} "
            "columns")
    return first, count


class Operation:
    """Set-up, warm-up, window and answers of one traffic mix on one
    configuration. Subclasses give the operation."""

    def __init__(self, config, n_chips, traffic=None):
        import jax

        from swiftly_tpu import make_full_facet_cover, make_full_subgrid_cover

        self.config = config
        self.traffic = dict(traffic or {})
        self.n_chips = int(n_chips)
        self.devices = jax.devices()[: self.n_chips]
        self.mesh = None
        if self.n_chips > 1:
            from swiftly_tpu.parallel.mesh import make_facet_mesh

            self.mesh = make_facet_mesh(n_devices=self.n_chips)
        self.pconfig = program_config(config)
        self.N = int(config["N"])
        self.yB = int(config["yB_size"])
        self.xA = int(config["xA_size"])
        self.facet_configs = make_full_facet_cover(self.pconfig)
        self.facet_input = config.get("facet_input", "dense")
        if self.facet_input not in FACET_INPUTS:
            raise ValueError(f"facet_input {self.facet_input!r} is not one "
                             f"of {FACET_INPUTS}")
        cover = make_full_subgrid_cover(self.pconfig)
        offs = sorted({sg.off0 for sg in cover})
        first, count = column_share(config, len(offs))
        self.columns = {"first": first, "count": count, "of": len(offs)}
        self.col_offs = offs[first:first + count]
        share = set(self.col_offs)
        self.cover = [sg for sg in cover if sg.off0 in share]
        self.per_column = len(self.cover) // len(self.col_offs)
        self._take = jax.jit(lambda g, ci, ri: g[ci, ri])
        self.samples = {}
        self._copies = []

    # -- inputs ------------------------------------------------------------

    def load(self, seed):
        """Draw the sky from ``seed`` and build the program's inputs."""
        self.seed = int(seed)
        self.sources = reference.draw_sky(
            self.N, self.yB, self.config["assumed"]["sky"], seed)
        self.pixels = reference.facet_pixels(self.N, self.yB, self.sources)
        if max(len(v[0]) for v in self.pixels.values()) > 1:
            raise ValueError("the sky put two pixels into one facet")
        from swiftly_tpu.ops.oracle import SparseRealFacet

        self.facet_tasks = []
        for fc in self.facet_configs:
            rows, cols, vals = self.pixels[(fc.off0, fc.off1)]
            if self.facet_input == "components":
                facet = SparseRealFacet(self.yB, rows, cols,
                                        vals.astype(np.float32))
            else:
                facet = np.zeros((self.yB, self.yB), np.float32)
                facet[rows, cols] = vals
            self.facet_tasks.append((fc, facet))
        # one subgrid of each column, drawn from the seed
        rng = np.random.default_rng([self.seed, 1])
        self.pick = {off0: int(rng.integers(self.per_column))
                     for off0 in self.col_offs}
        self.samples = {}
        self._copies = []

    # -- sampling of the timed path's answers -------------------------------

    def _sample(self, per_col, group):
        """Copy each column's drawn subgrid out of the group, on device,
        and start its transfer to the host."""
        ci, ri, keys = [], [], []
        for c, col in enumerate(per_col):
            k = self.pick[col[0][1].off0]
            ci.append(c)
            ri.append(k)
            keys.append((col[k][1].off0, col[k][1].off1))
        part = self._take(group, np.asarray(ci, np.int32),
                          np.asarray(ri, np.int32))
        part.copy_to_host_async()
        self._copies.append((keys, part))
        if len(self._copies) > 2:
            self._collect(len(self._copies) - 2)

    def _collect(self, n=None):
        n = len(self._copies) if n is None else n
        for keys, part in self._copies[:n]:
            host = np.asarray(part)
            for key, sg in zip(keys, host):
                self.samples[key] = sg[..., 0] + 1j * sg[..., 1]
        del self._copies[:n]

    def warm_take(self, group, sizes):
        """Compile the sample copy for each group size the window
        yields."""
        for g in sizes:
            part = self._take(group[:g], np.zeros(g, np.int32),
                              np.zeros(g, np.int32))
            part.block_until_ready()

    def group_sizes(self, G):
        """Sizes of the column groups of one pass, groups of ``G``."""
        n = len(self.col_offs)
        sizes = {min(G, n)}
        if n % G:
            sizes.add(n % G)
        return sorted(sizes)


class RoundTrip(Operation):
    """Facets -> subgrids -> facets, whole passes: at least the mix's
    ``min_passes`` (1 where it states none) a window."""

    def __init__(self, config, n_chips, traffic=None):
        super().__init__(config, n_chips, traffic)
        if self.columns["count"] != self.columns["of"]:
            raise ValueError(
                "the roundtrip mix runs the whole cover: a column share "
                "folds part of every facet, which neither facet_err nor "
                "the reference computes")
        self.min_passes = int(self.traffic.get("min_passes", 1))

    def build(self):
        from swiftly_tpu.parallel import StreamedForward
        from swiftly_tpu.plan import PlanInputs, compile_plan
        from swiftly_tpu.plan.model import DEFAULT_RESERVE_BYTES

        if self.mesh is not None:
            raise ValueError("the roundtrip mix runs on one chip")
        self.fwd = StreamedForward(self.pconfig, self.facet_tasks,
                                   residency="device")
        plan = compile_plan(PlanInputs.from_cover(
            self.pconfig, self.facet_configs, self.cover,
            real_facets=True, fold_group=2,
        ))
        if len(plan.backward.parts) != 1:
            raise ValueError(
                f"the plan splits the backward into "
                f"{len(plan.backward.parts)} passes; the roundtrip mix "
                "feeds one")
        self.fold_group = plan.backward.fold_group
        # the backward's accumulator shares the chip with the forward
        self.fwd.hbm_headroom = int(
            plan.backward.resident_bytes + DEFAULT_RESERVE_BYTES)

    def _backward(self):
        from swiftly_tpu.parallel import StreamedBackward

        return StreamedBackward(self.pconfig, self.facet_configs,
                                residency="sampled",
                                fold_group=self.fold_group)

    def warm(self):
        """Run the first column group through both directions, the
        pass's last, shorter group shape through the backward too, and
        a finish: every program the window runs, compiled."""
        bwd = self._backward()
        gen = self.fwd.stream_column_groups(self.cover)
        per_col, group = next(gen)
        gen.close()
        self.plan = plan_record(self.fwd.last_plan)
        G = int(self.fwd.last_plan["col_group"])
        cols = [[sg for _, sg in col] for col in per_col]
        sizes = self.group_sizes(G)
        self.warm_take(group, sizes)
        for g in sizes:
            bwd.add_subgrid_group(cols[:g], group[:g])
        bwd.finish_device().block_until_ready()

    def one_pass(self, tracer):
        bwd = self._backward()
        gen = self.fwd.stream_column_groups(self.cover)
        while True:
            with annotate("bench/fwd_group"):
                item = next(gen, None)
            if item is None:
                break
            per_col, group = item
            self._sample(per_col, group)
            with annotate("bench/bwd_add"):
                bwd.add_subgrid_group(
                    [[sg for _, sg in col] for col in per_col], group)
            tracer.boundary(fwd_columns=len(per_col),
                            bwd_columns=len(per_col))
        with annotate("bench/bwd_finish"):
            facets = bwd.finish_device()
        with annotate("bench/sync"):
            facets.block_until_ready()
        return facets

    def window(self, seconds, tracer):
        """Passes back to back; a pass starts where the one before says
        it can finish inside ``seconds``, or where fewer than
        ``min_passes`` have run. Returns (subgrids, seconds) of the
        passes counted."""
        t0 = time.perf_counter()
        done, t_last, n = 0, t0, 0
        while True:
            self.facets = None  # frees the last pass's facets
            self.facets = self.one_pass(tracer)
            t = time.perf_counter()
            done += len(self.cover)
            n += 1
            last, t_last = t - t_last, t
            if n >= self.min_passes and t - t0 + last > seconds:
                break
        tracer.stop()
        self._collect()
        return done, t_last - t0

    def free(self):
        self.fwd = None


class Forward(Operation):
    """Facets -> subgrids, the cover over and over."""

    def build(self):
        from swiftly_tpu.plan.model import DEFAULT_RESERVE_BYTES

        if self.mesh is not None:
            from swiftly_tpu.mesh import MeshStreamedForward

            self.fwd = MeshStreamedForward(self.pconfig, self.facet_tasks,
                                           mesh=self.mesh)
        else:
            from swiftly_tpu.parallel import StreamedForward

            self.fwd = StreamedForward(self.pconfig, self.facet_tasks,
                                       residency="device")
        self.fwd.hbm_headroom = int(DEFAULT_RESERVE_BYTES)

    def warm(self):
        gen = self.fwd.stream_column_groups(self.cover)
        _, group = next(gen)
        gen.close()
        self.plan = plan_record(self.fwd.last_plan)
        self.G = G = int(self.fwd.last_plan["col_group"])
        sizes = self.group_sizes(G)
        self.warm_take(group, sizes)
        for g in sizes:  # the stream's slice of a short last group
            group[:g].block_until_ready()

    def window(self, seconds, tracer, min_groups=1):
        """Column groups until ``seconds`` have passed; the group synced
        after that is not counted, unless fewer than ``min_groups`` were.
        Returns (subgrids, seconds) of the groups counted."""
        t0 = time.perf_counter()
        done, t_last, n = 0, t0, 0
        while True:
            gen = self.fwd.stream_column_groups(self.cover)
            stop = False
            while True:
                with annotate("bench/fwd_group"):
                    item = next(gen, None)
                if item is None:
                    break
                per_col, group = item
                self._sample(per_col, group)
                with annotate("bench/sync"):
                    group.block_until_ready()
                t = time.perf_counter()
                if t - t0 > seconds and n >= min_groups:
                    stop = True
                    break
                n += 1
                done += sum(len(col) for col in per_col)
                t_last = t
                tracer.boundary(fwd_columns=len(per_col))
            gen.close()
            if stop:
                break
        tracer.stop()
        self._collect()
        return done, t_last - t0

    def free(self):
        self.fwd = None


OPERATIONS = {"roundtrip": RoundTrip, "forward": Forward}
