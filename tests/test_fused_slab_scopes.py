"""The facet-slab plan's fused step for point-component facets
(`_fused_sparse_slab_step_j`): synthesis, sampled facet pass and column
step in one program, each credited to its own device scope, with the
same answer as the unfused dense slab path and counters of what it
synthesises."""

import numpy as np
import pytest
from conftest import compiled_stages

from swiftly_tpu import (
    SwiftlyConfig,
    make_full_facet_cover,
    make_full_subgrid_cover,
    make_sparse_facet,
)
from swiftly_tpu.parallel import StreamedForward
from swiftly_tpu.parallel.streamed import (
    _column_group_step_j,
    _facet_pass_sampled_j,
    _fused_sparse_slab_step_j,
    sampled_row_indices,
)

# the catalogue's 1k[1]-n512-512: 9 facets of 352, 3 columns of 448
PARAMS = {"W": 11.0, "fov": 1, "N": 1024, "yB_size": 352, "yN_size": 512,
          "xA_size": 448, "xM_size": 512}
SOURCES = [(1.0, 40, -30), (0.5, -300, 200), (0.75, 350, 380)]
FG, CHUNK = 2, 1  # the 128k plan runs slabs of one facet: see FG_SCOPED
FG_SCOPED = [1, FG]


@pytest.fixture(scope="module")
def setup():
    config = SwiftlyConfig(backend="planar", **PARAMS)
    facet_configs = make_full_facet_cover(config)
    tasks = [(fc, make_sparse_facet(config.image_size, fc, SOURCES))
             for fc in facet_configs]
    return config, tasks, make_full_subgrid_cover(config)


def _inputs(config, tasks, cover, Fg=FG):
    """The fused step's arguments for the first slab of ``Fg`` facets
    and the first column of the cover, as the slab executor builds
    them."""
    import jax.numpy as jnp

    core = config.core
    yB, xA, xM = PARAMS["yB_size"], PARAMS["xA_size"], core.xM_size
    fwd = StreamedForward(config, tasks, residency="device")
    assert fwd._facets_sparse
    off0 = min(sg.off0 for sg in cover)
    column = [sg for sg in cover if sg.off0 == off0]
    S = len(column)
    offs0 = np.asarray([fc.off0 for fc, _ in tasks[:Fg]], np.int32)
    offs1 = np.asarray([fc.off1 for fc, _ in tasks[:Fg]], np.int32)
    so_c = jnp.asarray(np.asarray(
        [(sg.off0, sg.off1) for sg in column]).reshape(1, 1, S, 2))
    return {
        "acc": lambda: jnp.zeros((1, CHUNK, S, xM, xM, 2), core.dtype),
        "pixels": fwd._sparse_pixels(0, Fg),
        "e0": jnp.asarray(offs0 - yB // 2),
        "krows": jnp.asarray(sampled_row_indices(core, [off0])),
        "offs0": jnp.asarray(offs0),
        "offs1": jnp.asarray(offs1),
        "so_c": so_c,
    }


@pytest.mark.parametrize("Fg", FG_SCOPED)
@pytest.mark.parametrize("colpass", ["einsum", "pallas"])
def test_each_stage_of_the_fused_step_carries_its_own_scope(
        setup, colpass, Fg, monkeypatch):
    monkeypatch.setenv("SWIFTLY_PALLAS_INTERPRET", "1")
    config, tasks, cover = setup
    a = _inputs(config, tasks, cover, Fg)
    yB = PARAMS["yB_size"]
    fused = _fused_sparse_slab_step_j(config.core, PARAMS["xA_size"], CHUNK,
                                      Fg, yB, colpass)
    compiled = fused.lower(a["acc"](), *a["pixels"], a["e0"], a["krows"],
                           a["offs0"], a["offs1"], a["so_c"]).compile()
    stages, made = compiled_stages(compiled)
    assert set(stages.values()) - {""} == {
        "swiftly/fwd.facet_synth", "swiftly/fwd.sampled_facet_pass",
        "swiftly/fwd.slab_step"}
    # every instruction that makes the synthesised plane (here the zero
    # fill is fused into it; `test_tpu_compile` checks the chip's fill)
    plane = {f"{Fg},{yB},{yB}", f"{Fg * yB},{yB}", f"{yB},{yB}"}
    synth = [n for n, (shape, op, _) in made.items()
             if shape in plane and op not in ("bitcast", "dot")]
    assert synth and all(stages[n] == "swiftly/fwd.facet_synth"
                         for n in synth), {n: stages[n] for n in synth}
    # the sampled DFT's products hold every facet column of the slab;
    # every other product is the column step's (or, where the compiler
    # made it anew without a name, no stage's)
    dots = {n: shape for n, (shape, op, _) in made.items() if op == "dot"}
    dft = [n for n, shape in dots.items()
           if str(Fg * yB) in shape.split(",")]
    assert dft and all(stages[n] == "swiftly/fwd.sampled_facet_pass"
                       for n in dft)
    rest = [n for n in dots if n not in dft]
    # (the Pallas body runs interpreted here: its products are dots)
    assert {stages[n] for n in rest} - {""} == {"swiftly/fwd.slab_step"}


@pytest.mark.parametrize("Fg", FG_SCOPED)
def test_fused_step_matches_the_unfused_dense_slab_path(setup, Fg):
    """The scopes, and the barrier that keeps the zero fill under its
    own, change no number: the fused program gives what the dense
    slab's sampled pass and column step give apart."""
    import jax.numpy as jnp

    config, tasks, cover = setup
    core = config.core
    a = _inputs(config, tasks, cover, Fg)
    yB, xA = PARAMS["yB_size"], PARAMS["xA_size"]
    fused = _fused_sparse_slab_step_j(core, xA, CHUNK, Fg, yB, "einsum")
    got = fused(a["acc"](), *a["pixels"], a["e0"], a["krows"], a["offs0"],
                a["offs1"], a["so_c"])
    slab = jnp.asarray(np.stack([sp.densify(np.dtype(core.dtype))
                                 for _, sp in tasks[:Fg]]))
    buf = _facet_pass_sampled_j(core, True)(slab, a["e0"], a["krows"])
    want = _column_group_step_j(core, xA, CHUNK, "einsum")(
        a["acc"](), buf, a["offs0"], a["offs1"], a["so_c"])
    assert float(jnp.max(jnp.abs(want))) > 0
    # float64 on the CPU: the same products in another program agree to
    # rounding, the bound the sparse-facet tests of the streamed forward
    # hold synthesised facets to against dense ones
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-10)


def test_counters_count_once_per_fused_dispatch(setup, monkeypatch):
    from swiftly_tpu.obs import metrics

    config, tasks, cover = setup
    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)
    fwd = StreamedForward(config, tasks, residency="device",
                          facet_group=FG)
    metrics.reset()
    metrics.enable()
    try:
        fwd.all_subgrids(cover)
        exported = metrics.export()
    finally:
        metrics.disable()
        metrics.reset()
    plan = fwd.last_plan
    assert plan["facet_source"] == "device-synth-sparse"
    n_cols = len({sg.off0 for sg in cover})
    dispatches = plan["n_slabs"] * -(-n_cols // plan["col_group"])
    counters = exported["counters"]
    assert counters["fwd.synth_slabs"] == dispatches
    assert exported["stages"]["fwd.slab_step"]["count"] == dispatches
    # every group synthesises every facet's components once, none of
    # the zero padding that evens the slabs out
    per_pass = sum(sp.n_pixels for _, sp in tasks)
    assert per_pass > 0
    assert counters["fwd.synth_pixels"] == per_pass * (
        dispatches // plan["n_slabs"])


def test_fused_step_keeps_one_slab_queued_where_slabs_are_large(
        setup, monkeypatch):
    """Where two slabs would take half the chip (128k), host-staged
    slabs go one at a time; a synthesised slab is the fused program's
    own scratch, so its dispatches keep one slab queued ahead of the
    device: the checksum drain waits on slab d-2, not d-1."""
    from swiftly_tpu.obs import metrics

    config, tasks, cover = setup
    slab = FG * PARAMS["yB_size"] ** 2 * np.dtype(config.core.dtype).itemsize
    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)
    monkeypatch.setattr(StreamedForward, "_hbm_budget",
                        lambda self: 3.9 * slab)
    drains = {}
    for name, facets in (("synthesised", tasks),
                         ("staged", [(fc, sp.densify(np.float64))
                                     for fc, sp in tasks])):
        fwd = StreamedForward(config, facets, residency="device",
                              facet_group=FG, col_group=1)
        metrics.reset()
        metrics.enable()
        try:
            fwd.all_subgrids(cover)
            exported = metrics.export()
        finally:
            metrics.disable()
            metrics.reset()
        assert fwd.last_plan["slab_depth"] == 1
        n_cols = len({sg.off0 for sg in cover})
        dispatches = fwd.last_plan["n_slabs"] * n_cols
        drains[name] = dispatches - exported["stages"]["fwd.drain"]["count"]
    assert drains == {"synthesised": 2, "staged": 1}
