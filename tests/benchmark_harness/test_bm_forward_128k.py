"""The `forward-128k` cell: one chip's column share of the 128k cover,
its facets handed to the program as point components and synthesised
on the device slab by slab. Runs at the tiny size on the CPU through
`harness.run`, with the facet-slab plan the program takes at 128k
forced, as `test_bm_share` forces it; and its one new reader,
`synth_frac`, on a synthetic reading."""

from types import SimpleNamespace

import bm_helpers
import numpy as np
import pytest

from benchmark import harness, reference
from benchmark.trace import Op, Span

SEED = 2**31 + 128


@pytest.fixture(autouse=True)
def _slab_plan(monkeypatch):
    """The facet-slab plan, which the program takes where the facet
    stack does not fit the chip (as at 128k); the precision given back
    after `harness.configure` sets it."""
    from swiftly_tpu.parallel import StreamedForward

    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")
    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)


def test_cell_runs_its_share_from_components_synthesised_on_the_device():
    r = bm_helpers.run_tiny("forward-128k", seed=SEED)
    assert r["correct"], r["checks"]
    assert r["run"]["facet_input"] == "components"
    # 18 centred columns of 293 lie inside the tiny middle column
    assert r["run"]["columns"] == {"first": 1, "count": 1, "of": 3}
    assert r["run"]["plan"]["facet_source"] == "device-synth-sparse"
    assert r["checks"]["missing"]["value"] == 0
    assert r["run"]["window_compiles"] == 0


def test_a_component_left_out_of_the_synthesis_fails_subgrid_err(
        monkeypatch):
    from swiftly_tpu.parallel import StreamedForward

    orig = StreamedForward._sparse_pixels

    def dropped(self, i0, i1):
        f, r, c, v = orig(self, i0, i1)
        v = v.copy()
        v[np.flatnonzero(v)[:1]] = 0  # the slab's first component
        return f, r, c, v

    monkeypatch.setattr(StreamedForward, "_sparse_pixels", dropped)
    r = bm_helpers.run_tiny("forward-128k", seed=SEED)
    assert not r["correct"]
    c = r["checks"]["subgrid_err"]
    assert c["value"] > c["limit"]
    assert r["failed"] > 0


def _reading(ops):
    from benchmark.reading import Reading

    config = dict(N=1024, yB_size=352, yN_size=512, xA_size=448,
                  xM_size=512, W=11.0)
    op = SimpleNamespace(devices=[SimpleNamespace(id=0)],
                         facet_configs=[None] * 9, col_offs=[0],
                         per_column=3)
    return Reading({0: ops}, [Span(0, 10, "bench/traced_span")], config,
                   harness.peak_of("TPU v5 lite"), op,
                   {"fwd_columns": 1, "bwd_columns": 0}, 1, 2)


def test_synth_frac_reads_the_synthesis_share_of_device_time():
    """Three stages of one fused program read apart: the synthesis as a
    share of the busy time, the sampled pass and the column step each
    under its own roofline."""
    readers = harness.resolve(harness.load_spec(), "forward-128k")["readers"]
    assert {"synth_frac", "fwd_facet_pass_roofline",
            "fwd_colpass_roofline"} <= set(readers)
    ops = [Op(0, 1, "scatter.1", "swiftly/fwd.facet_synth"),
           Op(1, 4, "fusion.2", "swiftly/fwd.sampled_facet_pass"),
           Op(4, 8, "colpass_pallas.3", "swiftly/fwd.slab_step")]
    assert readers["synth_frac"](_reading(ops)) == pytest.approx(
        100 * 1 / 8)
    # the fused step with one scope over all of it (the program before
    # the stages were scoped): no synthesis to read
    one = [o._replace(scope="swiftly/fwd.slab_step") for o in ops]
    assert readers["synth_frac"](_reading(one)) is None
    assert readers["fwd_facet_pass_roofline"](_reading(one)) is None
    assert readers["synth_frac"](_reading([])) is None


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 3 * 2**40 + 1])
def test_sky_at_128k_puts_one_source_in_each_chosen_facet(seed):
    N, yB = 131072, 45056
    sky = harness.resolve(harness.load_spec(), "forward-128k")[
        "config"]["assumed"]["sky"]
    sources = reference.draw_sky(N, yB, sky, seed)
    assert len(sources) == sky["n_sources"]
    pixels = reference.facet_pixels(N, yB, sources)
    assert len(pixels) == 9
    assert max(len(r) for r, _, _ in pixels.values()) == 1
    assert sum(len(r) for r, _, _ in pixels.values()) == len(sources)
    margin = int(sky["margin_frac"] * yB)
    offs = list(reference.cover_offsets(N, yB))
    for _, x0, x1 in sources:
        for x in (x0, x1):
            # inside the part of its facet that facet owns, the margin
            # clear of either border
            inside = []
            for i in range(len(offs)):
                lo, hi = reference.owned_interval(N, yB, i)
                for y in (x, x - N, x + N):
                    if lo + margin <= y < hi - margin:
                        inside.append(i)
            assert len(inside) == 1, (x, inside)
