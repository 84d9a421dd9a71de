"""What a configuration file may state as data alone: a share of the
cover's columns (``columns``) and the facet input (``facet_input``).
Runs at the tiny size on the CPU through `harness.run`, the device
stamp left out; a share stated at the published size is mapped onto
the tiny cover's 3 columns by `bm_helpers.tiny`."""

import json
import shutil

import bm_helpers
import pytest

from benchmark import drive, harness

# columns 37..73 of the 32k cover's 74: the tiny cover's columns 1 and 2
SHARE = {"first": 37, "count": 37}


@pytest.fixture(autouse=True)
def _precision_restored(monkeypatch):
    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")


def _slab_plan(monkeypatch):
    """The facet-slab plan, which the program takes where the facet
    stack does not fit the chip (as at 128k)."""
    from swiftly_tpu.parallel import StreamedForward

    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)


def test_tiny_share_keeps_the_part_of_the_cover():
    assert bm_helpers.tiny_share(SHARE, 74) == {"first": 1, "count": 2}
    assert bm_helpers.tiny_share({"first": 0, "count": 74}, 74) == {
        "first": 0, "count": 3}
    # 18 centred columns of 128k's 293 lie inside the tiny middle column
    assert bm_helpers.tiny_share({"first": 137, "count": 18}, 293) == {
        "first": 1, "count": 1}


def test_forward_share_streams_and_counts_only_its_columns():
    r = bm_helpers.run_tiny("forward-32k", columns=SHARE)
    assert r["correct"], r["checks"]
    assert r["run"]["columns"] == {"first": 1, "count": 2, "of": 3}
    assert r["checks"]["missing"]["value"] == 0
    assert r["attempted"] == 2 and r["failed"] == 0
    assert r["run"]["subgrids"] % (2 * 3) == 0  # whole share columns of 3


def test_a_window_that_skips_a_share_column_fails_missing(monkeypatch):
    from swiftly_tpu.parallel import StreamedForward

    orig = StreamedForward.stream_column_groups

    def stream(self, cover, spill=None):
        for per_col, group in orig(self, cover, spill=spill):
            yield per_col[1:], group[1:]

    monkeypatch.setattr(StreamedForward, "stream_column_groups", stream)
    r = bm_helpers.run_tiny("forward-32k", columns=SHARE)
    assert not r["correct"]
    assert r["checks"]["missing"]["value"] == 1


def test_the_whole_cover_is_the_share_where_none_is_stated():
    r = bm_helpers.run_tiny("forward-32k", seed=2**32 + 9)
    assert r["correct"], r["checks"]
    assert r["run"]["columns"] == {"first": 0, "count": 3, "of": 3}
    assert r["run"]["facet_input"] == "dense"
    assert r["attempted"] == 3


def test_roundtrip_refuses_a_share():
    with pytest.raises(ValueError, match="folds part of every facet"):
        bm_helpers.run_tiny("roundtrip-32k", columns=SHARE)


@pytest.mark.parametrize("share", [
    {"first": -1, "count": 2}, {"first": 0, "count": 0},
    {"first": 2, "count": 2}])
def test_a_share_outside_the_cover_is_refused(share):
    with pytest.raises(ValueError, match="not a share"):
        drive.column_share({"columns": share}, 3)


def test_an_unknown_facet_input_is_refused():
    with pytest.raises(ValueError, match="facet_input"):
        bm_helpers.run_tiny("forward-32k", facet_input="image")


def test_roundtrip_runs_at_least_min_passes(monkeypatch):
    """A window shorter than one pass still counts the mix's
    ``min_passes`` whole passes."""
    res = bm_helpers.tiny_cell("roundtrip-32k")
    assert res["traffic"]["min_passes"] == 2
    r = bm_helpers.run_res(res, seconds=0.0)
    assert r["correct"], r["checks"]
    assert r["run"]["subgrids"] == 2 * 9


@pytest.mark.parametrize("workload,slab", [
    ("forward-32k", False), ("forward-32k", True), ("roundtrip-32k", False)],
    ids=["forward-resident", "forward-slab", "roundtrip"])
def test_components_read_as_dense(workload, slab, monkeypatch):
    """The program's point-component facets give the same answers as
    dense planes of the same pixels, to float32 rounding."""
    if slab:
        _slab_plan(monkeypatch)
    seed = 2**31 + 303
    dense = bm_helpers.run_tiny(workload, seed=seed)
    comp = bm_helpers.run_tiny(workload, seed=seed,
                               facet_input="components")
    assert dense["correct"] and comp["correct"], comp["checks"]
    assert comp["run"]["facet_input"] == "components"
    if slab:
        assert comp["run"]["plan"]["facet_source"] == "device-synth-sparse"
        assert dense["run"]["plan"]["facet_source"] == "host"
    for name, c in dense["checks"].items():
        assert comp["checks"][name]["value"] == pytest.approx(
            c["value"], rel=2**-20), name


def test_a_cell_stated_as_data_alone_runs(tmp_path):
    """A new configuration file with both keys, a cell and a limits
    file, added to a copy of the benchmark: it resolves and runs with
    no file of the benchmark edited but BENCHMARK.json's lists."""
    root = bm_helpers.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = json.loads(
        (root / "benchmark/configs/swiftly-32k-n16k-512.json").read_text())
    config.update(name="swiftly-32k-share", columns=SHARE,
                  facet_input="components")
    (tmp_path / "benchmark/configs/swiftly-32k-share.json").write_text(
        json.dumps(config))
    (tmp_path / "benchmark/limits/forward-32k-share.json").write_text(
        json.dumps({"subgrid_err": 1e-5}))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "swiftly-32k-share", "source": spec["configs"][0]["source"],
        "file": "benchmark/configs/swiftly-32k-share.json",
        "reduced": ["columns"], "why": "half the 32k cover's columns"})
    spec["workloads"].append({
        "name": "forward-32k-share", "config": "swiftly-32k-share",
        "traffic": "forward", "chips": 1, "why": "a column share"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    res = harness.resolve(harness.load_spec(tmp_path), "forward-32k-share",
                          tmp_path)
    r = bm_helpers.run_res(bm_helpers.tiny(res))
    assert r["correct"], r["checks"]
    assert r["run"]["columns"] == {"first": 1, "count": 2, "of": 3}
    assert r["run"]["facet_input"] == "components"
