"""``BENCHMARK.json`` and the files the harness finds by name: every
cell, configuration, traffic mix, metric reader and limits file is
there; the file keeps to the benchmark's contract; a bad name or unit
is refused."""

import json
import re
import shutil

import bm_helpers
import pytest

from benchmark import drive, harness

ROOT = bm_helpers.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_paths_and_command():
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(isinstance(w, str) for w in cmd)
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if (ROOT / word).exists():
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_run_seconds_fits_the_check_with_every_cell():
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("workload", CELLS)
def test_resolves_every_cell_by_name(workload):
    res = harness.resolve(harness.load_spec(), workload)
    assert res["traffic"]["operation"] in drive.OPERATIONS
    assert set(res["limits"]) >= {"subgrid_err"}
    names = {m["name"] for m in res["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert res["per_layer"] and set(res["readers"]) == {
        m["name"] for m in res["per_layer"]}
    assert all(callable(r) for r in res["readers"].values())
    assert res["config"]["precision"] == "highest"


def test_configurations():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    assert 1 <= len(SPEC["configs"]) <= 24
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["source"].startswith("https://") and len(c["source"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert not key.endswith(("_dim", "_rank", "_size"))


def test_cells():
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        pairs.add((w["config"], w["traffic"]))
        assert (ROOT / "benchmark" / "traffic"
                / f"{w['traffic']}.json").is_file()
        assert (ROOT / "benchmark" / "limits" / f"{w['name']}.json").is_file()
    assert len(pairs) == len(SPEC["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = []
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        names.append(m["name"])
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        assert (ROOT / "benchmark" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        names.append(m["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(names) == len(set(names))
    for w in CELLS:
        assert any(w in m.get("workloads", CELLS) for m in SPEC["per_layer"])


def test_each_per_layer_metric_moves_a_metric_its_cells_report():
    e2e = {m["name"]: set(m.get("workloads", CELLS))
           for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= e2e[m["moves"]], m["name"]
    for w in CELLS:
        assert sum(w in cells for cells in e2e.values()) >= 2


def test_layers_are_named_alike():
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)


def _bad_copy(tmp_path, edit):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    edit(spec)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


def test_refuses_a_bad_workload_name(tmp_path):
    root = _bad_copy(tmp_path, lambda s: s["workloads"][0].update(
        name="round trip"))
    with pytest.raises(harness.SpecError):
        harness.load_spec(root)


def test_refuses_a_bad_unit(tmp_path):
    root = _bad_copy(tmp_path, lambda s: s["end_to_end"][0].update(
        unit="subgrids per second"))
    with pytest.raises(harness.SpecError):
        harness.load_spec(root)


def test_refuses_an_unknown_workload():
    with pytest.raises(harness.SpecError):
        harness.resolve(harness.load_spec(), "no-such-cell")


def test_finds_files_by_name_in_another_checkout(tmp_path):
    """A copy of the benchmark alone resolves every cell: nothing is
    found through the program's tree."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_spec(tmp_path)
    for w in CELLS:
        res = harness.resolve(spec, w, tmp_path)
        assert res["cell"]["name"] == w


def test_benchmark_imports_nothing_of_bench_chip_smoke_or_scripts():
    for path in (ROOT / "benchmark").rglob("*.py"):
        text = path.read_text()
        assert not re.search(
            r"^\s*(from|import)\s+(bench|chip_smoke|scripts)\b", text,
            re.MULTILINE), path
