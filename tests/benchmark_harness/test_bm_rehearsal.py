"""Both cells end to end at the tiny catalogue size on the CPU, through
`harness.run` with the device stamp left out: the round trip on one
device (its resident and its facet-slab forward plan), the forward on a
mesh of four virtual devices, and a traced run."""

import json

import bm_helpers
import pytest


@pytest.fixture(autouse=True)
def _precision_restored(monkeypatch):
    # harness.configure sets SWIFTLY_PRECISION; give it back afterwards
    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")


def _well_formed(result, metrics):
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == set(metrics)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert line["run"]["window_compiles"] == 0


def test_roundtrip_cell():
    r = bm_helpers.run_tiny("roundtrip-32k")
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] == 3 + 9
    _well_formed(r, {"subgrid_rate", "setup_s"})
    assert set(r["checks"]) == {"subgrid_err", "facet_err", "missing"}
    assert r["metrics"]["subgrid_rate"]["value"] > 0


def test_roundtrip_cell_on_the_facet_slab_plan(monkeypatch):
    """The plan the 32k cell runs on the chip: the dense facets staged
    on the host and uploaded a slab at a time for each column group."""
    from swiftly_tpu.parallel import StreamedForward

    monkeypatch.setattr(StreamedForward, "_facet_stack_fits",
                        lambda self: False)
    r = bm_helpers.run_tiny("roundtrip-32k", seed=11)
    assert r["correct"], r["checks"]
    _well_formed(r, {"subgrid_rate", "setup_s"})


def test_forward_cell_on_four_devices():
    r = bm_helpers.run_tiny("forward-64k-mesh4", seed=2**33 + 3)
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
    assert set(r["checks"]) == {"subgrid_err", "missing"}
    _well_formed(r, {"fwd_subgrid_rate", "setup_s"})


def test_traced_run_reports_per_layer_metrics_only(monkeypatch):
    from benchmark import drive, harness

    res = bm_helpers.tiny_cell("roundtrip-32k")
    # a tiny pass is one column group: trace from the first boundary on,
    # however few passes a loaded machine fits into the window
    monkeypatch.setattr(drive.Tracer, "SKIP_GROUPS", 0)
    monkeypatch.setattr(drive.Tracer, "SECONDS", 0.1)
    harness.configure(res["config"])
    r = harness.run(res, 12, 1.0, True, dict(bm_helpers.FAKE_DEVICE),
                    setup_t0=0.0)
    assert r["correct"], r["checks"]
    # the CPU has no device plane: every reader finds nothing and the
    # harness leaves its metric out; end-to-end metrics are not printed
    assert "subgrid_rate" not in r["metrics"]
    assert r["metrics"] == {}
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
