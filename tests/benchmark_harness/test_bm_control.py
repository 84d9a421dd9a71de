"""The control: the program on its own lower-precision path
(``SWIFTLY_PRECISION=high``, three bf16 passes a product on the TPU),
which the limits have to fail. The CPU computes float32 products in
full whatever the precision says, so here each ``high`` einsum is done
as the TPU does it: both operands split into a bfloat16 high and low
part and the three larger cross products summed."""

import bm_helpers
import pytest


def _bf16x3_einsum(orig):
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return hi, lo

    def einsum(spec, *operands, precision=None, **kw):
        if precision != jax.lax.Precision.HIGH or len(operands) != 2:
            return orig(spec, *operands, precision=precision, **kw)
        (ah, al), (bh, bl) = (split(jnp.asarray(o)) for o in operands)
        full = jax.lax.Precision.HIGHEST
        return (orig(spec, ah, bh, precision=full, **kw)
                + orig(spec, ah, bl, precision=full, **kw)
                + orig(spec, al, bh, precision=full, **kw))

    return einsum


@pytest.fixture
def high_precision(monkeypatch):
    import jax.numpy as jnp

    from swiftly_tpu.parallel import streamed

    monkeypatch.setenv("SWIFTLY_PRECISION", "high")
    monkeypatch.setattr(jnp, "einsum", _bf16x3_einsum(jnp.einsum))
    for name in dir(streamed):  # trace every stage anew, and after
        getattr(getattr(streamed, name), "cache_clear", lambda: None)()
    yield
    monkeypatch.undo()
    for name in dir(streamed):
        getattr(getattr(streamed, name), "cache_clear", lambda: None)()


@pytest.mark.parametrize("workload", ["roundtrip-32k", "forward-32k",
                                      "forward-64k-mesh4"])
def test_control_fails_the_limits(workload, high_precision):
    res = bm_helpers.tiny_cell(workload)
    res["config"]["precision"] = "high"
    from benchmark import harness

    harness.configure(res["config"])
    device = dict(bm_helpers.FAKE_DEVICE, count=res["cell"]["chips"])
    r = harness.run(res, 2**31 + 202, 0.5, False, device, setup_t0=0.0)
    assert not r["correct"], r["checks"]
    assert r["checks"]["subgrid_err"]["value"] > 3 * 5.5e-7


def test_control_runner_reads_each_seed(monkeypatch, capsys):
    """`benchmark/control.py` drives the cell's own timed path once per
    seed in one process and prints each seed's compared numbers."""
    import json

    from benchmark import control, harness

    monkeypatch.setenv("SWIFTLY_PRECISION", "highest")
    monkeypatch.setattr(harness, "device_stamp", lambda n: {})
    monkeypatch.setattr(harness, "use_cache", lambda: None)
    tiny = {w: bm_helpers.tiny_cell(w) for w in ["forward-64k-mesh4"]}
    monkeypatch.setattr(harness, "resolve", lambda spec, w: tiny[w])
    assert control.main(["--workload", "forward-64k-mesh4", "--seeds",
                         "5,6", "--seconds", "0"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [5, 6]
    assert all(x["correct"] and x["checks"]["missing"] == 0 for x in lines)
