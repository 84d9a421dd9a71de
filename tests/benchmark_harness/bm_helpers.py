"""Shared helpers of the benchmark's tests: a cell of ``BENCHMARK.json``
resolved at a tiny catalogue size, and a run of it on the CPU that skips
the harness's look for a chip."""

import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402

# the catalogue's 1k[1]-n512-512: 9 facets of 352 and 9 subgrids of 448
TINY = {"W": 11.0, "N": 1024, "yB_size": 352, "yN_size": 512,
        "yP_size": 512, "xA_size": 448, "xM_size": 512}

# what the test runs put in place of the harness's device stamp: the
# peaks table's kind, so a traced run can reduce its (empty) trace
FAKE_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_cell(workload, chips=None):
    """``harness.resolve`` of ``workload`` with its configuration cut to
    the tiny catalogue size (and, if given, another chip count)."""
    res = harness.resolve(harness.load_spec(), workload)
    res = copy.deepcopy(res)
    res["config"].update(TINY)
    if chips is not None:
        res["cell"]["chips"] = chips
    return res


def run_tiny(workload, seed=2**31 + 77, seconds=0.5, trace=False,
             chips=None):
    """One run of ``workload`` at the tiny size on the CPU, through
    `harness.run` (everything but the device stamp)."""
    res = tiny_cell(workload, chips)
    harness.configure(res["config"])
    device = dict(FAKE_DEVICE, count=res["cell"]["chips"])
    return harness.run(res, seed, seconds, trace, device, setup_t0=0.0)
