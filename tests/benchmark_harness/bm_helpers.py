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


TINY_COLUMNS = 3  # columns of the tiny cover: ceil(1024 / 448)


def tiny_share(columns, n_columns):
    """A configuration's ``columns`` share of a cover of ``n_columns``
    columns, mapped onto the same part of the tiny cover: the tiny
    columns that the share's span overlaps, at least one."""
    first = columns["first"] * TINY_COLUMNS // n_columns
    end = -(-(columns["first"] + columns["count"]) * TINY_COLUMNS
            // n_columns)
    return {"first": first, "count": max(1, end - first)}


def tiny(res, chips=None):
    """A resolved cell with its configuration cut to the tiny catalogue
    size, a column share mapped onto the tiny cover (and, if given,
    another chip count)."""
    res = copy.deepcopy(res)
    config = res["config"]
    if "columns" in config:
        n_columns = -(-int(config["N"]) // int(config["xA_size"]))
        config["columns"] = tiny_share(config["columns"], n_columns)
    config.update(TINY)
    if chips is not None:
        res["cell"]["chips"] = chips
    return res


def tiny_cell(workload, chips=None, **config):
    """``harness.resolve`` of ``workload``, with ``config`` keys set in
    its configuration as its file would state them, cut by `tiny`."""
    res = copy.deepcopy(harness.resolve(harness.load_spec(), workload))
    res["config"].update(config)
    return tiny(res, chips)


def run_tiny(workload, seed=2**31 + 77, seconds=0.5, trace=False,
             chips=None, **config):
    """One run of ``workload`` at the tiny size on the CPU, through
    `harness.run` (everything but the device stamp)."""
    return run_res(tiny_cell(workload, chips, **config), seed, seconds,
                   trace)


def run_res(res, seed=2**31 + 77, seconds=0.5, trace=False):
    """One run of a tiny resolved cell on the CPU, through
    `harness.run`."""
    harness.configure(res["config"])
    device = dict(FAKE_DEVICE, count=res["cell"]["chips"])
    return harness.run(res, seed, seconds, trace, device, setup_t0=0.0)
