"""The harness refuses a machine without the chips a cell asks for, and
a directory that holds only the benchmark."""

import os
import shutil
import subprocess
import sys

import bm_helpers
import pytest

from benchmark import harness


def test_device_stamp_refuses_the_cpu():
    with pytest.raises(SystemExit) as exc:
        harness.device_stamp(1)
    assert "needs a TPU" in str(exc.value)


def test_main_refuses_the_cpu_before_printing(capsys):
    with pytest.raises(SystemExit) as exc:
        harness.main(["--workload", "roundtrip-32k", "--seed", "1",
                      "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_a_directory_of_the_benchmark_alone_fails(tmp_path):
    """BENCHMARK.json and the files under its paths, without the
    program: the run exits non-zero and prints no result."""
    root = bm_helpers.ROOT
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    for p in ("benchmark", "tests/benchmark_harness"):
        shutil.copytree(root / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "roundtrip-32k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_process_age_is_positive_and_small():
    assert 0 < harness.process_age() < 24 * 3600
