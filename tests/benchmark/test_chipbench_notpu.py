"""No chip, no result: ``run.py`` exits non-zero and prints nothing on
standard output, in the checkout and in a directory that holds only
``BENCHMARK.json`` and the benchmark's own files."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "b1.cw",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "benchmark_alone"])
def test_run_without_a_tpu_prints_no_result(where, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cwd = ROOT
    if where == "benchmark_alone":
        cwd = tmp_path / "alone"
        shutil.copytree(BENCH, cwd / "benchmarks" / "chip",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", cwd / "BENCHMARK.json")
        env.pop("PYTHONPATH", None)
    proc = _run(cwd, env)
    assert proc.returncode != 0, proc.stdout
    assert proc.stdout.strip() == "", proc.stdout
