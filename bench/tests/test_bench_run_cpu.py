"""``bench/run.py`` measures only on a TPU: elsewhere it exits non-zero
and prints no result line."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ARGS = ["--workload", "qwen3-1.7b.lora-bf16", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, args=ARGS):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cpu_run_prints_no_result(trace):
    p = _run(harness.ROOT, ARGS[:-1] + [trace])
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_unknown_cell_prints_no_result():
    p = _run(harness.ROOT, ["--workload", "no-such-cell", "--seed", "1",
                            "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    holds no program to measure."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
