"""A run that cannot be measured prints no result and exits non-zero."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
ARGS = ["--workload", "lm144m-train-4k", "--seed", "5000000000",
        "--seconds", "1", "--trace", "0"]


def run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_only_host_exits_nonzero_without_result():
    p = run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.NoChip):
        harness.peaks("TPU v99 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
