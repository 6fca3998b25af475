"""Without a TPU, or without the program, a run exits non-zero and prints
no result."""

import os
import shutil
import subprocess
import sys

from bench.tests import tiny


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "ou_gan.train_b1024", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    return not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(tiny.REPO)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
