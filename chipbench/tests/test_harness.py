"""Where no result may be printed: without a TPU, and in a directory that
holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

import run as harness

CELL = "qwen3-1.7b.chat-restart"


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    out = _run(harness.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
