"""Without a TPU, or without the program beside it, a run prints no result
line and exits non-zero."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]
ARGS = ["--workload", CELL, "--seed", "4294967301", "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_line(tmp_path):
    out = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode != 0
    assert "correct" not in out.stdout
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode != 0
    assert "correct" not in out.stdout
    assert "No module named 'repro'" in out.stderr
