"""The harness refuses to measure without a TPU, and without the program."""

from __future__ import annotations

import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "vld-service-b16k", "--seed", "5000000011", "--seconds", "1",
        "--trace", "0"]


def _run(root: pathlib.Path, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    return subprocess.run(
        [sys.executable, str(root / "chipbench" / "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )


def test_exits_nonzero_on_cpu(tmp_path):
    proc = _run(ROOT, tmp_path)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_exits_nonzero_without_the_program(tmp_path):
    bare = tmp_path / "bare"
    shutil.copytree(ROOT / "chipbench", bare / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", bare / "BENCHMARK.json")
    proc = _run(bare, tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
