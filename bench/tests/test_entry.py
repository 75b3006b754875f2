"""The entry point refuses to measure where it cannot: on a machine whose
first device is not a TPU, and in a directory that holds only the
benchmark's own files (no program to run).  Either way it exits non-zero
and prints no result."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "grab4.backlog", "--seed", str(2**31 + 9),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode == 3
    assert not _has_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
