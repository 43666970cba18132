"""The benchmark's command refuses to run anywhere but on a TPU with the
cell's chips, and without the program beside it: it exits non-zero and
prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT

CELL = "qwen2-1.5b-f32.train4k-fill"


def _run(root, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(root / "benchmarks/chip/run.py"), "--workload",
         CELL, "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_refuses_the_cpu():
    proc = _run(ROOT, {})
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    with open(ROOT / "BENCHMARK.json") as f:
        paths = json.load(f)["paths"]
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in paths:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {})
    _no_result(proc)
    assert "no program" in proc.stderr
