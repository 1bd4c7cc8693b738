"""``bench/run.py`` measures nothing off the chip."""

import json
import os
import shutil
import subprocess
import sys

from bench.harness import REPO

ARGS = ["--workload", "bmi.retention-cold", "--seed", str(2**31 + 3), "--seconds", "1"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS, "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    for line in lines:
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_to_run_off_the_tpu():
    proc = _run(REPO)
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's paths."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert _no_result(proc)
