"""The benchmark measures nothing without a TPU: on the CPU it exits
non-zero and prints no result, and so it does in a directory that holds
only BENCHMARK.json and the benchmark's own files."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "minicpm-2b-deq.train", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def run_in(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except json.JSONDecodeError:
            pass
    return True


def test_refuses_without_a_tpu():
    p = run_in(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_in(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
