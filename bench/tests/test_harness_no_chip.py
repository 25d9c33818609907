"""Runs that cannot measure: no TPU, or no program beside the benchmark.
Each exits non-zero and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import harness

CMD = ["bench/run.py", "--workload", "a2a128_4p.sweep128", "--seed",
       "3000000000", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *CMD], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120,
    )


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            continue
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(harness.ROOT)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "no TPU" in out.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert _no_result(out.stdout)
