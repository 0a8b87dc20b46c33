"""The entry point measures nothing off the chip and nothing without the program."""

import os
import shutil
import subprocess
import sys

from chipbench.tests.conftest import BENCH, ROOT


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sort.8M", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_entry_point_refuses_a_cpu():
    proc = _run(ROOT)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert proc.stdout.strip() == "" or "{" not in proc.stdout.splitlines()[-1]
    assert "no TPU" in proc.stderr


def test_entry_point_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "chipbench", ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
