"""Shared fixtures: a copy of the benchmark with tiny cells added by files.

A tiny cell is the real cell's configuration under a traffic file of a few
thousand points or keys, added exactly as a later change would add one: a
traffic file and a `BENCHMARK.json` entry, no other edit.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY = {
    "tiny.kmeans": ("kmeans_paper", {"n": 4096, "clients": 2, "datasets": 2,
                                     "warmup_datasets": 2, "trace_jobs": 2}, 1),
    "tiny.sort": ("npb_is_a", {"n": 4096, "clients": 2, "datasets": 2,
                               "warmup_datasets": 1, "trace_jobs": 2}, 1),
    "tiny.sort.4chip": ("npb_is_a_mpi4", {"n": 4096, "clients": 2, "datasets": 2,
                                          "warmup_datasets": 1, "trace_jobs": 2}, 4),
}


def make_root(tmp: Path, cells=TINY) -> Path:
    """A checkout-like root: BENCHMARK.json with `cells` added, and a
    chipbench directory whose files are the real ones plus one traffic
    file per tiny cell."""
    bench = tmp / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("tests", "testdata", "__pycache__"))
    index = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {c["name"] for c in index["configs"]}
    for config in sorted({c for c, _, _ in cells.values()} - listed):
        # a configuration kept in chipbench/configs/ that no cell of
        # BENCHMARK.json runs yet
        index["configs"].append({"name": config, "source": "a test", "reduced": [],
                                 "file": f"chipbench/configs/{config}.json", "why": "a test"})
    for name, (config, traffic, chips) in cells.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
        index["workloads"].append({"name": name, "config": config, "traffic": name,
                                   "chips": chips, "why": "a test's tiny cell"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(index))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_python(code: str, *, devices: int = 1, timeout: int = 600, cwd=None) -> str:
    """Run `code` in a fresh interpreter on `devices` forced host CPUs."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd or ROOT,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"child failed ({proc.returncode}):\n{proc.stdout[-4000:]}\n"
                             f"{proc.stderr[-8000:]}")
    return proc.stdout
