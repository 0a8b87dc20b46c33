"""`chip_smoke.py` off the chip: its refusals, its checks, and its phases.

On the CPU the script itself must refuse to run (no TPU, no result), so the
phases are driven here directly at tiny sizes, with interpreted kernels:
the one-chip phase in this process, the four-chip phase over four forced
host devices in a child.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import run_in_subprocess

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"kmeans": 1 << 12, "sort": 1 << 12, "grep": 1 << 14,
        "kmeans_pallas": 1 << 10}


def _smoke():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    return chip_smoke


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_refuses_without_a_tpu():
    p = _run_script(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""


def test_smoke_checks_catch_wrong_answers():
    cs = _smoke()
    rng = np.random.default_rng(0)
    points = rng.uniform(size=(512, cs.D)).astype(np.float32)
    init = points[: cs.K]
    ref = cs.lloyd_reference(points, init, {3})
    good = {"centers": ref[3].astype(np.float32), "n_iter": 3, "halted": True}
    cs.check_kmeans(lambda _: None, "kmeans", good, points, init)
    bad = dict(good, centers=good["centers"] + 0.01)
    with pytest.raises(cs.SmokeFailure, match="numpy reference"):
        cs.check_kmeans(lambda _: None, "kmeans", bad, points, init)

    values = rng.standard_normal(64).astype(np.float32)
    res = {"sorted": np.sort(values), "rounds": 1, "dropped": np.zeros(1, np.int32),
           "counts": np.asarray([64.0])}
    cs.check_sort(lambda _: None, "sort", res, np.sort(values))
    with pytest.raises(cs.SmokeFailure, match="numpy's sort"):
        cs.check_sort(lambda _: None, "sort", dict(res, sorted=values),
                      np.sort(values))


def test_one_chip_phase_on_cpu():
    import jax

    cs = _smoke()
    lines = []
    cs.one_chip(jax.devices(), cs.make_data(0, TINY), TINY, lines.append)
    jobs = [line for line in lines if line.startswith("job=")]
    assert len(jobs) == 5, lines
    assert any(line.startswith("runner_cache ") for line in lines)


def test_four_chip_phase_on_forced_cpu_devices():
    out = run_in_subprocess(f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import jax
        import chip_smoke as cs
        tiny = {TINY!r}
        cs.four_chips(jax.devices(), cs.make_data(0, tiny), tiny, print)
    """, devices=4)
    assert "input_devices={'v': (0, 1, 2, 3)}" in out, out
    assert "check=kmeans_4chip_vs_1chip" in out, out
