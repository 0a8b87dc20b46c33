"""A k-means run with the timed path broken underneath reads `correct` false.

Each test skips only the harness's look for a chip and drives the rest of
a run of a tiny k-means cell: set-up, warm-up, window, references, checks.
"""

import pytest

from chipbench import harness
from chipbench.catalog import Catalog
from chipbench.tests import faults

SEED = 2**31 + 77


def _run(root):
    return harness.run("tiny.kmeans", SEED, 1.0, False, catalog=Catalog(root),
                       require_tpu=False, use_compile_cache=False, log=lambda s: None)


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"job_p50_s", "input_MiB_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_reads_incorrect(tiny_root, monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    out = _run(tiny_root)
    assert not out["correct"], out["checks"]
    failing = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert set(failing) & {"center_err_pts", "round_gap"}, out["checks"]
