"""A sort run with the timed path broken underneath reads `correct` false.

As for k-means: only the harness's look for a chip is skipped. The
four-chip cell's own fault, the exchange between chips left out, runs on
four host CPU devices in a child process.
"""

import json

import pytest

from chipbench import harness
from chipbench.catalog import Catalog
from chipbench.tests import faults
from chipbench.tests.conftest import make_root, run_python

SEED = 2**31 + 99


def _run(root, cell="tiny.sort"):
    return harness.run(cell, SEED, 1.0, False, catalog=Catalog(root),
                       require_tpu=False, use_compile_cache=False, log=lambda s: None)


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root)
    assert out["correct"], out["checks"]
    assert out["checks"]["mismatched"] == {"value": 0.0, "limit": 0.0}


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_reads_incorrect(tiny_root, monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    out = _run(tiny_root)
    assert not out["correct"], out["checks"]
    assert out["checks"]["mismatched"]["value"] > 0


FOUR = """
import json, tempfile
from pathlib import Path
import pytest
from chipbench import harness
from chipbench.catalog import Catalog
from chipbench.tests import faults
from chipbench.tests.conftest import make_root

root = make_root(Path(tempfile.mkdtemp()))
out = {}
for fault in ("none", "drop_exchange"):
    mp = pytest.MonkeyPatch()
    if fault == "drop_exchange":
        faults.drop_exchange(mp)
    r = harness.run("tiny.sort.4chip", %d, 1.0, False, catalog=Catalog(root),
                    require_tpu=False, use_compile_cache=False, log=lambda s: None)
    mp.undo()
    out[fault] = {"correct": r["correct"], "checks": r["checks"]}
print(json.dumps(out))
""" % SEED


def test_four_chips_sound_and_without_the_exchange():
    out = json.loads(run_python(FOUR, devices=4).strip().splitlines()[-1])
    assert out["none"]["correct"], out
    assert not out["drop_exchange"]["correct"], out
    assert out["drop_exchange"]["checks"]["mismatched"]["value"] > 0
