"""The control, the reference computed in the precision below the
configuration's and put in the program's place, reads `correct` false
through the run's own checks (the readings at the cells' sizes, on the
chip, are in PERF.md)."""

from pathlib import Path

import pytest

from chipbench import harness, readings
from chipbench.catalog import Catalog
from chipbench.tests.conftest import make_root

SEEDS = [2**31 + 3, 2**40 + 5]
CELLS = {
    "ctl.kmeans": ("kmeans_paper", {"n": 1 << 16, "clients": 2, "datasets": 2,
                                    "warmup_datasets": 2, "trace_jobs": 2}, 1),
    "ctl.sort": ("npb_is_a", {"n": 4096, "clients": 2, "datasets": 2,
                              "warmup_datasets": 1, "trace_jobs": 2}, 1),
    "ctl.sort.4chip": ("npb_is_a_mpi4", {"n": 4096, "clients": 2, "datasets": 2,
                                         "warmup_datasets": 1, "trace_jobs": 2}, 4),
}


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    return Catalog(make_root(Path(tmp_path_factory.mktemp("ctl")), CELLS))


def _control_reads_incorrect(catalog, cell, seed):
    got = readings.control_checks(cell, seed, catalog=catalog, require_tpu=False)
    assert set(got) == set(catalog.cell(cell, traced=False).job.CONTROLS)
    for variant, checks in got.items():
        assert not harness.correct(checks), (variant, checks)
        failing = {k for k, c in checks.items() if c["value"] > c["limit"]}
        assert failing & {"center_err_pts", "mismatched"}, (variant, checks)


@pytest.mark.parametrize("slot", [0, 1])
def test_kmeans_control_fails_its_limit(catalog, slot):
    _control_reads_incorrect(catalog, "ctl.kmeans", SEEDS[slot])


@pytest.mark.parametrize("config", ["npb_is_a", "npb_is_a_mpi4"])
def test_sort_control_fails_its_limit(catalog, config):
    cell = {"npb_is_a": "ctl.sort", "npb_is_a_mpi4": "ctl.sort.4chip"}[config]
    for seed in SEEDS:
        _control_reads_incorrect(catalog, cell, seed)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_reference_in_the_programs_place_reads_correct(catalog, cell):
    c = catalog.cell(cell, traced=False)
    datasets = harness.make_datasets(c, SEEDS[0])
    refs = {s: c.job.reference(c.config, d, []) for s, d in enumerate(datasets)}
    if c.job.__name__.endswith("kmeans"):
        answers = [{"centers": r["centers"][r["halt"] - 1], "n_iter": r["halt"]}
                   for r in refs.values()]
    else:
        answers = [{"sorted": r["sorted"], "rounds": 1} for r in refs.values()]
    checks = harness.check(c, datasets, list(refs), answers, window_compiles=0,
                           failed_jobs=0, refs=refs)
    assert harness.correct(checks), checks
    assert all(v["value"] == 0 for v in checks.values()), checks
