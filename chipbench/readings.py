"""The control's readings, which set the upper end of a cell's limits.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3
    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --fault half_batch

For each seed this makes the run's datasets at the cell's sizes, puts the
plain reference computed in the precision below the configuration's in
the program's place (`jobs/<kind>.py::control`, in each of its variants),
and judges its answers as a run judges the program's: through
`harness.check`, against the cell's limits. One JSON line per seed and
variant, with every number compared and whether the run would be correct.
The lower readings are the program's own: the `checks` of `run.py` over a
dozen seeds. With `--fault` it instead makes whole runs of the cell
(`--seconds` long) with that fault of `tests/faults.py` planted under the
timed path, and prints their checks. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def control_checks(cell_name: str, seed: int, *, catalog=None, require_tpu: bool = True):
    """{variant: checks} of the control on one seed's datasets."""
    from chipbench import harness
    from chipbench.catalog import Catalog

    cell = (catalog or Catalog()).cell(cell_name, traced=False)
    if require_tpu:
        harness._devices(cell.chips, require_tpu)
    job, config = cell.job, cell.config
    datasets = harness.make_datasets(cell, seed)
    refs = {slot: job.reference(config, data, []) for slot, data in enumerate(datasets)}
    out = {}
    for variant in job.CONTROLS:
        answers = [job.control(config, data, refs[slot], accumulate=variant)
                   for slot, data in enumerate(datasets)]
        out[variant] = harness.check(cell, datasets, list(range(len(datasets))), answers,
                                     window_compiles=0, failed_jobs=0, refs=refs)
    return out


def fault_checks(cell_name: str, seed: int, fault: str, seconds: float) -> dict:
    """{fault: checks} of one whole run with `fault` planted."""
    import pytest

    from chipbench import harness
    from chipbench.tests import faults

    with pytest.MonkeyPatch.context() as mp:
        if fault == "drop_exchange":
            faults.drop_exchange(mp)
        else:
            faults.plant(mp, fault)
        out = harness.run(cell_name, seed, seconds, False, log=lambda s: None)
    return {fault: out["checks"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--fault", help="a fault of tests/faults.py, or drop_exchange")
    ap.add_argument("--seconds", type=float, default=5.0, help="window of a --fault run")
    args = ap.parse_args(argv)
    from chipbench import harness

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            got = (fault_checks(args.workload, seed, args.fault, args.seconds) if args.fault
                   else control_checks(args.workload, seed))
        except harness.NoAccelerator as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        for variant, checks in got.items():
            print(json.dumps({"cell": args.workload, "seed": seed, "reading": variant,
                              "correct": harness.correct(checks), "checks": checks,
                              "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
