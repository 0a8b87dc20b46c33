"""Run one benchmark cell on the chips of this machine and print its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are listed in `BENCHMARK.json`. With `--trace 0` the result
carries the cell's end-to-end metrics, with `--trace 1` its per-layer ones,
read from a profiler trace of a fixed number of jobs. The last line of
standard output is one JSON object (`correct`, `attempted`, `failed`,
`metrics`, `device`, with `--trace 1` `breakdown`, and last `checks`: every
number compared with the plain reference, beside its limit); the checks are
also the last lines of standard error. Without a TPU, or with fewer chips
than the cell needs, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="a cell named in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window (--trace 0)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the profiler's .xplane.pb into DIR")
    args = ap.parse_args(argv)

    from chipbench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                          trace_dir=args.keep_trace, t_process=T_PROCESS)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
