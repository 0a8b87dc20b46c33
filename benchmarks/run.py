"""Benchmark harness: one module per paper table/figure.

  bench_convergence     Figs. 5-6  k-means convergence + threshold rule
  bench_iteration_time  Fig. 7     time/iteration vs input size + early exit
  bench_paging          Fig. 8     EPC-paging (cache miss) cliff
  bench_overhead        Fig. 9     encryption x enclave 4-combo overheads
  bench_data_volume     Table II   split/shuffle/output bytes per iteration
  bench_tcb             Table I    trusted-code-base sizes (+ <30 LOC scripts)
  bench_crypto          cipher throughput (the boundary tax primitive)
  bench_shuffle         coalesced vs per-leaf secure shuffle wire
                        (collectives/launches/bytes/time per round)
  bench_sharded_state   sharded vs replicated carried state (per-device
                        state bytes + collective counts on the sort round)
  bench_service         persistent job service: cold vs warm submit latency,
                        runner-cache hit rate, throughput vs queue depth
  bench_costmodel       calibrated cost model vs reality: per-workload
                        steady-state prediction error, sim consistency,
                        auto vs default knob vectors
  bench_roofline        §Roofline terms from the dry-run report

Prints ``name,us_per_call,derived`` CSV.

Machine-readable perf trajectory: driver-path metrics (compile time,
steady-state per-iteration time per keystream impl, rounds executed vs
dispatched, shuffle wire bytes) are serialized to ``BENCH_driver.json`` —
modules publish them via a module-level ``LAST_METRICS`` dict — and the
secure-shuffle wire metrics (collectives + keystream launches per round,
bytes, coalesced vs per-leaf steady state; ``bench_shuffle``) additionally
to ``BENCH_shuffle.json``, and the carried-state layout metrics (per-device
state bytes + sort-round collective counts, sharded vs replicated;
``bench_sharded_state``) to ``BENCH_sharded_state.json``, and the
calibrated cost-model prediction errors (``bench_costmodel``) to
``BENCH_costmodel.json``. Every artifact's full field-by-field schema is
documented in ``benchmarks/README.md``. CI runs ``run.py --smoke``
(reduced sizes, driver-relevant modules only) and uploads the JSONs as
artifacts so regressions are visible across PRs; the smoke lane fails if
any cost-model ``pred_error`` cell exceeds 50%.

``BENCH_service.json`` schema (``bench_service``; all latencies in seconds):

  {schema, smoke, backend, platform, jax,    # shared envelope
   service: {
     cold:  {latency_s, runner_misses, n_iter},   # empty-cache submit
     warm:  {latency_s, runner_misses,            # same-bucket resubmit;
             new_compiles},                       # both must be 0
     speedup_cold_over_warm,                      # acceptance: >= 10
     throughput: {"<depth>": {jobs, seconds, jobs_per_s}, ...},
     cache: {hits, misses, evictions, resident,
             max_resident, compile_cache_size},   # RunnerCache.stats()
     jobs_completed, round_base,                  # service counters
     sim: {burst | straggler:                     # AdmissionSim policies
           {bucketed_makespan_s, per_job_makespan_s,
            bucketed_compiles, per_job_compiles, speedup}}}}
"""

import argparse
import inspect
import json
import platform
import sys
import traceback

import jax

from benchmarks import (
    bench_convergence,
    bench_costmodel,
    bench_crypto,
    bench_data_volume,
    bench_iteration_time,
    bench_overhead,
    bench_paging,
    bench_roofline,
    bench_service,
    bench_sharded_state,
    bench_shuffle,
    bench_tcb,
)
from repro.compile_cache import enable_compile_cache

MODULES = [
    bench_tcb,
    bench_crypto,
    bench_convergence,
    bench_iteration_time,
    bench_shuffle,
    bench_sharded_state,
    bench_service,
    bench_costmodel,
    bench_paging,
    bench_overhead,
    bench_data_volume,
    bench_roofline,
]

# the modules exercised by the CI smoke lane: the driver + shuffle hot paths
SMOKE_MODULES = [bench_iteration_time, bench_shuffle, bench_sharded_state,
                 bench_service, bench_costmodel]

# envelope keys shared by every BENCH_*.json artifact
ENVELOPE = ("schema", "smoke", "backend", "platform", "jax")


def _warn_stale_sections(path: str, owned: set) -> None:
    """Warn when an existing artifact holds sections this run won't rewrite.

    Checked-in BENCH_*.json files outlive module renames; a section nobody
    owns any more (e.g. a leftover ``bench_oblivious``) would silently pin
    numbers from an old HEAD forever. The rewrite below drops it — this
    warning makes the drop visible in the CI log.
    """
    try:
        with open(path) as f:
            old = json.load(f)
    except (OSError, ValueError):
        return
    for key in old:
        if key not in owned and key not in ENVELOPE:
            print(f"WARNING: {path} section {key!r} is not produced by any "
                  f"current benchmark module; dropping it", file=sys.stderr)


def _run_module(mod, smoke: bool):
    """Call mod.run(), passing smoke= only when the module accepts it."""
    params = inspect.signature(mod.run).parameters
    if "smoke" in params:
        return mod.run(smoke=smoke)
    return mod.run()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes, driver-relevant modules only (CI lane)")
    ap.add_argument("--json-out", default="BENCH_driver.json",
                    help="path for the machine-readable driver metrics")
    ap.add_argument("--shuffle-json-out", default="BENCH_shuffle.json",
                    help="path for the machine-readable shuffle-wire metrics")
    ap.add_argument("--sharded-state-json-out", default="BENCH_sharded_state.json",
                    help="path for the machine-readable carried-state metrics")
    ap.add_argument("--service-json-out", default="BENCH_service.json",
                    help="path for the machine-readable job-service metrics "
                         "(schema in the module docstring above)")
    ap.add_argument("--costmodel-json-out", default="BENCH_costmodel.json",
                    help="path for the calibrated cost-model prediction-error "
                         "metrics (schema in benchmarks/README.md)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    modules = SMOKE_MODULES if args.smoke else MODULES
    print("name,us_per_call,derived")
    failures = 0
    metrics: dict = {
        "schema": 1,
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "jax": jax.__version__,
    }
    for mod in modules:
        try:
            for name, us, derived in _run_module(mod, args.smoke):
                print(f"{name},{us:.2f},{derived}")
        except Exception as e:
            failures += 1
            print(f"{mod.__name__},NaN,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
        mod_metrics = getattr(mod, "LAST_METRICS", None)
        if mod_metrics:
            metrics[mod.__name__.removeprefix("benchmarks.")] = mod_metrics
    _warn_stale_sections(
        args.json_out,
        {m.__name__.removeprefix("benchmarks.") for m in MODULES})
    with open(args.json_out, "w") as f:
        json.dump(metrics, f, indent=2, sort_keys=True)
    print(f"wrote {args.json_out}", file=sys.stderr)
    # the shuffle-wire trajectory gets its own artifact: the acceptance
    # numbers (collectives + keystream launches per secure round, bytes,
    # coalesced vs per-leaf timing) live here
    if bench_shuffle in modules:
        shuffle_metrics = {k: metrics[k] for k in ENVELOPE}
        shuffle_metrics["shuffle"] = getattr(bench_shuffle, "LAST_METRICS", {})
        with open(args.shuffle_json_out, "w") as f:
            json.dump(shuffle_metrics, f, indent=2, sort_keys=True)
        print(f"wrote {args.shuffle_json_out}", file=sys.stderr)
    # likewise for the carried-state layout trajectory: per-device state
    # bytes and sort-round collective counts, sharded vs replicated
    if bench_sharded_state in modules:
        state_metrics = {k: metrics[k] for k in ENVELOPE}
        state_metrics["sharded_state"] = getattr(
            bench_sharded_state, "LAST_METRICS", {})
        with open(args.sharded_state_json_out, "w") as f:
            json.dump(state_metrics, f, indent=2, sort_keys=True)
        print(f"wrote {args.sharded_state_json_out}", file=sys.stderr)
    # and the serving trajectory: cold/warm submit latency, runner-cache hit
    # rate, throughput vs queue depth, admission-sim policy makespans
    if bench_service in modules:
        service_metrics = {k: metrics[k] for k in ENVELOPE}
        service_metrics["service"] = getattr(bench_service, "LAST_METRICS", {})
        with open(args.service_json_out, "w") as f:
            json.dump(service_metrics, f, indent=2, sort_keys=True)
        print(f"wrote {args.service_json_out}", file=sys.stderr)
    # and the cost-model trajectory: per-(workload, impl) prediction error,
    # sim-vs-closed-form consistency, auto-vs-default knob vectors. The CI
    # bench-smoke lane fails when pred_error_max exceeds 0.5.
    if bench_costmodel in modules:
        cm_metrics = {k: metrics[k] for k in ENVELOPE}
        cm_metrics["costmodel"] = getattr(bench_costmodel, "LAST_METRICS", {})
        with open(args.costmodel_json_out, "w") as f:
            json.dump(cm_metrics, f, indent=2, sort_keys=True)
        print(f"wrote {args.costmodel_json_out}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
