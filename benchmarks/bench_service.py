"""Persistent job service: cold vs warm submit latency, hit rate, throughput.

The serving claim (`repro.serve.service`): once a size bucket's programs are
compiled, every further job admitted into that bucket runs WITHOUT tracing or
compiling anything — submit latency drops from the XLA-compile regime
(tens of seconds on the secure path) to the steady dispatch regime
(milliseconds). This benchmark measures exactly that, on a real service over
a multi-device mesh (`benchmarks/multidevice.py`: every attached chip in this
process, or forced host devices in a CPU child):

  * COLD job — first k-means submit into an empty cache: latency includes
    every chunk program compile (runner-cache misses > 0);
  * WARM jobs — same-bucket resubmits with different data and a DIFFERENT
    real size (padding reuses the bucket): per-job runner-cache misses must
    be 0 and the cache's XLA compile-cache size must not grow (zero new
    compiles — asserted), with warm latency >= 10x below cold (asserted);
  * THROUGHPUT at queue depths 1 / 4 / 16 — warm jobs submitted together,
    measuring end-to-end jobs/s as the admission queue deepens;
  * ADMISSION SIM — `runtime/sim.py::AdmissionSim` virtual makespans for
    the bucketed-cache policy vs compile-per-job on burst and straggler
    traces (no devices; the policy argument for the cache in one number).

Machine-readable output: `run()` fills the module-level `LAST_METRICS`
dict, which `benchmarks/run.py` serializes to BENCH_service.json (schema
documented there; uploaded by the CI bench-smoke lane).
"""

from __future__ import annotations

from benchmarks.multidevice import run_section

# Filled by run(); serialized by benchmarks/run.py into BENCH_service.json.
LAST_METRICS: dict = {}

_SERVICE_SECTION = """
import time
import numpy as np
import jax, jax.numpy as jnp
from repro.compat import make_mesh
from repro.core.shuffle import SecureShuffleConfig
from repro.serve.service import SecureJobService

mesh = make_mesh((n_dev,), ("data",))
secure = SecureShuffleConfig(
    key_words=jnp.arange(8, dtype=jnp.uint32),
    nonce_words=jnp.zeros((3,), jnp.uint32))
svc = SecureJobService(mesh, secure=secure, max_concurrent=max(depths),
                       min_chunk=1, max_chunk=max_chunk)

def points(n, seed):
    rng = np.random.default_rng(seed)
    c = rng.uniform(0.1, 0.9, size=(4, 2))
    return (c[rng.integers(0, 4, size=n)]
            + rng.normal(scale=0.05, size=(n, 2))).astype(np.float32)

out = {}
# COLD: empty cache — latency includes every chunk-program compile
h = svc.submit_kmeans(points(n_items, seed=0), 4, max_rounds=max_rounds)
h.result(1800)
out["cold"] = {"latency_s": h.latency_s, "runner_misses": h.runner_misses,
               "n_iter": h.result()["n_iter"]}
assert h.runner_misses > 0, "cold job must build runners"

# WARM: different data AND different real size, same geometric bucket —
# the submit must skip tracing entirely (zero new compiles, zero misses)
compiles_before = svc.cache.compile_cache_size()
h2 = svc.submit_kmeans(points(max(4, n_items - n_dev), seed=1), 4,
                       max_rounds=max_rounds)
h2.result(1800)
new_compiles = svc.cache.compile_cache_size() - compiles_before
assert h2.runner_misses == 0, f"warm job missed the cache: {h2.runner_misses}"
assert new_compiles == 0, f"warm job compiled {new_compiles} programs"
assert h2.latency_s * 10.0 <= h.latency_s, (
    f"warm submit latency {h2.latency_s:.4f}s not >= 10x below cold "
    f"{h.latency_s:.4f}s")
out["warm"] = {"latency_s": h2.latency_s, "runner_misses": h2.runner_misses,
               "new_compiles": new_compiles}
out["speedup_cold_over_warm"] = h.latency_s / max(h2.latency_s, 1e-9)

# THROUGHPUT vs queue depth, warm cache: depth jobs submitted together
out["throughput"] = {}
for depth in depths:
    t0 = time.perf_counter()
    handles = [svc.submit_kmeans(points(n_items, seed=10 + i), 4,
                                 max_rounds=max_rounds)
               for i in range(depth)]
    for hh in handles:
        hh.result(1800)
    dt = time.perf_counter() - t0
    assert all(hh.runner_misses == 0 for hh in handles)
    out["throughput"][str(depth)] = {"jobs": depth, "seconds": dt,
                                     "jobs_per_s": depth / dt}

out["cache"] = svc.cache.stats()
stats = svc.stats()
out["jobs_completed"] = stats["jobs_completed"]
out["round_base"] = stats["round_base"]
svc.close()
"""


def _run_sim(smoke: bool) -> dict:
    from repro.runtime.sim import AdmissionSim, burst_trace, straggler_trace

    sim = AdmissionSim()
    n_jobs = 8 if smoke else 16
    out = {}
    for name, trace in [("burst", burst_trace(n_jobs)),
                        ("straggler", straggler_trace(max(8, n_jobs - 4)))]:
        bucketed = sim.run(trace, "bucketed")
        per_job = sim.run(trace, "compile-per-job")
        out[name] = {
            "bucketed_makespan_s": bucketed["makespan_s"],
            "per_job_makespan_s": per_job["makespan_s"],
            "bucketed_compiles": bucketed["compiles"],
            "per_job_compiles": per_job["compiles"],
            "speedup": per_job["makespan_s"] / bucketed["makespan_s"],
        }
    return out


def run(smoke: bool = False):
    """Yields (name, us_per_call, derived) rows; fills LAST_METRICS."""
    n_dev = 4
    n_items = 64 if smoke else 256
    depths = (1, 4) if smoke else (1, 4, 16)
    max_rounds = 6 if smoke else 16
    max_chunk = 2 if smoke else 4

    metrics = run_section(_SERVICE_SECTION,
                          dict(n_items=n_items, depths=list(depths),
                               max_rounds=max_rounds, max_chunk=max_chunk),
                          n_dev=n_dev, timeout=3600)
    metrics["sim"] = _run_sim(smoke)
    LAST_METRICS.clear()
    LAST_METRICS.update(metrics)

    yield ("service_submit_cold", metrics["cold"]["latency_s"] * 1e6,
           f"misses={metrics['cold']['runner_misses']}")
    yield ("service_submit_warm", metrics["warm"]["latency_s"] * 1e6,
           f"speedup={metrics['speedup_cold_over_warm']:.0f}x "
           f"new_compiles={metrics['warm']['new_compiles']}")
    cache = metrics["cache"]
    hit_rate = cache["hits"] / max(1, cache["hits"] + cache["misses"])
    yield ("service_cache", 0.0,
           f"hits={cache['hits']} misses={cache['misses']} "
           f"hit_rate={hit_rate:.2f} resident={cache['resident']}")
    for depth, row in sorted(metrics["throughput"].items(), key=lambda kv: int(kv[0])):
        yield (f"service_throughput_depth{depth}",
               row["seconds"] / max(1, row["jobs"]) * 1e6,
               f"{row['jobs_per_s']:.1f} jobs/s")
    for trace, row in metrics["sim"].items():
        yield (f"service_sim_{trace}", 0.0,
               f"bucketed {row['bucketed_makespan_s']:.0f}s vs per-job "
               f"{row['per_job_makespan_s']:.0f}s ({row['speedup']:.1f}x)")
