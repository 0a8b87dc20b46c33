"""One run of one cell: set-up, warm-up, a closed-loop window, checks, metrics.

The window is a closed loop: `clients` threads each submit a job, wait for
its result and submit the next, cycling over the run's datasets. With
`--trace 0` they submit until `seconds` have passed and then let the jobs
in flight drain; with `--trace 1` they run a fixed count of whole jobs
(`trace_jobs` of the traffic file) under the profiler. Every job is timed
on the client's own clock, from its `submit_*` call to `.result()`
returning: `JobHandle.latency_s` starts only once `submit_*` has prepared
the job on the caller's thread, so it leaves that work out.

After the window every job's answer is compared with the plain reference
(`jobs/<kind>.py`), the references running once the device memory has been
read and the service is closed.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from chipbench import stats, trace as tracemod
from chipbench.catalog import Catalog, Cell

# what every run checks besides the job's own comparison: no program is
# compiled in the window, and every job returns
RUN_LIMITS = {"window_compiles": 0, "failed_jobs": 0}
DRAIN_TIMEOUT_S = 240.0
CACHE_DIR = Path(__file__).resolve().parents[1] / ".jax_cache"
SESSION_KEY = bytes(range(32))


class NoAccelerator(RuntimeError):
    pass


@dataclass
class RunContext:
    """What a metric reader sees of one run."""

    cell: Cell
    n_shards: int
    setup_s: float
    records: list  # stats.JobRecord per completed window job
    handles: list  # JobHandle per completed window job
    results: list  # finalized result dict per completed window job
    trace: tracemod.Trace | None = None
    peaks: dict | None = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic


def seed_words(seed: int) -> int:
    """Any whole number as a seed for numpy's SeedSequence."""
    return int(seed) % (1 << 64)


def make_datasets(cell: Cell, seed: int) -> list:
    """The run's datasets, drawn from the seed."""
    n = int(cell.traffic["n"])
    return [cell.job.make_data(cell.config, n, seed_words(seed), slot)
            for slot in range(int(cell.traffic["datasets"]))]


def secure_config():
    """The service's session key and nonce: one for every run. The service
    compiles its key into the shuffle program (its runner cache is keyed by
    it), so a key drawn from the seed would compile anew in every run."""
    from repro.core.shuffle import SecureShuffleConfig
    from repro.crypto import chacha

    return SecureShuffleConfig(key_words=chacha.key_to_words(SESSION_KEY),
                               nonce_words=chacha.nonce_to_words((1).to_bytes(12, "little")))


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform {devices[0].platform!r}); "
                            "this benchmark measures nothing off the chip")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices


def _memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _cache_entries(path) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


class _Window:
    """The closed loop of client threads."""

    def __init__(self, svc, cell: Cell, datasets: list, seed: int):
        self.svc, self.cell, self.datasets = svc, cell, datasets
        self.offset = seed_words(seed) % len(datasets)
        self.lock = threading.Lock()
        self.done: list = []  # (record, handle, result)
        self.errors: list = []  # (client, slot, exception)
        self.attempted = 0

    def client(self, c: int, until: float | None, quota: int | None):
        from jax.profiler import TraceAnnotation

        job = self.cell.job
        for j in range(1 << 30):
            if quota is not None and j >= quota:
                return
            if until is not None and time.perf_counter() >= until:
                return
            slot = (c + j + self.offset) % len(self.datasets)
            data = self.datasets[slot]
            with self.lock:
                self.attempted += 1
            t0 = time.perf_counter()
            try:
                with TraceAnnotation("bench.submit"):
                    h = job.submit(self.svc, data, self.cell.config)
                with TraceAnnotation("bench.wait"):
                    res = h.result(timeout=DRAIN_TIMEOUT_S)
            except Exception as e:  # a failed job is counted, not raised
                with self.lock:
                    self.errors.append((c, slot, e))
                return
            t1 = time.perf_counter()
            rec = stats.JobRecord(c, slot, t0, t1, data["input_bytes"])
            with self.lock:
                self.done.append((rec, h, res))

    def run(self, *, seconds: float | None, quota_total: int | None) -> tuple[float, float]:
        clients = int(self.cell.traffic["clients"])
        t_start = time.perf_counter()
        until = t_start + seconds if seconds is not None else None
        quotas = [None] * clients
        if quota_total is not None:
            quotas = [quota_total // clients + (c < quota_total % clients) for c in range(clients)]
        threads = [threading.Thread(target=self.client, args=(c, until, quotas[c]),
                                    name=f"bench-client-{c}", daemon=True)
                   for c in range(clients)]
        for t in threads:
            t.start()
        deadline = time.perf_counter() + (seconds or 0) + DRAIN_TIMEOUT_S
        for t in threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        hung = [t for t in threads if t.is_alive()]
        if hung:
            raise RuntimeError(f"{len(hung)} clients still waiting {DRAIN_TIMEOUT_S:.0f} s "
                               "after the window")
        return t_start, time.perf_counter()


def run(cell_name: str, seed: int, seconds: float, traced: bool, *,
        catalog: Catalog | None = None, require_tpu: bool = True,
        use_compile_cache: bool = True, trace_dir: str | None = None,
        t_process: float | None = None, log=None) -> dict:
    """One run; returns the result line's object."""
    t0 = time.perf_counter() if t_process is None else t_process
    log = log or (lambda s: print(s, flush=True))
    catalog = catalog or Catalog()
    cell = catalog.cell(cell_name, traced=traced)
    devices = _devices(cell.chips, require_tpu)[: cell.chips]
    d0 = devices[0]
    peaks = catalog.peaks(d0.device_kind) if require_tpu else None

    import jax

    if use_compile_cache:
        # a fixed directory inside the checkout, whatever the environment
        # names: the path is part of the cache's key
        cache_dir = str(CACHE_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # every program, however quick to compile, is loaded from the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.compat import make_mesh
    from repro.core.shuffle import resolve_chacha_impl
    from repro.serve.service import RunnerCache, SecureJobService

    log(f"device platform={d0.platform} kind={d0.device_kind} count={len(jax.devices())} "
        f"cell_chips={cell.chips} jax={jax.__version__} {env_summary()}")
    entries0 = _cache_entries(cache_dir) if use_compile_cache else 0
    t_init = time.perf_counter()

    traffic, config = cell.traffic, cell.config
    datasets = make_datasets(cell, seed)
    t_data = time.perf_counter()

    mesh = make_mesh((cell.chips,), ("data",), devices=devices)
    svc = SecureJobService(mesh, secure=secure_config(), cache=RunnerCache())
    impl, interpret = resolve_chacha_impl(svc.secure.impl)
    log(f"impls keystream={impl} interpret={interpret} coalesce={svc.secure.coalesce} "
        f"kmeans_map={svc.kmeans_impl} state={svc.state_mode} "
        f"max_concurrent={svc.max_concurrent} chunks={svc.min_chunk}..{svc.max_chunk}")
    clean = False
    try:
        warm = [cell.job.submit(svc, datasets[s], config)
                for s in range(int(traffic["warmup_datasets"]))]
        for h in warm:
            h.result()
        t_warm = time.perf_counter()
        setup = {"init_s": t_init - t0, "data_s": t_data - t_init, "warmup_s": t_warm - t_data,
                 "warmup_runner_misses": sum(h.runner_misses for h in warm),
                 "compile_cache_new_entries": (_cache_entries(cache_dir) - entries0
                                               if use_compile_cache else None)}
        log(f"setup {json.dumps(setup)} runner_cache {json.dumps(svc.cache.stats())}")

        window = _Window(svc, cell, datasets, seed)
        tdir = None
        if traced:
            from jax import profiler

            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            profiler.start_trace(tdir, profiler_options=opts)
        try:
            t_first, t_last = window.run(seconds=None if traced else seconds,
                                         quota_total=int(traffic["trace_jobs"]) if traced else None)
        finally:
            if traced:
                profiler.stop_trace()
        setup_s = t_first - t0
        memory_peak = _memory_peak(devices)
        clean = True
    finally:
        # a job that never returned may hold the scheduler: then do not wait
        svc.close(wait=clean)
    log(f"window jobs={len(window.done)} failed={len(window.errors)} "
        f"window_s={t_last - t_first:.3f} runner_cache {json.dumps(svc.cache.stats())}")
    for c, slot, e in window.errors:
        log(f"job failed client={c} dataset={slot}: {type(e).__name__}: {e}")
    del svc

    done = sorted(window.done, key=lambda d: d[0].submit_s)
    records = [d[0] for d in done]
    handles = [d[1] for d in done]
    results = [d[2] for d in done]

    checks = check(cell, datasets, [r.dataset for r in records], results,
                   window_compiles=sum(h.runner_misses for h in handles),
                   failed_jobs=len(window.errors))

    tr = _read_trace(tdir, [d.id for d in devices], trace_dir) if traced else None
    ctx = RunContext(cell=cell, n_shards=cell.chips, setup_s=setup_s, records=records,
                     handles=handles, results=results, trace=tr, peaks=peaks)
    metrics = {}
    for m in cell.metrics:
        v = m.reader.read(ctx) if records else None
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}

    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": correct(checks),
           "attempted": window.attempted, "failed": len(window.errors),
           "metrics": metrics, "device": device}
    if tr is not None:
        busy = tracemod.busy_s(tr)
        lo, hi = tr.window
        device["busy_s"] = sum(busy.values()) / max(1, len(busy))
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = {"device_ops": tracemod.top_ops(tr), "idle_gaps": tracemod.idle_gaps(tr)}
    out["checks"] = checks
    return out


def check(cell: Cell, datasets, slots, results, *, window_compiles: int, failed_jobs: int,
          refs: dict | None = None) -> dict:
    """Every number compared, the worst over the answers, with its limit.

    `results[i]` answers dataset `slots[i]`; the plain reference runs once
    per dataset (or is taken from `refs`, by slot)."""
    job, config = cell.job, cell.config
    limits = dict(config["limits"], **RUN_LIMITS)
    worst = {name: 0.0 for name in limits}
    worst["window_compiles"] = window_compiles
    worst["failed_jobs"] = failed_jobs
    refs = dict(refs or {})
    for slot, data in enumerate(datasets):
        mine = [res for s, res in zip(slots, results) if s == slot]
        if not mine:
            continue
        if slot not in refs:
            refs[slot] = job.reference(config, data, mine)
        for res in mine:
            for name, v in job.compare(config, refs[slot], res).items():
                worst[name] = max(worst[name], v)
    return {name: {"value": float(worst[name]), "limit": float(limits[name])} for name in limits}


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _read_trace(tdir: str, device_ids: list, keep: str | None):
    try:
        found = sorted(Path(tdir).rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {tdir}")
        if keep:
            Path(keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(found[-1], Path(keep) / found[-1].name)
        return tracemod.load(found[-1], devices=set(device_ids))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def report(out: dict, stream_out=sys.stdout, stream_err=sys.stderr) -> None:
    """Print the checks as the last lines of stderr and the result as the
    last line of stdout."""
    for name, c in out["checks"].items():
        print(f"check {name} value={c['value']!r} limit={c['limit']!r}", file=stream_err)
    stream_err.flush()
    print(json.dumps(out), file=stream_out, flush=True)


def env_summary() -> str:
    keys = ("JAX_COMPILATION_CACHE_DIR", "LIBTPU_INIT_ARGS", "JAX_PLATFORMS")
    return " ".join(f"{k}={os.environ.get(k)!r}" for k in keys)
