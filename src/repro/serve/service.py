"""Persistent-mesh secure job service: bucketed runner cache + batched admission.

The paper's deployment model is a long-lived cluster: the enclave session is
established once and MANY jobs flow through it. The repo's entry points
(`kmeans_fit`, `sample_sort`, `grep_count`) instead pay per-call setup — a
fresh runner dict, a fresh trace, a fresh XLA compile — which on the secure
path dwarfs the job itself (compiles are tens of seconds; a converged fit is
milliseconds). This module makes the session persistent:

  * `RunnerCache` — ONE process-wide compile cache, keyed by
    (workload spec identity x padded input bucket x chunk size x knob tuple:
    chacha impl / wire coalescing / state mode / halt loop / donation /
    secure key material). It replaces the ad-hoc per-call `runners` dict of
    `core/driver.py::run_until` through the driver's duck-typed
    `get_or_build(n_rounds, build)` contract (see the driver's Serving
    section), counts hits / misses / evictions, and bounds residency with
    LRU eviction ($REPRO_SERVICE_MAX_RUNNERS).

  * GEOMETRIC SIZE BUCKETS — `bucket_for` rounds every job's input length up
    a fixed geometric ladder (x`$REPRO_BUCKET_GROWTH`, default 2, aligned to
    the mesh), so a job of size 1.1xN pads to the same 2xN bucket an earlier
    job compiled and REUSES its program instead of recompiling. Padding is
    inert by construction in each workload: k-means pads zero-weight rows
    (contribute nothing), sort pads +inf (non-finite records are marked
    invalid and never shuffled), grep pads -1 tokens (match no pattern).

  * `SecureJobService` — owns one mesh + one `SecureShuffleConfig` for its
    lifetime and serves concurrent k-means / sort / grep jobs. `submit_*()`
    returns a future-backed `JobHandle` immediately; a single scheduler
    thread admits queued jobs into free concurrency slots and round-robins
    ONE adaptive chunk per job per pass through the driver's cooperative
    `run_until_chunks` generators, so a long job cannot head-of-line block
    a short one. Interleaving is bit-identical to serial execution: each
    suspended generator owns its carried state, and every job draws from a
    provably disjoint keystream range — admission assigns each job a round
    BASE from a monotone counter advanced by its `max_rounds` budget
    (`round_offset` disjointness contract, `core/driver.py`).

`benchmarks/bench_service.py` measures the payoff (cold vs warm submit
latency, hit rate, throughput vs queue depth) and `runtime/sim.py`'s
`AdmissionSim` replays arrival traces against the cost model to compare
admission policies without touching a device.
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.driver import (
    DEFAULT_HALT_LOOP,
    resolve_state_mode,
    run_until_chunks,
)
from repro.core.grep import make_grep_spec
from repro.core.kmeans import make_kmeans_iterative_spec
from repro.core.shuffle import (
    SecureShuffleConfig,
    resolve_chacha_impl,
    resolve_coalesce,
)
from repro.core.sort import make_sample_sort_spec

BUCKET_GROWTH_ENV = "REPRO_BUCKET_GROWTH"
MAX_RUNNERS_ENV = "REPRO_SERVICE_MAX_RUNNERS"


def _model_recommendation(knob: str, **ctx):
    """Calibrated-model answer for an `auto` knob, or None when no
    calibration is active (see `core/shuffle.py::_model_recommendation`)."""
    from repro.perf.model import recommendation

    return recommendation(knob, **ctx)


def resolve_bucket_growth(growth=None) -> float:
    """Resolve the geometric bucket-ladder growth factor (a float > 1).

    None/'auto' defers to $REPRO_BUCKET_GROWTH, then to the calibrated cost
    model when one is active (the factor minimizing AdmissionSim makespan
    under the calibrated TimingModel; `repro/perf/model.py`), then to the
    default 2.0 — power-of-two buckets; an explicit number always wins over
    the environment. Smaller factors waste less padding per job but compile
    more distinct buckets; the trade is measured by
    `runtime/sim.py::AdmissionSim`.
    """
    from_env = False
    if growth in (None, "auto"):
        env_val = os.environ.get(BUCKET_GROWTH_ENV)
        if env_val is None:
            rec = _model_recommendation("bucket_growth")
            if rec is None:
                return 2.0
            growth = rec
        else:
            growth, from_env = env_val.strip(), True
    try:
        val = float(growth)
    except (TypeError, ValueError):
        val = float("nan")
    if not val > 1.0:
        if from_env:
            raise ValueError(
                f"invalid ${BUCKET_GROWTH_ENV}={growth!r} in the environment: "
                f"bucket growth must be a number > 1 "
                f"(unset ${BUCKET_GROWTH_ENV} to use the default 2.0)")
        raise ValueError(
            f"bucket growth must be a number > 1 or 'auto', got {growth!r}")
    return val


def resolve_max_resident(limit="auto") -> int | None:
    """Resolve the runner-cache residency cap (int >= 1, or None = unbounded).

    'auto' defers to $REPRO_SERVICE_MAX_RUNNERS, then to the calibrated
    cost model when one is active (which answers 'unbounded' — evictions
    only ever add recompiles; `repro/perf/model.py`), then to the default
    unbounded (0 or 'none' mean unbounded explicitly); an explicit int/None
    always wins over the environment. The cap bounds how many compiled
    runner programs stay resident — the LRU loser is evicted (and its
    compiles with it).
    """
    from_env = False
    if limit == "auto":
        env_val = os.environ.get(MAX_RUNNERS_ENV)
        if env_val is None:
            rec = _model_recommendation("max_resident")
            if rec is None or rec == "unbounded":
                return None
            limit = rec
        else:
            limit, from_env = env_val.strip().lower(), True
    if limit in ("none", "unbounded", "0"):
        return None
    if limit is None:
        return None
    try:
        val = int(limit)
    except (TypeError, ValueError):
        val = 0
    if val < 1:
        if from_env:
            raise ValueError(
                f"invalid ${MAX_RUNNERS_ENV}={limit!r} in the environment: "
                f"the resident-runner cap must be an integer >= 1, or "
                f"0/'none' for unbounded "
                f"(unset ${MAX_RUNNERS_ENV} to use the default unbounded)")
        raise ValueError(
            f"max_resident must be an integer >= 1, None, or 'auto', "
            f"got {limit!r}")
    return val


def bucket_for(n: int, *, multiple: int = 1, growth=None) -> int:
    """Round `n` up to the geometric bucket ladder.

    The ladder starts at `multiple` (the mesh-alignment unit — every bucket
    must divide evenly over the shards) and each rung is the previous one
    x`growth`, rounded up to the next `multiple`. The rungs depend only on
    (multiple, growth), never on `n`, so every job size in (rung_{i-1},
    rung_i] lands on the SAME rung and shares its compiled programs.
    """
    growth = resolve_bucket_growth(growth)
    if n < 1:
        raise ValueError(f"bucket_for needs n >= 1, got {n}")
    if multiple < 1:
        raise ValueError(f"bucket_for needs multiple >= 1, got {multiple}")
    b = multiple
    while b < n:
        # strictly increasing even when growth barely clears the alignment
        b = max(int(math.ceil(b * growth / multiple)) * multiple, b + multiple)
    return b


def _mesh_token(mesh: Mesh):
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in np.asarray(mesh.devices).flat))


def _secure_token(secure: SecureShuffleConfig | None,
                  chacha_impl, coalesce) -> tuple:
    """Hashable identity of the secure wire a runner was traced against.

    Key/nonce material is baked into the traced program's closure (the
    driver's runner-cache contract), so it MUST key the cache: two sessions
    with different keys can never share a compiled runner. Impl/coalesce are
    resolved here so 'auto' (environment-dependent) never aliases a concrete
    choice.
    """
    if secure is None:
        return ("plain", resolve_coalesce(coalesce if coalesce is not None
                                          else "auto"))
    secure = secure.with_impl(chacha_impl).with_coalesce(coalesce)
    impl, interpret = resolve_chacha_impl(secure.impl)
    return (
        np.asarray(secure.key_words, np.uint32).tobytes(),
        np.asarray(secure.nonce_words, np.uint32).tobytes(),
        int(secure.counter0),
        impl, bool(interpret),
        resolve_coalesce(secure.coalesce),
    )


class _CacheView:
    """`run_until(runners=...)` adapter bound to one fully-resolved key base.

    Exposes the driver's duck-typed `get_or_build(n_rounds, build)` —
    `build` (closed over the caller's spec/mesh/secure) is only invoked on a
    miss; the key base already pins everything the closure bakes in.
    Iteration yields the resident chunk sizes for this base, mirroring the
    legacy plain-dict cache (`sorted(view)` works the same way).
    """

    def __init__(self, cache: "RunnerCache", key_base: tuple):
        self.cache = cache
        self.key_base = key_base

    def get_or_build(self, n_rounds: int, build):
        return self.cache.get_or_build(self.key_base + (int(n_rounds),), build)

    def chunk_sizes(self):
        return [k[-1] for k in self.cache.keys() if k[:-1] == self.key_base]

    def __iter__(self):
        return iter(self.chunk_sizes())

    def __len__(self):
        return len(self.chunk_sizes())

    def __contains__(self, n_rounds):
        return self.key_base + (int(n_rounds),) in self.cache.keys()


class RunnerCache:
    """Process-wide keyed LRU cache of compiled `make_iterative_runner`s.

    Keys are (spec identity x mesh x secure material x knobs x chunk size)
    tuples assembled by `view(...)`; values are the driver's runner
    callables (each owning one jitted program). `max_resident` bounds
    residency with least-recently-used eviction; hits / misses / evictions
    are counted, and `compile_cache_size()` sums the resident runners' XLA
    compile-cache entries — the "zero new compiles on a warm resubmit"
    acceptance proof reads this before and after.
    """

    def __init__(self, max_resident="auto"):
        self.max_resident = resolve_max_resident(max_resident)
        self._runners: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def view(self, *, spec_id, mesh: Mesh, axis_name: str,
             secure: SecureShuffleConfig | None = None,
             chacha_impl: str | None = None, loop_impl: str | None = None,
             coalesce=None, donate_state: bool = True) -> _CacheView:
        """Bind a key base; returns the `get_or_build` view `run_until` takes.

        `spec_id` is the caller-chosen workload identity (workload name,
        static shape/knob facts — e.g. ("kmeans", k, d, impl, bucket)); the
        mesh, secure material, and impl knobs are folded in here so callers
        cannot accidentally share a runner across sessions or layouts. The
        view only KEYS on these — building still happens through the
        `build` closure the driver passes to `get_or_build`, which must
        have been constructed from the same arguments (the driver's
        runner-cache contract; `make_kmeans_runner(cache=...)` and
        `SecureJobService` both guarantee this by construction).
        """
        key_base = (
            spec_id,
            _mesh_token(mesh),
            axis_name,
            _secure_token(secure, chacha_impl, coalesce),
            loop_impl or DEFAULT_HALT_LOOP,
            bool(donate_state),
        )
        return _CacheView(self, key_base)

    def get_or_build(self, key, build):
        with self._lock:
            runner = self._runners.get(key)
            if runner is not None:
                self.hits += 1
                self._runners.move_to_end(key)
                return runner
            self.misses += 1
            runner = self._runners[key] = build()
            if self.max_resident is not None:
                while len(self._runners) > self.max_resident:
                    self._runners.popitem(last=False)
                    self.evictions += 1
            return runner

    def keys(self):
        with self._lock:
            return list(self._runners.keys())

    def __len__(self):
        with self._lock:
            return len(self._runners)

    def compile_cache_size(self) -> int:
        """Total XLA compile-cache entries across resident runners."""
        with self._lock:
            runners = list(self._runners.values())
        total = 0
        for runner in runners:
            cache_size = getattr(getattr(runner, "jitted", None),
                                 "_cache_size", None)
            if cache_size is not None:
                total += int(cache_size())
        return total

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "resident": len(self._runners),
                "max_resident": self.max_resident,
                "compile_cache_size": self.compile_cache_size(),
            }

    def clear(self):
        with self._lock:
            self._runners.clear()


_default_cache: RunnerCache | None = None
_default_cache_lock = threading.Lock()


def default_runner_cache() -> RunnerCache:
    """The lazily created process-wide cache (one per process, env-config'd)."""
    global _default_cache
    with _default_cache_lock:
        if _default_cache is None:
            _default_cache = RunnerCache()
        return _default_cache


@dataclass
class JobHandle:
    """Future-backed handle for a submitted job.

    `result(timeout)` blocks for the job's finalized output (a plain dict of
    numpy arrays; see the `submit_*` docstrings). Timing fields are
    `time.perf_counter()` stamps, `submitted_at` taken on entry to
    `submit_*`: `latency_s` spans submit -> finish (what a client observes,
    the submit's own host work included), `queue_s` the wait until the
    scheduler starts the job. `runner_misses` counts the runner-cache misses
    charged to THIS job — 0 means the job ran entirely on cached programs (a
    warm job). `runners` holds the runners its chunks dispatched, whose
    `op_layers()` maps the compiled program to layers. `input_devices` maps
    each input leaf to the ids of the devices holding its shards, once
    placed.
    """

    job_id: int
    kind: str
    n: int
    bucket: int
    round_base: int
    max_rounds: int
    priority: int = 0
    future: Future = field(default_factory=Future, repr=False)
    submitted_at: float = 0.0
    started_at: float | None = None
    finished_at: float | None = None
    runner_misses: int = 0
    chunks: int = 0
    runners: list = field(default_factory=list, repr=False)
    input_devices: dict = field(default_factory=dict)

    def result(self, timeout: float | None = None):
        return self.future.result(timeout)

    def done(self) -> bool:
        return self.future.done()

    @property
    def warm(self) -> bool:
        return self.runner_misses == 0

    @property
    def latency_s(self) -> float | None:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def queue_s(self) -> float | None:
        if self.started_at is None:
            return None
        return self.started_at - self.submitted_at


class _JobRunners:
    """Per-job wrapper over a `_CacheView` charging cache misses to the job.

    All dispatch happens on the service's single scheduler thread, so the
    before/after miss-counter delta is exactly this job's misses.
    """

    def __init__(self, view: _CacheView, handle: JobHandle):
        self._view = view
        self._handle = handle

    def get_or_build(self, n_rounds, build):
        before = self._view.cache.misses
        runner = self._view.get_or_build(n_rounds, build)
        self._handle.runner_misses += self._view.cache.misses - before
        if not any(r is runner for r in self._handle.runners):
            self._handle.runners.append(runner)
        return runner


class _Job:
    __slots__ = ("handle", "make_gen", "finalize", "gen")

    def __init__(self, handle, make_gen, finalize):
        self.handle = handle
        self.make_gen = make_gen
        self.finalize = finalize
        self.gen = None


class SecureJobService:
    """Serve concurrent secure MapReduce jobs over ONE persistent mesh.

    The service owns its mesh and (optional) `SecureShuffleConfig` for its
    lifetime — the deployment shape of the paper's long-lived enclave
    session. `submit_kmeans` / `submit_sort` / `submit_grep` enqueue a job
    and return a `JobHandle` immediately; a single daemon scheduler thread

      1. ADMITS pending jobs FIFO into up to `max_concurrent` active slots,
      2. round-robins ONE chunk dispatch per active job per pass (the
         driver's cooperative `run_until_chunks` generators — each
         suspended generator owns its carried state and round offset),
      3. resolves the job's future with the finalized host-side result.

    All device dispatch happens on that one thread, so jobs interleave at
    chunk granularity without locking the runtime. Every job is padded up
    to a geometric size bucket (`bucket_for`) and runs on programs from the
    shared `RunnerCache`, so a warm-bucket submit compiles NOTHING; every
    job gets a disjoint global-round range (monotone `round_base` advanced
    by its `max_rounds` budget), so concurrent secure jobs can never reuse
    keystream no matter how their chunks interleave (`core/driver.py`,
    Serving). Jobs submitted in the same order produce bit-identical
    results at any concurrency, including serial.
    """

    def __init__(self, mesh: Mesh, *, axis_name: str = "data",
                 secure: SecureShuffleConfig | None = None,
                 chacha_impl: str | None = None,
                 loop_impl: str | None = None,
                 coalesce: bool | None = None,
                 kmeans_impl: str = "jnp",
                 cache: RunnerCache | None = None,
                 bucket_growth=None,
                 max_concurrent: int = 4,
                 min_chunk: int = 1,
                 max_chunk: int = 8):
        if max_concurrent < 1:
            raise ValueError(f"max_concurrent must be >= 1, got {max_concurrent}")
        if secure is not None:
            # resolve the wire once: the knob tuple the cache keys on is
            # then concrete for the service's whole lifetime
            secure = secure.with_impl(chacha_impl).with_coalesce(coalesce)
            chacha_impl = None
        self.mesh = mesh
        self.axis_name = axis_name
        self.secure = secure
        self.chacha_impl = chacha_impl
        self.loop_impl = loop_impl
        self.coalesce = coalesce
        self.kmeans_impl = kmeans_impl
        self.cache = cache if cache is not None else RunnerCache()
        self.bucket_growth = resolve_bucket_growth(bucket_growth)
        self.max_concurrent = max_concurrent
        self.min_chunk = max(1, min_chunk)
        self.max_chunk = max(self.min_chunk, max_chunk)
        self.n_shards = mesh.shape[axis_name]
        self.state_mode = resolve_state_mode("auto")

        self._cv = threading.Condition()
        # two-level admission queue: priority > 0 jobs admit ahead of the
        # FIFO normal class (FIFO within each class); already-ACTIVE jobs
        # are never preempted — priority orders admission, not dispatch
        self._pending: deque[_Job] = deque()
        self._pending_high: deque[_Job] = deque()
        self._active: list[_Job] = []
        self._next_id = 0
        self._round_base = 0
        self._jobs_completed = 0
        self._closed = False
        self._thread = threading.Thread(
            target=self._scheduler, name="secure-job-service", daemon=True)
        self._thread.start()

    # -- lifecycle ---------------------------------------------------------

    def close(self, wait: bool = True):
        """Stop admitting; drain queued + active jobs, then stop the thread."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if wait:
            self._thread.join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def stats(self) -> dict:
        with self._cv:
            return {
                "jobs_completed": self._jobs_completed,
                "jobs_active": len(self._active),
                "jobs_pending": len(self._pending) + len(self._pending_high),
                "round_base": self._round_base,
                "cache": self.cache.stats(),
            }

    # -- scheduler ---------------------------------------------------------

    def _scheduler(self):
        while True:
            with self._cv:
                while (not self._pending and not self._pending_high
                       and not self._active and not self._closed):
                    self._cv.wait()
                if (self._closed and not self._pending
                        and not self._pending_high and not self._active):
                    return
                while ((self._pending or self._pending_high)
                       and len(self._active) < self.max_concurrent):
                    queue = self._pending_high or self._pending
                    self._active.append(queue.popleft())
                batch = list(self._active)
            for job in batch:
                try:
                    if job.gen is None:
                        job.handle.started_at = time.perf_counter()
                        with obs.span(obs.PREPARE, job=job.handle.job_id):
                            job.gen = job.make_gen(job.handle)
                    next(job.gen)
                    job.handle.chunks += 1
                except StopIteration as stop:
                    self._finish(job, stop.value)
                except BaseException as exc:  # surface through the future
                    self._finish(job, None, exc)

    def _finish(self, job: _Job, res, exc=None):
        if exc is None:
            try:
                with obs.span(obs.FINALIZE, job=job.handle.job_id):
                    value = job.finalize(res)
            except BaseException as finalize_exc:
                exc = finalize_exc
        job.handle.finished_at = time.perf_counter()
        with self._cv:
            self._active.remove(job)
            self._jobs_completed += 1
            self._cv.notify_all()
        if exc is not None:
            job.handle.future.set_exception(exc)
        else:
            job.handle.future.set_result(value)

    @contextmanager
    def _submission(self):
        """Open a `submit_*` call: stamp it, take its job id and open its
        `repro.submit` span, which closes once `_submit` has enqueued it."""
        t0 = time.perf_counter()
        with self._cv:
            job_id = self._next_id
            self._next_id += 1
        with obs.span(obs.SUBMIT, job=job_id):
            yield job_id, t0

    def _submit(self, ticket, kind, n, bucket, max_rounds, make_gen, finalize,
                priority: int = 0) -> JobHandle:
        priority = int(priority)
        if priority < 0:
            raise ValueError(f"priority must be >= 0, got {priority}")
        job_id, submitted_at = ticket
        with self._cv:
            if self._closed:
                raise RuntimeError("SecureJobService is closed")
            handle = JobHandle(
                job_id=job_id, kind=kind, n=n, bucket=bucket,
                round_base=self._round_base, max_rounds=max_rounds,
                priority=priority, submitted_at=submitted_at,
            )
            # keystream disjointness across jobs: reserve this job's whole
            # round budget on the monotone per-service counter
            self._round_base += max_rounds
            queue = self._pending_high if priority > 0 else self._pending
            queue.append(_Job(handle, make_gen, finalize))
            self._cv.notify()
        return handle

    def _view(self, spec_id) -> _CacheView:
        return self.cache.view(
            spec_id=spec_id, mesh=self.mesh, axis_name=self.axis_name,
            secure=self.secure, chacha_impl=self.chacha_impl,
            loop_impl=self.loop_impl, coalesce=self.coalesce,
        )

    def _run_chunks(self, spec, inputs, init_state, handle, view, *,
                    max_rounds, min_chunk, max_chunk):
        # split the host inputs over the mesh axis in one transfer, straight
        # to the devices that own each shard (never staged on the default
        # device); the driver then finds them already in the runner's layout
        inputs = jax.device_put(inputs, NamedSharding(self.mesh, P(self.axis_name)))
        handle.input_devices = {
            name: tuple(s.device.id for s in leaf.addressable_shards)
            for name, leaf in inputs.items()}
        return run_until_chunks(
            spec, inputs, init_state, self.mesh, self.axis_name,
            secure=self.secure, max_rounds=max_rounds,
            round_offset=handle.round_base,
            min_chunk=min_chunk, max_chunk=max_chunk,
            chacha_impl=self.chacha_impl, loop_impl=self.loop_impl,
            coalesce=self.coalesce,
            runners=_JobRunners(view, handle), job_tag=handle.job_id,
        )

    # -- workloads ---------------------------------------------------------

    def submit_kmeans(self, points, k: int, *, threshold: float | None = None,
                      max_rounds: int = 64, weights=None, init_centers=None,
                      min_chunk: int | None = None,
                      max_chunk: int | None = None,
                      priority: int = 0) -> JobHandle:
        """k-means to convergence (paper §V). Result: {"centers" (k, d),
        "n_iter", "shifts" (n_iter,), "halted", "n_dispatches"}.

        The threshold (default: the paper's diag/1000 rule on THIS job's
        data) rides in carried state (`runtime_threshold=True`), so jobs
        with different data share one compiled program per bucket; rows
        padded up to the bucket carry weight 0 and contribute nothing.
        `priority > 0` admits ahead of the normal FIFO class (active jobs
        are never preempted).
        """
        with self._submission() as ticket:
            points = np.asarray(points, np.float32)
            if points.ndim != 2 or points.shape[0] < 1:
                raise ValueError(f"points must be (n, d) with n >= 1, got {points.shape}")
            n, d = points.shape
            if not 1 <= k <= n:
                raise ValueError(f"k must be in [1, n={n}], got {k}")
            if weights is None:
                weights = np.ones((n,), np.float32)
            weights = np.asarray(weights, np.float32)
            if init_centers is None:
                init_centers = points[:k]
            init_centers = np.asarray(init_centers, np.float32)
            if threshold is None:
                diag = float(np.linalg.norm(points.max(axis=0) - points.min(axis=0)))
                threshold = diag / 1000.0  # paper §V
            bucket = bucket_for(n, multiple=self.n_shards, growth=self.bucket_growth)
            spec = make_kmeans_iterative_spec(
                k, self.n_shards, impl=self.kmeans_impl, axis_name=self.axis_name,
                runtime_threshold=True)
            view = self._view(("kmeans", k, d, self.kmeans_impl, bucket))
            min_chunk = self.min_chunk if min_chunk is None else min_chunk
            max_chunk = self.max_chunk if max_chunk is None else max_chunk

            def make_gen(handle):
                pts = np.zeros((bucket, d), np.float32)
                pts[:n] = points
                wts = np.zeros((bucket,), np.float32)  # padding weight 0: inert
                wts[:n] = weights
                init = {"c": init_centers, "thr": np.float32(threshold)}
                return self._run_chunks(spec, {"p": pts, "w": wts}, init, handle, view,
                                        max_rounds=max_rounds,
                                        min_chunk=min_chunk, max_chunk=max_chunk)

            def finalize(res):
                return {
                    "centers": np.asarray(res.state["c"]),
                    "n_iter": res.rounds_executed,
                    "shifts": np.asarray(res.aux["shift"]),
                    "halted": res.halted,
                    "n_dispatches": res.n_dispatches,
                }

            return self._submit(ticket, "kmeans", n, bucket, max_rounds, make_gen, finalize,
                                priority=priority)

    def submit_sort(self, values, *, balance: float = 1.5, max_rounds: int = 4,
                    lo: float | None = None, hi: float | None = None,
                    capacity: int | None = None,
                    min_chunk: int | None = None,
                    max_chunk: int | None = None,
                    priority: int = 0) -> JobHandle:
        """Sampling sort with splitter refinement. Result: {"sorted" (<= n,),
        "counts" (R,), "rounds", "halted", "dropped" (rounds,)}.

        The record total rides in carried state (`dynamic_total=True`) so
        the lossless+balanced halt reads the REAL size at run time; padding
        up to the bucket is +inf, marked invalid by the map and never
        shuffled. Per-(source, dest) capacity defaults to the bucket's
        lossless worst case.
        """
        with self._submission() as ticket:
            values = np.asarray(values, np.float32)
            if values.ndim != 1 or values.shape[0] < 1:
                raise ValueError(f"values must be (n,) with n >= 1, got {values.shape}")
            n = values.shape[0]
            r = self.n_shards
            bucket = bucket_for(n, multiple=r, growth=self.bucket_growth)
            if capacity is None:
                rec = _model_recommendation("sort_capacity", bucket=bucket, n_shards=r)
                capacity = bucket // r if rec is None else int(rec)
            if lo is None:
                lo = float(values.min())
            if hi is None:
                hi = float(values.max())
            span = max(hi - lo, 1e-6)
            spec = make_sample_sort_spec(
                r, capacity, axis_name=self.axis_name, balance=balance,
                shard_state=self.state_mode, dynamic_total=True)
            view = self._view(("sort", r, capacity, float(balance),
                               self.state_mode, bucket))
            min_chunk = self.min_chunk if min_chunk is None else min_chunk
            max_chunk = self.max_chunk if max_chunk is None else max_chunk

            def make_gen(handle):
                vals = np.full((bucket,), np.inf, np.float32)  # +inf: inert pad
                vals[:n] = values
                edges = np.asarray(lo + span * np.arange(r + 1) / r, np.float32)
                edges[-1] = hi + 1e-3 * span  # open top edge keeps hi in-bucket
                init = {
                    "edges": edges,
                    "sorted": np.full((r, r * capacity), np.inf, np.float32),
                    "counts": np.zeros((r,), np.float32),
                    "total": np.float32(n),
                }
                return self._run_chunks(spec, {"v": vals}, init,
                                        handle, view, max_rounds=max_rounds,
                                        min_chunk=min_chunk, max_chunk=max_chunk)

            def finalize(res):
                rows = np.asarray(res.state["sorted"])
                counts = np.asarray(res.state["counts"])
                out = np.concatenate([rows[i, : int(counts[i])] for i in range(r)])
                return {
                    "sorted": out,
                    "counts": counts,
                    "rounds": res.rounds_executed,
                    "halted": res.halted,
                    "dropped": np.asarray(res.dropped),
                }

            return self._submit(ticket, "sort", n, bucket, max_rounds, make_gen, finalize,
                                priority=priority)

    def submit_grep(self, tokens, patterns, *, n_rounds: int = 4,
                    max_matches: int | None = None,
                    min_chunk: int | None = None,
                    max_chunk: int | None = None,
                    priority: int = 0) -> JobHandle:
        """Streaming grep over the token stream. Result: {"counts" (n_pat,),
        "per_round" (rounds, n_pat), "rounds", "halted"}.

        The stream cursor rides in carried state (`core/grep.py`), so the
        job is agnostic to the round base the service assigns it; padding
        up to the bucket is -1 tokens (match no pattern). Without
        `max_matches` the whole stream runs as one fused dispatch; with it,
        chunks grow adaptively so an early limit stops the stream.
        """
        with self._submission() as ticket:
            tokens = np.asarray(tokens, np.int32)
            if tokens.ndim != 1 or tokens.shape[0] < 1:
                raise ValueError(f"tokens must be (n,) with n >= 1, got {tokens.shape}")
            if n_rounds < 1:
                raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
            n = tokens.shape[0]
            patterns = np.asarray(patterns, np.int32)
            # bucket aligned to shards x rounds so every shard holds n_rounds
            # equal chunks of the padded stream
            multiple = self.n_shards * n_rounds
            bucket = bucket_for(n, multiple=multiple, growth=self.bucket_growth)
            chunk = bucket // multiple
            spec = make_grep_spec(patterns, chunk, axis_name=self.axis_name,
                                  max_matches=max_matches)
            view = self._view(("grep", patterns.tobytes(), chunk,
                               max_matches, bucket))
            if min_chunk is None:
                min_chunk = n_rounds if max_matches is None else 1
            if max_chunk is None:
                max_chunk = n_rounds

            def make_gen(handle):
                toks = np.full((bucket,), -1, np.int32)  # -1: matches no pattern
                toks[:n] = tokens
                init = {"hits": np.zeros((patterns.shape[0],), np.float32),
                        "cursor": np.uint32(0)}
                return self._run_chunks(spec, {"t": toks}, init,
                                        handle, view, max_rounds=n_rounds,
                                        min_chunk=min_chunk, max_chunk=max_chunk)

            def finalize(res):
                return {
                    "counts": np.asarray(res.state["hits"]),
                    "per_round": np.asarray(res.aux["round_hits"]),
                    "rounds": res.rounds_executed,
                    "halted": res.halted,
                }

            return self._submit(ticket, "grep", n, bucket, n_rounds, make_gen, finalize,
                                priority=priority)
