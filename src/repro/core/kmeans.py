"""k-means clustering via secure MapReduce — the paper's evaluation workload.

Paper (§III, Fig. 1): step (ii) — assign each observation to the nearest
center — is the *map* function; step (iii) — recompute each center as the
centroid of its assigned points — is the *reduce* function. Mappers emit
(center_id, (point, 1)); a combiner pre-aggregates per-center partial sums
locally; the shuffle routes partials to reducer hash(c) % R; reducers average
and the client redistributes the new centers (here: a psum in which each
center row is contributed by exactly one owner).

Termination (§V): iterate until the average distance between consecutive
centers drops below a threshold; the paper uses diag/1000 of the bounding box.
The threshold test IS the job's halt predicate: `kmeans_fit` bakes it into
`IterativeSpec.halt_fn`, so the convergence decision is taken ON DEVICE by
`repro.core.driver.run_until` — the fused round loop stops paying for
map/shuffle/reduce (and stops consuming keystream, in secure mode) the moment
the average center shift crosses the threshold, and the host dispatches
adaptively growing chunks so a run converging in 7 rounds never compiles a
32-round program.

Two execution paths share the identical per-round math:
  * `make_kmeans_step` — one iteration per dispatch (the historical loop;
    kept as the oracle for equivalence tests);
  * `kmeans_fit` — convergence-aware fused rounds through
    `repro.core.driver.run_until` (halt-masked `lax.scan` under shard_map).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.driver import IterativeSpec, run_until
from repro.core.engine import MapReduceSpec, identity_hash
from repro.core.shuffle import SecureShuffleConfig, bucket_pack, keyed_all_to_all
from repro.kernels.kmeans.ops import kmeans_assign


@dataclass(frozen=True)
class KMeansResult:
    centers: jax.Array
    n_iter: int
    center_shift: list  # avg centroid move per iteration
    inertia: float
    n_dispatches: int = 0  # host->device round-trips spent on iterations
    n_rounds_dispatched: int = 0  # rounds shipped to device (>= n_iter executed)


def _assign_partials(points, weights, centers, impl):
    """Map + combine: fused assign + local per-center partials ("enclave")."""
    k = centers.shape[0]
    _, sums, counts = kmeans_assign(points, centers, weights, impl=impl)
    keys = jnp.arange(k, dtype=jnp.int32)
    return keys, {"s": sums, "c": counts}


def _reduce_centers(centers, rk, rv, valid, *, axis_name, n_shards):
    """Reduce + redistribute: own-center aggregation, psum assembly, shift."""
    k = centers.shape[0]
    rs = rv["s"]
    rc = rv["c"]
    seg = jnp.where(valid, rk, 0)
    own_sums = jax.ops.segment_sum(jnp.where(valid[:, None], rs, 0.0), seg, num_segments=k)
    own_counts = jax.ops.segment_sum(jnp.where(valid, rc, 0.0), seg, num_segments=k)

    # each center row owned by exactly one reducer; psum assembles the full
    # table on every shard (client gather) — restores state replication.
    my = lax.axis_index(axis_name)
    mine = (jnp.arange(k) % n_shards) == my
    total_sums = lax.psum(jnp.where(mine[:, None], own_sums, 0.0), axis_name)
    total_counts = lax.psum(jnp.where(mine, own_counts, 0.0), axis_name)

    new_centers = total_sums / jnp.maximum(total_counts, 1e-9)[:, None]
    # keep empty clusters where they were (standard practice)
    new_centers = jnp.where((total_counts > 0)[:, None], new_centers, centers)
    shift = jnp.mean(jnp.linalg.norm(new_centers - centers, axis=1))
    return new_centers, shift


def _kmeans_shard_step(points, weights, centers, *, axis_name, n_shards, secure, impl):
    """One k-means iteration on one shard (runs inside shard_map)."""
    k = centers.shape[0]
    keys, partials = _assign_partials(points, weights, centers, impl)
    bucket = keys % n_shards
    capacity = -(-k // n_shards)
    bk, bv, _ = bucket_pack(keys, bucket, partials, n_shards, capacity)
    recv = keyed_all_to_all({"k": bk, "v": bv}, axis_name, secure)

    rk = recv["k"].reshape(-1)
    rv = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), recv["v"])
    return _reduce_centers(centers, rk, rv, rk >= 0, axis_name=axis_name, n_shards=n_shards)


def make_kmeans_step(mesh: Mesh, axis_name: str = "data", secure: SecureShuffleConfig | None = None,
                     impl: str = "jnp", chacha_impl: str | None = None,
                     coalesce: bool | None = None):
    """Build the jitted one-iteration function over `mesh` (oracle path).

    `impl` selects the assignment kernel; `chacha_impl` the secure-shuffle
    keystream backend and `coalesce` its wire layout (see `core/shuffle.py`).
    """
    if secure is not None:
        secure = secure.with_impl(chacha_impl).with_coalesce(coalesce)
    n_shards = mesh.shape[axis_name]
    body = partial(
        _kmeans_shard_step,
        axis_name=axis_name,
        n_shards=n_shards,
        secure=secure,
        impl=impl,
    )
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_kmeans_iterative_spec(k: int, n_shards: int, *, impl: str = "jnp",
                               n_rounds: int = 1, axis_name: str = "data",
                               threshold: float | None = None,
                               runtime_threshold: bool = False) -> IterativeSpec:
    """The same per-round math as `make_kmeans_step`, as a driver spec.

    Carried state = the (k, d) center table (replicated); aux per round =
    {"centers", "shift"} so convergence mid-chunk is recoverable on the host.

    `threshold` (paper §V convergence rule) installs the on-device halt
    predicate `shift < threshold` as the spec's `halt_fn` — the shift is a
    function of the replicated center table, so every shard agrees on the
    decision by construction (the driver's replicated-halt contract). The
    comparison is done in float32, matching the dtype of the on-device
    shift, so host-side reference loops must compare in float32 too to stop
    at the identical round.

    `runtime_threshold=True` is the SERVING variant: the paper's threshold
    is data-dependent (diag/1000 of the job's bounding box), so baking it
    into the traced program would force a recompile per job. Instead the
    carried state becomes {"c": centers, "thr": () f32} and the halt
    predicate reads `state["thr"]` at run time — one compiled runner then
    serves any threshold. `threshold` is ignored in this mode; weight-0
    points contribute nothing to sums/counts, so inputs padded with
    zero-weight rows up to a serving bucket fit the same program.
    """
    if runtime_threshold:
        def map_fn(state, inputs, r):
            return _assign_partials(inputs["p"], inputs["w"], state["c"], impl)

        def reduce_fn(state, rk, rv, valid, r):
            new_centers, shift = _reduce_centers(
                state["c"], rk, rv, valid, axis_name=axis_name, n_shards=n_shards
            )
            new_state = {"c": new_centers, "thr": state["thr"]}
            return new_state, {"centers": new_centers, "shift": shift}

        def halt_fn(state, aux, r):
            return aux["shift"] < state["thr"]

        return IterativeSpec(
            map_fn=map_fn,
            reduce_fn=reduce_fn,
            hash_fn=identity_hash,
            capacity=-(-k // n_shards),
            n_rounds=n_rounds,
            halt_fn=halt_fn,
            state_specs=P(),
        )

    def map_fn(centers, inputs, r):
        return _assign_partials(inputs["p"], inputs["w"], centers, impl)

    def reduce_fn(centers, rk, rv, valid, r):
        new_centers, shift = _reduce_centers(
            centers, rk, rv, valid, axis_name=axis_name, n_shards=n_shards
        )
        return new_centers, {"centers": new_centers, "shift": shift}

    halt_fn = None
    if threshold is not None:
        thr = jnp.float32(threshold)

        def halt_fn(centers, aux, r):
            return aux["shift"] < thr

    return IterativeSpec(
        map_fn=map_fn,
        reduce_fn=reduce_fn,
        hash_fn=identity_hash,
        capacity=-(-k // n_shards),
        n_rounds=n_rounds,
        halt_fn=halt_fn,
        # the center table is small and every shard's map_fn reads all of
        # it each round — replicated (P()) is the right layout, declared
        # explicitly now that the driver supports per-leaf sharding
        state_specs=P(),
    )


@dataclass
class KMeansRunnerCache:
    """Prebuilt `run_until` runner cache for `kmeans_fit` (shareable jit cache).

    Holds the iterative spec (halt threshold baked in) and the per-chunk-size
    jitted runners that `run_until` populates lazily; pass as `kmeans_fit`'s
    `runner=` to amortize the (expensive, secure-mode) XLA compiles across
    many fits with the same k/mesh/secure/impl/threshold.

    `runners` is a plain per-cache dict by default; `make_kmeans_runner`'s
    `cache=` hook replaces it with a keyed view of the process-wide serving
    `repro.serve.service.RunnerCache` (same duck-typed contract `run_until`
    accepts), so ad-hoc fits and the job service share one compile cache.
    """

    spec: IterativeSpec
    mesh: Mesh
    axis_name: str
    secure: SecureShuffleConfig | None
    chacha_impl: str | None
    loop_impl: str | None
    max_chunk: int
    threshold: float | None
    min_chunk: int = 1
    coalesce: bool | None = None
    runners: object = field(default_factory=dict)


def make_kmeans_runner(mesh: Mesh, k: int, *, axis_name: str = "data",
                       secure: SecureShuffleConfig | None = None, impl: str = "jnp",
                       rounds_per_dispatch: int = 8, threshold: float | None = None,
                       min_chunk: int = 1, chacha_impl: str | None = None,
                       loop_impl: str | None = None,
                       coalesce: bool | None = None,
                       cache=None) -> KMeansRunnerCache:
    """Prebuild the convergence-aware runner cache for `kmeans_fit`.

    `threshold` bakes the paper's §V stopping rule into the on-device
    halt_fn (None leaves halting to `kmeans_fit`'s resolved threshold at
    fit time — but then the cache cannot be reused, so pass it when known).
    `rounds_per_dispatch` caps the adaptive chunk growth (`run_until`
    max_chunk); `min_chunk` sets the first chunk's size (larger values
    amortize more rounds per dispatch up front at the cost of more masked
    no-op rounds when convergence is very fast). `chacha_impl` selects the
    secure keystream backend and `coalesce` the secure wire layout (see
    `core/shuffle.py`); `loop_impl` the halt-loop shape (`core/driver.py`).

    `cache` (a `repro.serve.service.RunnerCache`) backs the per-chunk-size
    runners with the process-wide keyed serving cache instead of a private
    dict: fits keyed by (k, mesh, secure material, impl knobs, threshold)
    then share compiled programs with the job service and each other, and
    the cache's hit/miss/evict counters see them.
    """
    spec = make_kmeans_iterative_spec(k, mesh.shape[axis_name], impl=impl,
                                      axis_name=axis_name, threshold=threshold)
    runner_cache = KMeansRunnerCache(
        spec=spec, mesh=mesh, axis_name=axis_name, secure=secure,
        chacha_impl=chacha_impl, loop_impl=loop_impl, coalesce=coalesce,
        max_chunk=max(1, rounds_per_dispatch), threshold=threshold,
        min_chunk=max(1, min_chunk),
    )
    if cache is not None:
        runner_cache.runners = cache.view(
            spec_id=("kmeans-fit", k, mesh.shape[axis_name], axis_name, impl,
                     float(threshold) if threshold is not None else None),
            mesh=mesh, axis_name=axis_name, secure=secure,
            chacha_impl=chacha_impl, loop_impl=loop_impl, coalesce=coalesce,
        )
    return runner_cache


def kmeans_fit(
    points,
    k: int,
    mesh: Mesh,
    *,
    axis_name: str = "data",
    secure: SecureShuffleConfig | None = None,
    impl: str = "jnp",
    threshold: float | None = None,
    max_iter: int = 200,
    init_centers=None,
    init: str = "first",
    weights=None,
    rounds_per_dispatch: int = 8,
    min_chunk: int = 1,
    runner: KMeansRunnerCache | None = None,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
) -> KMeansResult:
    """Iterate to convergence. threshold=None -> paper's diag/1000 rule.

    init: "first" (paper-style arbitrary start) or "farthest" (greedy
    farthest-point, k-means++-like, robust to clumped starts).

    Convergence is decided ON DEVICE: the threshold rule is the job's
    `halt_fn`, and `repro.core.driver.run_until` runs the fused round loop
    with adaptive dispatch chunking (chunks grow 1, 2, 4, ... up to
    `rounds_per_dispatch`), early-exiting the moment the average center
    shift crosses the threshold. Post-convergence rounds are never executed
    — no map, no shuffle, no keystream — and the host pays
    `KMeansResult.n_dispatches` round-trips, ~log2 of the iteration count
    plus the steady-state chunks. The global iteration count threads into
    each chunk as the driver's round_offset, keeping every secure round's
    keystream disjoint across dispatches. `runner`: a prebuilt
    `make_kmeans_runner(...)` cache to reuse its jit cache across fits
    (must match k/mesh/secure/impl/threshold; its baked-in threshold wins).
    `chacha_impl` selects the secure keystream backend and `coalesce` the
    secure wire layout (see `core/shuffle.py`); `loop_impl` the halt-loop
    shape (`core/driver.py`); all three ignored when `runner` is supplied.
    """
    points = jnp.asarray(points, jnp.float32)
    n = points.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if init_centers is None:
        init_centers = points[:k] if init == "first" else _farthest_point_init(points, k)
    centers = jnp.asarray(init_centers, jnp.float32)

    if runner is not None and runner.threshold is not None:
        threshold = runner.threshold
    elif threshold is None:
        lo = jnp.min(points, axis=0)
        hi = jnp.max(points, axis=0)
        threshold = float(jnp.linalg.norm(hi - lo)) / 1000.0  # paper §V

    if runner is None:
        runner = make_kmeans_runner(
            mesh, k, axis_name=axis_name, secure=secure, impl=impl,
            rounds_per_dispatch=max(1, min(rounds_per_dispatch, max_iter)),
            threshold=threshold, min_chunk=min_chunk,
            chacha_impl=chacha_impl, loop_impl=loop_impl, coalesce=coalesce,
        )
    elif runner.threshold is None:
        raise ValueError(
            "kmeans_fit runner cache was built without a threshold: pass "
            "threshold= to make_kmeans_runner so the on-device halt_fn is "
            "baked into its cached programs")
    inputs = {"p": points, "w": jnp.asarray(weights, jnp.float32)}

    res = run_until(
        runner.spec, inputs, centers, runner.mesh, runner.axis_name,
        secure=runner.secure, max_rounds=max_iter, max_chunk=runner.max_chunk,
        min_chunk=runner.min_chunk, chacha_impl=runner.chacha_impl,
        loop_impl=runner.loop_impl, coalesce=runner.coalesce,
        runners=runner.runners,
    )
    centers = jnp.asarray(res.state)
    shifts = [float(s) for s in np.asarray(res.aux["shift"])]

    d2 = (
        jnp.sum(points * points, axis=1, keepdims=True)
        + jnp.sum(centers * centers, axis=1)[None, :]
        - 2.0 * points @ centers.T
    )
    inertia = float(jnp.sum(jnp.min(d2, axis=1)))
    return KMeansResult(centers=centers, n_iter=res.rounds_executed, center_shift=shifts,
                        inertia=inertia, n_dispatches=res.n_dispatches,
                        n_rounds_dispatched=res.rounds_dispatched)


def _farthest_point_init(points, k: int):
    """Greedy farthest-point seeding (deterministic k-means++ variant)."""
    centers = [points[0]]
    d2 = jnp.sum((points - centers[0]) ** 2, axis=1)
    for _ in range(1, k):
        nxt = points[jnp.argmax(d2)]
        centers.append(nxt)
        d2 = jnp.minimum(d2, jnp.sum((points - nxt) ** 2, axis=1))
    return jnp.stack(centers)


def kmeans_step_ref(points, centers, weights=None):
    """Single-host oracle for one iteration (tests)."""
    assign, sums, counts = kmeans_assign(points, centers, weights, impl="jnp")
    new = sums / jnp.maximum(counts, 1e-9)[:, None]
    new = jnp.where((counts > 0)[:, None], new, centers)
    return new, jnp.mean(jnp.linalg.norm(new - centers, axis=1))


def generate_points(n: int, k: int, d: int = 2, seed: int = 0, spread: float = 0.05):
    """Paper §V: n random observations around k ground-truth centers in [0,1]^d."""
    rng = np.random.default_rng(seed)
    true_centers = rng.uniform(0.1, 0.9, size=(k, d))
    idx = rng.integers(0, k, size=n)
    pts = true_centers[idx] + rng.normal(scale=spread, size=(n, d))
    return pts.astype(np.float32), true_centers.astype(np.float32)
