"""Iterative secure MapReduce driver: N rounds inside ONE jitted dispatch.

Why
---
The paper's headline workload — k-means — is an *iterative* MapReduce job,
yet `repro.core.engine.run_mapreduce` executes exactly one
map→shuffle→reduce round per dispatch, so every iteration pays a host
round-trip, fresh argument transfers, and (in secure mode) re-derived
keystream setup. SGX-MR (arXiv:2009.03518) makes the same observation for
enclaves: regulating the whole dataflow inside the trusted boundary, not
per-round hops through untrusted orchestration, is what keeps overhead low.
This driver runs the full round loop as a single `lax.scan` under
`shard_map`, so a converged k-means run costs O(n_rounds / rounds_per_dispatch)
host round-trips instead of O(n_rounds).

Round structure
---------------
Each round r of `run_iterative_mapreduce` executes, per shard:

    mapped_k, mapped_v = spec.map_fn(state, inputs, r)      # "mapper enclave"
    [mapped_k, mapped_v = spec.combine_fn(mapped_k, mapped_v)]
    bucket  = spec.hash_fn(mapped_k) % R
    send    = bucket_pack(...)                              # fixed (R, C, ...)
    recv    = keyed_all_to_all(send, axis, secure, round_index=r)
    state, aux = spec.reduce_fn(state, keys, values, valid, r)   # "reducer"

and the scan threads `state` (e.g. k-means centroids) into the next round.
Per-round aux (stacked over rounds) and per-round overflow counts
(`n_dropped`, psum'd over shards) come back to the host so convergence can
be judged — and a mid-chunk convergence point recovered from aux — without
re-entering the device loop.

Termination
-----------
Fixed `n_rounds` is the wrong contract for convergence-driven jobs: after
the centroids stop moving, every remaining round in the chunk still pays the
full map → bucket_pack → encrypt → all_to_all → decrypt → reduce pipeline.
`IterativeSpec.halt_fn(state, aux, round_index) -> bool` moves the
termination decision on-device, and `run_until` stops paying for
post-convergence rounds at two levels:

  * ON-DEVICE the round loop is halt-aware. `halt_fn` is evaluated right
    after each round's reduce, on the freshly reduced (replicated) state and
    that round's aux; once it returns True the remaining rounds of the chunk
    become no-ops. Two interchangeable loop shapes implement this (select
    with `loop_impl`, default `DEFAULT_HALT_LOOP` = 'while'):
      - 'while'      — a `lax.while_loop` whose predicate is
        `~halted & (i < n_rounds)`, writing aux into preallocated buffers;
      - 'masked_scan' — the fixed-length `lax.scan` is kept, but a
        `lax.cond` gates the whole round body into a cheap passthrough
        (state unchanged, zero aux, no shuffle) once halted.
    Both return `(state, aux, dropped, rounds_executed, halted)` and are
    bit-identical; `benchmarks/bench_iteration_time.py` measures both (the
    while loop compiles ~2x faster and skips the masked tail entirely,
    hence the default; see the note at `DEFAULT_HALT_LOOP`).

    REPLICATED-HALT CONTRACT: `halt_fn` must be a pure function of
    replicated values (the carried state — which `reduce_fn` must replicate
    before returning — the aux derived from it, and the round index). All
    shards then compute the same predicate by construction, so the
    collectives inside `lax.cond` / `lax.while_loop` branch uniformly
    across the mesh. A halt decision derived from shard-local data is a
    deadlock (shards disagree about whether the all_to_all happens).

  * KEYSTREAM ACCOUNTING FOR HALTED ROUNDS: a halted round consumes NO
    keystream — the passthrough branch performs no encryption and no
    collective (`record_wire_bytes` shows zero bytes for it). The global
    round index keeps advancing per *executed* round only: `run_until`
    feeds each chunk's returned `rounds_executed` into the next chunk's
    `round_offset`, so executed rounds worldwide occupy the disjoint,
    gapless counter range [round_offset, round_offset + total_executed).
    Round indices skipped by a halted chunk tail were never used to derive
    keystream, so re-issuing them to the next chunk cannot reuse a pad.

  * ON THE HOST `run_until` dispatches adaptively sized chunks: starting at
    `min_chunk` rounds and growing geometrically (×`growth`, capped at
    `max_chunk`), so a job converging in 7 rounds never dispatches — or
    compiles — a 32-round program, while long jobs still amortize host
    round-trips at the full chunk size.

Carried-state contract (two tiers: replicated | sharded)
--------------------------------------------------------
Each leaf of `state` lives in one of two layouts, chosen PER LEAF by
`IterativeSpec.state_specs` — a pytree of `jax.sharding.PartitionSpec`s
matching the state's structure (None, the default, means `P()` everywhere
and preserves the historical all-replicated contract bit-for-bit):

  * REPLICATED leaf — `P()`: every shard holds the same value on entry,
    and `reduce_fn` must restore replication before returning (end in a
    collective — psum / all_gather — exactly like the paper's "client
    redistributes the new centers" step). A reduce_fn that returns
    shard-varying data in a replicated leaf is a bug the shuffle cannot
    fix.
  * SHARDED leaf — `P(axis)`: the leaf stays partitioned over the mesh
    axis ACROSS rounds, resident where it was produced. Inside the round
    body `map_fn`/`reduce_fn` see the LOCAL shard (leading dim divided by
    the axis size) and `reduce_fn` returns the updated LOCAL shard — no
    re-replicating gather at the end of the round. This is what removes
    the per-round all_gather for large per-reducer state (sort output,
    join tables): per-device state bytes shrink by ~the axis size and the
    round loses a collective, with zero new collectives introduced
    (proven by jaxpr inspection in `tests/test_sharded_state.py`).

  RESHARDING RULE: the driver NEVER reshards carried state between rounds
  or between chunks. The spec declared for a leaf is simultaneously (a) the
  layout of the value `reduce_fn` must return every round, (b) the layout
  the next round's `map_fn`/`reduce_fn` receive, and (c) the layout of the
  final state a runner returns — a global jax.Array; `np.asarray` (or any
  host read) gathers it AFTER the loop, which is the one-time cost sharded
  mode defers from every round to the end of the job.

  HALT-FN RESTRICTION: `halt_fn` stays a pure function of REPLICATED
  values only — replicated state leaves, the (replicated) aux, and the
  round index. The driver enforces this at trace time: sharded leaves are
  replaced by guard objects in the state `halt_fn` sees, and touching one
  raises a ValueError naming the leaf. (A halt predicate over shard-local
  data is a deadlock: shards would disagree about whether the next
  round's collectives execute.)

  DONATION is layout-agnostic: `donate_state=True` aliases sharded leaves'
  per-device buffers exactly like replicated ones — `run_until`'s chunk
  loop keeps sharded state resident on its devices with zero copies
  between chunks.

The driver shards `inputs` over the mesh axis and replicates `aux`
(out_specs `P()`); aux must therefore be replicated by `reduce_fn` just
like replicated state leaves.

Counter-space layout (extends core/shuffle.py)
----------------------------------------------
A multi-round job performs many encrypted shuffles under one session key.
The per-shuffle layout (nonce word 0 ^= source index, counter = ctr0 +
leaf_offset + dest_row·blocks_per_row) is unchanged; the driver additionally
XORs the round index into nonce word 1 via
`keyed_all_to_all(..., round_index=r)`. The keystream spaces of distinct
rounds are therefore disjoint by construction — reusing one (as the
per-round Python loop historically did, re-dispatching with an identical
nonce/counter every iteration) is a two-time pad. The round index is part
of the replicated loop state; both endpoints derive the keystream locally
and nothing about it crosses the wire.

The index is GLOBAL across dispatches: a convergence loop that calls the
same runner in chunks passes `round_offset` = rounds already executed, so
chunk 2 continues at round n_rounds, not back at round 0 (which would
reuse chunk 1's keystreams). `run_until` does exactly this with each
chunk's `rounds_executed`; `kmeans_fit` and the other convergence loops
inherit the contract by running on it.

Workloads on the driver: `repro.core.kmeans` (paper §V), `repro.core.sort`
(TeraSort-style sampling sort with splitter refinement), `repro.core.grep`
(multi-round streaming grep) — all three terminate through `run_until`.

Serving (multi-tenant jobs over one persistent mesh)
----------------------------------------------------
`repro.serve.service.SecureJobService` runs MANY concurrent jobs through
this driver on one mesh and one `SecureShuffleConfig`. Two driver-level
contracts make that safe and cheap:

  * RUNNER-CACHE CONTRACT: `run_until(runners=...)` accepts either the
    historical plain dict (chunk size -> runner) or ANY object exposing
    `get_or_build(n_rounds, build_fn) -> runner` — duck-typed, so the
    service's process-wide `RunnerCache` (keyed by workload spec identity
    x padded input bucket x chunk size x knob tuple, with hit/miss/evict
    counters and geometric size buckets) plugs in without this module
    importing serve code. Whatever the container, the cached runner MUST
    have been built from the same spec (sans n_rounds), mesh, secure
    config (including key/nonce material — it is baked into the traced
    program's closure), impl/coalesce knobs, and donation mode; the
    service guarantees this by keying on all of them.

  * ROUND_OFFSET DISJOINTNESS ACROSS JOBS: all jobs served under ONE
    session key share one (key, nonce, counter) space, distinguished only
    by the round index XORed into nonce word 1. The per-job contract above
    (gapless executed-rounds range [round_offset, round_offset +
    rounds_executed)) therefore extends across jobs: the service assigns
    each admitted job a round BASE from a monotone per-service counter
    advanced by the job's max_rounds budget, so concurrent jobs draw from
    provably disjoint keystream ranges no matter how their chunk
    dispatches interleave. A workload whose map_fn consumes the global
    round index as data (streaming cursors) must carry its own cursor in
    state instead (see `core/grep.py`) to stay offset-agnostic.

`run_until_chunks` is the cooperative form of `run_until`: a generator
that yields after every chunk dispatch, so a host scheduler can
round-robin many jobs' dispatches on one thread (each suspended generator
holds its own carried state, runner cache view, and round offset). The
overflow warning is per JOB — accumulated across chunks and emitted once,
with global round indices — rather than per dispatched chunk.

Tuning (calibrated `auto` knobs)
--------------------------------
Every perf knob this driver exposes has an `auto` mode that resolves, in
order: explicit argument -> environment variable -> calibrated cost model
-> historical default. The model activates ONLY when $REPRO_CALIBRATION
names a calibration JSON (written once per backend/device-count by
`PYTHONPATH=src python -m repro.perf.calibrate --out calibration.json`);
with it unset, every `auto` resolves to its historical default bit-for-bit.

    knob            resolver                 env var               default
    chunk growth    resolve_chunk_growth     $REPRO_CHUNK_GROWTH   2
    loop impl       resolve_halt_loop        $REPRO_HALT_LOOP      'while'
    auto capacity   resolve_capacity_factor  —                     2.0
    chacha impl     shuffle.resolve_chacha_impl  $REPRO_CHACHA_IMPL    'pallas'
    coalesce        shuffle.resolve_coalesce     $REPRO_SHUFFLE_COALESCE True
    bucket growth   serve.resolve_bucket_growth  $REPRO_BUCKET_GROWTH  2.0
    residency cap   serve.resolve_max_resident   $REPRO_SERVICE_MAX_RUNNERS unbounded

`repro/perf/model.py` documents what each recommendation minimizes;
`benchmarks/bench_costmodel.py` reports predicted-vs-measured error
(BENCH_costmodel.json `pred_error`) so the calibration stays honest, and
`launch/hillclimb.py --cell K` ranks full knob vectors offline by
predicted AdmissionSim makespan.
"""

from __future__ import annotations

import os
import warnings
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import default_hash
from repro.core.shuffle import (
    SecureShuffleConfig,
    bucket_pack,
    keyed_all_to_all,
    wire_accounting,
)

HALT_LOOP_IMPLS = ("masked_scan", "while")
# Measured on CPU with the pallas-interpret keystream
# (benchmarks/bench_iteration_time.py, secure k-means, 8-round chunk
# converging at round 5): 'while' compiles ~2x faster (34s vs 67s — the
# cond-gated scan traces the round body into an extra conditional branch)
# and is ~13% faster per executed round at steady state (it exits the loop
# instead of running the masked no-op tail), so it is the default.
# 'masked_scan' is the documented loser but is kept: its traced skip branch
# is what makes the zero-bytes-for-halted-rounds claim auditable via
# `record_wire_bytes`, and its aux layout matches the non-halting scan.
DEFAULT_HALT_LOOP = "while"

STATE_SPECS_ENV = "REPRO_STATE_SPECS"
_STATE_MODES = ("replicated", "sharded")


def resolve_state_mode(mode: str = "auto") -> str:
    """Resolve a carried-state layout selector to 'replicated' | 'sharded'.

    The env-matrix hook for workloads that support both layouts (e.g.
    `core/sort.py`): 'auto'/None defers to $REPRO_STATE_SPECS (default
    'sharded' — the layout this repo ships); an explicit mode always wins
    over the environment. Like the chacha/coalesce selectors, the choice is
    read at trace time.
    """
    from_env = False
    if mode in (None, "auto"):
        env_val = os.environ.get(STATE_SPECS_ENV)
        if env_val is None:
            return "sharded"
        mode, from_env = env_val.strip().lower(), True
    if mode not in _STATE_MODES:
        if from_env:
            raise ValueError(
                f"invalid ${STATE_SPECS_ENV}={mode!r} in the environment: "
                f"carried-state mode must be one of {_STATE_MODES} "
                f"(unset ${STATE_SPECS_ENV} to use the default 'sharded')")
        raise ValueError(
            f"carried-state mode must be one of {_STATE_MODES} or 'auto', "
            f"got {mode!r}")
    return mode


CHUNK_GROWTH_ENV = "REPRO_CHUNK_GROWTH"
HALT_LOOP_ENV = "REPRO_HALT_LOOP"


def _model_recommendation(knob: str, **ctx):
    """Calibrated-model answer for an `auto` knob, or None when no
    calibration is active (see `core/shuffle.py::_model_recommendation`)."""
    from repro.perf.model import recommendation

    return recommendation(knob, **ctx)


def resolve_halt_loop(loop_impl: str | None = None) -> str:
    """Resolve the halt-aware loop shape ('while' | 'masked_scan').

    An explicit value always wins; None/'auto' defers to $REPRO_HALT_LOOP,
    then to the calibrated cost model when one is active (the cond-gated
    scan traces the round body twice, so the model prices its compile at
    ~2x; `repro/perf/model.py`), then to the measured default
    `DEFAULT_HALT_LOOP` = 'while'.
    """
    from_env = False
    if loop_impl in (None, "auto"):
        env_val = os.environ.get(HALT_LOOP_ENV)
        if env_val is None:
            rec = _model_recommendation("halt_loop")
            loop_impl = DEFAULT_HALT_LOOP if rec is None else rec
        else:
            loop_impl, from_env = env_val.strip(), True
    if loop_impl not in HALT_LOOP_IMPLS:
        if from_env:
            raise ValueError(
                f"invalid ${HALT_LOOP_ENV}={loop_impl!r} in the environment: "
                f"loop_impl must be one of {HALT_LOOP_IMPLS} "
                f"(unset ${HALT_LOOP_ENV} to use the default "
                f"{DEFAULT_HALT_LOOP!r})")
        raise ValueError(
            f"loop_impl must be one of {HALT_LOOP_IMPLS}, got {loop_impl!r}")
    return loop_impl


def resolve_chunk_growth(growth="auto", *, min_chunk: int = 1,
                         max_rounds: int = 64,
                         max_chunk: int | None = None) -> int:
    """Resolve the chunk-ladder growth factor to a concrete int >= 1.

    An explicit int always wins; 'auto'/None defers to $REPRO_CHUNK_GROWTH,
    then to the calibrated cost model when one is active (which minimizes
    distinct-ladder-size compiles + dispatch round trips for THIS
    min_chunk/max_rounds window; `repro/perf/model.py`), then to the
    historical default 2.
    """
    from_env = False
    if growth in (None, "auto"):
        env_val = os.environ.get(CHUNK_GROWTH_ENV)
        if env_val is None:
            rec = _model_recommendation(
                "chunk_growth", min_chunk=min_chunk, max_rounds=max_rounds,
                max_chunk=max_chunk)
            return 2 if rec is None else int(rec)
        growth, from_env = env_val.strip(), True
    try:
        val = int(growth)
    except (TypeError, ValueError):
        val = 0
    if val < 1:
        if from_env:
            raise ValueError(
                f"invalid ${CHUNK_GROWTH_ENV}={growth!r} in the environment: "
                f"chunk growth must be an integer >= 1 "
                f"(unset ${CHUNK_GROWTH_ENV} to use the default 2)")
        raise ValueError(
            f"growth must be an integer >= 1 or 'auto', got {growth!r}")
    return val


def resolve_capacity_factor() -> float:
    """Headroom factor for the auto bucket capacity (ceil(n/R) * factor).

    Consults the calibrated cost model when one is active; the model only
    departs from the historical 2.0 when its calibration carries a
    deployment-measured key-skew entry (overflow silently drops records, so
    no generic probe may shrink this; `repro/perf/model.py`).
    """
    rec = _model_recommendation("capacity_factor")
    return 2.0 if rec is None else float(rec)


def _resolve_state_specs(spec: "IterativeSpec", state):
    """Resolve `spec.state_specs` against a concrete state pytree.

    Returns (spec_tree, flat_is_sharded): `spec_tree` mirrors the state's
    structure with one `PartitionSpec` per leaf (usable directly as
    shard_map in/out specs); `flat_is_sharded` flags, in flat leaf order,
    the leaves that carry a mesh axis. None (the whole attribute or a
    leaf) defaults to `P()` — the replicated contract — and a single bare
    `PartitionSpec` broadcasts to every leaf (so `state_specs=P()` declares
    any state shape fully replicated). Raises ValueError — at trace/build
    time, not inside the loop — when the declared tree does not match the
    state's structure or holds a non-PartitionSpec leaf.
    """
    flat, treedef = jax.tree_util.tree_flatten(state)
    if spec.state_specs is None:
        flat_specs = [P()] * len(flat)
    elif isinstance(spec.state_specs, P):
        flat_specs = [spec.state_specs] * len(flat)
    else:
        try:
            flat_specs = treedef.flatten_up_to(spec.state_specs)
        except ValueError as e:
            raise ValueError(
                "IterativeSpec.state_specs must be a pytree matching the "
                f"carried state's structure {treedef}; got "
                f"{spec.state_specs!r}") from e
        checked = []
        for i, p in enumerate(flat_specs):
            if p is None:
                p = P()
            if not isinstance(p, P):
                raise ValueError(
                    "IterativeSpec.state_specs leaves must be "
                    "jax.sharding.PartitionSpec (or None for replicated); "
                    f"leaf {i} is {p!r}")
            checked.append(p)
        flat_specs = checked
    sharded = [any(a is not None for a in tuple(p)) for p in flat_specs]
    return jax.tree_util.tree_unflatten(treedef, flat_specs), sharded


class _ShardedHaltGuard:
    """Trace-time stand-in for a sharded state leaf inside `halt_fn`.

    The replicated-halt contract (module docstring) forbids deriving the
    halt predicate from shard-varying data; sharded leaves are therefore
    swapped for these guards in the state `halt_fn` receives, and ANY use —
    arithmetic, jnp coercion, attribute access, iteration — raises a
    ValueError naming the leaf, at trace time, instead of deadlocking the
    mesh at run time.
    """

    def __init__(self, path: str, pspec):
        object.__setattr__(self, "_path", path)
        object.__setattr__(self, "_pspec", pspec)

    def _halt_guard_raise(self, *_a, **_k):
        raise ValueError(
            f"IterativeSpec.halt_fn touched the SHARDED carried-state leaf "
            f"state{self._path} (state_specs leaf {self._pspec}): the "
            "replicated-halt contract requires halt_fn to be a pure "
            "function of replicated values only (replicated state leaves, "
            "aux, round index) — a shard-varying predicate would deadlock "
            "the mesh. Derive the halt signal from a replicated leaf or "
            "from aux, or declare this leaf P() in state_specs.")

    def __getattr__(self, name):
        self._halt_guard_raise()

    def __repr__(self):
        return f"_ShardedHaltGuard(state{self._path}: {self._pspec})"


for _name in (
    "__array__", "__bool__", "__int__", "__float__",
    "__index__", "__len__", "__iter__", "__getitem__", "__neg__", "__pos__",
    "__abs__", "__invert__", "__add__", "__radd__", "__sub__", "__rsub__",
    "__mul__", "__rmul__", "__truediv__", "__rtruediv__", "__floordiv__",
    "__rfloordiv__", "__mod__", "__rmod__", "__pow__", "__rpow__",
    "__matmul__", "__rmatmul__", "__and__", "__rand__", "__or__", "__ror__",
    "__xor__", "__rxor__", "__lshift__", "__rlshift__", "__rshift__",
    "__rrshift__", "__lt__", "__le__", "__gt__", "__ge__", "__eq__",
    "__ne__", "__format__",
):
    setattr(_ShardedHaltGuard, _name, _ShardedHaltGuard._halt_guard_raise)


def _guard_state_for_halt(state, spec_tree, flat_sharded):
    """Swap sharded leaves for `_ShardedHaltGuard`s in halt_fn's state view."""
    if not any(flat_sharded):
        return state
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    flat_specs = treedef.flatten_up_to(spec_tree)
    guarded = [
        _ShardedHaltGuard(jax.tree_util.keystr(path), pspec) if sh else leaf
        for (path, leaf), pspec, sh in zip(paths_leaves, flat_specs, flat_sharded)
    ]
    return jax.tree_util.tree_unflatten(treedef, guarded)


@dataclass(frozen=True)
class IterativeSpec:
    """A multi-round MapReduce job over fixed-shape shards.

    map_fn(state, inputs, round_index) -> (mapped_keys, mapped_values)
        Per-shard, vectorized. `inputs` is the (local slice of the) sharded
        input pytree; `round_index` is a traced u32 scalar for round-varying
        behavior (streaming slices, phase switches). Sharded state leaves
        (see `state_specs`) arrive as their LOCAL shard.
    combine_fn(keys, values) -> (keys, values)
        Optional local pre-aggregation before the shuffle.
    reduce_fn(state, keys, values, valid, round_index) -> (new_state, aux)
        Per-shard over the received pairs. Replicated state leaves must be
        restored to replication (end in psum/all_gather); sharded leaves
        must be returned as the updated LOCAL shard in the declared layout
        (module docstring: Carried-state contract). `aux` is any pytree of
        per-round REPLICATED diagnostics (stacked over rounds by the scan).
    hash_fn(keys) -> u32
        destination shard = hash_fn(k) % R.
    capacity:  per-destination slots C; 0 -> auto (ceil(n_mapped / R) * 2).
    n_rounds:  rounds fused into one dispatch.
    halt_fn(state, aux, round_index) -> bool scalar  [optional]
        Convergence predicate, evaluated after every round on that round's
        freshly reduced state/aux. MUST depend only on replicated values so
        every shard agrees (module docstring: Termination); sharded state
        leaves are guarded at trace time and raise on use. When set, the
        fused loop stops executing rounds — and consuming keystream — as
        soon as it returns True; runners then also return
        (rounds_executed, halted).
    state_specs:  pytree of `jax.sharding.PartitionSpec` matching the
        carried state's structure, choosing each leaf's cross-round layout:
        `P()` (or None) = replicated — the default everywhere when
        `state_specs` is None, preserving the historical contract
        bit-for-bit — `P(axis)` = resident-sharded over the mesh axis
        (module docstring: Carried-state contract).
    """

    map_fn: Callable[[Any, Any, Any], tuple]
    reduce_fn: Callable[[Any, Any, Any, Any, Any], tuple]
    combine_fn: Callable[[Any, Any], tuple] | None = None
    hash_fn: Callable = default_hash
    capacity: int = 0
    n_rounds: int = 1
    halt_fn: Callable[[Any, Any, Any], Any] | None = None
    state_specs: Any = None


def _round_body(state, r, *, inputs, spec: IterativeSpec, axis_name: str, n_shards: int,
                secure: SecureShuffleConfig | None, coalesce=None,
                trace_info: dict | None = None):
    mk, mv = spec.map_fn(state, inputs, r)
    if spec.combine_fn is not None:
        mk, mv = spec.combine_fn(mk, mv)
    n_mapped = mk.shape[0]
    capacity = spec.capacity or max(
        1, int(np.ceil(-(-n_mapped // n_shards) * resolve_capacity_factor())))
    if trace_info is not None:
        # shapes are static, so the resolved capacity is a trace-time fact;
        # the host reads it back to annotate overflow warnings
        trace_info["capacity"] = capacity
        trace_info["capacity_auto"] = not spec.capacity

    bucket = (spec.hash_fn(mk) % jnp.uint32(n_shards)).astype(jnp.int32)
    bk, bv, dropped = bucket_pack(mk, bucket, mv, n_shards, capacity)

    recv = keyed_all_to_all({"k": bk, "v": bv}, axis_name, secure, round_index=r,
                            coalesce=coalesce)
    flat_k = recv["k"].reshape(-1)
    flat_v = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), recv["v"])
    valid = flat_k >= 0

    new_state, aux = spec.reduce_fn(state, flat_k, flat_v, valid, r)
    return new_state, (aux, lax.psum(dropped, axis_name))


def _shard_body(inputs, state, round_offset, *, spec: IterativeSpec, axis_name: str,
                n_shards: int, secure: SecureShuffleConfig | None, coalesce=None,
                trace_info: dict | None = None):
    rounds = jnp.asarray(round_offset, jnp.uint32) + jnp.arange(spec.n_rounds, dtype=jnp.uint32)
    body = partial(_round_body, inputs=inputs, spec=spec, axis_name=axis_name,
                   n_shards=n_shards, secure=secure, coalesce=coalesce,
                   trace_info=trace_info)
    final_state, (aux, dropped) = lax.scan(body, state, rounds)
    return final_state, aux, dropped


def _halting_shard_body(inputs, state, round_offset, *, spec: IterativeSpec, axis_name: str,
                        n_shards: int, secure: SecureShuffleConfig | None, loop_impl: str,
                        coalesce=None, trace_info: dict | None = None):
    """Halt-aware round loop: stops executing (and consuming keystream) once
    `spec.halt_fn` fires. Returns (state, aux, dropped, rounds_executed, halted).
    """
    n_rounds = spec.n_rounds
    body = partial(_round_body, inputs=inputs, spec=spec, axis_name=axis_name,
                   n_shards=n_shards, secure=secure, coalesce=coalesce,
                   trace_info=trace_info)
    r0 = jnp.asarray(round_offset, jnp.uint32)
    # halt_fn's replicated-only state view: sharded leaves raise on use
    state_spec_tree, flat_sharded = _resolve_state_specs(spec, state)

    # abstract round output, for the passthrough branch / preallocated
    # buffers; suppressed so the shape-only pass is invisible to wire
    # accounting (it derives no keystream and moves no bytes)
    with wire_accounting.suppressed():
        _state_sds, (aux_sds, dropped_sds) = jax.eval_shape(body, state, r0)

    def _zeros(sds_tree):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), sds_tree)

    def _halt(new_state, aux, r):
        guarded = _guard_state_for_halt(new_state, state_spec_tree, flat_sharded)
        return jnp.reshape(jnp.asarray(spec.halt_fn(guarded, aux, r), jnp.bool_), ())

    if loop_impl == "while":
        aux0 = jax.tree.map(lambda s: jnp.zeros((n_rounds,) + s.shape, s.dtype), aux_sds)
        dropped0 = jnp.zeros((n_rounds,) + dropped_sds.shape, dropped_sds.dtype)

        def cond(carry):
            i, _state, _aux, _dropped, halted = carry
            return jnp.logical_and(~halted, i < n_rounds)

        def w_body(carry):
            i, state, aux_buf, dropped_buf, _halted = carry
            r = r0 + i.astype(jnp.uint32)
            new_state, (aux, dropped) = body(state, r)
            aux_buf = jax.tree.map(
                lambda buf, a: lax.dynamic_update_index_in_dim(buf, a, i, 0), aux_buf, aux)
            dropped_buf = lax.dynamic_update_index_in_dim(dropped_buf, dropped, i, 0)
            return (i + 1, new_state, aux_buf, dropped_buf, _halt(new_state, aux, r))

        i, final_state, aux, dropped, halted = lax.while_loop(
            cond, w_body, (jnp.int32(0), state, aux0, dropped0, jnp.bool_(False)))
        return final_state, aux, dropped, i, halted

    def step(carry, r):
        state, halted, n_exec = carry

        def live(s):
            new_state, (aux, dropped) = body(s, r)
            return new_state, aux, dropped, _halt(new_state, aux, r)

        def skip(s):
            # no shuffle, no keystream: the halted round is a pure
            # passthrough (auditable via record_wire_bytes)
            wire_accounting.note_halted_round(secure is not None)
            return (s, _zeros(aux_sds),
                    jnp.zeros(dropped_sds.shape, dropped_sds.dtype), jnp.bool_(True))

        new_state, aux, dropped, halt = lax.cond(halted, skip, live, state)
        n_exec = n_exec + jnp.where(halted, 0, 1).astype(jnp.int32)
        return (new_state, halted | halt, n_exec), (aux, dropped)

    rounds = r0 + jnp.arange(n_rounds, dtype=jnp.uint32)
    (final_state, halted, n_exec), (aux, dropped) = lax.scan(
        step, (state, jnp.bool_(False), jnp.int32(0)), rounds)
    return final_state, aux, dropped, n_exec, halted


def make_iterative_runner(
    spec: IterativeSpec,
    mesh: Mesh,
    axis_name: str = "data",
    secure: SecureShuffleConfig | None = None,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
    donate_state: bool = False,
):
    """Build the jitted fused-round function once; call it many times.

    `chacha_impl` overrides the secure config's keystream backend
    ('pallas' | 'pallas-interpret' | 'jnp'; see `core/shuffle.py`) — baked
    in at build time, since the impl choice is part of the traced program.
    `coalesce` overrides the wire layout the same way, in BOTH modes (True —
    one packed wire through ONE all_to_all per round, plus one keystream
    launch each side in secure mode — False — the per-leaf oracle; None
    keeps the secure config's own setting / the plaintext 'auto' default).
    `loop_impl` selects the halt-aware loop shape (`HALT_LOOP_IMPLS`; only
    meaningful when `spec.halt_fn` is set).

    `donate_state=True` donates the carried-state argument's buffers to the
    dispatch (`jax.jit` donate_argnums): XLA writes the chunk's final state
    into the input's storage instead of allocating a fresh replica every
    dispatch — the natural fit for `run_until`'s chunk loop, which always
    feeds a chunk's output state into the next chunk. CALLERS OWN THE
    ALIASING CONTRACT: the state passed in is consumed (its buffers are
    deleted) and must not be reused after the call.

    Returns fn(inputs, state, round_offset=0) ->
      (final_state, aux_per_round, dropped_per_round)                  and,
      when `spec.halt_fn` is set, additionally
      (..., rounds_executed, halted)
    where aux leaves and `dropped` carry a leading (n_rounds,) dim; entries
    past `rounds_executed` are zero-filled no-op rounds. The returned
    callable exposes `.trace_info`, a dict populated at first trace with the
    resolved per-destination `capacity` (and whether it was auto-derived).

    `round_offset` is the GLOBAL index of the chunk's first round. Callers
    that dispatch the same runner repeatedly (convergence loops) MUST pass
    the running total of completed rounds: the scan executes global rounds
    offset..offset+n_rounds-1, and that global index is what map_fn /
    reduce_fn receive and what keys the per-round keystream — restarting it
    at 0 every chunk would reuse round-0's keystream across chunks (a
    two-time pad). With a halt_fn, "completed" means *executed*: halted
    rounds consume no keystream, so the next chunk resumes at
    offset + rounds_executed. It is a traced scalar: varying it never
    recompiles.
    """
    if secure is not None:
        secure = secure.with_impl(chacha_impl).with_coalesce(coalesce)
    n_shards = mesh.shape[axis_name]
    trace_info: dict = {}
    if spec.halt_fn is not None:
        loop = resolve_halt_loop(loop_impl)
        body = partial(_halting_shard_body, spec=spec, axis_name=axis_name,
                       n_shards=n_shards, secure=secure, loop_impl=loop,
                       coalesce=coalesce, trace_info=trace_info)
        extra_out = (P(), P())  # rounds_executed, halted (replicated scalars)
    else:
        body = partial(_shard_body, spec=spec, axis_name=axis_name, n_shards=n_shards,
                       secure=secure, coalesce=coalesce, trace_info=trace_info)
        extra_out = ()

    def in_specs(inputs_tree):
        return jax.tree.map(lambda _: P(axis_name), inputs_tree)

    def run(inputs, state, round_offset=0):
        # per-leaf carried-state layout (module docstring): identical spec
        # tree in and out — the driver never reshards between rounds
        state_spec_tree, _ = _resolve_state_specs(spec, state)
        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(in_specs(inputs), state_spec_tree, P()),
            out_specs=(
                state_spec_tree,
                P(),
                P(),
            ) + extra_out,
            check_vma=False,
        )
        return fn(inputs, state, jnp.asarray(round_offset, jnp.uint32))

    # arg 1 is the carried state: its output has identical shapes/dtypes, so
    # donation lets XLA alias the buffers instead of re-allocating per chunk
    jitted = jax.jit(run, donate_argnums=(1,) if donate_state else ())

    def runner(inputs, state, round_offset=0):
        return jitted(inputs, state, round_offset)

    runner.trace_info = trace_info
    runner.abstract_fn = run  # un-jitted body, for make_jaxpr inspection
    runner.jitted = jitted  # exposes .lower() for donation/lowering audits
    return runner


def _warn_overflow(dropped, first_round: int, trace_info: dict | None, stacklevel: int = 3):
    """Surface per-round bucket_pack overflow with enough context to act on.

    Names every overflowing GLOBAL round index and the per-destination
    capacity that was in force (flagging when it was auto-derived), so users
    can size `IterativeSpec.capacity` without bisecting rounds.
    """
    dropped = np.asarray(dropped)
    bad = np.nonzero(dropped > 0)[0]
    if bad.size == 0:
        return
    trace_info = trace_info or {}
    cap = trace_info.get("capacity")
    cap_s = "capacity unknown (runner not yet traced)"
    if cap is not None:
        cap_s = (f"auto capacity {cap}" if trace_info.get("capacity_auto")
                 else f"capacity {cap}")
    detail = ", ".join(
        f"round {first_round + int(j)}: n_dropped={int(dropped[j])}" for j in bad)
    warnings.warn(
        f"shuffle overflow — {detail} (per-destination {cap_s}); "
        f"raise IterativeSpec.capacity to make the job lossless",
        RuntimeWarning, stacklevel=stacklevel)


def run_iterative_mapreduce(
    spec: IterativeSpec,
    inputs,
    init_state,
    mesh: Mesh,
    axis_name: str = "data",
    secure: SecureShuffleConfig | None = None,
    round_offset: int = 0,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
    warn_on_overflow: bool = True,
):
    """One-shot convenience: run `spec.n_rounds` fused rounds over
    `mesh[axis_name]`. `inputs` is a pytree sharded on the leading dim;
    `init_state` is replicated carried state. `round_offset`: see
    `make_iterative_runner` — pass the count of rounds already executed
    when continuing a job across dispatches. `chacha_impl` selects the
    secure keystream backend and `coalesce` the secure wire layout (see
    `core/shuffle.py`).

    Returns (final_state, aux_per_round, dropped_per_round) — dropped has
    shape (n_rounds,) and must be all-zero for a lossless job — plus
    (rounds_executed, halted) when `spec.halt_fn` is set. Any round with
    n_dropped > 0 raises a RuntimeWarning naming the round and the capacity
    in force (`warn_on_overflow=False` to silence, e.g. when overflow is an
    expected phase of the job).
    """
    runner = make_iterative_runner(spec, mesh, axis_name, secure,
                                   chacha_impl=chacha_impl, loop_impl=loop_impl,
                                   coalesce=coalesce)
    out = runner(inputs, init_state, round_offset)
    if warn_on_overflow:
        dropped = out[2]
        n_exec = int(out[3]) if spec.halt_fn is not None else spec.n_rounds
        _warn_overflow(np.asarray(dropped)[:n_exec], round_offset, runner.trace_info)
    return out


@dataclass(frozen=True)
class RunUntilResult:
    """Outcome of a convergence-aware `run_until` job.

    state:             final carried state (device arrays, replicated) — the
                       state produced by the round that triggered the halt
                       (or the last round when the budget ran out).
    aux:               per-round aux pytree, leaves stacked over the
                       `rounds_executed` EXECUTED rounds only (numpy);
                       masked no-op rounds are trimmed.
    dropped:           (rounds_executed,) overflow counts per executed round.
    rounds_executed:   rounds whose body actually ran (== keystream rounds
                       consumed in secure mode).
    rounds_dispatched: rounds the host shipped to the device across all
                       chunks (>= rounds_executed; the gap is the masked
                       no-op tail of the halting chunk).
    n_dispatches:      host->device round trips.
    halted:            True when halt_fn fired; False when `max_rounds` was
                       exhausted first.
    """

    state: Any
    aux: Any
    dropped: Any
    rounds_executed: int
    rounds_dispatched: int
    n_dispatches: int
    halted: bool


def run_until(
    spec: IterativeSpec,
    inputs,
    init_state,
    mesh: Mesh,
    axis_name: str = "data",
    *,
    secure: SecureShuffleConfig | None = None,
    max_rounds: int = 64,
    round_offset: int = 0,
    min_chunk: int = 1,
    growth="auto",
    max_chunk: int | None = None,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
    donate_state: bool = True,
    runners=None,
    warn_on_overflow: bool = True,
    job_tag=None,
) -> RunUntilResult:
    """Run a job until `spec.halt_fn` fires or `max_rounds` rounds executed.

    The convergence-aware twin of `run_iterative_mapreduce`: rounds are
    dispatched in adaptively sized chunks — `min_chunk` rounds first, then
    ×`growth` per dispatch up to `max_chunk` (default `max_rounds`;
    `growth` 'auto' resolves through `resolve_chunk_growth`) — and
    each chunk's fused round loop early-exits on device the moment
    `halt_fn` fires (module docstring: Termination). A job converging in 7
    rounds therefore neither compiles nor dispatches a 32-round program,
    and pays for no post-convergence rounds beyond the masked no-op tail of
    its final chunk.

    The global round index — and with it the secure keystream space — is
    threaded across chunks automatically: chunk i+1's round_offset is
    `round_offset` + total rounds *executed* so far, which is exactly the
    keystream-disjointness contract (halted rounds consume none).

    `spec.n_rounds` is ignored (chunk sizes are chosen here). A spec
    without `halt_fn` is allowed: the job simply runs all `max_rounds`
    rounds (useful to share this entry point across workloads).

    `donate_state` (default True) donates each dispatch's carried-state
    buffers: the chunk loop always feeds a chunk's output state into the
    next chunk, so XLA can write the new state into the old one's storage
    instead of re-allocating it every dispatch. The caller's `init_state`
    is protected by ONE defensive device copy up front (donation would
    otherwise delete the caller's buffers on the first chunk); every
    subsequent dispatch re-uses storage with zero copies.

    `runners`: optional mutable runner cache reused across calls to amortize
    XLA compiles — a plain dict mapping chunk size -> runner, or any object
    with `get_or_build(n_rounds, build_fn) -> runner` (the serving path's
    keyed `RunnerCache` views; module docstring: Serving). Callers own its
    validity: it must have been populated with the SAME spec (sans
    n_rounds) / mesh / secure / impl / donation arguments.

    `job_tag`: optional job id under which the job's traced shuffles are
    recorded (`wire_accounting.tagged`), so interleaved jobs sharing a
    `record_wire_bytes` sink stay separable.
    """
    gen = run_until_chunks(
        spec, inputs, init_state, mesh, axis_name, secure=secure,
        max_rounds=max_rounds, round_offset=round_offset, min_chunk=min_chunk,
        growth=growth, max_chunk=max_chunk, chacha_impl=chacha_impl,
        loop_impl=loop_impl, coalesce=coalesce, donate_state=donate_state,
        runners=runners, warn_on_overflow=warn_on_overflow, job_tag=job_tag)
    while True:
        try:
            next(gen)
        except StopIteration as stop:
            return stop.value


def run_until_chunks(
    spec: IterativeSpec,
    inputs,
    init_state,
    mesh: Mesh,
    axis_name: str = "data",
    *,
    secure: SecureShuffleConfig | None = None,
    max_rounds: int = 64,
    round_offset: int = 0,
    min_chunk: int = 1,
    growth="auto",
    max_chunk: int | None = None,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
    donate_state: bool = True,
    runners=None,
    warn_on_overflow: bool = True,
    job_tag=None,
):
    """Cooperative (generator) form of `run_until` — same arguments.

    Yields a progress dict after every chunk dispatch ({"chunk_rounds",
    "rounds_executed", "n_dispatches", "halted"}) and RETURNS the final
    `RunUntilResult` as the generator's `StopIteration.value`. A host
    scheduler (the serving admission loop) drives many jobs' generators
    round-robin, one chunk per turn, on a single dispatch thread; each
    suspended generator keeps its own carried state and global round
    offset, so interleaving any number of jobs is bit-identical to running
    them serially.

    The shuffle-overflow warning is emitted ONCE per job, after the last
    chunk, summarizing every overflowing GLOBAL round index — not once per
    dispatched chunk — so a long queued job cannot flood the log.
    """
    if max_rounds < 1:
        raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
    growth = resolve_chunk_growth(growth, min_chunk=min_chunk,
                                  max_rounds=max_rounds, max_chunk=max_chunk)
    if min_chunk < 1 or growth < 1:
        raise ValueError(f"min_chunk and growth must be >= 1, got {min_chunk}, {growth}")
    max_chunk = min(max_chunk or max_rounds, max_rounds)
    runners = {} if runners is None else runners
    # duck-typed cache: the serving RunnerCache view, or the legacy dict
    get_or_build = getattr(runners, "get_or_build", None)

    # place inputs and carried state ONCE in the layouts the runner's
    # shard_map declares: inputs split over the axis (not parked on the
    # default device and re-split every chunk), state in its per-leaf specs
    # — the sharding every chunk returns it in, so chunk 2 sees the aval
    # chunk 1 was traced with and reuses its program
    inputs = jax.device_put(inputs, NamedSharding(mesh, P(axis_name)))
    state_spec_tree, _ = _resolve_state_specs(spec, init_state)
    state = jax.device_put(
        init_state,
        jax.tree.map(lambda p: NamedSharding(mesh, p), state_spec_tree,
                     is_leaf=lambda x: isinstance(x, P)))
    if donate_state:
        # placement may hand back the caller's own device buffer (even when
        # it reshards), so copy every leaf that came in as a device array:
        # the first chunk's donation must not delete init_state. All later
        # chunks donate run_until's own output state, which nothing else holds
        state = jax.tree.map(
            lambda src, x: x.copy() if isinstance(src, jax.Array) else x,
            init_state, state)
    executed = dispatched = n_dispatches = 0
    halted = False
    aux_chunks: list = []
    dropped_chunks: list = []
    overflow_trace_info: dict | None = None
    chunk = min(max(1, min_chunk), max_chunk)
    while executed < max_rounds and not halted:
        n = min(chunk, max_rounds - executed)

        def build(n=n):
            return make_iterative_runner(
                replace(spec, n_rounds=n), mesh, axis_name, secure,
                chacha_impl=chacha_impl, loop_impl=loop_impl,
                coalesce=coalesce, donate_state=donate_state)

        if get_or_build is not None:
            runner = get_or_build(n, build)
        else:
            runner = runners.get(n)
            if runner is None:
                runner = runners[n] = build()
        with wire_accounting.tagged(job_tag):
            out = runner(inputs, state, round_offset + executed)
        if spec.halt_fn is None:
            state, aux, dropped = out
            n_exec, chunk_halted = n, False
        else:
            state, aux, dropped, n_exec, chunk_halted = out
            n_exec, chunk_halted = int(n_exec), bool(chunk_halted)
        n_dispatches += 1
        dispatched += n
        aux_chunks.append(jax.tree.map(lambda a: np.asarray(a)[:n_exec], aux))
        dropped_chunks.append(np.asarray(dropped)[:n_exec])
        if warn_on_overflow and overflow_trace_info is None and np.any(
                dropped_chunks[-1] > 0):
            overflow_trace_info = dict(runner.trace_info)
        executed += n_exec
        halted = chunk_halted
        chunk = min(chunk * growth, max_chunk)
        yield {"chunk_rounds": n, "rounds_executed": executed,
               "n_dispatches": n_dispatches, "halted": halted}

    aux = jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *aux_chunks)
    dropped = np.concatenate(dropped_chunks) if dropped_chunks else np.zeros((0,), np.int32)
    if warn_on_overflow and overflow_trace_info is not None:
        # ONE summary warning per job: executed rounds are gapless from
        # round_offset, so the concatenated per-round drops carry every
        # overflowing GLOBAL index (capacity from the chunk that overflowed)
        _warn_overflow(dropped, round_offset, overflow_trace_info, stacklevel=4)
    return RunUntilResult(
        state=state,
        aux=aux,
        dropped=dropped,
        rounds_executed=executed,
        rounds_dispatched=dispatched,
        n_dispatches=n_dispatches,
        halted=halted,
    )
