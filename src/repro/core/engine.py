"""Device-level secure MapReduce engine (shard_map pipeline).

One jitted program runs the full paper pipeline on a mesh axis:

    split (sharded input)
      └─ map_fn        per-shard, vectorized ("mapper enclave")
      └─ combine_fn    optional local pre-aggregation (paper's combiner)
      └─ bucket_pack   hash(key) % R  →  (R, C, ...) send buffer
      └─ keyed_all_to_all   [+ ChaCha20 on the wire in secure mode]
      └─ reduce_fn     per-shard over received pairs ("reducer enclave")

All user functions are vectorized fixed-shape JAX functions (or SecVM
programs via `repro.core.secvm.secvm_map_fn` for code confidentiality).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.shuffle import SecureShuffleConfig, bucket_pack, keyed_all_to_all


def default_hash(keys):
    """Knuth multiplicative mix — the paper's `hash(key, rcount)` slot."""
    return (keys.astype(jnp.uint32) * jnp.uint32(2654435761)) >> 1


def identity_hash(keys):
    return keys.astype(jnp.uint32)


@dataclass(frozen=True)
class MapReduceSpec:
    """A MapReduce job over fixed-shape shards.

    map_fn(keys, values)            -> (mapped_keys, mapped_values)
    combine_fn(keys, values)        -> (keys, values)  [optional, local]
    reduce_fn(keys, values, valid)  -> per-shard output (typically followed by
                                       a psum/all_gather the caller encodes
                                       inside reduce_fn itself)
    hash_fn(keys) -> u32            destination = hash_fn(k) % R
    capacity: per-destination slots C (like MoE capacity factor).
    """

    map_fn: Callable[[Any, Any], tuple]
    reduce_fn: Callable[[Any, Any, Any], Any]
    combine_fn: Callable[[Any, Any], tuple] | None = None
    hash_fn: Callable = default_hash
    capacity: int = 0  # 0 → auto: ceil(n_mapped / R) * 2


def _shard_body(keys, values, *, spec: MapReduceSpec, axis_name: str, n_shards: int,
                secure: SecureShuffleConfig | None):
    mk, mv = spec.map_fn(keys, values)
    if spec.combine_fn is not None:
        mk, mv = spec.combine_fn(mk, mv)
    n_mapped = mk.shape[0]
    capacity = spec.capacity or max(1, -(-n_mapped // n_shards) * 2)

    bucket = (spec.hash_fn(mk) % jnp.uint32(n_shards)).astype(jnp.int32)
    bk, bv, dropped = bucket_pack(mk, bucket, mv, n_shards, capacity)

    recv = keyed_all_to_all({"k": bk, "v": bv}, axis_name, secure)
    rk, rv = recv["k"], recv["v"]

    flat_k = rk.reshape(-1)
    flat_v = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), rv)
    valid = flat_k >= 0
    out = spec.reduce_fn(flat_k, flat_v, valid)
    return out, lax.psum(dropped, axis_name)


def run_mapreduce(
    spec: MapReduceSpec,
    keys,
    values,
    mesh: Mesh,
    axis_name: str = "data",
    secure: SecureShuffleConfig | None = None,
    out_specs=P(),
    chacha_impl: str | None = None,
    coalesce: bool | None = None,
):
    """Run the pipeline over `mesh[axis_name]`. Inputs are host-global arrays
    sharded on their leading dim; output spec defaults to replicated (the
    usual case: reduce_fn ends in a psum/all_gather).

    `chacha_impl` overrides the secure config's keystream backend
    ('pallas' | 'pallas-interpret' | 'jnp') and `coalesce` its wire layout
    (True — single coalesced wire, one all_to_all — False — per-leaf
    oracle; see `core/shuffle.py`).

    Returns (output, n_dropped) — n_dropped must be 0 for a lossless job.
    """
    if secure is not None:
        secure = secure.with_impl(chacha_impl).with_coalesce(coalesce)
    n_shards = mesh.shape[axis_name]
    body = partial(_shard_body, spec=spec, axis_name=axis_name, n_shards=n_shards, secure=secure)
    in_specs = (P(axis_name), jax.tree.map(lambda _: P(axis_name), values))
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=(out_specs, P()), check_vma=False
    )
    return jax.jit(fn)(keys, values)


def run_mapreduce_until(
    spec: MapReduceSpec,
    keys,
    values,
    init_state,
    mesh: Mesh,
    axis_name: str = "data",
    *,
    halt_fn,
    fold_fn=None,
    max_rounds: int = 16,
    secure: SecureShuffleConfig | None = None,
    chacha_impl: str | None = None,
    loop_impl: str | None = None,
    coalesce: bool | None = None,
    min_chunk: int = 1,
    growth: int = 2,
    max_chunk: int | None = None,
):
    """Repeat a single-round MapReduce job until `halt_fn` says stop.

    Lifts `spec` into the convergence-aware iterative driver: every round
    re-maps the same sharded (keys, values), reduces per shard, folds the
    round's reduce output into the carried state via
    `fold_fn(state, round_output)` (default: the output REPLACES the
    state), then evaluates `halt_fn(state, round_output, round_index)` on
    the folded state — all inside the fused, halt-masked round loop of
    `repro.core.driver.run_until` (adaptive dispatch chunking, on-device
    early exit, per-round disjoint keystreams in secure mode). The driver's
    replicated-halt contract applies: `spec.reduce_fn` must end in a
    collective and `halt_fn` must depend only on replicated values.

    Returns the driver's `RunUntilResult` (state, per-round aux = the raw
    reduce outputs, rounds executed vs dispatched, halted).
    """
    # local import: driver imports this module for default_hash
    from repro.core.driver import IterativeSpec, run_until

    def map_fn(state, inputs, r):
        return spec.map_fn(inputs["k"], inputs["v"])

    def reduce_fn(state, rk, rv, valid, r):
        out = spec.reduce_fn(rk, rv, valid)
        new_state = out if fold_fn is None else fold_fn(state, out)
        return new_state, out

    ispec = IterativeSpec(
        map_fn=map_fn,
        reduce_fn=reduce_fn,
        combine_fn=spec.combine_fn,
        hash_fn=spec.hash_fn,
        capacity=spec.capacity,
        halt_fn=halt_fn,
        # lifted single-round jobs fold the whole reduce output into state
        # and hand it to halt_fn — replicated everywhere by construction
        state_specs=P(),
    )
    return run_until(
        ispec, {"k": keys, "v": values}, init_state, mesh, axis_name,
        secure=secure, max_rounds=max_rounds, min_chunk=min_chunk,
        growth=growth, max_chunk=max_chunk, chacha_impl=chacha_impl,
        loop_impl=loop_impl, coalesce=coalesce,
    )
