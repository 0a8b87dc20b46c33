"""jit'd wrapper for the fused k-means kernel: padding + impl dispatch."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.kmeans.kernel import DEFAULT_TILE_N, kmeans_assign_tiles
from repro.kernels.kmeans.ref import kmeans_assign_ref


@functools.partial(jax.jit, static_argnames=("impl", "tile_n", "interpret"))
def kmeans_assign(
    points: jax.Array,
    centers: jax.Array,
    weights: jax.Array | None = None,
    *,
    impl: str = "pallas",
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool | None = None,
):
    """assign (N,) int32, sums (K, D) f32, counts (K,) f32.

    Padded points get weight 0: they contribute to nothing (their assignment
    entries are discarded by the caller via the original N). `interpret`
    None follows the backend (`repro.kernels.resolve_interpret`). Interpreted,
    a small N shrinks the tile to the next power of two; compiled, the tile
    stays `tile_n` (the TPU layout rule in `kernel.py`) and N pads up to it.
    """
    n = points.shape[0]
    if weights is None:
        weights = jnp.ones((n,), jnp.float32)
    if impl == "jnp":
        return kmeans_assign_ref(points, centers, weights)

    interpret = resolve_interpret(interpret)
    tn = tile_n
    if interpret:
        tn = min(tile_n, max(8, 1 << (n - 1).bit_length()))
    pad = (-n) % tn
    if pad:
        points = jnp.concatenate([points, jnp.zeros((pad, points.shape[1]), points.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])
    assign, sums, counts = kmeans_assign_tiles(
        points, centers, weights, tile_n=tn, interpret=interpret
    )
    return assign[:n], sums, counts.reshape(-1)
