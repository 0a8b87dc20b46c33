"""Pallas TPU kernel: fused k-means assign + per-center accumulate.

The paper's map step computes n×k distances and assigns each observation to
its nearest center; the reduce step averages. Materializing the (N, K)
distance matrix in HBM makes the step memory-bound (and on SGX triggered the
paging cliff). The fusion keeps everything for a tile in VMEM:

  grid i over point tiles (TN, D):
      d2      = |x|² − 2·x@cᵀ + |c|²        (TN, K)   MXU matmul
      assign  = argmin d2                   (TN,)     VPU
      onehot  = assign == iota(K)           (TN, K)   VPU, never leaves VMEM
      sums   += onehotᵀ @ x                 (K, D)    MXU matmul
      counts += Σ onehot                    (1, K)

Accumulator outputs map every grid step to the same block; TPU grid order is
sequential so `+=` is well-defined (the standard Pallas reduction idiom).
Tiling: TN=1024 rows; centers (K, D) stay resident. VMEM ≈ TN·D + K·D + TN·K
floats — e.g. D=64, K=256: ~1.8 MB. The compiled TPU lowering needs TN to be
a multiple of 1024: XLA lays a 1-D s32 array out in T(1024) tiles and Mosaic
refuses an `assign` block whose tiling differs (ops.py pads N to the tile).
The (N, D) and (N, 1) operands are padded to 128 lanes in HBM, so at D=2 the
layout copies cost ~1 KiB per point (a known cost of this row layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

DEFAULT_TILE_N = 1024
# full f32 matmuls: the TPU's default precision rounds f32 operands to bf16
# (ref.py gives the measured cost)
_F32 = jax.lax.Precision.HIGHEST


def _kmeans_tile_kernel(x_ref, c_ref, w_ref, assign_ref, sums_ref, counts_ref):
    i = pl.program_id(0)

    x = x_ref[...]  # (TN, D)
    c = c_ref[...]  # (K, D)
    w = w_ref[...]  # (TN, 1)

    x2 = jnp.sum(x * x, axis=1, keepdims=True)  # (TN, 1)
    c2 = jnp.sum(c * c, axis=1)[None, :]  # (1, K)
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32,
    )  # (TN, K) = x @ c.T  on the MXU
    d2 = x2 + c2 - 2.0 * xc

    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)  # (TN,)
    assign_ref[...] = assign

    k = c.shape[0]
    onehot = (assign[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)).astype(
        jnp.float32
    ) * w  # (TN, K), weighted

    part_sums = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), precision=_F32,
        preferred_element_type=jnp.float32,
    )  # (K, D) = onehot.T @ x
    part_counts = jnp.sum(onehot, axis=0, keepdims=True)  # (1, K)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sums_ref[...] += part_sums
    counts_ref[...] += part_counts


def kmeans_assign_tiles(
    points: jax.Array,
    centers: jax.Array,
    weights: jax.Array,
    *,
    tile_n: int = DEFAULT_TILE_N,
    interpret: bool | None = None,
):
    """Fused assign+accumulate. N must be a multiple of tile_n (ops.py pads).
    `interpret` None follows the backend (`repro.kernels.resolve_interpret`).

    Returns assign (N,) int32, sums (K, D) f32, counts (1, K) f32.
    """
    n, d = points.shape
    k = centers.shape[0]
    assert n % tile_n == 0, (n, tile_n)
    grid = (n // tile_n,)
    return pl.pallas_call(
        functools.partial(_kmeans_tile_kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((tile_n, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n,), lambda i: (i,)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(points, centers, weights.reshape(n, 1))
