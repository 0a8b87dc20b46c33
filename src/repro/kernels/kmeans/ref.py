"""Pure-jnp oracle for the fused k-means assign+accumulate kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# full f32 matmuls: the TPU's default precision rounds f32 operands to bf16,
# which reassigned 13% of 2^24 points on a v5e
_F32 = jax.lax.Precision.HIGHEST


def _centroid_sums(onehot, points):
    """(K, D) = onehot.T @ points, as a reduction rather than a matmul.

    On a v5e the matmul (even at HIGHEST precision, and even split into
    blocked partials, which XLA folds back into one contraction) drifted
    ~1e-3 relative over 2^24 points; a reduction over N does not.
    """
    return jnp.sum(onehot[:, :, None] * points[:, None, :], axis=0)


def kmeans_assign_ref(points, centers, weights=None):
    """Map step of the paper's k-means: nearest center + weighted partials.

    Args:
      points:  (N, D) f32
      centers: (K, D) f32
      weights: (N,) f32 validity/sample weights (None -> ones)

    Returns:
      assign (N,) int32, sums (K, D) f32, counts (K,) f32
    """
    if weights is None:
        weights = jnp.ones((points.shape[0],), jnp.float32)
    x2 = jnp.sum(points * points, axis=1, keepdims=True)  # (N, 1)
    c2 = jnp.sum(centers * centers, axis=1)[None, :]  # (1, K)
    d2 = x2 + c2 - 2.0 * jnp.matmul(points, centers.T, precision=_F32)  # (N, K)
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)
    onehot = jax.nn.one_hot(assign, centers.shape[0], dtype=jnp.float32) * weights[:, None]
    sums = _centroid_sums(onehot, points)  # (K, D)
    counts = jnp.sum(onehot, axis=0)  # (K,)
    return assign, sums, counts
