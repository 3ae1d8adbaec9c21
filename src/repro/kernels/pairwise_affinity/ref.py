"""Pure-jnp oracle for the pairwise-distance kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["pairwise_distance"]


@jax.jit
def pairwise_distance(points: jax.Array) -> jax.Array:
    """D[i, j] = ||x_i - x_j||_2 for points (N, F) -> (N, N)."""
    x = jnp.asarray(points, jnp.float32)
    sq = jnp.sum(x * x, axis=-1)
    # HIGHEST: a TPU would otherwise take one bf16 pass for this f32 matmul
    gram = jnp.dot(x, x.T, precision=jax.lax.Precision.HIGHEST)
    d2 = sq[:, None] + sq[None, :] - 2.0 * gram
    return jnp.sqrt(jnp.maximum(d2, 0.0))
