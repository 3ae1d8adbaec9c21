"""Pallas TPU kernel: tiled pairwise Euclidean distances.

TPU-native design (not a CUDA port): the (N, N) distance matrix is produced
in 128x128 MXU-aligned tiles.  Each grid cell loads a (Bm, F) row block and a
(Bn, F) column block into VMEM, computes the Gram tile on the MXU via
``jnp.dot(..., preferred_element_type=f32)`` and finishes on the VPU with the
||x||^2 + ||y||^2 - 2<x,y> expansion.  F (feature dim, ~10) is zero-padded to
the 128-lane boundary by the wrapper so every matmul operand is
hardware-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pairwise_distance_kernel", "pairwise_distance_pallas"]


def pairwise_distance_kernel(x_ref, y_ref, out_ref):
    """One (Bm, Bn) output tile: distances between x rows and y rows."""
    x = x_ref[...].astype(jnp.float32)           # (Bm, F)
    y = y_ref[...].astype(jnp.float32)           # (Bn, F)
    gram = jax.lax.dot_general(
        x, y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,     # full f32, not one bf16 pass
        preferred_element_type=jnp.float32,
    )                                            # (Bm, Bn) on the MXU
    xsq = jnp.sum(x * x, axis=1, keepdims=True)  # (Bm, 1)
    ysq = jnp.sum(y * y, axis=1, keepdims=True)  # (Bn, 1)
    d2 = xsq + ysq.T - 2.0 * gram
    out_ref[...] = jnp.sqrt(jnp.maximum(d2, 0.0))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def pairwise_distance_pallas(points: jax.Array, *, block_m: int = 128,
                             block_n: int = 128,
                             interpret: bool = False) -> jax.Array:
    """(N_pad, F_pad) -> (N_pad, N_pad); caller pads/slices.

    Grid is (N/Bm, N/Bn); both operands stream the full (padded) feature dim
    so each tile is a single VMEM-resident MXU contraction:
    VMEM footprint = Bm*F + Bn*F + Bm*Bn floats ~= 194 KiB at 128/128/128.
    """
    n, f = points.shape
    assert n % block_m == 0 and n % block_n == 0, "pad N to the block size"
    grid = (n // block_m, n // block_n)
    return pl.pallas_call(
        pairwise_distance_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, f), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(points, points)
