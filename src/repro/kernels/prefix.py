"""Prefix sums inside Pallas kernels.

Mosaic (the Pallas TPU lowering) has no `cumsum`, so kernels compute prefix
sums along the last axis as a matmul against a triangular 0/1 matrix built
from iotas.  The matmul runs at HIGHEST precision, so float inputs keep
float32 accuracy on the MXU, and 0/1 integer counts below 2^24 come out
exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def prefix_sum(x: jnp.ndarray, *, exclusive: bool = False) -> jnp.ndarray:
    """Inclusive (or exclusive) prefix sum of float32 `x` along its last axis.

    x is (..., S) with at least two dims (Mosaic matmuls are 2-D); returns
    the same shape in float32.
    """
    s = x.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (s, s), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (s, s), 1)
    tri = (row < col) if exclusive else (row <= col)
    return jnp.dot(x.astype(jnp.float32), tri.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
