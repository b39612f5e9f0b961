"""Pallas TPU kernel: ray-march composition (Eq. 1) over ray blocks.

Rays are independent, so the kernel blocks over rays and keeps a whole ray's
sample axis resident in VMEM; the transmittance prefix product is an
exclusive prefix sum of sigma*delta, computed as a triangular matmul on the
MXU (`repro.kernels.prefix`: Mosaic has no cumsum).  This keeps the (R, S)
intermediates out of HBM — the rendering analogue of the accelerator doing
Step 4 on-chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..prefix import prefix_sum

DEFAULT_BLOCK_RAYS = 128


def _composite_kernel(sigma_ref, rgb_ref, deltas_ref, ts_ref, color_ref, depth_ref, opac_ref):
    tau = sigma_ref[...].astype(jnp.float32) * deltas_ref[...].astype(jnp.float32)
    transmittance = jnp.exp(-prefix_sum(tau, exclusive=True))
    alpha = 1.0 - jnp.exp(-tau)
    weights = transmittance * alpha  # (B, S)
    color_ref[...] = jnp.sum(
        weights[..., None] * rgb_ref[...].astype(jnp.float32), axis=-2
    ).astype(color_ref.dtype)
    depth_ref[...] = jnp.sum(
        weights * ts_ref[...].astype(jnp.float32), axis=-1, keepdims=True
    ).astype(depth_ref.dtype)
    opac_ref[...] = jnp.sum(weights, axis=-1, keepdims=True).astype(opac_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rays", "interpret"))
def composite_pallas(sigma, rgb, deltas, ts, *, block_rays: int = DEFAULT_BLOCK_RAYS, interpret: bool):
    """sigma (R,S), rgb (R,S,3), deltas (R,S), ts (R,S) -> (color, depth, opacity)."""
    r, s = sigma.shape
    assert r % block_rays == 0
    grid = (r // block_rays,)
    color, depth, opac = pl.pallas_call(
        _composite_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rays, s), lambda i: (i, 0)),
            pl.BlockSpec((block_rays, s, 3), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_rays, s), lambda i: (i, 0)),
            pl.BlockSpec((block_rays, s), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rays, 3), lambda i: (i, 0)),
            pl.BlockSpec((block_rays, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rays, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 3), jnp.float32),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(sigma, rgb, deltas, ts)
    return color, depth[:, 0], opac[:, 0]
