"""Jitted wrapper for volume rendering with backend routing + ray padding.

Routing resolves through the `repro.kernels` KernelBackend registry;
`backend=None` uses the process default.

The Pallas compositing kernel is forward-only (and does not materialize
per-sample weights); a custom VJP backs it with the autodiff of the jnp
reference so pallas backends stay trainable.  Callers needing `weights`
(e.g. distortion losses) should route that computation through 'ref'.

`deltas` is a first-class per-sample array on every backend (kernel and
ref alike): the adaptive sampler's variable-spacing quadrature flows
through the same entry point as the uniform sampler's diff-based widths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _composite_pallas(sigma, rgb, deltas, ts, block_rays, interpret):
    r = sigma.shape[0]
    pad = (-r) % block_rays
    if pad:
        z = lambda x: jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        sigma, rgb, deltas, ts = z(sigma), z(rgb), z(deltas), z(ts)
    color, depth, opac = _kernel.composite_pallas(
        sigma, rgb, deltas, ts, block_rays=block_rays, interpret=interpret,
    )
    return color[:r], depth[:r], opac[:r]


def _composite_fwd(sigma, rgb, deltas, ts, block_rays, interpret):
    out = _composite_pallas(sigma, rgb, deltas, ts, block_rays, interpret)
    return out, (sigma, rgb, deltas, ts)


def _ref_cdo(sigma, rgb, deltas, ts):
    o = ref.composite(sigma, rgb, deltas, ts)
    return o.color, o.depth, o.opacity


def _composite_bwd(block_rays, interpret, res, g):
    _, vjp = jax.vjp(_ref_cdo, *res)
    return vjp(g)


_composite_pallas.defvjp(_composite_fwd, _composite_bwd)


def composite(sigma, rgb, deltas, ts, *, backend=None, block_rays: int = _kernel.DEFAULT_BLOCK_RAYS):
    """Render rays. 'ref' returns RenderOut (incl. weights, autodiff path);
    pallas backends return RenderOut with weights=None (fused kernel)."""
    from .. import resolve_backend
    be = resolve_backend(backend, op="composite")
    if be.use_pallas:
        color, depth, opac = _composite_pallas(
            sigma, rgb, deltas, ts, block_rays, be.interpret
        )
        return ref.RenderOut(color, depth, opac, None)
    return ref.composite(sigma, rgb, deltas, ts)
