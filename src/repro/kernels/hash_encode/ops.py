"""Jitted public API for hash-grid encoding with BUM-merged backward.

`make_hash_encode(...)` returns a differentiable `encode(points, tables)`
whose custom VJP scatters table gradients through the BUM merge
(`kernels.grid_update.ops.merged_scatter_add`) instead of a naive duplicate
scatter-add.  All L levels are merged in one pass by offsetting level-l
addresses by l*T — a merge window covering the whole batch across levels,
strictly stronger than the paper's 16-deep per-core buffer.

Backend routing resolves through the `repro.kernels` KernelBackend registry
('ref' = pure jnp, the production CPU path and the autodiff oracle;
'pallas-interpret'/'pallas-tpu' = the Pallas kernel).  `backend=None` defers
to the process default at encoder-build time.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from . import kernel as _kernel
from ..grid_update import ops as grid_update_ops
from ...obs import trace as _trace


# Padding sentinel for point batches that aren't a block multiple.  Real
# points live in [0,1)^3; sentinel rows are detected in-kernel (coordinate
# < 0), routed to table row 0 (one fixed address, no reads scattered into
# live cells) and masked to zero in the output.
PAD_SENTINEL = -1.0


def _pad_to(x: jnp.ndarray, multiple: int, fill=PAD_SENTINEL):
    n = x.shape[0]
    if n % multiple == 0:
        return x, n
    pad = multiple - n % multiple
    pad_block = jnp.full((pad,) + x.shape[1:], fill, x.dtype)
    return jnp.concatenate([x, pad_block]), n


def _forward(points, tables, resolutions, dense_flags, be, block_points: int):
    from .. import resolve_backend
    be = resolve_backend(be, op="hash_encode")  # accepts registry names too
    with _trace.stage("hash_grid/fwd", cat="kernels"):
        if be.use_pallas:
            pts, n = _pad_to(points, block_points)
            out = _kernel.hash_encode_pallas(
                pts,
                tables,
                jnp.asarray(resolutions, jnp.int32),
                jnp.asarray(dense_flags, jnp.int32),
                block_points=block_points,
                interpret=be.interpret,
            )
            return out[:n]
        return ref.hash_encode(points, tables, resolutions)


def _corner_updates(points, resolutions, dense_flags, table_size, grad):
    """Flattened (idx, val) update stream across all levels.

    grad: (N, L, F).  Returns idx (N*8*L,) int32 into the flat (L*T) table and
    vals (N*8*L, F) f32.
    """
    num_l = grad.shape[1]
    all_idx, all_val = [], []
    with _trace.stage("hash_grid/bwd/stream", cat="kernels"):
        for l in range(num_l):
            res = int(resolutions[l])
            corners, weights = ref._level_corners(points, res)  # (N,8,3), (N,8)
            idx = ref.corner_index(corners, res, table_size, bool(dense_flags[l]))
            upd = weights[..., None] * grad[:, l, None, :]  # (N, 8, F)
            all_idx.append((idx + l * table_size).reshape(-1))
            all_val.append(upd.reshape(-1, grad.shape[-1]))
        return jnp.concatenate(all_idx), jnp.concatenate(all_val)


def make_hash_encode(
    resolutions,
    table_size: int,
    n_features: int,
    *,
    backend=None,
    merged_backward: bool = True,
    block_points: int = _kernel.DEFAULT_BLOCK_POINTS,
) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Build a differentiable multires hash encoder for fixed level geometry.

    resolutions: static per-level grid resolutions (from ref.level_resolutions).
    backend: registry name or None (process default, resolved at build time).
    Returns encode(points (N,3), tables (L,T,F)) -> (N, L*F) float32.
    """
    from .. import resolve_backend
    be = resolve_backend(backend, op="hash_encode")
    commit_be = resolve_backend(backend, op="bum_scatter", width=n_features)
    resolutions = tuple(int(r) for r in resolutions)
    dense_flags = tuple(
        bool(x) for x in ref.level_is_dense(np.asarray(resolutions), table_size)
    )
    num_l = len(resolutions)

    @jax.custom_vjp
    def encode(points, tables):
        return _forward(points, tables, resolutions, dense_flags, be, block_points)

    def encode_fwd(points, tables):
        out = _forward(points, tables, resolutions, dense_flags, be, block_points)
        # zero-size residual carries tables' dtype (dtypes aren't JAX types)
        return out, (points, jnp.zeros((0,), tables.dtype))

    def encode_bwd(res, g):
        with _trace.stage("hash_grid/bwd", cat="kernels"):
            points, tproto = res
            tdtype = tproto.dtype
            grad = g.reshape(points.shape[0], num_l, n_features).astype(jnp.float32)
            idx, vals = _corner_updates(points, resolutions, dense_flags, table_size, grad)
            flat = jnp.zeros((num_l * table_size, n_features), jnp.float32)
            if merged_backward:
                # the merge resolves as its own op from the same request: on a
                # TPU `auto` runs the forward on ref and the merge in Pallas
                flat = grid_update_ops.merged_scatter_add(flat, idx, vals,
                                                          backend=commit_be)
            else:
                flat = flat.at[idx].add(vals)
            grad_tables = flat.reshape(num_l, table_size, n_features).astype(tdtype)
            return jnp.zeros_like(points), grad_tables

    encode.defvjp(encode_fwd, encode_bwd)
    return encode


def access_stream(points, resolutions, dense_flags, table_size: int):
    """Forward-order corner address stream (paper Fig. 8-10 instrumentation).

    Not jitted — level geometry stays static python.  Returns (N*8*L,) int32
    addresses into the flat (L*T) table, in forward traversal order.
    """
    grad = jnp.ones((points.shape[0], len(resolutions), 1), jnp.float32)
    idx, _ = _corner_updates(points, tuple(resolutions), tuple(dense_flags), table_size, grad)
    return idx
