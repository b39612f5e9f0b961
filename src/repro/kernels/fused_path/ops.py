"""Jitted public API for the fused compacted-path encode with pre-sorted BUM
backward.

`make_fused_encode(...)` returns a differentiable

    encode(points, *tables) -> tuple of (N, L*F) features, one per grid

that evaluates every hash grid of a field (density + color share level
geometry — same resolutions, different table sizes) in one fused pass:

* corner coords / trilinear weights are computed ONCE and shared by all
  grids and by both directions (the unfused path recomputes them per grid
  per direction — 4x for a decomposed field); on Pallas backends the
  forward runs one kernel per grid (each with in-block dedup) and the
  shared-geometry pass serves the VJP planning;
* the residuals deliberately trade memory for backward compute: weights
  (L,N,8) plus two (L*N*8,) index streams per grid stay live between
  forward and backward (~a few MB at the compacted budgets used here;
  see ROADMAP for a recompute policy on memory-bound devices);
* the forward plans the backward: it computes the stable argsort of each
  grid's corner-address stream (quasi-sorted already, because the caller
  feeds Morton-ordered points) and stashes it as a residual;
* the custom VJP replays that order to emit each grid's table-gradient
  stream already address-sorted, so `merged_scatter_add(presorted=True)`
  commits it without any backward-pass argsort (the BUM analogue) and with
  no corner/index recomputation.

On the ref backend the fused encode is bit-identical to
`hash_encode.ref.hash_encode` per grid, and — because the stable argsort of
an identical address stream is the same permutation the unfused backward
would compute — its table gradients are bit-identical to the unfused
merged-backward path.  Pallas flavors route the forward through
`kernel.fused_encode_pallas` (block-deduplicated corner reads).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from . import kernel as _kernel
from ..hash_encode import ref as he_ref
from ..hash_encode import ops as he_ops
from ..grid_update import ops as gu_ops
from ...obs import trace as _trace

DEFAULT_BLOCK_POINTS = _kernel.DEFAULT_BLOCK_POINTS
RESIDUAL_POLICIES = ("stash", "recompute")


def make_fused_encode(
    resolutions,
    table_sizes,
    n_features: int,
    *,
    residual_policy: str = "recompute",
    backend=None,
    merged_backward: bool = True,
    block_points: int = DEFAULT_BLOCK_POINTS,
) -> Callable:
    """Build the fused multi-grid encoder for fixed level geometry.

    resolutions: static per-level grid resolutions (shared by all grids).
    table_sizes: one table size per grid (e.g. (T_density, T_color)).
    Returns encode(points (N,3), *tables[(L,T_g,F)]) -> tuple[(N, L*F)].

    Contracts the rest of the stack relies on (previously only recorded in
    CHANGES.md):

    * **Input ordering.** `points` should be Morton (Z-order) sorted — the
      pipeline's compact stage guarantees this (uniform or redistributed
      samples alike).  Correctness never depends on it, but both wins do:
      block-level corner-read dedup on Pallas (FMU) and the quasi-sorted
      address streams that make the forward's stable argsort cheap.
    * **Presorted invariant.** The forward stashes, per grid, the *stable*
      argsort of the canonical corner-address stream (level-major, then
      point, then corner).  The VJP replays exactly that permutation and
      commits through `merged_scatter_add(presorted=True)`, which skips its
      own argsort.  Because a stable sort of an identical key stream is an
      identical permutation, the committed gradient is bit-identical to the
      unfused merged-backward path — property-tested in
      tests/test_grid_update.py.
    * **Sentinel invariant.** Pallas block padding uses
      `hash_encode.PAD_SENTINEL` (-1.0): kernels must map sentinel rows to
      zero output while reading row 0 of the table (a harmless in-bounds
      address), so padded lanes neither contribute features nor fault.
      Regression-tested in tests/test_hash_encode.py.
    * **Residual footprint.** Set by `residual_policy`.  "stash" is the
      PR 3 set: weights (L,N,8) plus two (L·N·8,) index streams per grid
      stay live from forward to backward and the VJP does no geometry work.
      "recompute" (default) keeps only the points alias and re-derives
      geometry + streams in the backward with the same deterministic ops —
      BIT-identical gradients (stable argsort of an identical address stream
      is an identical permutation), just traded from residual bandwidth to
      backward FLOPs; the right default at production L=16/100k-point scale.
    """
    if residual_policy not in RESIDUAL_POLICIES:
        raise ValueError(f"residual_policy must be one of {RESIDUAL_POLICIES}")
    from .. import resolve_backend
    be = resolve_backend(backend, op="fused_encode")
    commit_be = resolve_backend(backend, op="bum_scatter", width=n_features)
    resolutions = tuple(int(r) for r in resolutions)
    table_sizes = tuple(int(t) for t in table_sizes)
    num_l = len(resolutions)
    n_grids = len(table_sizes)
    dense_flags = tuple(
        tuple(bool(x) for x in he_ref.level_is_dense(np.asarray(resolutions), t))
        for t in table_sizes
    )

    def _forward(points, tables):
        if be.use_pallas:
            pts, n = he_ops._pad_to(points, block_points)
            outs = []
            for g in range(n_grids):
                out = _kernel.fused_encode_pallas(
                    pts,
                    tables[g],
                    jnp.asarray(resolutions, jnp.int32),
                    jnp.asarray(dense_flags[g], jnp.int32),
                    block_points=block_points,
                    interpret=be.interpret,
                )
                outs.append(out[:n])
            return tuple(outs)
        corners, weights = ref.corner_geometry(points, resolutions)
        return tuple(
            ref.encode_from_indices(
                tables[g],
                ref.level_indices(corners, resolutions, table_sizes[g], dense_flags[g]),
                weights,
            )
            for g in range(n_grids)
        )

    def _plan(points):
        """Shared geometry + backward plan: weights (L,N,8) and, per grid,
        the stable argsort of the canonical corner-address stream — the
        unfused backward's merge order."""
        corners, weights = ref.corner_geometry(points, resolutions)
        idx_by_grid = [
            ref.level_indices(corners, resolutions, table_sizes[g], dense_flags[g])
            for g in range(n_grids)
        ]
        streams = []
        for g in range(n_grids):
            addr = ref.address_stream(idx_by_grid[g], table_sizes[g])
            order = jnp.argsort(addr)
            streams.append((addr[order], order))
        return jnp.stack(weights), tuple(streams), idx_by_grid, weights

    @jax.custom_vjp
    def encode(points, *tables):
        with _trace.stage("hash_grid/fwd", cat="kernels"):
            return _forward(points, tables)

    def encode_fwd(points, *tables):
        with _trace.stage("hash_grid/fwd", cat="kernels"):
            return _encode_fwd(points, tables)

    def _encode_fwd(points, tables):
        protos = tuple(jnp.zeros((0,), t.dtype) for t in tables)
        if residual_policy == "recompute":
            # Only the points alias crosses to the backward; the plan is
            # re-derived there (bit-identical — same deterministic ops on the
            # same inputs) and pure forwards never pay for it at all.
            return _forward(points, tables), (points, None, None, protos)
        w_stack, streams, idx_by_grid, weights = _plan(points)
        if be.use_pallas:
            outs = _forward(points, tables)
        else:
            outs = tuple(
                ref.encode_from_indices(tables[g], idx_by_grid[g], weights)
                for g in range(n_grids)
            )
        return outs, (points, w_stack, streams, protos)

    def encode_bwd(res_pack, g_out):
        with _trace.stage("hash_grid/bwd", cat="kernels"):
            return _encode_bwd(res_pack, g_out)

    def _encode_bwd(res_pack, g_out):
        points, w_stack, streams, protos = res_pack
        if streams is None:  # recompute policy
            w_stack, streams, _, _ = _plan(points)
        n = points.shape[0]
        grads = []
        for g in range(n_grids):
            gg = g_out[g].reshape(n, num_l, n_features).astype(jnp.float32)
            # Update values in canonical stream order (level-major, then
            # point, then corner) — identical elementwise products to the
            # unfused `_corner_updates`.
            vals = (
                w_stack[:, :, :, None] * jnp.transpose(gg, (1, 0, 2))[:, :, None, :]
            ).reshape(-1, n_features)
            addr_sorted, order = streams[g]
            flat = jnp.zeros((num_l * table_sizes[g], n_features), jnp.float32)
            if merged_backward:
                flat = gu_ops.merged_scatter_add(
                    flat, addr_sorted, vals[order], presorted=True, backend=commit_be
                )
            else:
                flat = flat.at[addr_sorted].add(vals[order])
            grads.append(
                flat.reshape(num_l, table_sizes[g], n_features).astype(protos[g].dtype)
            )
        return (jnp.zeros_like(points), *grads)

    encode.defvjp(encode_fwd, encode_bwd)
    return encode
