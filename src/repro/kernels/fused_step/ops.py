"""Jitted public API for the one-kernel training step.

`make_fused_step(...)` returns a differentiable

    step(points, sh, t_density, t_color, mlp_d, mlp_c)
        -> (density head out (N, 1+geo), raw rgb (N, 3))

covering the whole shade stage of a decomposed field in ONE custom-VJP op:
shared corner geometry, both grid encodes, and both MLP heads.  On the ref
backend every primitive is the PR 3 chain's primitive (`fused_path.ref`
geometry + `fused_mlp.ref` MLPs), so forward values, table gradients and
MLP gradients are all bit-identical to `make_fused_encode` + `mlp_heads`;
on Pallas backends the forward runs `kernel.fused_step_pallas` (segment-sum
dedup + in-VMEM MLP epilogue) and the backward runs the hand-written
`kernel.fused_step_bwd_pallas`.

residual_policy — what the VJP keeps live between forward and backward:

* "stash": the PR 3 residual set — trilinear weights (L,N,8), two
  (L*N*8,) pre-sorted index streams per grid, and both feature blocks for
  the MLP pullback.  Backward does no geometry work at all.
* "recompute" (default): stash only the Morton-sorted INPUTS (points, sh,
  tables, MLP params — all aliases, nothing materialized) and re-derive
  geometry, streams and features in the backward.  Because the recompute
  runs exactly the forward's deterministic ops on exactly the same inputs,
  its gradients are BIT-identical to "stash" — the knob trades backward
  FLOPs for residual bandwidth, never numerics (property-tested on ref and
  pallas-interpret).  At production scale (L=16, 100k points) the stash set
  is hundreds of MB/step while the recompute set is just the live model —
  hence the default.  On Pallas backends the hand-written backward kernel
  recomputes in-VMEM under either policy (the residual set is identical);
  the knob only changes the ref/XLA path.

Table-gradient commits route through `grid_update.windowed_scatter_add`'s
stacked per-step form (each step is a one-row window; the F_D:F_C schedule
in trainer.py makes multi-row windows by freezing a branch's stream), which
is bit-identical to `merged_scatter_add` per stream by the shared
`_segment_commit` body.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from . import kernel as _kernel
from ..fused_path import ref as fp_ref
from ..hash_encode import ref as he_ref
from ..hash_encode import ops as he_ops
from ..grid_update import ops as gu_ops
from ...obs import trace as _trace

DEFAULT_BLOCK_POINTS = _kernel.DEFAULT_BLOCK_POINTS
RESIDUAL_POLICIES = ("stash", "recompute")


def make_fused_step(
    resolutions,
    table_sizes,
    n_features: int,
    *,
    residual_policy: str = "recompute",
    backend=None,
    merged_backward: bool = True,
    block_points: int = DEFAULT_BLOCK_POINTS,
) -> Callable:
    """Build the one-kernel step for fixed level geometry.

    resolutions: static per-level grid resolutions (shared by both grids).
    table_sizes: (T_density, T_color).
    Returns step(points (N,3), sh (N,S), t_d (L,Td,F), t_c (L,Tc,F),
                 mlp_d {w1,b1,w2,b2}, mlp_c {w1..b3}) -> (out_d, raw_c).

    Inherits every fused-path contract (Morton-ordered input, presorted
    commit invariant, PAD_SENTINEL padding) — see `fused_path.ops`.
    """
    if residual_policy not in RESIDUAL_POLICIES:
        raise ValueError(f"residual_policy must be one of {RESIDUAL_POLICIES}")
    from .. import resolve_backend
    be = resolve_backend(backend, op="fused_step")
    resolutions = tuple(int(r) for r in resolutions)
    table_sizes = tuple(int(t) for t in table_sizes)
    assert len(table_sizes) == 2, "fused step covers decomposed fields (2 grids)"
    num_l = len(resolutions)
    dense_flags = tuple(
        tuple(bool(x) for x in he_ref.level_is_dense(np.asarray(resolutions), t))
        for t in table_sizes
    )

    def _geometry(points):
        corners, weights = fp_ref.corner_geometry(points, resolutions)
        idx = [
            fp_ref.level_indices(corners, resolutions, table_sizes[g], dense_flags[g])
            for g in range(2)
        ]
        return idx, weights

    def _forward(points, sh, tables, mlp_d, mlp_c):
        if be.use_pallas:
            pts, n = he_ops._pad_to(points, block_points)
            shp, _ = he_ops._pad_to(sh, block_points, fill=0.0)
            out_d, raw_c = _kernel.fused_step_pallas(
                pts, shp, tables[0], tables[1], mlp_d, mlp_c,
                jnp.asarray(resolutions, jnp.int32),
                jnp.asarray(dense_flags[0], jnp.int32),
                jnp.asarray(dense_flags[1], jnp.int32),
                block_points=block_points, interpret=be.interpret,
            )
            return out_d[:n], raw_c[:n]
        idx, weights = _geometry(points)
        hd = fp_ref.encode_from_indices(tables[0], idx[0], weights)
        hc = fp_ref.encode_from_indices(tables[1], idx[1], weights)
        return ref.mlp_heads(hd, hc, sh, mlp_d, mlp_c)

    def _table_grads(w_stack, streams, g_feats, protos):
        """PR 3 encode_bwd, committed through the stacked windowed form.

        Each grid's stream is a one-row window (W=1); `_segment_commit`
        sharing makes this bit-identical to `merged_scatter_add`.  The two
        grids stay SEPARATE commits so a frozen branch's whole chain
        (values + argsort) dead-code-eliminates out of the step.
        """
        grads = []
        for g in range(2):
            n = g_feats[g].shape[0]
            gg = g_feats[g].reshape(n, num_l, n_features).astype(jnp.float32)
            vals = (
                w_stack[:, :, :, None] * jnp.transpose(gg, (1, 0, 2))[:, :, None, :]
            ).reshape(-1, n_features)
            addr_sorted, order = streams[g]
            flat = jnp.zeros((num_l * table_sizes[g], n_features), jnp.float32)
            if merged_backward:
                flat = gu_ops.windowed_scatter_add(
                    flat, addr_sorted[None], vals[order][None],
                    presorted=True, backend=be,
                )
            else:
                flat = flat.at[addr_sorted].add(vals[order])
            grads.append(
                flat.reshape(num_l, table_sizes[g], n_features).astype(protos[g].dtype)
            )
        return grads

    def _plan_streams(idx):
        streams = []
        for g in range(2):
            addr = fp_ref.address_stream(idx[g], table_sizes[g])
            order = jnp.argsort(addr)
            streams.append((addr[order], order))
        return tuple(streams)

    @jax.custom_vjp
    def step(points, sh, t_density, t_color, mlp_d, mlp_c):
        # non-differentiated calls (pure renders) run the primal, not
        # step_fwd — name both so serve-side traces see the kernel too
        with _trace.stage("kernels/fused_step/fwd", cat="kernels",
                         args={"policy": residual_policy, "backend": be.name}):
            return _forward(points, sh, (t_density, t_color), mlp_d, mlp_c)

    def step_fwd(points, sh, t_density, t_color, mlp_d, mlp_c):
        # a stage: its ops carry the name as op_name metadata; with obs on,
        # also a host span timing the forward's trace (the compile-side cost
        # of the one-kernel step)
        with _trace.stage("kernels/fused_step/fwd", cat="kernels",
                         args={"policy": residual_policy, "backend": be.name}):
            tables = (t_density, t_color)
            if be.use_pallas or residual_policy == "recompute":
                # Nothing but input aliases crosses to the backward; notably
                # the forward also SKIPS stream planning — pure renders pay
                # zero backward-prep cost, and a frozen grid's recomputed
                # plan is dead code in the backward.
                outs = _forward(points, sh, tables, mlp_d, mlp_c)
                return outs, (points, sh, tables, mlp_d, mlp_c, None)
            idx, weights = _geometry(points)
            hd = fp_ref.encode_from_indices(tables[0], idx[0], weights)
            hc = fp_ref.encode_from_indices(tables[1], idx[1], weights)
            outs = ref.mlp_heads(hd, hc, sh, mlp_d, mlp_c)
            protos = tuple(jnp.zeros((0,), t.dtype) for t in tables)
            stash = (jnp.stack(weights), _plan_streams(idx), hd, hc)
            return outs, (points, sh, protos, mlp_d, mlp_c, stash)

    def step_bwd(res, g_out):
        with _trace.stage("kernels/fused_step/bwd", cat="kernels",
                         args={"policy": residual_policy, "backend": be.name}):
            return _step_bwd(res, g_out)

    def _step_bwd(res, g_out):
        points, sh, tables, mlp_d, mlp_c, stash = res
        if be.use_pallas:
            return _kernel_bwd(points, sh, tables, mlp_d, mlp_c, g_out)
        if stash is None:
            # recompute: same deterministic ops as the forward -> the
            # residual quantities are bit-equal to what "stash" kept.
            idx, weights = _geometry(points)
            hd = fp_ref.encode_from_indices(tables[0], idx[0], weights)
            hc = fp_ref.encode_from_indices(tables[1], idx[1], weights)
            w_stack, streams = jnp.stack(weights), _plan_streams(idx)
            protos = tuple(jnp.zeros((0,), t.dtype) for t in tables)
        else:
            w_stack, streams, hd, hc = stash
            protos = tables  # zero-size dtype carriers from the forward
        # MLP pullback: jax.vjp over the exact ref chain — the same autodiff
        # program the unfused path runs, fed the same (hd, hc, sh) values.
        _, mlp_vjp = jax.vjp(
            lambda hd_, hc_, sh_, md_, mc_: ref.mlp_heads(hd_, hc_, sh_, md_, mc_),
            hd, hc, sh, mlp_d, mlp_c,
        )
        g_hd, g_hc, g_sh, g_md, g_mc = mlp_vjp(g_out)
        g_td, g_tc = _table_grads(w_stack, streams, (g_hd, g_hc), protos)
        return (jnp.zeros_like(points), g_sh, g_td, g_tc, g_md, g_mc)

    def _kernel_bwd(points, sh, tables, mlp_d, mlp_c, g_out):
        pts, n = he_ops._pad_to(points, block_points)
        shp, _ = he_ops._pad_to(sh, block_points, fill=0.0)
        gd, _ = he_ops._pad_to(g_out[0], block_points, fill=0.0)
        gc, _ = he_ops._pad_to(g_out[1], block_points, fill=0.0)
        g_td, g_tc, g_md, g_mc, g_sh = _kernel.fused_step_bwd_pallas(
            pts, shp, gd, gc, tables[0], tables[1], mlp_d, mlp_c,
            jnp.asarray(resolutions, jnp.int32),
            jnp.asarray(dense_flags[0], jnp.int32),
            jnp.asarray(dense_flags[1], jnp.int32),
            block_points=block_points, interpret=be.interpret,
        )
        return (jnp.zeros_like(points), g_sh[:n], g_td, g_tc, g_md, g_mc)

    step.defvjp(step_fwd, step_bwd)
    return step
