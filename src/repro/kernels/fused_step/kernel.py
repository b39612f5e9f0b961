"""Pallas kernels for the one-kernel training step (fwd + hand-written bwd).

Forward (`fused_step_pallas`) — grid (point-block, level), level innermost:

* each (block, level) step streams ONE level table per grid HBM->VMEM and
  runs the segment-sum dedup encode: the block's corner-address stream is
  sorted, duplicate runs are collapsed, each point's trilinear weights are
  segment-summed at the unique in-block addresses, and the level's features
  come out of a dense (B, B*8) x (B*8, F) matmul against the uniquely
  gathered rows — the FMU dedup as MXU *compute*, not just gather
  coalescing;
* the concatenated (B, L*F) feature blocks (one per grid) live in
  revisited VMEM output blocks across the level steps — the encode->MLP
  boundary never touches HBM;
* at the last level the 2-layer density MLP and 3-layer color MLP run as an
  in-kernel epilogue on the resident feature blocks, so the whole shade
  stage is ONE pallas_call.

Backward (`fused_step_bwd_pallas`) — grid (point-block,):

* the residual-policy "recompute" contract realized in-kernel: corner
  geometry, indices and features are re-derived from the stashed
  Morton-sorted points block; the (L,N,8) weight tensor and the index
  streams NEVER exist in HBM;
* MLP backward is hand-chained on the recomputed activations (matmul
  transposes on the MXU), producing weight-gradient partial sums that
  accumulate across blocks in revisited output blocks (zeroed at block 0,
  `+=` thereafter — the canonical pallas accumulation pattern);
* table gradients apply the in-block BUM: per (level, grid) the block's
  update stream is segment-merged at unique addresses and committed with
  one scatter per run into the VMEM-resident gradient table.

Both kernels are validated with interpret=True against the ref backend
(allclose — the dedup pre-sum and per-block accumulation reassociate float
adds).  Neither lowers for the TPU at `FieldConfig()` widths, so
`repro.kernels.TPU_LOWERING` routes the op to `ref` there: the forward keeps
whole level tables in VMEM and sorts in-kernel, and the backward holds both
full (L,T,F) gradient tables resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..hash_encode import kernel as he_kernel

DEFAULT_BLOCK_POINTS = 256

_MLP_D_KEYS = ("w1", "b1", "w2", "b2")
_MLP_C_KEYS = ("w1", "b1", "w2", "b2", "w3", "b3")


def _dedup_encode_block(table, idx, weights):
    """Segment-sum dedup encode for one (block, level, grid) step.

    table (T,F), idx (B,8) int32, weights (B,8) f32 -> (B,F) f32.
    Mirrors `ref.dedup_weight_matrix` exactly: sorted address runs, per-run
    representative gather, per-point weight pre-sum, dense reconstruction
    matmul.  Sentinel rows (weight 0) produce all-zero W rows.
    """
    b = idx.shape[0]
    m = b * 8
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)
    sa = flat[order]
    is_start = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
    seg = jnp.cumsum(is_start) - 1
    uniq = jax.ops.segment_min(sa, seg, num_segments=m)
    uniq = jnp.minimum(uniq, jnp.max(flat))  # clamp empty-run INT32_MAX pads
    rows = table[uniq].astype(jnp.float32)  # (m, F): one gather per run
    pt = order // 8
    w_mat = jnp.zeros((b, m), jnp.float32).at[pt, seg].add(weights.reshape(-1)[order])
    return w_mat @ rows


def _mlp2_fwd(x, w1, b1, w2, b2):
    h1 = jnp.maximum(x @ w1.astype(jnp.float32) + b1, 0.0)
    return h1 @ w2.astype(jnp.float32) + b2


def _mlp3_fwd(x, w1, b1, w2, b2, w3, b3):
    h1 = jnp.maximum(x @ w1.astype(jnp.float32) + b1, 0.0)
    h2 = jnp.maximum(h1 @ w2.astype(jnp.float32) + b2, 0.0)
    return h2 @ w3.astype(jnp.float32) + b3


def _fused_step_kernel(res_ref, dd_ref, dc_ref, pts_ref, sh_ref, td_ref, tc_ref,
                       w1d_ref, b1d_ref, w2d_ref, b2d_ref,
                       w1c_ref, b1c_ref, w2c_ref, b2c_ref, w3c_ref, b3c_ref,
                       featd_ref, featc_ref, outd_ref, outc_ref):
    l = pl.program_id(1)
    num_l = pl.num_programs(1)
    f = td_ref.shape[-1]
    pts = pts_ref[...].astype(jnp.float32)

    # --- encode this level for both grids (shared corner geometry) ---
    idx_d, weights = he_kernel.corner_indices_block(
        pts, res_ref[l], dd_ref[l], td_ref.shape[1]
    )
    idx_c, _ = he_kernel.corner_indices_block(
        pts, res_ref[l], dc_ref[l], tc_ref.shape[1]
    )
    featd_ref[:, pl.ds(l * f, f)] = _dedup_encode_block(td_ref[0], idx_d, weights)
    featc_ref[:, pl.ds(l * f, f)] = _dedup_encode_block(tc_ref[0], idx_c, weights)

    # --- MLP epilogue on the VMEM-resident feature blocks ---
    @pl.when(l == num_l - 1)
    def _epilogue():
        hd = featd_ref[...]
        hc = featc_ref[...]
        outd_ref[...] = _mlp2_fwd(hd, w1d_ref[...], b1d_ref[...],
                                  w2d_ref[...], b2d_ref[...])
        cin = jnp.concatenate([hc, sh_ref[...].astype(jnp.float32)], axis=-1)
        outc_ref[...] = _mlp3_fwd(cin, w1c_ref[...], b1c_ref[...],
                                  w2c_ref[...], b2c_ref[...],
                                  w3c_ref[...], b3c_ref[...])


@functools.partial(jax.jit, static_argnames=("block_points", "interpret"))
def fused_step_pallas(points, sh, t_density, t_color, mlp_d: dict, mlp_c: dict,
                      resolutions, dense_d, dense_c, *,
                      block_points: int = DEFAULT_BLOCK_POINTS,
                      interpret: bool):
    """One-kernel forward.  points (N,3) sentinel-padded to block_points,
    sh (N,S); returns (out_d (N, 1+geo), raw_c (N,3)) f32."""
    n = points.shape[0]
    assert n % block_points == 0, (n, block_points)
    n_blocks = n // block_points
    num_l, td, f = t_density.shape
    tc = t_color.shape[1]
    s_dim = sh.shape[1]
    d_out = mlp_d["w2"].shape[1]

    def const2(a):  # whole array resident, revisited every step
        return pl.BlockSpec(a.shape, lambda i, l, *_: (0,) * a.ndim)

    weights = [mlp_d[k] for k in _MLP_D_KEYS] + [mlp_c[k] for k in _MLP_C_KEYS]
    _, _, out_d, out_c = pl.pallas_call(
        _fused_step_kernel,
        grid_spec=he_kernel.level_grid_spec(
            n_blocks, num_l, 3,  # resolution, dense (density), dense (color)
            in_specs=[
                pl.BlockSpec((block_points, 3), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((block_points, s_dim), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((1, td, f), lambda i, l, *_: (l, 0, 0)),  # one level/step
                pl.BlockSpec((1, tc, f), lambda i, l, *_: (l, 0, 0)),
            ] + [const2(w) for w in weights],
            out_specs=[
                # feature accumulators: revisited across the level axis, so the
                # concatenated (B, L*F) block stays VMEM-resident into the epilogue
                pl.BlockSpec((block_points, num_l * f), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((block_points, num_l * f), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((block_points, d_out), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((block_points, 3), lambda i, l, *_: (i, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, num_l * f), jnp.float32),
            jax.ShapeDtypeStruct((n, num_l * f), jnp.float32),
            jax.ShapeDtypeStruct((n, d_out), jnp.float32),
            jax.ShapeDtypeStruct((n, 3), jnp.float32),
        ],
        interpret=interpret,
    )(resolutions, dense_d, dense_c, points, sh, t_density, t_color, *weights)
    return out_d, out_c


def _fused_step_bwd_kernel(pts_ref, sh_ref, gd_ref, gc_ref,
                           res_ref, dd_ref, dc_ref, td_ref, tc_ref,
                           w1d_ref, b1d_ref, w2d_ref, b2d_ref,
                           w1c_ref, b1c_ref, w2c_ref, b2c_ref, w3c_ref, b3c_ref,
                           dtd_ref, dtc_ref,
                           dw1d_ref, db1d_ref, dw2d_ref, db2d_ref,
                           dw1c_ref, db1c_ref, dw2c_ref, db2c_ref,
                           dw3c_ref, db3c_ref, dsh_ref):
    i = pl.program_id(0)
    num_l = td_ref.shape[0]
    f = td_ref.shape[-1]
    pts = pts_ref[...].astype(jnp.float32)
    g_d = gd_ref[...].astype(jnp.float32)
    g_c = gc_ref[...].astype(jnp.float32)

    @pl.when(i == 0)
    def _zero_accumulators():
        for r in (dtd_ref, dtc_ref, dw1d_ref, db1d_ref, dw2d_ref, db2d_ref,
                  dw1c_ref, db1c_ref, dw2c_ref, db2c_ref, dw3c_ref, db3c_ref):
            r[...] = jnp.zeros(r.shape, r.dtype)

    # --- recompute corner geometry + features from the stashed points block
    # (the residual_policy="recompute" contract: no (L,N,8) weight loads) ---
    geom = []  # per level: (idx_d, idx_c, weights)
    hd_cols, hc_cols = [], []
    for l in range(num_l):
        idx_d, weights = he_kernel.corner_indices_block(
            pts, res_ref[l], dd_ref[l], td_ref.shape[1]
        )
        idx_c, _ = he_kernel.corner_indices_block(
            pts, res_ref[l], dc_ref[l], tc_ref.shape[1]
        )
        geom.append((idx_d, idx_c, weights))
        hd_cols.append(jnp.sum(
            weights[..., None] * td_ref[l][idx_d.reshape(-1)]
            .reshape(idx_d.shape + (f,)).astype(jnp.float32), axis=1))
        hc_cols.append(jnp.sum(
            weights[..., None] * tc_ref[l][idx_c.reshape(-1)]
            .reshape(idx_c.shape + (f,)).astype(jnp.float32), axis=1))
    hd = jnp.concatenate(hd_cols, axis=-1)
    hc = jnp.concatenate(hc_cols, axis=-1)

    # --- hand-chained MLP backward on recomputed activations ---
    w1d = w1d_ref[...].astype(jnp.float32)
    w2d = w2d_ref[...].astype(jnp.float32)
    z1d = hd @ w1d + b1d_ref[...]
    h1d = jnp.maximum(z1d, 0.0)
    g_h1d = jnp.where(z1d > 0, g_d @ w2d.T, 0.0)
    dw2d_ref[...] += h1d.T @ g_d
    db2d_ref[...] += jnp.sum(g_d, axis=0)
    dw1d_ref[...] += hd.T @ g_h1d
    db1d_ref[...] += jnp.sum(g_h1d, axis=0)
    g_hd = g_h1d @ w1d.T

    cin = jnp.concatenate([hc, sh_ref[...].astype(jnp.float32)], axis=-1)
    w1c = w1c_ref[...].astype(jnp.float32)
    w2c = w2c_ref[...].astype(jnp.float32)
    w3c = w3c_ref[...].astype(jnp.float32)
    z1c = cin @ w1c + b1c_ref[...]
    h1c = jnp.maximum(z1c, 0.0)
    z2c = h1c @ w2c + b2c_ref[...]
    h2c = jnp.maximum(z2c, 0.0)
    g_h2c = jnp.where(z2c > 0, g_c @ w3c.T, 0.0)
    g_h1c = jnp.where(z1c > 0, g_h2c @ w2c.T, 0.0)
    dw3c_ref[...] += h2c.T @ g_c
    db3c_ref[...] += jnp.sum(g_c, axis=0)
    dw2c_ref[...] += h1c.T @ g_h2c
    db2c_ref[...] += jnp.sum(g_h2c, axis=0)
    dw1c_ref[...] += cin.T @ g_h1c
    db1c_ref[...] += jnp.sum(g_h1c, axis=0)
    g_cin = g_h1c @ w1c.T
    g_hc = g_cin[:, : num_l * f]
    dsh_ref[...] = g_cin[:, num_l * f:]

    # --- table gradients: in-block BUM (segment-merge + one scatter per run)
    def commit(acc_ref, l, idx, g_feat, weights):
        b = idx.shape[0]
        m = b * 8
        upd = (weights[:, :, None] * g_feat[:, None, :]).reshape(-1, f)
        flat = idx.reshape(-1)
        order = jnp.argsort(flat)
        sa = flat[order]
        is_start = jnp.concatenate([jnp.ones((1,), bool), sa[1:] != sa[:-1]])
        seg = jnp.cumsum(is_start) - 1
        summed = jax.ops.segment_sum(upd[order], seg, num_segments=m)
        seg_idx = jax.ops.segment_min(sa, seg, num_segments=m)
        acc_ref[l, :, :] = acc_ref[l].at[seg_idx].add(summed, mode="drop")

    for l in range(num_l):
        idx_d, idx_c, weights = geom[l]
        commit(dtd_ref, l, idx_d, g_hd[:, l * f:(l + 1) * f], weights)
        commit(dtc_ref, l, idx_c, g_hc[:, l * f:(l + 1) * f], weights)


@functools.partial(jax.jit, static_argnames=("block_points", "interpret"))
def fused_step_bwd_pallas(points, sh, g_d, g_c, t_density, t_color,
                          mlp_d: dict, mlp_c: dict,
                          resolutions, dense_d, dense_c, *,
                          block_points: int = DEFAULT_BLOCK_POINTS,
                          interpret: bool):
    """Hand-written one-kernel backward.  Inputs padded like the forward
    (g rows zero on pad lanes); returns (d_t_density, d_t_color, d_mlp_d,
    d_mlp_c, d_sh)."""
    n = points.shape[0]
    assert n % block_points == 0, (n, block_points)
    n_blocks = n // block_points
    num_l, td, f = t_density.shape
    tc = t_color.shape[1]
    s_dim = sh.shape[1]
    d_out = mlp_d["w2"].shape[1]

    def block2(cols):
        return pl.BlockSpec((block_points, cols), lambda i: (i, 0))

    def const(a):
        shape = a.shape
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    weights = [mlp_d[k] for k in _MLP_D_KEYS] + [mlp_c[k] for k in _MLP_C_KEYS]
    acc_shape = [jax.ShapeDtypeStruct(t_density.shape, jnp.float32),
                 jax.ShapeDtypeStruct(t_color.shape, jnp.float32)] + [
        jax.ShapeDtypeStruct(w.shape, jnp.float32) for w in weights
    ]
    outs = pl.pallas_call(
        _fused_step_bwd_kernel,
        grid=(n_blocks,),
        in_specs=[
            block2(3), block2(s_dim), block2(d_out), block2(3),
            const(resolutions), const(dense_d), const(dense_c),
            const(t_density), const(t_color),
        ] + [const(w) for w in weights],
        out_specs=[const(s) for s in acc_shape] + [block2(s_dim)],
        out_shape=acc_shape + [jax.ShapeDtypeStruct((n, s_dim), jnp.float32)],
        interpret=interpret,
    )(points, sh, g_d, g_c, resolutions, dense_d, dense_c,
      t_density, t_color, *weights)
    d_td, d_tc = outs[0], outs[1]
    wg = outs[2:12]
    d_mlp_d = dict(zip(_MLP_D_KEYS, wg[:4]))
    d_mlp_c = dict(zip(_MLP_C_KEYS, wg[4:]))
    return (d_td.astype(t_density.dtype), d_tc.astype(t_color.dtype),
            d_mlp_d, d_mlp_c, outs[12])
