"""Pallas TPU kernel for multiresolution hash-grid encoding.

TPU adaptation of the paper's grid cores + FRM unit (DESIGN.md §3):

* Each level's full hash table is one VMEM block — the analogue of the
  paper's on-chip multi-bank SRAM hash-table storage.  That only fits small
  tables: VMEM pads the trailing F=2 axis to the 128-lane tiling, so a
  2^18 x 2 f32 level takes 2^18 x 128 x 4 B = 128 MiB, against v5e's 16 MiB
  default scoped VMEM limit.  At `FieldConfig()` widths the op therefore has
  no TPU lowering and `repro.kernels.TPU_LOWERING` routes it to `ref`.
* Points are processed in VREG-aligned blocks; all 8 corner reads of a block
  are issued as one vectorized gather per level — the batch-granularity
  analogue of the FRM mapping many single reads into one multi-bank access.
* The grid iterates (point-block, level); BlockSpec index maps stream one
  level table at a time HBM->VMEM, so the VMEM working set is
  |table_level| + |point block| + |out block| regardless of L.
* Level geometry (resolution, dense flag) arrives through scalar prefetch
  (SMEM) and is indexed by the level grid coordinate.

Layout notes for real TPU lowering: the trailing feature dim F (typically 2)
is below the 128-lane width; production tables should be stored feature-major
padded to the lane width, or multiple levels packed per lane group.  The
kernel is written shape-generically and validated with interpret=True;
`ops.py` routes to the jnp oracle on CPU and on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

DEFAULT_BLOCK_POINTS = 256


def corner_indices_block(pts, resolution, dense, t):
    """Shared in-kernel corner enumeration for one (point-block, level) step.

    pts (B,3) f32, resolution/dense scalars, t = table rows.  Returns
    (idx (B,8) int32, weights (B,8) f32) with sentinel rows (coordinate < 0,
    ops.PAD_SENTINEL padding) pinned to row 0 and zero weight — they must
    not hash into live table cells nor contribute output.  Used by both the
    plain hash_encode kernel and the fused-path kernel, so hashing/sentinel
    semantics cannot diverge between them.
    """
    valid = pts[:, 0] >= 0.0  # (B,)
    scaled = pts * resolution.astype(jnp.float32)
    base = jnp.floor(scaled)
    frac = scaled - base  # (B, 3)

    # Corner offsets {0,1}^3 generated in-kernel (Pallas kernels cannot
    # capture host constants): bit d of corner id c selects dim d's +1.
    cid = jax.lax.broadcasted_iota(jnp.int32, (8, 3), 0)
    dim = jax.lax.broadcasted_iota(jnp.int32, (8, 3), 1)
    offs = (cid >> dim) & 1  # (8, 3) int32; row c = (c&1, c>>1&1, c>>2&1) == ref.CORNERS
    corners = base.astype(jnp.int32)[:, None, :] + offs[None, :, :]  # (B, 8, 3)

    ix, iy, iz = corners[..., 0], corners[..., 1], corners[..., 2]
    # Dense index, computed in uint32 (wraps harmlessly when the level is
    # hashed and the product overflows — the `where` discards it).
    stride = (resolution + 1).astype(jnp.uint32)
    dense_idx = (
        ix.astype(jnp.uint32) + iy.astype(jnp.uint32) * stride
        + iz.astype(jnp.uint32) * stride * stride
    ).astype(jnp.int32)
    hash_idx = (
        (
            ix.astype(jnp.uint32) * ref.PI1
            ^ iy.astype(jnp.uint32) * ref.PI2
            ^ iz.astype(jnp.uint32) * ref.PI3
        )
        & jnp.uint32(t - 1)
    ).astype(jnp.int32)
    idx = jnp.where(dense > 0, dense_idx, hash_idx)  # (B, 8)
    idx = jnp.where(valid[:, None], idx, 0)  # sentinel rows read row 0 only

    offs_f = offs.astype(jnp.float32)  # (8, 3)
    w = jnp.where(offs_f[None, :, :] > 0, frac[:, None, :], 1.0 - frac[:, None, :])
    # explicit product: Mosaic has no reduce_prod lowering
    weights = w[..., 0] * w[..., 1] * w[..., 2] * valid.astype(jnp.float32)[:, None]  # (B, 8)
    return idx, weights


def level_grid_spec(n_blocks: int, num_l: int, n_geometry: int, *, in_specs, out_specs):
    """(point-block, level) grid whose first `n_geometry` operands are (L,)
    int32 level-geometry arrays passed by scalar prefetch into SMEM — the
    TPU lowering refuses (1,) VMEM blocks of an (L,) array.  Shared by every
    hash kernel that walks the level axis."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_geometry, grid=(n_blocks, num_l),
        in_specs=in_specs, out_specs=out_specs,
    )


def _encode_kernel(res_ref, dense_ref, pts_ref, tbl_ref, out_ref):
    """One (point-block, level) grid step; res/dense are the (L,) SMEM
    scalar-prefetch operands."""
    l = pl.program_id(1)
    table = tbl_ref[0]  # (T, F)
    pts = pts_ref[...].astype(jnp.float32)  # (B, 3)
    idx, weights = corner_indices_block(pts, res_ref[l], dense_ref[l], table.shape[0])

    # FRM analogue: one vectorized gather for the whole block's 8 corners.
    feats = table[idx.reshape(-1)].reshape(idx.shape + (table.shape[-1],))

    out_ref[...] = jnp.sum(
        weights[..., None] * feats.astype(jnp.float32), axis=1
    )[:, None, :].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_points", "interpret"))
def hash_encode_pallas(
    points: jnp.ndarray,
    tables: jnp.ndarray,
    resolutions: jnp.ndarray,
    dense_flags: jnp.ndarray,
    *,
    block_points: int = DEFAULT_BLOCK_POINTS,
    interpret: bool,
) -> jnp.ndarray:
    """points (N,3) f32, tables (L,T,F), resolutions/dense_flags (L,) i32.

    Returns (N, L*F) f32.  N must be a multiple of block_points (ops.py pads).
    """
    n = points.shape[0]
    num_l, t, f = tables.shape
    assert n % block_points == 0, (n, block_points)
    n_blocks = n // block_points

    out = pl.pallas_call(
        _encode_kernel,
        grid_spec=level_grid_spec(
            n_blocks, num_l, 2,
            in_specs=[
                pl.BlockSpec((block_points, 3), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((1, t, f), lambda i, l, *_: (l, 0, 0)),  # whole level
            ],
            out_specs=pl.BlockSpec((block_points, 1, f), lambda i, l, *_: (i, l, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, num_l, f), jnp.float32),
        interpret=interpret,
    )(resolutions, dense_flags, points, tables)
    return out.reshape(n, num_l * f)
