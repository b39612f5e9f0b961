"""Pallas kernel for the fused compacted-path encode (FMU-style block dedup).

Differences from the plain `hash_encode` kernel (which issues one vectorized
gather of all B*8 corner addresses in point order):

* The caller feeds Morton-sorted points, so a block's corner addresses are
  quasi-sorted and heavily duplicated (points in one grid cell share all 8
  corners).  The kernel sorts the block's address vector and gathers in that
  order — duplicate addresses become *adjacent* lanes of one gather, which
  is the memory-system shape the FMU exploits: one bank read broadcast to
  every lane of a run.  On TPU the sorted gather turns random VMEM banking
  into sequential runs; in interpret mode it is numerically identical to the
  unsorted gather (same rows fetched).
* Corner features are staged entirely in VMEM registers — the (B, 8, F)
  per-point corner tensor never exists in HBM; only the (B, F) per-level
  output block is written out.
* Sentinel-padded rows (coordinate < 0, see hash_encode.ops.PAD_SENTINEL)
  read row 0 only and contribute exactly zero output.

Grid iterates (point-block, level) like the hash_encode kernel, one level
table resident in VMEM per step — which is why, like hash_encode, the op has
no TPU lowering at `FieldConfig()` widths (see `repro.kernels.TPU_LOWERING`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..hash_encode import kernel as he_kernel

DEFAULT_BLOCK_POINTS = 256


def _fused_encode_kernel(res_ref, dense_ref, pts_ref, tbl_ref, out_ref):
    """One (point-block, level) step with block-sorted (deduped) corner reads."""
    l = pl.program_id(1)
    table = tbl_ref[0]  # (T, F)
    pts = pts_ref[...].astype(jnp.float32)  # (B, 3)
    # corner enumeration + sentinel semantics shared with the hash_encode
    # kernel — only the gather strategy below differs
    idx, weights = he_kernel.corner_indices_block(
        pts, res_ref[l], dense_ref[l], table.shape[0]
    )

    # FMU analogue: sort the block's corner addresses so duplicates occupy
    # adjacent lanes of ONE gather (a run of equal addresses = one coalesced
    # table read), then scatter the fetched rows back to point order.  All of
    # this stays in VMEM; the (B, 8, F) corner tensor never reaches HBM.
    flat = idx.reshape(-1)  # (B*8,)
    order = jnp.argsort(flat)
    feats_sorted = table[flat[order]]  # (B*8, F) — duplicate-adjacent reads
    feats = (
        jnp.zeros_like(feats_sorted)
        .at[order]
        .set(feats_sorted)
        .reshape(idx.shape + (table.shape[-1],))
    )

    out_ref[...] = jnp.sum(
        weights[..., None] * feats.astype(jnp.float32), axis=1
    )[:, None, :].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_points", "interpret"))
def fused_encode_pallas(
    points: jnp.ndarray,
    tables: jnp.ndarray,
    resolutions: jnp.ndarray,
    dense_flags: jnp.ndarray,
    *,
    block_points: int = DEFAULT_BLOCK_POINTS,
    interpret: bool,
) -> jnp.ndarray:
    """points (N,3) f32, tables (L,T,F), resolutions/dense_flags (L,) i32.

    Returns (N, L*F) f32.  N must be a multiple of block_points (ops pads
    with the sentinel).
    """
    n = points.shape[0]
    num_l, t, f = tables.shape
    assert n % block_points == 0, (n, block_points)
    n_blocks = n // block_points

    out = pl.pallas_call(
        _fused_encode_kernel,
        grid_spec=he_kernel.level_grid_spec(
            n_blocks, num_l, 2,
            in_specs=[
                pl.BlockSpec((block_points, 3), lambda i, l, *_: (i, 0)),
                pl.BlockSpec((1, t, f), lambda i, l, *_: (l, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_points, 1, f), lambda i, l, *_: (i, l, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, num_l, f), jnp.float32),
        interpret=interpret,
    )(resolutions, dense_flags, points, tables)
    return out.reshape(n, num_l * f)
