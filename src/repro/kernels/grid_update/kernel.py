"""Pallas TPU kernel: BUM — Back-propagation Update Merger (TPU adaptation).

The paper's BUM is a CAM-like buffer that merges SRAM writes to the same hash
address within a sliding window before committing them.  The TPU has no CAM;
the idiomatic equivalent (DESIGN.md §3) is:

    sort updates by address  ->  merge runs of equal addresses  ->  one
    scatter per unique address.

The sort happens once in XLA (`ops.merged_scatter_add`); this kernel performs
the *merge + commit* stage on sorted input:

* grid steps walk the sorted update stream in blocks (the "sliding window",
  except the window is a whole VMEM block — strictly stronger merging than
  the paper's 16-deep buffer);
* run detection is a shifted compare; the per-run sums are computed with a
  one-hot matmul (segment-id one-hot  @  values), putting the accumulation on
  the MXU instead of a serial CAM;
* each block commits at most one write per unique address; the output table
  is input/output-aliased and blocks accumulate sequentially (TPU grid order
  is sequential, so read-modify-write across steps is sound).

Cross-block duplicate addresses (a run straddling a block edge) cost one
extra commit — same behaviour as the paper's BUM when a run exceeds the
buffer depth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 512


def _bum_kernel(idx_ref, val_ref, tbl_ref, out_ref):
    b = idx_ref.shape[0]
    t_plus_1 = out_ref.shape[0]
    idx = idx_ref[...]  # (B,) int32, sorted; padding rows carry idx == T
    vals = val_ref[...].astype(jnp.float32)  # (B, F)

    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = tbl_ref[...]

    # Run detection on the sorted stream.
    prev = jnp.concatenate([idx[:1] - 1, idx[:-1]])
    is_start = idx != prev  # (B,) — first row of each equal-address run
    seg_id = jnp.cumsum(is_start.astype(jnp.int32)) - 1  # (B,) in [0, B)

    # One-hot matmul segment sum: (B, B) @ (B, F) on the MXU.
    one_hot = (seg_id[None, :] == jnp.arange(b, dtype=jnp.int32)[:, None]).astype(
        jnp.float32
    )
    seg_sums = one_hot @ vals  # (B, F), row s = sum of run s

    # Commit one write per run start; non-starts write +0 to the spill row T.
    write_vals = jnp.where(is_start[:, None], seg_sums[seg_id], 0.0)
    write_idx = jnp.where(is_start, idx, t_plus_1 - 1)

    out_ref[write_idx] = out_ref[write_idx] + write_vals.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bum_scatter_pallas(
    table: jnp.ndarray,
    idx_sorted: jnp.ndarray,
    vals_sorted: jnp.ndarray,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool,
) -> jnp.ndarray:
    """Merged scatter-add of a sorted update stream into table (T, F).

    idx_sorted (M,) int32 ascending; padding entries must equal T (spill row).
    vals_sorted (M, F).  M must be a multiple of `block`.
    Returns the updated (T, F) table.
    """
    t, f = table.shape
    m = idx_sorted.shape[0]
    assert m % block == 0, (m, block)

    table_ext = jnp.concatenate(
        [table.astype(jnp.float32), jnp.zeros((1, f), jnp.float32)], axis=0
    )
    out = pl.pallas_call(
        _bum_kernel,
        grid=(m // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block, f), lambda i: (i, 0)),
            pl.BlockSpec((t + 1, f), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((t + 1, f), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t + 1, f), jnp.float32),
        interpret=interpret,
    )(idx_sorted, vals_sorted, table_ext)
    return out[:t].astype(table.dtype)
