"""Pallas TPU kernel: BUM — Back-propagation Update Merger (TPU adaptation).

The paper's BUM is a CAM-like buffer that merges SRAM writes to the same hash
address within a sliding window before committing them.  The TPU has no CAM;
the idiomatic equivalent (DESIGN.md §3) is:

    sort updates by address  ->  merge runs of equal addresses  ->  one
    write per table row.

The sort happens once in XLA (`ops.merged_scatter_add`); this kernel merges
the address-sorted stream and commits it, *output-stationary*:

* the table is cut into blocks of `block_rows` rows and the sorted stream
  into tiles of `tile` rows.  Because the stream is sorted, each table
  block's rows are one contiguous run of it; `plan` finds the runs with a
  `searchsorted` over the block starts and enumerates the (block, tile)
  pairs that overlap — the work items — the way a grouped matmul builds its
  group metadata.  Their number is at most blocks + tiles (consecutive
  blocks share at most one tile), a static bound; padding items do nothing;
* the grid walks the work items in order, the item lists arriving as
  scalar-prefetch operands that pick each step's stream tile and table
  block.  A step builds the one-hot `(idx - r0 == row)` tile and adds its
  product with the values into the resident output block.  Rows of the
  tile outside the block, and the sentinel padding rows (`idx == T`), match
  no row.  Consecutive items of one block accumulate in VMEM, and the block
  is written back once, when the block changes;
* the table is input/output-aliased: a block no row touches is never
  visited and keeps its input value, and a visited block starts from its
  input value (zeros in a hash encoder's backward).  The item lists live
  in SMEM, so a stream with more than `MAX_ITEMS` items is merged by
  several calls in turn, each over its share of the items; a block cut by
  a call's edge resumes from what the call before wrote.

Layout: the rows are F = 2 floats wide at the hash grids' widths, so the
kernel works on the transposed arrays — values (F, M), table (F, T) — with
the stream and the table rows on the 128 lanes and F on sublanes.  A
`(block, tile) @ (tile, F)` product would pad F to 128 lanes and do ~60x
the MXU work; here the product is `(3F, tile) . (block, tile)^T`, F rows
against a one-hot.

Precision: the one-hot's entries are 0 or 1, exact in bfloat16, so each
float32 value is split into three bfloat16 pieces (hi + mid + lo == value
exactly) stacked as 3F rows of one bfloat16 product with float32
accumulation: every product is exact and every sum is a float32 add, in
one MXU pass and whatever JAX's default matmul precision is set to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256   # table rows per output block (lanes of a (F, block) tile)
DEFAULT_TILE = 1024        # sorted-stream rows per work item
MAX_ITEMS = 1 << 16        # work items per call: their two int32 lists take half
                           # of a v5e core's 1 MiB of SMEM


def plan(idx_sorted: jnp.ndarray, n_rows: int, block_rows: int, tile: int):
    """Work items of the merge: (table block, stream tile) pairs in order.

    idx_sorted (M,) non-decreasing, M a multiple of `tile`; rows outside
    [0, n_rows) belong to no block.  Returns (blocks (W,), tiles (W,),
    total (1,)) int32 with W = ceil(n_rows / block_rows) + M / tile, the
    static bound; items at or past `total` repeat the last real item and
    do nothing.
    """
    m = idx_sorted.shape[0]
    n_blocks = -(-n_rows // block_rows)
    n_tiles = m // tile
    w_max = n_blocks + n_tiles
    bounds = jnp.minimum(jnp.arange(n_blocks + 1, dtype=jnp.int32) * block_rows, n_rows)
    edges = jnp.searchsorted(idx_sorted, bounds, side="left").astype(jnp.int32)
    starts, ends = edges[:-1], edges[1:]
    first_tile = starts // tile
    count = jnp.where(ends > starts, (ends - 1) // tile - first_tile + 1, 0)
    offs = jnp.cumsum(count)
    total = offs[-1]
    # each block with rows marks its first item; an item belongs to the last
    # mark at or before it, and items past the last real one keep its block
    marks = jnp.zeros((w_max,), jnp.int32).at[jnp.where(count > 0, offs - count, w_max)].max(
        jnp.arange(n_blocks, dtype=jnp.int32), mode="drop")
    blocks = jax.lax.cummax(marks)
    w = jnp.minimum(jnp.arange(w_max, dtype=jnp.int32), jnp.maximum(total - 1, 0))
    tiles = first_tile[blocks] + w - (offs[blocks] - count[blocks])
    return blocks, jnp.clip(tiles, 0, n_tiles - 1).astype(jnp.int32), total.reshape(1)


def _bum_kernel(blocks_ref, tiles_ref, total_ref, idx_ref, val_ref, tbl_ref, out_ref):
    del tiles_ref  # consumed by the index maps
    w = pl.program_id(0)
    f, block_rows = out_ref.shape
    blk = blocks_ref[w]

    @pl.when(jnp.logical_or(w == 0, blk != blocks_ref[jnp.maximum(w - 1, 0)]))
    def _open_block():
        out_ref[...] = tbl_ref[...]

    @pl.when(w < total_ref[0])
    def _accumulate():
        local = idx_ref[...] - blk * block_rows  # (1, tile)
        rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, local.shape[1]), 0)
        one_hot = (rows == local).astype(jnp.bfloat16)  # (block, tile)
        v = val_ref[...]  # (F, tile) f32
        hi = v.astype(jnp.bfloat16)
        rest = v - hi.astype(jnp.float32)
        mid = rest.astype(jnp.bfloat16)
        lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            jnp.concatenate([hi, mid, lo], axis=0), one_hot,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        )  # (3F, block)
        out_ref[...] += part[:f] + part[f:2 * f] + part[2 * f:]


@functools.partial(jax.jit, static_argnames=("block_rows", "tile", "max_items", "interpret"))
def bum_scatter_pallas(
    table: jnp.ndarray,
    idx_sorted: jnp.ndarray,
    vals_sorted: jnp.ndarray,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    tile: int = DEFAULT_TILE,
    max_items: int = MAX_ITEMS,
    interpret: bool,
) -> jnp.ndarray:
    """Merged scatter-add of a sorted update stream into table (T, F).

    idx_sorted (M,) int32 ascending; padding entries equal T (or lie outside
    [0, T): they are dropped).  vals_sorted (M, F).  M must be a multiple
    of `tile`.  Returns the updated (T, F) table.
    """
    t, f = table.shape
    m = idx_sorted.shape[0]
    assert m % tile == 0, (m, tile)
    pad = (-t) % block_rows
    table_t = table.astype(jnp.float32).T
    if pad:
        table_t = jnp.concatenate([table_t, jnp.zeros((f, pad), jnp.float32)], axis=1)
    blocks, tiles, total = plan(idx_sorted, t, block_rows, tile)
    idx_2d, vals_t = idx_sorted.reshape(1, m), vals_sorted.astype(jnp.float32).T
    out = table_t
    for lo in range(0, blocks.shape[0], max_items):
        hi = min(lo + max_items, blocks.shape[0])
        out = pl.pallas_call(
            _bum_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(hi - lo,),
                in_specs=[
                    pl.BlockSpec((1, tile), lambda w, b, s, n: (0, s[w])),
                    pl.BlockSpec((f, tile), lambda w, b, s, n: (0, s[w])),
                    pl.BlockSpec((f, block_rows), lambda w, b, s, n: (0, b[w])),
                ],
                out_specs=pl.BlockSpec((f, block_rows), lambda w, b, s, n: (0, b[w])),
            ),
            out_shape=jax.ShapeDtypeStruct(table_t.shape, jnp.float32),
            input_output_aliases={5: 0},
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(blocks[lo:hi], tiles[lo:hi], jnp.clip(total - lo, 0, hi - lo), idx_2d, vals_t, out)
    return out[:, :t].T.astype(table.dtype)
