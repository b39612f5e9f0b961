"""Jitted wrappers for BUM-style merged grid updates.

`merged_scatter_add` is the production path: sort-by-address + run merge +
one write per row.  It is mathematically identical to the naive duplicate
scatter-add (ref.py) but removes write collisions — the TPU analogue of the
paper's BUM unit (DESIGN.md §3).  The merge and commit route through the
`repro.kernels` registry as op "bum_scatter", by the rows' width: the XLA
segment merge on `ref`; the output-stationary Pallas kernel (kernel.py) on
`pallas-interpret`, and on `pallas-tpu` for rows up to
`repro.kernels.TPU_MAX_WIDTH["bum_scatter"]` wide, which `auto` picks on a
TPU.  Each trace of a
commit counts `grid_update.commit.<backend>` in `repro.obs` (knob on).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from ...obs import metrics as _metrics
from ...obs import trace as _trace


def _sort_updates(idx: jnp.ndarray, vals: jnp.ndarray, table_size: int, pad_to: int | None,
                  presorted: bool = False):
    """Sort the update stream by address; pad with spill-row entries.

    presorted=True skips the argsort: the caller guarantees idx is already
    non-decreasing (e.g. the fused-path VJP, which emits the stream through
    the stable order computed once in its forward pass).  Because jnp.argsort
    is stable, sorting an already-sorted stream is the identity permutation,
    so both paths are bit-identical on sorted input.
    """
    with _trace.stage("grid_update/sort", cat="kernels"):
        if presorted:
            idx_s, vals_s = idx, vals
        else:
            order = jnp.argsort(idx)
            idx_s = idx[order]
            vals_s = vals[order]
        if pad_to is not None and idx.shape[0] % pad_to != 0:
            pad = pad_to - idx.shape[0] % pad_to
            idx_s = jnp.concatenate([idx_s, jnp.full((pad,), table_size, jnp.int32)])
            vals_s = jnp.concatenate([vals_s, jnp.zeros((pad,) + vals.shape[1:], vals.dtype)])
        return idx_s, vals_s


def _segment_commit(table: jnp.ndarray, idx_s: jnp.ndarray, vals_s: jnp.ndarray) -> jnp.ndarray:
    """Segment-merge an address-SORTED stream and scatter once per run.

    The single definition of the XLA merge body: `merged_scatter_add` calls
    it directly and the windowed/stacked commit scans it per window, so a
    one-window stacked commit is bit-identical to one merged commit by
    construction (same ops, same segment count).
    """
    m = idx_s.shape[0]
    with _trace.stage("grid_update/merge", cat="kernels"):
        is_start = jnp.concatenate([jnp.ones((1,), bool), idx_s[1:] != idx_s[:-1]])
        seg_id = jnp.cumsum(is_start) - 1  # (M,)
        summed = jax.ops.segment_sum(vals_s.astype(jnp.float32), seg_id, num_segments=m)
        # Representative address per run; empty trailing segments get INT32_MAX
        # from segment_min's identity and are dropped by the scatter.
        seg_idx = jax.ops.segment_min(idx_s, seg_id, num_segments=m)
    with _trace.stage("grid_update/commit", cat="kernels"):
        return table.at[seg_idx].add(summed.astype(table.dtype), mode="drop")


def _commit(table: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray, be,
            presorted: bool) -> jnp.ndarray:
    """Sort, merge and commit one stream on the resolved backend `be`."""
    if _trace.enabled():
        _metrics.counter(f"grid_update.commit.{be.name}").inc()
    t = table.shape[0]
    if be.use_pallas:
        idx_s, vals_s = _sort_updates(idx, vals, t, _kernel.DEFAULT_TILE, presorted=presorted)
        with _trace.stage("grid_update/commit", cat="kernels"):
            return _kernel.bum_scatter_pallas(table, idx_s, vals_s, interpret=be.interpret)
    idx_s, vals_s = _sort_updates(idx, vals, t, None, presorted=presorted)
    return _segment_commit(table, idx_s, vals_s)


@functools.partial(jax.jit, static_argnames=("backend", "presorted"))
def merged_scatter_add(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    backend=None,
    presorted: bool = False,
) -> jnp.ndarray:
    """table (T,F) += vals (M,F) at rows idx (M,) with BUM-merged writes.

    `backend` (a `repro.kernels` registry name or KernelBackend; None =>
    process default) picks the merge and commit, resolved as op
    "bum_scatter" at the rows' width F: the XLA segment merge on ref, the
    Pallas kernel otherwise.

    presorted=True promises idx is already non-decreasing and skips the
    argsort — the BUM fast path for callers that control update order (the
    fused compacted-path VJP emits its table-gradient stream pre-sorted).
    """
    from .. import resolve_backend
    be = resolve_backend(backend, op="bum_scatter", width=table.shape[-1])
    return _commit(table, idx, vals, be, presorted)


@jax.jit
def num_unique_addresses(idx: jnp.ndarray) -> jnp.ndarray:
    """How many unique table rows a batch of updates touches (Fig. 10 stat)."""
    s = jnp.sort(idx)
    return jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]]).sum()


@functools.partial(jax.jit, static_argnames=("window", "presorted", "backend"))
def windowed_scatter_add(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    window: int = 4096,
    presorted: bool = False,
    backend=None,
) -> jnp.ndarray:
    """BUM with the paper's *sliding window*: merge duplicates only within
    windows of the update stream, then commit each window's merged updates
    in stream order.

    Two window shapes are supported:

    * idx (M,) — legacy fixed-size chunking: one long stream is cut into
      `window`-sized pieces.  The faithful adaptation for data-parallel
      settings (EXPERIMENTS.md §Perf iteration 3): a GLOBAL sort must
      materialize and gather every (update, d_model) vector across shards;
      windows bound the live set to (window x F) regardless of stream
      length, exactly like the paper's 16-deep CAM bounds SRAM.
    * idx (W, M) with vals (W, M, F) — *stacked per-step streams*, the real
      BUM-across-iterations analogue: each row is one training step's
      gradient stream (e.g. the color grid's updates accumulated across an
      F_D:F_C update-frequency window), and the whole window commits as one
      `lax.scan` of the shared `_segment_commit` merge body in step order.
      Because each scan iteration runs exactly the ops `merged_scatter_add`
      would run for that step, the windowed commit is BIT-identical to W
      sequential per-step commits — additivity buys merging, not
      reassociation (property-tested across the {1:1, 1:0.5, 1:0.25}
      schedules in tests/test_grid_update.py).

    presorted=True promises every row of idx is already non-decreasing and
    skips the per-window argsort (the fused-step VJP emits rows through the
    stable order its forward — or recompute-policy backward — planned).
    `backend` picks each window's commit stage, same contract as
    `merged_scatter_add`.
    """
    from .. import resolve_backend
    t, f = table.shape
    be = resolve_backend(backend, op="bum_scatter", width=f)

    if idx.ndim == 1:
        m = idx.shape[0]
        pad = (-m) % window
        if pad:
            idx = jnp.concatenate([idx, jnp.full((pad,), t, jnp.int32)])
            vals = jnp.concatenate([vals, jnp.zeros((pad, f), vals.dtype)])
        n_win = idx.shape[0] // window
        idx = idx.reshape(n_win, -1)
        vals = vals.reshape(n_win, -1, f)

    vals = vals.astype(jnp.float32)

    def commit_window(tbl, inp):
        wi, wv = inp
        return _commit(tbl, wi, wv, be, presorted), None

    # Small static window counts (every per-step caller: the fused-step VJP
    # commits W=1; the F_D:F_C schedules make W<=4) unroll to straightline
    # code — a length-1 lax.scan still lowers to an XLA while loop that
    # dynamic-slices the whole stream per trip.  Same body, same order, so
    # the result stays bit-identical to the scan.
    if idx.shape[0] <= 8:
        out = table
        for w in range(idx.shape[0]):
            out, _ = commit_window(out, (idx[w], vals[w]))
        return out
    out, _ = jax.lax.scan(commit_window, table, (idx, vals))
    return out
