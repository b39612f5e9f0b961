"""Kernel validation: BUM merged scatter — merged == naive, Pallas == naive."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.grid_update import ref, ops, kernel


@pytest.mark.parametrize("t,f,m", [(64, 2, 300), (512, 2, 3000), (128, 4, 999), (16, 1, 64)])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_merged_matches_naive(t, f, m, use_pallas, rng):
    table = jnp.asarray(rng.normal(size=(t, f)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, t, size=m).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))
    naive = ref.scatter_add(table, idx, vals)
    backend = "pallas-interpret" if use_pallas else "ref"
    merged = ops.merged_scatter_add(table, idx, vals, backend=backend)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(naive), atol=1e-4, rtol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    t=st.sampled_from([16, 64, 256]),
    m=st.integers(1, 400),
    seed=st.integers(0, 2**31 - 1),
    heavy_collisions=st.booleans(),
)
def test_merge_property(t, m, seed, heavy_collisions):
    """Property: for ANY update stream, merged result == naive scatter-add."""
    r = np.random.default_rng(seed)
    hi = max(t // 16, 1) if heavy_collisions else t
    idx = jnp.asarray(r.integers(0, hi, size=m).astype(np.int32))
    vals = jnp.asarray(r.normal(size=(m, 2)).astype(np.float32))
    table = jnp.zeros((t, 2), jnp.float32)
    naive = ref.scatter_add(table, idx, vals)
    merged = ops.merged_scatter_add(table, idx, vals)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(naive), atol=1e-4, rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    t=st.sampled_from([16, 64, 256]),
    m=st.integers(1, 400),
    seed=st.integers(0, 2**31 - 1),
    heavy_collisions=st.booleans(),
)
def test_presorted_property(t, m, seed, heavy_collisions):
    """Property: on an address-sorted stream, presorted=True (skip argsort)
    is BIT-identical to the unsorted path — stable argsort of sorted input
    is the identity, so both run the same segment merge."""
    r = np.random.default_rng(seed)
    hi = max(t // 16, 1) if heavy_collisions else t
    idx = np.sort(r.integers(0, hi, size=m).astype(np.int32))
    vals = jnp.asarray(r.normal(size=(m, 2)).astype(np.float32))
    table = jnp.asarray(r.normal(size=(t, 2)).astype(np.float32))
    idx = jnp.asarray(idx)
    fast = ops.merged_scatter_add(table, idx, vals, presorted=True)
    slow = ops.merged_scatter_add(table, idx, vals, presorted=False)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


def test_presorted_pallas_matches(rng):
    """presorted routing also reaches the Pallas commit kernel unchanged."""
    t, m = 128, 500
    idx = jnp.asarray(np.sort(rng.integers(0, t, size=m)).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(m, 2)).astype(np.float32))
    table = jnp.zeros((t, 2), jnp.float32)
    naive = ref.scatter_add(table, idx, vals)
    fast = ops.merged_scatter_add(table, idx, vals, backend="pallas-interpret",
                                  presorted=True)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(naive), atol=1e-4, rtol=1e-5)


def test_unique_counting(rng):
    idx = jnp.asarray(np.array([1, 1, 2, 5, 5, 5, 9], np.int32))
    assert int(ops.num_unique_addresses(idx)) == 4


def test_merge_reduces_writes(rng):
    """The architectural claim (paper Fig. 10): backward streams have ~5x
    address duplication, so the merged stream is much shorter."""
    m = 8000
    idx = jnp.asarray(rng.integers(0, 1000, size=m).astype(np.int32))  # duplicates
    uniq = int(ops.num_unique_addresses(idx))
    assert uniq < m / 5


@pytest.mark.parametrize("freq,expected_w", [(1.0, 1), (0.5, 2), (0.25, 4)])
@pytest.mark.parametrize("presorted", [True, False])
def test_windowed_stacked_commits_match_per_step_across_schedules(
        freq, expected_w, presorted, rng):
    """BUM across iterations: gradient streams accumulated over an F_D:F_C
    update-frequency window ({1:1, 1:0.5, 1:0.25}) and committed as ONE
    stacked windowed call are BIT-identical to committing every step's
    stream sequentially — additivity buys merging, not reassociation.  The
    window boundaries come from the trainer's real schedule predicate."""
    from repro.core.trainer import _branch_update

    t, f, m = 96, 2, 200
    table_seq = jnp.asarray(rng.normal(size=(t, f)).astype(np.float32))
    table_win = table_seq
    pending_idx, pending_vals = [], []
    for i in range(8):
        idx = rng.integers(0, t, size=m).astype(np.int32)
        if presorted:
            idx = np.sort(idx)
        idx = jnp.asarray(idx)
        vals = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))
        pending_idx.append(idx)
        pending_vals.append(vals)
        table_seq = ops.merged_scatter_add(table_seq, idx, vals,
                                           presorted=presorted)
        if _branch_update(i, freq):
            assert len(pending_idx) == expected_w
            table_win = ops.windowed_scatter_add(
                table_win, jnp.stack(pending_idx), jnp.stack(pending_vals),
                presorted=presorted,
            )
            pending_idx, pending_vals = [], []
    assert not pending_idx  # every stream committed (schedule flushed)
    np.testing.assert_array_equal(np.asarray(table_win), np.asarray(table_seq))


def test_windowed_stacked_pallas_matches_xla(rng):
    """The stacked form's per-window Pallas commit stays allclose to the
    XLA segment merge (same contract as merged_scatter_add)."""
    t, f, w, m = 64, 2, 3, 150
    table = jnp.asarray(rng.normal(size=(t, f)).astype(np.float32))
    idx = jnp.asarray(np.sort(rng.integers(0, t, size=(w, m)).astype(np.int32), axis=1))
    vals = jnp.asarray(rng.normal(size=(w, m, f)).astype(np.float32))
    got = ops.windowed_scatter_add(table, idx, vals, presorted=True,
                                   backend="pallas-interpret")
    want = ops.windowed_scatter_add(table, idx, vals, presorted=True, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("m,window", [(100, 32), (1000, 256), (64, 64), (10, 16)])
def test_windowed_merge_matches_naive(m, window, rng):
    """The sliding-window BUM (paper-faithful bounded merge) is exact too —
    merging within windows then scattering each window accumulates to the
    same table as the naive duplicate scatter."""
    t, f = 128, 3
    table = jnp.asarray(rng.normal(size=(t, f)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, t, size=m).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))
    naive = ref.scatter_add(table, idx, vals)
    windowed = ops.windowed_scatter_add(table, idx, vals, window=window)
    np.testing.assert_allclose(np.asarray(windowed), np.asarray(naive), atol=1e-4, rtol=1e-4)


# ---- the output-stationary Pallas merge (kernel.py), at small blocks ----

_BT, _BS = 128, 128   # table rows per block, stream rows per tile


def _stream(kind: str, t: int, rng) -> np.ndarray:
    """Sorted address streams that stress the kernel's block and tile edges."""
    if kind == "straddle":  # runs of 37 equal addresses across block and tile edges
        idx = np.repeat(np.arange(_BT - 20, 3 * _BT + 20, 7), 37)
    elif kind == "repeat":  # one address over several tiles (~320 rows on a coarse cell)
        idx = np.concatenate([rng.integers(0, t, 90), np.full(3 * _BS + 5, _BT + 3),
                              rng.integers(0, t, 60)])
    elif kind == "untouched":  # whole blocks no row touches, inside one tile's range
        idx = np.concatenate([rng.integers(0, _BT, 150), rng.integers(5 * _BT, 6 * _BT, 150)])
    else:  # "sentinel": the stream ends in padding rows idx == T
        idx = np.concatenate([rng.integers(0, t, 200), np.full(2 * _BS - 200 + 17, t)])
    return np.sort(idx).astype(np.int32)


@pytest.mark.parametrize("f", [1, 2, 4, 8])
@pytest.mark.parametrize("kind", ["straddle", "repeat", "untouched", "sentinel"])
def test_pallas_merge_edges_match_naive(kind, f, rng):
    t = 8 * _BT
    idx = _stream(kind, t, rng)
    pad = (-idx.shape[0]) % _BS
    idx = np.concatenate([idx, np.full(pad, t, np.int32)])
    vals = rng.normal(size=(idx.shape[0], f)).astype(np.float32)
    vals[idx == t] = 0.0
    table = rng.normal(size=(t, f)).astype(np.float32)
    live = idx < t
    want = ref.scatter_add(jnp.asarray(table), jnp.asarray(idx[live]), jnp.asarray(vals[live]))
    got = kernel.bum_scatter_pallas(jnp.asarray(table), jnp.asarray(idx), jnp.asarray(vals),
                                    block_rows=_BT, tile=_BS, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5)
    if kind == "untouched":  # blocks 1-4 and 6-7 keep the input table exactly
        for b in (1, 2, 3, 4, 6, 7):
            rows = slice(b * _BT, (b + 1) * _BT)
            np.testing.assert_array_equal(np.asarray(got)[rows], table[rows])


@pytest.mark.parametrize("kind", ["every_tile_opens_a_block", "one_row_per_block"])
def test_plan_stays_within_its_static_bound(kind, rng):
    """Adversarial streams for the work-item count: the plan's items are the
    (block, tile) overlaps, in order, and never pass blocks + tiles."""
    n_blocks, n_tiles = 16, 15
    t = n_blocks * _BT
    if kind == "every_tile_opens_a_block":  # tile k holds the end of block k, start of k+1
        idx = np.arange(n_tiles * _BS) + _BT // 2
    else:  # rows spread one per block, then padding
        idx = np.concatenate([np.arange(n_blocks) * _BT,
                              np.full(n_tiles * _BS - n_blocks, t)])
    idx = idx.astype(np.int32)
    blocks, tiles, total = (np.asarray(a) for a in
                            kernel.plan(jnp.asarray(idx), t, _BT, _BS))
    assert blocks.shape == tiles.shape == (n_blocks + n_tiles,)
    total = int(total[0])
    want = sorted({(int(i) // _BT, j // _BS) for j, i in enumerate(idx) if i < t})
    assert list(zip(blocks[:total].tolist(), tiles[:total].tolist())) == want
    assert total <= n_blocks + n_tiles
    vals = rng.normal(size=(idx.shape[0], 2)).astype(np.float32)
    live = idx < t
    want_tbl = ref.scatter_add(jnp.zeros((t, 2)), jnp.asarray(idx[live]), jnp.asarray(vals[live]))
    got = kernel.bum_scatter_pallas(jnp.zeros((t, 2)), jnp.asarray(idx), jnp.asarray(vals),
                                    block_rows=_BT, tile=_BS, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_tbl), atol=1e-5, rtol=1e-6)


def test_hash_grid_backward_routes_to_the_tpu_merge(monkeypatch):
    """Under `auto` on a TPU the hash grid's forward stays on ref while its
    backward's merge resolves to pallas-tpu and is counted there; rows too
    wide for the kernel keep the XLA merge."""
    import jax
    from repro import kernels, obs
    from repro.kernels.hash_encode import ops as he_ops, ref as he_ref
    from repro.obs import metrics, trace

    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    assert kernels.resolve_backend("auto", op="bum_scatter", width=2) is kernels.PALLAS_TPU
    assert kernels.resolve_backend("auto", op="bum_scatter", width=64) is kernels.REF
    with pytest.raises(ValueError, match="rows up to 8 wide"):
        kernels.resolve_backend("pallas-tpu", op="bum_scatter", width=64)

    res = tuple(int(r) for r in he_ref.level_resolutions(2, 4, 8))
    encode = he_ops.make_hash_encode(res, 64, 2, backend="auto")
    pts = jax.ShapeDtypeStruct((32, 3), jnp.float32)
    tables = jax.ShapeDtypeStruct((2, 64, 2), jnp.float32)
    was = trace.enabled()
    trace.set_enabled(True)
    obs.reset()
    try:
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, tb: encode(p, tb).sum(), argnums=1))(
            pts, tables)
        counts = {k: v["value"] for k, v in metrics.snapshot().items()}
    finally:
        obs.reset()
        trace.set_enabled(was)
    assert counts.get("grid_update.commit.pallas-tpu", 0) >= 1
    assert "grid_update.commit.ref" not in counts
    assert "pallas_call" in str(jaxpr)

    wide = ops.merged_scatter_add.lower(jnp.zeros((16, 64)), jnp.zeros((40,), jnp.int32),
                                        jnp.zeros((40, 64)), backend="auto").as_text()
    assert "tpu_custom_call" not in wide and "scatter" in wide


@pytest.mark.parametrize("max_items", [1, 3, 7])
def test_pallas_merge_split_across_calls(max_items, rng):
    """A stream with more work items than one call's SMEM lists hold is
    merged by several calls in turn: bit-identical to one call, blocks cut
    by a call's edge included."""
    t = 8 * _BT
    idx = np.concatenate([_stream("straddle", t, rng), _stream("repeat", t, rng)])
    idx = np.sort(np.concatenate([idx, np.full((-idx.shape[0]) % _BS, t)])).astype(np.int32)
    vals = rng.normal(size=(idx.shape[0], 2)).astype(np.float32)
    vals[idx == t] = 0.0
    table = jnp.asarray(rng.normal(size=(t, 2)).astype(np.float32))
    args = (table, jnp.asarray(idx), jnp.asarray(vals))
    one = kernel.bum_scatter_pallas(*args, block_rows=_BT, tile=_BS, interpret=True)
    split = kernel.bum_scatter_pallas(*args, block_rows=_BT, tile=_BS, max_items=max_items,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(split), np.asarray(one))
    live = idx < t
    want = ref.scatter_add(table, jnp.asarray(idx[live]), jnp.asarray(vals[live]))
    np.testing.assert_allclose(np.asarray(split), np.asarray(want), atol=1e-4, rtol=1e-5)
