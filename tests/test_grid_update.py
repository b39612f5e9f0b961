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
