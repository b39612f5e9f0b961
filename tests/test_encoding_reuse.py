"""Encoding-reuse cache correctness (ISSUE 9 tentpole part c).

The cache's one promise: a hit is bit-identical to recomputing.  The
property test drives a random sequence of table updates (row-targeted and
whole-grid), occupancy folds, and encodes at random points, comparing every
encode against the `hash_encode.ref` oracle bitwise — if invalidation were
ever stale, some sequence here would catch the drift.  Counter-based tests
pin the other direction: reuse actually happens when tables are stable, and
NO reuse happens when every row updates each step.
"""
import numpy as np
import jax.numpy as jnp

from repro.kernels.fused_path.reuse import EncodingReuseCache, stream_reuse_mask
from repro.kernels.hash_encode import ref as he_ref

from hypothesis import given, settings, strategies as st

RES = (4, 8, 16)
T = {"density": 64, "color": 32}
F = 2


def _tables(rng, grid):
    return jnp.asarray(
        rng.standard_normal((len(RES), T[grid], F)).astype(np.float32))


def _points(rng, n=32):
    return jnp.asarray(rng.random((n, 3), dtype=np.float32) * (1 - 1e-6))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_cached_encodings_never_stale(seed):
    """Any sequence of {row update, grid update, fold, encode} keeps cached
    encodings bit-identical to a fresh oracle computation."""
    rng = np.random.default_rng(seed)
    cache = EncodingReuseCache(RES, T)
    tabs = {g: _tables(rng, g) for g in T}
    for _ in range(12):
        op = rng.choice(["encode", "rows", "grid", "fold"])
        g = str(rng.choice(list(T)))
        if op == "rows":
            # touch a random row subset and tell the cache exactly which
            n = int(rng.integers(1, 16))
            rows = rng.integers(0, len(RES) * T[g], n)
            l, idx = rows // T[g], rows % T[g]
            tabs[g] = tabs[g].at[l, idx].add(1.0)
            cache.note_table_update(g, touched_rows=rows)
        elif op == "grid":
            tabs[g] = tabs[g] * np.float32(1.01)
            cache.note_table_update(g)          # conservative: whole grid
        elif op == "fold":
            cache.note_fold()
        else:
            pts = _points(rng, int(rng.integers(8, 48)))
            for gg in T:
                out = cache.encode(gg, pts, tabs[gg])
                ref = he_ref.hash_encode(pts, tabs[gg], RES)
                assert np.array_equal(np.asarray(out), np.asarray(ref)), \
                    f"stale cache for grid {gg}"


def test_reuse_happens_when_tables_stable():
    """Stable tables + overlapping point sets => hits on the second encode,
    and the hit path returns the identical bits (not just close values)."""
    rng = np.random.default_rng(0)
    cache = EncodingReuseCache(RES, {"density": T["density"]})
    tabs = _tables(rng, "density")
    pts = _points(rng, 64)
    ref = np.asarray(he_ref.hash_encode(pts, tabs, RES))
    out1 = cache.encode("density", pts, tabs)
    assert cache.hits == 0 and cache.misses > 0
    out2 = cache.encode("density", pts, tabs)
    assert cache.hits > 0, "no reuse despite bit-stable tables"
    assert np.array_equal(np.asarray(out1), ref)
    assert np.array_equal(np.asarray(out2), ref)
    assert cache.stats()["corner_reads_saved"] == cache.hits * 8


def test_zero_reuse_when_every_row_updates_each_step():
    """Counter test: a whole-grid update between every encode keeps the hit
    counter at exactly zero — the cache can never serve across an update it
    was told about."""
    rng = np.random.default_rng(1)
    cache = EncodingReuseCache(RES, {"density": T["density"]})
    tabs = _tables(rng, "density")
    pts = _points(rng, 64)
    for step in range(5):
        out = cache.encode("density", pts, tabs)
        assert np.array_equal(
            np.asarray(out), np.asarray(he_ref.hash_encode(pts, tabs, RES)))
        tabs = tabs + np.float32(0.1)           # every row changes
        cache.note_table_update("density")
    assert cache.hits == 0
    assert cache.hit_rate() == 0.0


def test_fold_drops_entries():
    """A fold starts a new epoch: the same points re-miss even though the
    tables never changed (the live cell set may have moved)."""
    rng = np.random.default_rng(2)
    cache = EncodingReuseCache(RES, {"color": T["color"]})
    tabs = _tables(rng, "color")
    pts = _points(rng, 16)
    cache.encode("color", pts, tabs)
    cache.note_fold()
    h0 = cache.hits
    cache.encode("color", pts, tabs)
    assert cache.hits == h0, "entries survived a fold"
    assert cache.fold == 1


def test_cohort_members_share_entries():
    """Cohort sharing: members with bit-identical tables (the cohort
    training guarantee) hit each other's entries — the second member's
    encode is served entirely from cache, bit-identical to the oracle."""
    rng = np.random.default_rng(3)
    cache = EncodingReuseCache(RES, {"density": T["density"]})
    tabs = _tables(rng, "density")
    pts = _points(rng, 40)
    cache.encode("density", pts, tabs)          # member A warms the cache
    m0 = cache.misses
    out_b = cache.encode("density", pts, tabs)  # member B, same scene
    assert cache.misses == m0, "member B re-gathered despite shared tables"
    assert np.array_equal(np.asarray(out_b),
                          np.asarray(he_ref.hash_encode(pts, tabs, RES)))


def test_stream_reuse_mask_names_stable_rows():
    """The reuse-aware address-stream view: rows untouched since a version
    are reusable, touched rows are not."""
    stamp = np.zeros(8, np.int64)
    stamp[[2, 5]] = 3                            # rows 2 and 5 changed at v3
    addrs = np.array([0, 2, 4, 5, 7])
    np.testing.assert_array_equal(
        stream_reuse_mask(addrs, stamp, since=2),
        np.array([True, False, True, False, True]))
    assert stream_reuse_mask(addrs, stamp, since=3).all()
