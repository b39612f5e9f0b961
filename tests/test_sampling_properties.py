"""Property-test suite for redistribute v3 (pipeline stage 2b).

The ISSUE 9 invariants, asserted over random occupancy/EMA/budget draws
(hypothesis when installed, the deterministic shim otherwise):

1. budget conservation — `sum(S'_i) <= budget` ALWAYS, with a floor of 1
   per ray (the allocation telescopes a floor'd CDF, so this is checked as
   a property, not proved only on the happy path);
2. per-ray CDF monotone non-decreasing and normalized (last entry ~ 1);
3. quadrature deltas per ray sum to the ray's total live segment length
   (dead rays: the full near-far span, the uniform-fallback convention);
4. every placed (valid-lane) sample falls in a live stratum;
5. knob-off path is bit-identical to v2 / uniform via the never-traced
   monkeypatch-raiser pattern from PR 4.

Draws are integer seeds expanded through numpy's PRNG on the host — the
shim's strategy surface (integers/booleans/sampled_from) is all that's
needed, and every failing example reproduces from its printed seed.
"""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig, occupancy
from repro.core.pipeline import RenderPipeline
from repro.core.rendering import RenderConfig
from repro.data import build_dataset, RaySampler

from hypothesis import given, settings, strategies as st

FIELD_CFG = FieldConfig(n_levels=4, max_resolution=64, log2_table_density=12,
                        log2_table_color=10)
RCFG = RenderConfig(n_samples=16)


def _draw_case(seed: int, use_ema: bool):
    """Random (pipe, ts, live, ema, budget) from one integer seed."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(2, 24))
    s = int(rng.integers(4, 33))
    budget = int(rng.integers(b, 4 * b * s + 1))
    cfg = RenderConfig(n_samples=s)
    pipe = RenderPipeline(None, cfg, redistribute_v3=True,
                          v3_oversub=int(rng.integers(2, 7)))
    h = (cfg.far - cfg.near) / s
    jit = rng.random((b, s), dtype=np.float32)
    ts = (cfg.near + (np.arange(s)[None, :] + jit) * h).astype(np.float32)
    # occupancy per row: anything from fully dead to fully live
    live = rng.random((b, s)) < rng.random((b, 1)) * 1.2
    # trunc_exp densities span orders of magnitude; mimic that spread
    ema = (rng.random((b, s), dtype=np.float32) ** 4 * 50.0) if use_ema else None
    return pipe, jnp.asarray(ts), jnp.asarray(live), \
        None if ema is None else jnp.asarray(ema), budget


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), use_ema=st.booleans())
def test_budget_conservation_and_floor(seed, use_ema):
    """(1) sum(S'_i) <= budget by construction, every ray's floor of 1
    honored, and the validity mask agrees with the allocation."""
    pipe, ts, live, ema, budget = _draw_case(seed, use_ema)
    plan = pipe.v3_plan(ts, live, ema, budget)
    _, _, valid = pipe.redistribute_v3(ts, live, ema, budget)
    s_ray = np.asarray(plan["s_ray"])
    assert int(s_ray.sum()) <= budget
    assert (s_ray >= 1).all()
    assert (s_ray <= plan["s_cap"]).all()
    assert (np.asarray(valid).sum(axis=1) == s_ray).all()


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), use_ema=st.booleans())
def test_cdf_monotone_and_normalized(seed, use_ema):
    """(2) each ray's weighted CDF is monotone non-decreasing and ends at
    ~1 (f32 cumsum rounding is the only slack)."""
    pipe, ts, live, ema, budget = _draw_case(seed, use_ema)
    plan = pipe.v3_plan(ts, live, ema, budget)
    cdf = np.asarray(plan["cdf"], np.float64)
    pdf = np.asarray(plan["pdf"], np.float64)
    assert (pdf >= 0.0).all()
    assert (np.diff(cdf, axis=1) >= -1e-7).all()
    np.testing.assert_allclose(cdf[:, -1], 1.0, rtol=1e-5)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), use_ema=st.booleans())
def test_deltas_sum_to_live_length(seed, use_ema):
    """(3) valid-lane quadrature deltas sum per ray to the live segment
    length (dead rays: the full span); invalid lanes carry exactly 0."""
    pipe, ts, live, ema, budget = _draw_case(seed, use_ema)
    _, deltas, valid = pipe.redistribute_v3(ts, live, ema, budget)
    plan = pipe.v3_plan(ts, live, ema, budget)
    s = ts.shape[1]
    h = (pipe.cfg.far - pipe.cfg.near) / s
    live_len = np.asarray(live).sum(axis=1) * h
    target = np.where(np.asarray(plan["dead"]),
                      pipe.cfg.far - pipe.cfg.near, live_len)
    d = np.asarray(deltas, np.float64)
    assert (d[~np.asarray(valid)] == 0.0).all()
    np.testing.assert_allclose(d.sum(axis=1), target, rtol=1e-5, atol=1e-6)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), use_ema=st.booleans())
def test_samples_land_in_live_strata(seed, use_ema):
    """(4) every valid placed sample falls in a live stratum (rays with no
    live stratum are exempt: they take the uniform fallback), and ts stays
    monotone non-decreasing per ray with invalid lanes parked at far."""
    pipe, ts, live, ema, budget = _draw_case(seed, use_ema)
    ts_new, _, valid = pipe.redistribute_v3(ts, live, ema, budget)
    plan = pipe.v3_plan(ts, live, ema, budget)
    s = ts.shape[1]
    near, far = pipe.cfg.near, pipe.cfg.far
    h = (far - near) / s
    tsn = np.asarray(ts_new)
    live_np = np.asarray(live)
    dead = np.asarray(plan["dead"])
    stratum = np.clip(((tsn - near) / h).astype(np.int64), 0, s - 1)
    for i in range(tsn.shape[0]):
        assert (np.diff(tsn[i]) >= -1e-6).all()
        assert (tsn[i][~np.asarray(valid)[i]] == np.float32(far)).all()
        if dead[i]:
            continue
        ks = np.asarray(valid)[i]
        assert live_np[i][stratum[i][ks]].all(), \
            f"ray {i}: sample outside live strata"


# ---- (5) knob-off bit-identity (never-traced raiser pattern) ----


def _short_train(forbid_v3: bool = False, forbid_v2: bool = False, **cfg_kw):
    ds = build_dataset(seed=0, n_views=4, h=16, w=16, cfg=RCFG, gt_samples=48)[1]
    tcfg = TrainerConfig(
        n_rays=128, iters=24, render=RCFG, min_budget=128,
        occ=occupancy.OccupancyConfig(resolution=8, update_interval=8,
                                      warmup_steps=8),
        **cfg_kw,
    )
    tr = Instant3DTrainer(Field(FIELD_CFG), tcfg)
    if forbid_v3:
        def _boom_v3(*a, **k):
            raise AssertionError("redistribute_v3 traced with the knob off")
        tr.pipeline.redistribute_v3 = _boom_v3
    if forbid_v2:
        def _boom_v2(*a, **k):
            raise AssertionError("redistribute (v2) traced with the knob off")
        tr.pipeline.redistribute = _boom_v2
    state = tr.init(jax.random.PRNGKey(0))
    state, hist = tr.train(state, RaySampler(ds), iters=tcfg.iters, log_every=8)
    return state, hist


def _assert_states_equal(sa, sb):
    za = jax.tree_util.tree_leaves_with_path((sa.params, sa.opt_state,
                                              sa.occ_state))
    zb = jax.tree_util.tree_leaves((sb.params, sb.opt_state, sb.occ_state))
    for (p, a), b in zip(za, zb):
        assert bool(np.array_equal(np.asarray(a), np.asarray(b))), \
            f"state drift at {p}"


def test_v3_off_never_traced_and_bit_identical():
    """(5) with redistribute_v3 off the v3 stage is never traced (raiser on
    the method survives a full training run) and the whole train state —
    params, optimizer moments, occupancy EMA — is bit-identical to a run
    without the raiser."""
    s1, h1 = _short_train(forbid_v3=True, forbid_v2=True, max_budget=256)
    s2, h2 = _short_train(max_budget=256)
    _assert_states_equal(s1, s2)
    assert h1["loss"] == h2["loss"]


def test_v2_path_untouched_by_v3_code():
    """(5b) the v2 knob still runs the PR 4 stage with the v3 method never
    traced — v3's presence cannot perturb the committed v2 numbers."""
    s1, h1 = _short_train(forbid_v3=True, redistribute=True, max_budget=256)
    s2, h2 = _short_train(redistribute=True, max_budget=256)
    _assert_states_equal(s1, s2)
    assert h1["loss"] == h2["loss"]


def test_v3_on_trains_within_budget():
    """v3 end-to-end: finite losses, ceiling honored, zero overflow by
    construction (ragged packing never exceeds the compact budget)."""
    state, hist = _short_train(redistribute_v3=True, max_budget=256)
    assert all(np.isfinite(hist["loss"]))
    assert hist["points_queried"][-1] <= 256
    assert hist["overflow_total"] == 0


def test_v3_equals_v2_under_uniform_weights_allocation():
    """With ema=None and every stratum live, the weighted CDF degenerates
    to v2's uniform live CDF (pdf rows exactly 1/S) and the allocation
    splits the budget evenly — the even split is the stratified-CDF fixed
    point for equal masses, so v3 contains v2's S' = budget // B as its
    homogeneous special case."""
    b, s, budget = 8, 16, 64
    cfg = RenderConfig(n_samples=s)
    pipe = RenderPipeline(None, cfg, redistribute_v3=True)
    rng = np.random.default_rng(3)
    h = (cfg.far - cfg.near) / s
    ts = jnp.asarray((cfg.near + (np.arange(s)[None, :]
                                  + rng.random((b, s), dtype=np.float32)) * h)
                     .astype(np.float32))
    live = jnp.ones((b, s), bool)
    plan = pipe.v3_plan(ts, live, None, budget)
    np.testing.assert_array_equal(np.asarray(plan["s_ray"]),
                                  np.full(b, budget // b))
    # uniform weights: pdf rows are exactly 1/S
    np.testing.assert_allclose(np.asarray(plan["pdf"]), 1.0 / s, rtol=1e-6)


# ---- occupancy mass/mask degeneration (ISSUE 9 small fix) ----


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_ray_segment_mass_degrades_to_mask(seed):
    """Thresholding the EMA-weighted mass recovers the binary mask exactly:
    `ray_segment_mass(...) > 0 == ray_segment_mask(bits, ...)` whenever
    bits = ema > threshold (the folded-state bitfield)."""
    rng = np.random.default_rng(seed)
    r = int(rng.choice([4, 8]))
    thr = 0.05
    ema = jnp.asarray((rng.random(r ** 3, dtype=np.float32) ** 2) * 0.5)
    bits = ema > thr
    mids = jnp.asarray(rng.random((6, 12, 3), dtype=np.float32) * (1 - 1e-6))
    mass = occupancy.ray_segment_mass(ema, mids, r, thr)
    mask = occupancy.ray_segment_mask(bits, mids, r)
    np.testing.assert_array_equal(np.asarray(mass) > 0, np.asarray(mask))
    # where live, the mass is the cell's EMA itself
    d = occupancy.point_density(ema, mids, r)
    np.testing.assert_array_equal(
        np.asarray(mass), np.where(np.asarray(mask), np.asarray(d), 0.0))
