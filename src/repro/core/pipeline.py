"""Staged render pipeline with occupancy-compacted field queries.

The paper's central bottleneck is hash-grid interpolation traffic
(~200k lookups/iteration); Instant-3D wins by *not issuing* memory traffic
for samples the occupancy grid already culled.  The monolithic
`rendering.render_rays` queried the field at all B×S points and only zeroed
sigma afterward — empty-space skipping saved no compute.  This module splits
rendering into explicit stages so the field only ever sees live points:

    1. generate_samples   rays × ts -> world points, per-sample dirs
    2. cull               AABB test + occupancy-bitfield lookup -> live mask
   2b. redistribute       (optional) re-spend each ray's freed sample budget
                          on its live occupancy segments: inverse-CDF
                          placement over the per-ray live-bin mask, reduced
                          per-ray count S' = budget // B so the total point
                          budget stays at or below the pow2 bucket; emits
                          per-sample quadrature deltas (dt is no longer the
                          uniform stratum width)
    3. compact            stable argsort to a fixed, jit-stable `budget` of
                          points, live-first in Morton (Z-order) key order
                          so spatially adjacent points share kernel blocks
                          (overflow accounted)
    4. shade              hash-encode + MLPs on the compacted set only; by
                          default via the fused path (one encode pass over
                          all grids, pre-sorted BUM backward)
    5. scatter/composite  scatter sigma/rgb back to B×S, volume-render
                          (variable-spacing quadrature when 2b ran)

The budget is a *static* python int (it fixes compiled shapes); callers pick
it from a measured live fraction — `suggest_budget` buckets to powers of two
so recompiles are bounded.  With `budget=None` the pipeline runs the dense
path (query everything, mask sigma), which is also the autodiff oracle the
compaction tests compare against.

Compaction is differentiable: gather of points/dirs carries no parameter
gradient, and the scatter of (sigma, rgb) is a permutation `.at[idx].set`
whose VJP is the corresponding gather — gradients w.r.t. field params match
the dense path exactly whenever every live point fits in the budget.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import occupancy as occ_lib
from . import rendering as _r
from ..kernels.volume_render import ops as vr_ops
from ..kernels.volume_render import ref as vr_ref
from ..kernels.fused_path import ref as fp_ref
from ..obs import trace as _trace


def _cube_root(n: int) -> int:
    r = round(n ** (1.0 / 3.0))
    for cand in (r - 1, r, r + 1):
        if cand > 0 and cand ** 3 == n:
            return cand
    raise ValueError(f"bitfield length {n} is not a cube")


def suggest_budget(
    live_fraction: float,
    n_total: int,
    *,
    headroom: float = 1.3,
    min_budget: int = 512,
    max_budget: int | None = None,
) -> int:
    """Pow2-bucketed point budget for a measured live fraction.

    Bucketing bounds the number of distinct compiled shapes to
    O(log2(n_total / min_budget)); headroom absorbs drift between the
    measurement (e.g. occupancy fraction at the last grid update) and the
    live fraction of the current batch.

    max_budget models a hard per-step point ceiling (on-device memory or
    latency caps).  When it clamps the bucket *below* the live count the
    uniform sampler must drop live points every step (systematic Morton-tail
    truncation — see `compact`); the redistribute stage is the graceful
    alternative, spending exactly the ceiling with zero overflow.
    """
    want = int(n_total * min(1.0, max(0.0, live_fraction) * headroom))
    b = min_budget
    while b < want:
        b *= 2
    b = min(b, n_total)
    if max_budget is not None:
        b = min(b, int(max_budget))
    return b


class CompactionPlan(NamedTuple):
    idx: jnp.ndarray       # (budget,) unique flat-sample indices, live-first
    keep: jnp.ndarray      # (budget,) bool — False on padded dead lanes
    n_live: jnp.ndarray    # () int32 total live points before compaction
    overflow: jnp.ndarray  # () int32 live points dropped (budget too small)


class RenderPipeline:
    """Callable pipeline; stages are exposed as methods for testing/benching.

    fused_path: route the compacted shade stage through the field's fused
    query (one encode pass over all grids, FMU-deduplicated corner reads,
    pre-sorted BUM backward).  Only the budgeted branch is affected; the
    dense path always uses the plain per-grid query.  On the ref backend the
    fused query is bit-identical to the unfused one, so this knob changes
    where the work happens, never the numbers.

    fused_step: with the fused path on, collapse the shade stage further into
    the field's ONE-kernel step (`field.query_step`): encode + both MLP heads
    in a single differentiable op with the residual policy from the field
    config.  Bit-identical to the fused encode + separate MLPs on the ref
    backend; fields without `query_step` (or non-decomposed ones) fall back
    to `query_fused` inside the field, so the knob is always safe to leave on.

    redistribute: adaptive ray marching (stage 2b).  With a bitfield and a
    budget present, each ray's fixed S-sample budget is re-spent on its live
    occupancy segments: S' = budget // B samples per ray, placed by
    inverse-CDF over the per-ray liveness of the uniform candidate samples,
    so every point the compacted shade stage pays for lands in live space
    with finer stratification — and the point budget is spent evenly across
    rays (no overflow ever), instead of Morton-tail truncation when a hard
    budget ceiling bites.  When the knob is off (the default), every code
    path is byte-for-byte the uniform sampler: the stage is never traced,
    deltas fall back to the `jnp.diff` stratum widths, and results are
    bit-identical to a pipeline built without the knob.

    redistribute_v3: density-weighted, workload-balanced stage 2b.  Two
    upgrades over v2, same gating discipline (knob off => never traced):

    * each live stratum is weighted by the *occupancy EMA* of its cell
      (saturating alpha weight, see `v3_stratum_weights`) instead of the
      binary live/dead vote, so in-ray placement concentrates where the
      surface actually is;
    * the fixed per-ray split S' = budget // B becomes a per-ray variable
      S'_i allocated by one global inverse-CDF over the batch's per-ray
      live masses — rays with long live segments get more of the point
      budget, dead-heavy rays keep a floor of 1, and `sum(S'_i) <= budget`
      holds by construction (see `v3_plan`).  The ragged rays live in a
      fixed (B, S_cap) lane grid with a validity mask; the compact stage
      packs the valid lanes Morton-ordered into the caller's exact budget
      with zero overflow, so ragged allocation costs no compiled-shape
      churn.  `v3_oversub` bounds S_cap (the densest ray can take at most
      oversub × the even split).
    """

    def __init__(self, field, cfg: _r.RenderConfig, *, fused_path: bool = True,
                 fused_step: bool = True, redistribute: bool = False,
                 redistribute_v3: bool = False, v3_oversub: int = 4):
        self.field = field
        self.cfg = cfg
        self.fused_path = fused_path and hasattr(field, "query_fused")
        self.fused_step = (
            self.fused_path and fused_step and hasattr(field, "query_step")
        )
        # v3 subsumes v2: it is the same stage slot, so turning it on takes
        # the 2b branch over even if the v2 knob is also set.
        self.redistribute_on = redistribute or redistribute_v3
        self.redistribute_v3_on = redistribute_v3
        self.v3_oversub = int(v3_oversub)

    # ---- stage 1: sample generation ----

    def generate_samples(self, origins, dirs, ts):
        """-> (flat world points (N,3), flat dirs (N,3), unit coords (N,3)).

        N = B·S flattens row-major (ray-major, then sample), so index
        `i*S + k` is ray i's k-th sample — the scatter in stage 5 relies on
        this layout to reshape back to (B, S).  `unit` is the [0,1)^3 coord
        every grid lookup (hash encode, occupancy, Morton key) consumes;
        world points only feed the AABB test.  Works for any ts — uniform
        strata or stage 2b's adaptive placements."""
        points = origins[:, None, :] + ts[..., None] * dirs[:, None, :]  # (B,S,3)
        flat_pts = points.reshape(-1, 3)
        flat_dirs = jnp.broadcast_to(dirs[:, None, :], points.shape).reshape(-1, 3)
        unit = _r.normalize_points(flat_pts, self.cfg)
        return flat_pts, flat_dirs, unit

    # ---- stage 2: cull ----

    def cull(self, flat_pts, unit, bitfield=None, mask_fn=None):
        """AABB + occupancy liveness.  bitfield is a (R^3,) bool array (the
        jit-traceable form from occupancy.bitfield); mask_fn is the legacy
        closure hook kept for render_rays compatibility."""
        live = _r.inside_aabb(flat_pts, self.cfg)
        if bitfield is not None:
            r = _cube_root(bitfield.shape[0])
            live = live & occ_lib.point_liveness(bitfield, unit, r)
        if mask_fn is not None:  # composes with the bitfield when both given
            live = live & mask_fn(unit)
        return live

    # ---- stage 2b: redistribute (adaptive ray marching) ----

    def redistribute(self, ts, live, *, n_out: int | None = None):
        """Inverse-CDF sample redistribution over live occupancy segments.

        `live` (B, S) is the cull-stage liveness of the incoming stratified
        samples (stage 2 on the uniform candidates) — it doubles as the
        per-ray occupancy probe.  Using the *jittered* samples as probes
        (instead of, say, fixed stratum midpoints) matters: a stratum that
        partially overlaps a live cell flickers live/dead with the
        stratified jitter, so every region receives samples in expectation
        across steps.  A deterministic probe would carve permanent per-ray
        blind spots into training — live surface slivers between two dead
        probe points would never be sampled on any step.

        The live mask becomes each ray's piecewise-constant live-length CDF
        over the S strata, and `n_out` stratified samples are placed by
        inverting it.  Rays with no live stratum fall back to the uniform
        CDF (they carry no radiance; compositing still needs monotone ts).
        In-stratum jitter is likewise reused from `ts`, so the stage is a
        pure deterministic function of (ts, live) — no extra rng plumbing,
        and training streams stay reproducible under suspend/resume.

        Returns (ts_new (B, n_out), deltas (B, n_out)):

        * ts_new is ascending per ray and lands only in live strata (up to
          the uniform fallback);
        * deltas are the per-sample quadrature widths dt_k = h / (p_k · S')
          — the live arc length each sample represents; summed per ray they
          equal the ray's live length, so `composite` integrates the same
          transmittance as a dense quadrature over live space (dead gaps
          between segments contribute exactly zero because no sample's dt
          spans them).
        """
        b, s = ts.shape
        n_out = s if n_out is None else int(n_out)
        near, far = self.cfg.near, self.cfg.far
        h = (far - near) / s

        w = live.astype(jnp.float32)                       # (B, S)
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = jnp.where(total > 0, w, 1.0)                   # dead ray -> uniform
        pdf = w / jnp.sum(w, axis=-1, keepdims=True)
        cdf = jnp.cumsum(pdf, axis=-1)

        # stratified u in (0,1): stratum index from n_out, jitter from ts
        jitter = (ts[:, :n_out] - near) / (far - near) * s - jnp.arange(n_out)
        jitter = jnp.clip(jitter, 0.0, 1.0 - 1e-6)
        u = (jnp.arange(n_out) + jitter) / n_out           # (B, n_out) ascending
        u = u * cdf[:, -1:]                                # absorb cumsum rounding

        j = jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="right"))(cdf, u)
        j = jnp.clip(j, 0, s - 1)
        cdf_lo = jnp.where(
            j > 0, jnp.take_along_axis(cdf, jnp.maximum(j - 1, 0), axis=-1), 0.0
        )
        p = jnp.maximum(jnp.take_along_axis(pdf, j, axis=-1), 1e-12)
        frac = jnp.clip((u - cdf_lo) / p, 0.0, 1.0 - 1e-6)
        ts_new = near + (j.astype(jnp.float32) + frac) * h
        deltas = h / (p * n_out)
        return ts_new, deltas

    # ---- stage 2b, v3: density-weighted, workload-balanced ----

    # Weight floor for live strata: keeps every live cell sampleable even
    # when its EMA alpha is ~0 (fresh surfaces, warmup), and bounds the
    # concentration ratio between the densest and thinnest live stratum to
    # (floor + 1) / floor ≈ 21 — raw EMA ratios span ~1e4 and would starve
    # low-density live cells entirely.
    V3_WEIGHT_FLOOR = 0.05

    def v3_stratum_weights(self, live, ema_vals):
        """Per-stratum sampling weight (B, S) f32 for the v3 CDF.

        `live` bool (B, S) from the cull probe; `ema_vals` (B, S) the
        occupancy EMA of each candidate's cell (`occupancy.point_density`),
        or None when no EMA is available (serving without state, tests).
        The weight is the stratum's saturating alpha `1 - exp(-ema * h)` —
        the fraction of light a stratum of width h at the cell's EMA
        density would absorb — plus the floor, masked to live strata.
        With ema=None it degrades to `floor * live`: a uniform live-strata
        CDF, i.e. exactly v2's placement density."""
        b, s = live.shape
        h = (self.cfg.far - self.cfg.near) / s
        w = jnp.full((b, s), self.V3_WEIGHT_FLOOR, jnp.float32)
        if ema_vals is not None:
            w = w + 1.0 - jnp.exp(-jnp.maximum(ema_vals, 0.0) * h)
        return live.astype(jnp.float32) * w

    def v3_plan(self, ts, live, ema_vals, budget: int):
        """Global ragged-allocation plan for redistribute v3.

        Returns a dict of (B,·) arrays — exposed separately from the
        placement so the property suite can check the plan's invariants
        directly:

        * ``pdf``/``cdf`` (B, S): each ray's weighted piecewise-constant
          placement density over the S probe strata (dead rays fall back
          to uniform); cdf is monotone non-decreasing with cdf[:, -1] ≈ 1.
        * ``s_ray`` (B,) int32: per-ray sample counts S'_i.  Allocation:
          every ray gets the floor of 1; the extra E = budget − B samples
          are split by stratifying the rays' normalized live-mass CDF at E
          points (`diff(floor(ray_cdf * E + 0.5))` — the edges telescope,
          so `sum(s_ray) <= budget` holds *by construction*, not by test).
          Per-ray counts are clamped to the static lane cap ``s_cap``.
        * ``s_cap`` int (static): lane-grid width, min(oversub × even
          split, budget − B + 1).
        * ``mass`` (B,): the per-ray weighted live masses the allocation is
          proportional to; ``dead`` (B,) bool marks zero-mass rays.
        """
        b, s = ts.shape
        budget = int(budget)
        e = budget - b                       # extra lanes beyond the 1-floor
        s_cap = max(1, min(max(1, budget // b) * self.v3_oversub, e + 1))

        w = self.v3_stratum_weights(live, ema_vals)        # (B, S)
        mass = jnp.sum(w, axis=-1)                         # (B,)
        dead = mass <= 0.0
        w_ray = jnp.where(dead[:, None], jnp.ones_like(w), w)
        pdf = w_ray / jnp.sum(w_ray, axis=-1, keepdims=True)
        cdf = jnp.cumsum(pdf, axis=-1)

        # global workload balance: stratify the batch's live-mass CDF at E
        # points.  Normalizing by the last entry makes ray_cdf[-1] exactly
        # 1.0, so edges[-1] == E and the telescoped sum never exceeds the
        # budget even under f32 cumsum rounding.
        ray_mass = jnp.where(dead, 0.0, mass)
        total = jnp.sum(ray_mass)
        ray_pdf = jnp.where(total > 0.0, ray_mass / jnp.maximum(total, 1e-12),
                            1.0 / b)
        ray_cdf = jnp.cumsum(ray_pdf)
        ray_cdf = ray_cdf / ray_cdf[-1]
        edges = jnp.floor(ray_cdf * e + 0.5).astype(jnp.int32)
        extra = jnp.diff(jnp.concatenate([jnp.zeros((1,), jnp.int32), edges]))
        s_ray = 1 + jnp.clip(extra, 0, s_cap - 1)
        return {"pdf": pdf, "cdf": cdf, "s_ray": s_ray, "s_cap": s_cap,
                "mass": mass, "dead": dead}

    def redistribute_v3(self, ts, live, ema_vals, budget: int):
        """Density-weighted inverse-CDF placement at ragged per-ray S'.

        Same probe/jitter discipline as v2 (`redistribute`): liveness and
        in-stratum jitter both come from the uniform candidates `ts`, so the
        stage stays a pure deterministic function of (ts, live, ema) with no
        rng plumbing.  Returns fixed-shape lanes:

        * ts_new (B, s_cap): ascending per ray; lane k of ray i is a placed
          sample iff ``valid[i, k]`` (k < S'_i), else parked at `far`;
        * deltas (B, s_cap): per-sample quadrature widths, 0 on invalid
          lanes.  Raw widths h / (p_j · S'_i) are renormalized per ray so
          the valid lanes sum *exactly* to the ray's live arc length (for
          uniform weights the factor is 1 and v2's quadrature is
          recovered); dead rays normalize to the full near–far span, v2's
          uniform-fallback convention.
        * valid (B, s_cap) bool: the ragged-ray mask the compact stage
          packs (invalid lanes are culled, so they cost no shade work and
          composite as exactly zero).
        """
        b, s = ts.shape
        near, far = self.cfg.near, self.cfg.far
        h = (far - near) / s
        plan = self.v3_plan(ts, live, ema_vals, budget)
        pdf, cdf, s_ray, s_cap = (
            plan["pdf"], plan["cdf"], plan["s_ray"], plan["s_cap"])

        k = jnp.arange(s_cap)
        valid = k[None, :] < s_ray[:, None]                # (B, s_cap)

        # stratified u in (0,1) at the ray's own S': jitter recycled from
        # the candidate samples (column k mod S keeps every lane jittered)
        tsrc = ts[:, k % s]
        jitter = (tsrc - near) / (far - near) * s
        jitter = jnp.clip(jitter - jnp.floor(jitter), 0.0, 1.0 - 1e-6)
        sr = s_ray.astype(jnp.float32)[:, None]
        u = jnp.clip((k[None, :] + jitter) / sr, 0.0, 1.0 - 1e-9)
        u = u * cdf[:, -1:]                                # absorb rounding

        j = jax.vmap(lambda c, uu: jnp.searchsorted(c, uu, side="right"))(cdf, u)
        j = jnp.clip(j, 0, s - 1)
        cdf_lo = jnp.where(
            j > 0, jnp.take_along_axis(cdf, jnp.maximum(j - 1, 0), axis=-1), 0.0
        )
        p = jnp.maximum(jnp.take_along_axis(pdf, j, axis=-1), 1e-12)
        frac = jnp.clip((u - cdf_lo) / p, 0.0, 1.0 - 1e-6)
        ts_new = near + (j.astype(jnp.float32) + frac) * h
        ts_new = jnp.where(valid, ts_new, far)             # park invalid lanes

        # ragged quadrature: dt = h / (p_j · S'_i) on valid lanes, then a
        # per-ray renormalization pins the row sum to the live arc length
        dt_raw = jnp.where(valid, h / (p * sr), 0.0)
        live_len = jnp.sum(live.astype(jnp.float32), axis=-1) * h
        target = jnp.where(plan["dead"], far - near, live_len)
        deltas = dt_raw * (target / jnp.maximum(jnp.sum(dt_raw, -1), 1e-12))[:, None]
        return ts_new, deltas, valid

    # ---- stage 3: compact ----

    def compact(self, live, budget: int, unit=None) -> CompactionPlan:
        """Live-first compaction to a fixed budget, padded with dead samples.

        With `unit` coords given, the live set is ordered by Morton (Z-order)
        key instead of flat sample order: spatially adjacent points land in
        the same kernel block, which is what makes the fused path's corner
        reads coalescible (FMU) and its backward update stream quasi-sorted
        (BUM).  Costs nothing — the single stable argsort just sorts a
        different key (dead lanes get the max key, so they still pad the
        tail).  Without `unit`, falls back to the PR 1 flat-order behavior.

        Overflow caveat: when n_live > budget the dropped live points are
        the highest Morton keys (the box corner nearest (1,1,1)) instead of
        flat order's end-of-batch rays — either truncation is systematic,
        and the trainer reacts the same way (widens the next budget bucket).
        """
        if unit is None:
            order = jnp.argsort(jnp.logical_not(live))  # stable: live-first
        else:
            key = fp_ref.morton_key(unit)
            key = jnp.where(live, key, jnp.uint32(0xFFFFFFFF))
            order = jnp.argsort(key)  # stable: live in Z-order, dead last
        idx = order[:budget]
        n_live = jnp.sum(live.astype(jnp.int32))
        keep = live[idx]
        overflow = jnp.maximum(n_live - budget, 0)
        return CompactionPlan(idx, keep, n_live, overflow)

    # ---- stage 4: shade ----

    def shade(self, params, unit, dirs, fused: bool = False):
        """Field query on (already compacted) unit coords -> (sigma, rgb).

        fused=True routes through `field.query_fused` (one encode pass over
        all grids, pre-sorted BUM backward) — bit-identical to the per-grid
        query on the ref backend, so the flag is a placement choice, not a
        numerics choice.  With the pipeline's `fused_step` knob also on, the
        stage collapses further into `field.query_step`: encode AND both MLP
        heads in one custom-VJP op (still bit-identical on ref).  The stage
        is agnostic to how `unit` was sampled; it sees only the compacted
        point set."""
        if fused:
            if self.fused_step:
                return self.field.query_step(params, unit, dirs)
            return self.field.query_fused(params, unit, dirs)
        return self.field.query(params, unit, dirs)

    # ---- stage 5: scatter + composite ----

    def composite(self, sigma, rgb, ts, deltas=None):
        """Volume-render (B·S,) sigma / (B·S,3) rgb along ts (B,S).

        deltas: optional per-sample quadrature widths (B,S) — required after
        `redistribute`, where consecutive ts may straddle dead gaps that the
        naive `diff(ts)` spacing would wrongly charge to the preceding
        sample's density.  With deltas=None the uniform-sampler convention
        applies unchanged (diff, last stratum padded with the mean width) —
        bit-identical to the pre-redistribute pipeline.
        """
        b, s = ts.shape
        if deltas is None:
            deltas = vr_ref.uniform_deltas(ts, self.cfg.far - self.cfg.near)
        out = vr_ops.composite(sigma.reshape(b, s), rgb.reshape(b, s, 3), deltas, ts)
        color = out.color
        if self.cfg.white_background:
            color = color + (1.0 - out.opacity[..., None])
        return {
            "rgb": color,
            "depth": out.depth,
            "opacity": out.opacity,
            "weights": out.weights,
        }

    # ---- full pipeline ----

    def __call__(
        self,
        params,
        origins,
        dirs,
        ts,
        *,
        bitfield=None,
        mask_fn=None,
        budget: int | None = None,
        occ_ema=None,
    ):
        """Render a ray batch.  budget MUST be a static python int (or None
        for the dense path) — it fixes the compiled point-batch shape.

        With `redistribute` on (and a bitfield + budget present), stage 2b
        replaces ts by S' = budget // B adaptively placed samples per ray
        before compaction, and the effective budget becomes B·S' ≤ budget —
        the reported `points_queried` can only shrink.  `live_fraction` then
        reports the probe's (uniform-equivalent) live fraction so budget
        controllers keep seeing the quantity they calibrate against.

        With `redistribute_v3` on, stage 2b instead places a *variable*
        S'_i per ray (density-weighted when `occ_ema` — the (R^3,) f32
        occupancy EMA — is given), emitting a ragged (B, S_cap) lane grid
        whose valid lanes the compact stage packs into exactly `budget`
        points, zero overflow by construction.  `occ_ema` is only read by
        the v3 branch; passing it elsewhere changes nothing.
        """
        b, s = ts.shape
        n = b * s
        # each stage is a named scope, so its ops carry the stage's name in
        # their op_name metadata; with obs on it is also a host span, which
        # under jit times the *trace* of the stage (the compile-side cost
        # breakdown) and in eager use times execution.  Neither touches
        # array values.
        with _trace.stage("pipeline/sample", cat="pipeline"):
            flat_pts, flat_dirs, unit = self.generate_samples(origins, dirs, ts)
        with _trace.stage("pipeline/cull", cat="pipeline"):
            live = self.cull(flat_pts, unit, bitfield=bitfield, mask_fn=mask_fn)

        deltas = probe_live_frac = None
        # redistribution allocates per ray, so it needs budget >= B for at
        # least one sample each; below that, fall through to plain uniform
        # compaction, which honors sub-B budgets by truncation instead of
        # silently exceeding the ceiling
        if (self.redistribute_on and bitfield is not None
                and budget is not None and int(budget) >= b):
            with _trace.stage("pipeline/redistribute", cat="pipeline"):
                # the uniform candidates' liveness doubles as the (jittered)
                # occupancy probe; their mean is exactly the uniform sampler's
                # live fraction — what the budget controller calibrates against
                probe_live_frac = jnp.mean(live.astype(jnp.float32))
                if self.redistribute_v3_on:
                    ema_vals = None
                    if occ_ema is not None:
                        r = _cube_root(occ_ema.shape[0])
                        ema_vals = occ_lib.point_density(
                            occ_ema, unit, r).reshape(b, s)
                    ts, deltas, lane_valid = self.redistribute_v3(
                        ts, live.reshape(b, s), ema_vals, int(budget))
                    n = b * ts.shape[1]
                    flat_pts, flat_dirs, unit = self.generate_samples(
                        origins, dirs, ts)
                    # invalid lanes are dead by decree: they never reach the
                    # shade stage, and sum(S') <= budget makes the compacted
                    # packing overflow-free
                    live = lane_valid.reshape(-1) & self.cull(
                        flat_pts, unit, bitfield=bitfield, mask_fn=mask_fn)
                else:
                    s = min(s, min(int(budget), n) // b)
                    ts, deltas = self.redistribute(ts, live.reshape(b, -1), n_out=s)
                    budget = n = b * s
                    flat_pts, flat_dirs, unit = self.generate_samples(origins, dirs, ts)
                    live = self.cull(flat_pts, unit, bitfield=bitfield, mask_fn=mask_fn)

        if budget is None:
            with _trace.stage("pipeline/shade", cat="pipeline",
                             args={"points": n, "dense": True}):
                sigma, rgb = self.shade(params, unit, flat_dirs)
            sigma = jnp.where(live, sigma, 0.0)
            n_live = jnp.sum(live.astype(jnp.int32))
            overflow = jnp.zeros((), jnp.int32)
            points_queried = n
        else:
            budget = min(int(budget), n)
            with _trace.stage("pipeline/compact", cat="pipeline",
                             args={"budget": budget}):
                plan = self.compact(live, budget, unit)
            with _trace.stage("pipeline/shade", cat="pipeline",
                             args={"points": budget, "dense": False}):
                sigma_c, rgb_c = self.shade(
                    params, unit[plan.idx], flat_dirs[plan.idx],
                    fused=self.fused_path,
                )
            sigma = jnp.zeros((n,), sigma_c.dtype).at[plan.idx].set(
                jnp.where(plan.keep, sigma_c, 0.0)
            )
            rgb = jnp.zeros((n, 3), rgb_c.dtype).at[plan.idx].set(
                rgb_c * plan.keep[:, None].astype(rgb_c.dtype)
            )
            n_live, overflow = plan.n_live, plan.overflow
            points_queried = budget

        with _trace.stage("pipeline/composite", cat="pipeline"):
            out = self.composite(sigma, rgb, ts, deltas)
        out.update(
            live_fraction=(
                probe_live_frac if probe_live_frac is not None
                else jnp.mean(live.astype(jnp.float32))
            ),
            n_live=n_live,
            overflow=overflow,
            points_queried=jnp.asarray(points_queried, jnp.int32),
        )
        return out
