"""Instant-3D training loop (paper §3 + §5.1 settings).

The paper's two algorithm knobs are first-class here:

* different grid sizes: `FieldConfig.log2_table_density/color` (S_D : S_C);
* different update frequencies: `f_density`, `f_color` in [0, 1].  An
  iteration updates branch b iff floor(i*F_b) > floor((i-1)*F_b).  Frozen
  branches are routed through `stop_gradient` (their gradient scatter
  disappears from the backward HLO — the compute saving is real, not masked)
  and the optimizer skips their moments (`AdamW.apply(mask=...)`).

Two jitted step functions are compiled once (freeze_color True/False); the
scheduler picks per-iteration, mirroring the accelerator "skipping one
back-propagation every 1/(1-F) iterations" (paper §4.6).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import field as field_lib
from . import losses, occupancy, rendering
from .pipeline import RenderPipeline, suggest_budget
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..optim import AdamW

# note: the sampler/dataset arguments below are duck-typed (repro.data types);
# importing repro.data here would create a package cycle


# ---- shared eval-render compile cache ----
#
# Keyed per (field config, render config, chunk size): concurrent scene
# sessions with the *same* geometry share exactly one compiled function,
# while sessions with different grid sizes get distinct entries instead of
# silently thrashing (or worse, sharing) one trainer's cached jit.  The
# function closes over a Field built from the config, so any caller holding
# only configs (e.g. the serve3d RenderService) can use it too.
_EVAL_RENDER_CACHE: dict[tuple, Any] = {}


def make_render_chunk(field_cfg, render_cfg: rendering.RenderConfig):
    """Unjitted dense-pipeline chunk renderer built purely from configs:
    (params, origins (N,3), dirs (N,3), ts (N,S)) -> (rgb, depth).  The single
    construction point for every eval-render cache (plain and vmapped), so
    their entries always compute the same function."""
    pipeline = RenderPipeline(field_lib.Field(field_cfg), render_cfg)

    def render_chunk(params, origins, dirs, ts):
        out = pipeline(params, origins, dirs, ts)
        return out["rgb"], out["depth"]

    return render_chunk


def eval_render_fn(field_cfg, render_cfg: rendering.RenderConfig, chunk: int):
    """Jitted `make_render_chunk` for (field_cfg, render_cfg, chunk)."""
    key = (field_cfg, render_cfg, int(chunk))
    if key not in _EVAL_RENDER_CACHE:
        _EVAL_RENDER_CACHE[key] = jax.jit(make_render_chunk(field_cfg, render_cfg))
    return _EVAL_RENDER_CACHE[key]


def make_redistributed_render_chunk(field_cfg, render_cfg: rendering.RenderConfig,
                                    occ_cfg: occupancy.OccupancyConfig, budget: int,
                                    redistribute_v3: bool = False):
    """Occupancy-redistributed chunk renderer (pipeline stage 2b) built purely
    from configs: (params, origins (N,3), dirs (N,3), ts (N,S), occ_ema,
    occ_step) -> (rgb, depth).

    Instead of shading all N·S dense samples, the cull liveness of the dense
    candidates becomes each ray's occupancy probe and S' = budget // N
    redistributed samples are shaded per ray — the same quadrature the
    redistributing trainer marches, which is what closes the train/eval
    quadrature mismatch for served views.  The occupancy state rides along as
    plain arrays (jit-traceable), so callers holding only a published
    snapshot (params + occ EMA) can render without a live trainer; while
    occ_step == 0 the bitfield reads all-occupied and redistribution
    degrades gracefully to a uniform S'-sample preview.

    fused_path stays OFF here: the fused query's forward-pass corner-stream
    argsort buys its cost back in the pre-sorted backward merge, and a
    render has no backward — the plain per-grid query shades the compacted
    set cheaper.

    redistribute_v3=True serves the density-weighted ragged path instead:
    per-ray sample counts follow the chunk's live-mass distribution (the
    coalescer's compact stage packs the unequal rays Morton-ordered into
    the same static budget), and the published occupancy EMA weights the
    in-ray placement — served views then march the same v3 quadrature a
    redistribute_v3 trainer trains with."""
    pipeline = RenderPipeline(field_lib.Field(field_cfg), render_cfg,
                              fused_path=False, redistribute=True,
                              redistribute_v3=bool(redistribute_v3))

    def render_chunk(params, origins, dirs, ts, occ_ema, occ_step):
        bits = occupancy.bitfield(occupancy.OccupancyState(occ_ema, occ_step), occ_cfg)
        out = pipeline(params, origins, dirs, ts, bitfield=bits,
                       budget=int(budget), occ_ema=occ_ema)
        return out["rgb"], out["depth"]

    return render_chunk


_REDIST_RENDER_CACHE: dict[tuple, Any] = {}


def default_samples_per_ray(n_samples: int) -> int:
    """The serving default for the redistributed per-ray budget: S/4 (the
    PR 4 equal-PSNR point), floored at 4 and capped at S.  One definition
    shared by the serve3d service and `evaluate`, so offline eval and served
    renders march the same quadrature by construction."""
    s = int(n_samples)
    return min(s, max(4, s // 4))


def redistributed_render_fn(field_cfg, render_cfg: rendering.RenderConfig,
                            occ_cfg: occupancy.OccupancyConfig,
                            chunk: int, samples_per_ray: int,
                            redistribute_v3: bool = False):
    """Jitted `make_redistributed_render_chunk`; budget = chunk·samples_per_ray."""
    key = (field_cfg, render_cfg, occ_cfg, int(chunk), int(samples_per_ray),
           bool(redistribute_v3))
    if key not in _REDIST_RENDER_CACHE:
        _REDIST_RENDER_CACHE[key] = jax.jit(make_redistributed_render_chunk(
            field_cfg, render_cfg, occ_cfg, int(chunk) * int(samples_per_ray),
            redistribute_v3=bool(redistribute_v3),
        ))
    return _REDIST_RENDER_CACHE[key]


# vmapped-over-sessions flavor of the eval renderers: same make_render_chunk
# construction, keyed the same way plus the padded group size, so sessions
# with different grid sizes can never share an entry.  Lives here (not in
# serve3d.render) so `evaluate` and the serve3d RenderService hit the same
# compiled functions — on XLA:CPU a vmapped group of 1 differs from the
# unvmapped renderer by ~1 ulp, so sharing one entry point is what makes
# "offline eval == served render" hold bit-for-bit, not just approximately.
_BATCH_RENDER_CACHE: dict[tuple, Any] = {}


def batched_render_fn(field_cfg, render_cfg: rendering.RenderConfig,
                      chunk: int, group: int):
    """(params stacked over G, origins (G,chunk,3), dirs (G,chunk,3),
    ts (chunk,S)) -> (rgb (G,chunk,3), depth (G,chunk))."""
    key = (field_cfg, render_cfg, int(chunk), int(group))
    if key not in _BATCH_RENDER_CACHE:
        _BATCH_RENDER_CACHE[key] = jax.jit(
            jax.vmap(make_render_chunk(field_cfg, render_cfg),
                     in_axes=(0, 0, 0, None))
        )
    return _BATCH_RENDER_CACHE[key]


def batched_redistributed_render_fn(field_cfg, render_cfg: rendering.RenderConfig,
                                    occ_cfg, chunk: int, group: int,
                                    samples_per_ray: int,
                                    redistribute_v3: bool = False):
    """Redistributed flavor of `batched_render_fn`: adds per-session
    occupancy (ema (G,R^3), fold count (G,)) inputs and shades only
    chunk·samples_per_ray points per session instead of chunk·S.

    redistribute_v3=True serves the density-weighted ragged path: the
    coalescer's chunk budget is spent unevenly across the chunk's rays
    (long live segments get more samples, packed Morton-ordered by the
    pipeline's compact stage), with the snapshot EMA weighting in-ray
    placement."""
    key = (field_cfg, render_cfg, occ_cfg, int(chunk), int(group),
           int(samples_per_ray), bool(redistribute_v3))
    if key not in _BATCH_RENDER_CACHE:
        _BATCH_RENDER_CACHE[key] = jax.jit(
            jax.vmap(make_redistributed_render_chunk(
                field_cfg, render_cfg, occ_cfg,
                int(chunk) * int(samples_per_ray),
                redistribute_v3=bool(redistribute_v3)),
                in_axes=(0, 0, 0, None, 0, 0))
        )
    return _BATCH_RENDER_CACHE[key]


def image_rays(pose, h: int, w: int, focal: float, eval_chunk: int):
    """Full-image rays padded to a chunk quantum.

    Returns (origins, dirs, n, chunk) with origins/dirs of length
    ceil(n/chunk)*chunk — the padding repeats the last ray so dirs stay
    unit-norm; callers trim to n.  Shared by `render_image` and the serve3d
    RenderService so both produce identical chunks (and hit the same
    compile-cache entries)."""
    py, px = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
    o, d = rendering.pixel_rays(
        jnp.asarray(pose), px.reshape(-1), py.reshape(-1), h, w, focal
    )
    n = h * w
    chunk = min(int(eval_chunk), n)
    pad = (-n) % chunk
    if pad:
        o = jnp.concatenate([o, jnp.broadcast_to(o[-1:], (pad, 3))])
        d = jnp.concatenate([d, jnp.broadcast_to(d[-1:], (pad, 3))])
    return o, d, n, chunk


@dataclass(frozen=True)
class TrainerConfig:
    n_rays: int = 1024
    iters: int = 400
    lr: float = 1e-2
    eps: float = 1e-15              # Instant-NGP's Adam epsilon
    b2: float = 0.99
    mlp_weight_decay: float = 1e-6
    # update frequencies, F_D : F_C = 1 : 0.5 by default (paper §5.1)
    f_density: float = 1.0
    f_color: float = 0.5
    use_occupancy: bool = True
    occ: occupancy.OccupancyConfig = dc_field(default_factory=occupancy.OccupancyConfig)
    render: rendering.RenderConfig = dc_field(default_factory=rendering.RenderConfig)
    seed: int = 0
    eval_chunk: int = 4096
    # occupancy-compacted field queries (pipeline stage 3): only live points
    # hit the hash grids; the budget tracks the measured live fraction in
    # pow2 buckets (bounded recompiles) with headroom against drift.
    compact: bool = True
    budget_headroom: float = 1.3
    min_budget: int = 512
    # fused compacted-path kernel (default on): the shade stage encodes all
    # grids in one pass over the Morton-ordered budget batch and back-props
    # table gradients through the pre-sorted BUM merge.  Bit-identical to the
    # unfused compacted path on the ref backend; turn off to time/debug the
    # PR 1 per-grid shade.
    fused_path: bool = True
    # one-kernel shade (default on, only meaningful with fused_path): the
    # compacted shade runs encode + both MLP heads as ONE custom-VJP op
    # (`field.query_step`) with the field config's residual policy deciding
    # what survives to the backward.  Bit-identical to fused_path with
    # separate MLP dispatches on the ref backend; turn off to time/debug the
    # PR 3 encode-then-MLP split.
    fused_step: bool = True
    # occupancy-guided sample redistribution (pipeline stage 2b): re-spend
    # each ray's freed sample budget on its live segments — S' = budget // B
    # samples per ray, inverse-CDF placed, per-sample quadrature deltas.
    # Points per step can only shrink (B*S' <= budget) while live regions
    # get finer stratification.  Enable it when a hard max_budget ceiling
    # bites (uniform compaction then truncates live points; BENCH_sampler
    # measures +1.8 dB held-out at equal points) — at generous budgets keep it off:
    # the uniform sampler is already unbiased there and shares its
    # quadrature with the dense eval renderer.  Off is the bit-exact
    # baseline.  Interaction with the budget-keyed step
    # cache: S' derives from the *static* budget at trace time, so the
    # existing (freeze_color, freeze_density, budget, use_bits) key already
    # pins the redistributed shapes — no new cache dimension.
    redistribute: bool = False
    # density-weighted, workload-balanced redistribution (stage 2b, v3):
    # live strata are weighted by the occupancy EMA (samples concentrate at
    # surface crossings) and the per-ray sample count S'_i is allocated by
    # one global inverse-CDF over the batch's live masses — rays with long
    # live segments get more of the point budget, sum(S') <= budget by
    # construction, and the compact stage Morton-packs the ragged rays into
    # exactly the budget.  Supersedes `redistribute` when both are set.
    # Budget keying: the ragged lane shapes derive from the *static* budget
    # at trace time and the knob lives on this config, so the existing
    # (cfg, ..., budget, use_bits) step-cache key already pins every v3
    # shape variant — no new cache dimension.  Off (default) is bit-exact:
    # the stage is never traced.
    redistribute_v3: bool = False
    # hard per-step point ceiling (on-device memory/latency cap).  When it
    # clamps the bucket below the live count, the uniform sampler must drop
    # live points every step (Morton-tail truncation); redistribution
    # spends exactly the ceiling instead, evenly across rays.
    max_budget: int | None = None


def autotune_max_budget(
    field_cfg,
    render_cfg: rendering.RenderConfig,
    *,
    memory_bytes: int | None = None,
    latency_ms: float | None = None,
    us_per_point: float | None = None,
    mlp_width: int = 64,
    min_budget: int = 512,
) -> int | None:
    """Derive a `TrainerConfig.max_budget` ceiling from device constraints.

    The on-device caps the paper targets are memory (a headset SoC's working
    set) and per-step latency; this hook turns either into the pow2 point
    ceiling the budget controller (and redistribute v3's exact-spend
    allocation) consumes:

    * memory: bytes/point is modeled from the field config — per grid
      L·F·4 B of features plus 8·4 B of corner indices, point/dir/sigma/rgb
      lanes, and two MLP activation slabs (forward + the recompute-policy
      backward residual).  `memory_bytes // bytes_per_point`, bucketed DOWN
      to a power of two (a ceiling must never round up).
    * latency: `latency_ms` over a measured `us_per_point` (e.g. the
      BENCH_fused_path per-point time) — callers without a measurement can
      pass none and get a memory-only answer.

    Returns the binding (smaller) ceiling, floored at `min_budget`, or None
    when no constraint was given (no ceiling — the suggest_budget default).
    """
    caps = []
    if memory_bytes is not None:
        n_grids = 2 if getattr(field_cfg, "decomposed", True) else 1
        feat = field_cfg.n_levels * field_cfg.n_features * 4 * n_grids
        corners = field_cfg.n_levels * 8 * 4 * n_grids
        lanes = (3 + 3 + 1 + 3) * 4                      # point/dir/sigma/rgb
        acts = 2 * mlp_width * 4                          # fwd + bwd residual
        caps.append(int(memory_bytes) // (feat + corners + lanes + acts))
    if latency_ms is not None and us_per_point:
        caps.append(int(float(latency_ms) * 1e3 / float(us_per_point)))
    if not caps:
        return None
    cap = max(min(caps), int(min_budget))
    b = 1
    while b * 2 <= cap:
        b *= 2
    return b


@jax.jit
def _finite_reduce(trees) -> jax.Array:
    acc = jnp.bool_(True)
    for leaf in jax.tree_util.tree_leaves(trees):
        x = jnp.asarray(leaf)
        if jnp.issubdtype(x.dtype, jnp.inexact):
            acc = acc & jnp.all(jnp.isfinite(x))
    return acc


def tree_all_finite(*trees) -> bool:
    """True iff every inexact leaf of every tree is finite (no NaN/Inf).

    The serve3d divergence guard's deep check: params, optimizer moments and
    the occupancy EMA are reduced to one host bool per call.  Integer leaves
    (opt step counts, occupancy fold counts) are skipped — finiteness is a
    float question.  The reduction is jitted (cached per tree structure, so
    per-slice cost is one dispatch + one scalar sync, the ≤ 1% guard-overhead
    budget) but runs strictly *outside* the training step's compiled path,
    so enabling the guard can never perturb traced training code."""
    acc = _finite_reduce(tuple(trees))
    return bool(acc)


def _branch_update(i: int, freq: float) -> bool:
    """Whether branch with frequency `freq` updates at iteration i (0-based)."""
    if freq >= 1.0:
        return True
    return math.floor((i + 1) * freq) > math.floor(i * freq)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    occ_state: occupancy.OccupancyState
    step: int


def _make_opt(cfg: TrainerConfig) -> AdamW:
    def lr_scale(path):
        # grids at full lr, MLPs at 0.1x — the NGP recipe
        return 1.0 if any("grid" in p for p in path) else 0.1

    return AdamW(
        lr=cfg.lr, b2=cfg.b2, eps=cfg.eps, weight_decay=0.0, lr_scale_fn=lr_scale
    )


def _make_raw_step(field, opt, pipeline, cfg: TrainerConfig, freeze_color: bool,
                   freeze_density: bool, budget: int | None, use_bits: bool):
    """Unjitted single-member train step: (params, opt_state, batch, ts,
    occ_ema) -> (params, opt_state, loss, aux).  The one construction point
    for both the legacy per-instance jit (`Instant3DTrainer.step_fn`) and the
    member-axis cohort step (`cohort_step_fn`), so they always compute the
    same function."""
    decomposed = field.cfg.decomposed

    def loss_fn(params, batch: rendering.RayBatch, ts, occ_ema):
        if freeze_color and decomposed:
            params = dict(params)
            params["color_grid"] = jax.lax.stop_gradient(params["color_grid"])
        if freeze_density:
            params = dict(params)
            params["density_grid"] = jax.lax.stop_gradient(params["density_grid"])
        bits = None
        if use_bits:
            # zero-init EMA is exactly zero until the first update folds
            # (trunc_exp densities are strictly positive afterwards), so
            # max>0 recovers the step for bitfield's all-occupied warmup
            # even when callers invoke step_fn directly on a fresh state
            folded = (jnp.max(occ_ema) > 0.0).astype(jnp.int32)
            state = occupancy.OccupancyState(occ_ema, folded)
            bits = occupancy.bitfield(state, cfg.occ)
        out = pipeline(
            params, batch.origins, batch.dirs, ts, bitfield=bits, budget=budget,
            # the EMA only feeds redistribute v3's stratum weights; the
            # pipeline ignores it on every other path
            occ_ema=occ_ema if use_bits else None,
        )
        aux = {
            "live_fraction": out["live_fraction"],
            "overflow": out["overflow"],
            "points_queried": out["points_queried"],
        }
        return losses.mse(out["rgb"], batch.rgb_gt), aux

    def step(params, opt_state, batch, ts, occ_ema):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, ts, occ_ema
        )
        mask = jax.tree.map(lambda _: True, params)
        if freeze_color:
            mask["color_grid"] = False
        if freeze_density:
            mask["density_grid"] = False
        params, opt_state = opt.apply(params, grads, opt_state, mask=mask)
        return params, opt_state, loss, aux

    return step


# ---- cohort step / occupancy-update compile caches (module level) ----
#
# Keyed per (field config, trainer config, step variant, cohort size M):
# every trainer instance and every train cohort with the same configs shares
# ONE compiled step — sequential baselines re-built per scene (benchmarks,
# parity checks) stop re-jitting, and a cohort re-formed under a different
# lead session never recompiles.
#
# The member axis is batched with `jax.lax.map` (scan), NOT `jax.vmap`:
# vmapping the step lets XLA:CPU re-tile the batched matmul/reduction
# contractions, which reassociates float accumulation and drifts the cohort
# ~1e-9 from the sequential path per step.  The scan body compiles once at
# singleton shapes and is empirically invariant to the trip count M and the
# member order (asserted by tests/test_serve3d_cohort.py), which is what
# makes cohort == sequential EXACT — `Instant3DTrainer.train` routes through
# the same construction at M=1.
_COHORT_STEP_CACHE: dict[tuple, Any] = {}
_OCC_UPDATE_CACHE: dict[tuple, Any] = {}


def _cohort_step_key(field_cfg, cfg: TrainerConfig, freeze_color: bool,
                     freeze_density: bool, budget: int | None, use_bits: bool,
                     m: int) -> tuple:
    """Cache key for one compiled step variant — also the observable the
    trace layer uses to split trainer/step_compile from trainer/step (a key
    first enters the cache on the same call that compiles it)."""
    return (field_cfg, cfg, bool(freeze_color), bool(freeze_density),
            budget, bool(use_bits), int(m))


def step_variant_cached(field_cfg, cfg: TrainerConfig, freeze_color: bool,
                        freeze_density: bool, budget: int | None,
                        use_bits: bool, m: int) -> bool:
    """Whether this step variant has already been built (and therefore
    compiled on its first call)."""
    return _cohort_step_key(field_cfg, cfg, freeze_color, freeze_density,
                            budget, use_bits, m) in _COHORT_STEP_CACHE


def cohort_step_fn(field_cfg, cfg: TrainerConfig, freeze_color: bool,
                   freeze_density: bool, budget: int | None, use_bits: bool,
                   m: int):
    """Jitted member-axis train step for an M-member cohort.

    (params, opt_state, batch, occ_ema) carry a leading member axis of size
    M; ts is shared (cohort members march the same step-keyed sample
    stream).  Stacked params/opt buffers are donated — the cohort advances
    in place like the per-instance step."""
    key = _cohort_step_key(field_cfg, cfg, freeze_color, freeze_density,
                           budget, use_bits, m)
    if obs_trace.enabled():
        which = "miss" if key not in _COHORT_STEP_CACHE else "hit"
        obs_metrics.counter(f"trainer.step_cache.{which}").inc()
    if key not in _COHORT_STEP_CACHE:
        field = field_lib.Field(field_cfg)
        pipeline = RenderPipeline(
            field, cfg.render, fused_path=cfg.fused_path,
            fused_step=cfg.fused_step, redistribute=cfg.redistribute,
            redistribute_v3=cfg.redistribute_v3,
        )
        raw = _make_raw_step(field, _make_opt(cfg), pipeline, cfg,
                             freeze_color, freeze_density, budget, use_bits)

        def member_steps(params, opt_state, batch, ts, occ_ema):
            return jax.lax.map(
                lambda a: raw(a[0], a[1], a[2], ts, a[3]),
                (params, opt_state, batch, occ_ema),
            )

        _COHORT_STEP_CACHE[key] = jax.jit(member_steps, donate_argnums=(0, 1))
    return _COHORT_STEP_CACHE[key]


def occ_update_fn(field_cfg, occ_cfg: occupancy.OccupancyConfig, m: int):
    """Jitted member-axis occupancy update for an M-member cohort.

    One compiled R^3-point density re-query serves the whole cohort (shared
    jitter rng, per-member params/EMA) instead of M eager op-by-op sweeps —
    the single biggest fixed cost the cohort amortizes.  Bit-identical to
    the eager `occupancy.update` at every M (the update is gather + matmul +
    elementwise max; no batched reassociation)."""
    key = (field_cfg, occ_cfg, int(m))
    if key not in _OCC_UPDATE_CACHE:
        field = field_lib.Field(field_cfg)

        def update_members(params, ema, step, rng):
            return jax.lax.map(
                lambda a: occupancy.update(
                    field, a[0], occupancy.OccupancyState(a[1], a[2]), occ_cfg, rng
                ),
                (params, ema, step),
            )

        _OCC_UPDATE_CACHE[key] = jax.jit(update_members)
    return _OCC_UPDATE_CACHE[key]


class Instant3DTrainer:
    def __init__(self, field: field_lib.Field, cfg: TrainerConfig):
        self.field = field
        self.cfg = cfg
        self.opt = _make_opt(cfg)
        self.pipeline = RenderPipeline(
            field, cfg.render, fused_path=cfg.fused_path,
            fused_step=cfg.fused_step, redistribute=cfg.redistribute,
            redistribute_v3=cfg.redistribute_v3,
        )
        self._step_fns = {}
        # host-side live-fraction estimate driving the compaction budget;
        # starts at 1.0 (occupancy warmup = all-occupied => dense)
        self._live_frac = 1.0
        # rolling per-step overflow scalars (device) feeding the budget-widening
        # check; kept on the instance (not per train() call) so time-sliced
        # training — many short train() calls — widens exactly like one long
        # sequential run regardless of where the slice boundaries fall
        self._overflow_window: list = []

    # ---- state ----

    def init(self, rng: jax.Array) -> TrainState:
        params = self.field.init(rng)
        return TrainState(
            params=params,
            opt_state=self.opt.init(params),
            occ_state=occupancy.init_state(self.cfg.occ),
            step=0,
        )

    # ---- jitted step ----

    def _make_step(self, freeze_color: bool, freeze_density: bool = False,
                   budget: int | None = None, use_bits: bool = False):
        step = _make_raw_step(self.field, self.opt, self.pipeline, self.cfg,
                              freeze_color, freeze_density, budget, use_bits)
        return jax.jit(step, donate_argnums=(0, 1))

    def step_fn(self, freeze_color: bool, freeze_density: bool = False,
                budget: int | None = None, use_bits: bool | None = None):
        if use_bits is None:
            use_bits = self.cfg.use_occupancy
        key = (freeze_color, freeze_density, budget, use_bits)
        if key not in self._step_fns:
            self._step_fns[key] = self._make_step(
                freeze_color, freeze_density, budget, use_bits
            )
        return self._step_fns[key]

    def _current_budget(self, use_bits: bool) -> int | None:
        """Static point budget for the next step, or None for the dense path.

        Gated on use_bits: before the first occupancy update the bitfield is
        inactive and nearly all in-box samples are live, so a carried-over
        budget (e.g. trainer reused on a fresh state) would silently drop
        live samples."""
        if not (self.cfg.compact and self.cfg.use_occupancy and use_bits):
            return None
        n_total = self.cfg.n_rays * self.cfg.render.n_samples
        budget = suggest_budget(
            self._live_frac, n_total,
            headroom=self.cfg.budget_headroom, min_budget=self.cfg.min_budget,
            max_budget=self.cfg.max_budget,
        )
        return None if budget >= n_total else budget

    # ---- driver ----

    def train(
        self,
        state: TrainState,
        sampler,
        iters: int | None = None,
        log_every: int = 50,
        callback=None,
    ) -> tuple[TrainState, dict]:
        """Advance training by `iters` iterations.

        Implemented as a train cohort of one: the exact same member-axis
        compiled step and batched occupancy update that advance an M-scene
        cohort in serve3d run here at M=1, so a session trained inside a
        cohort and one trained alone produce bit-identical streams."""
        states, hists = train_cohort(
            [self], [state], [sampler],
            iters=iters, log_every=log_every, callback=callback,
        )
        return states[0], hists[0]

    def step_cache_keys(self) -> set:
        """Compiled step-variant keys for this trainer's configs (freeze
        flags, budget, use_bits, cohort size) — the observable for "did this
        run recompile?" probes now that step compilation is shared module-
        wide (benchmarks/bench_pipeline.py uses it to detect budget-bucket
        widening)."""
        return {
            k[2:] for k in _COHORT_STEP_CACHE
            if k[0] == self.field.cfg and k[1] == self.cfg
        }

    # ---- suspend / resume (host-state hooks for time-sliced sessions) ----

    def suspend(self, state: TrainState) -> dict:
        """Device -> host snapshot of everything needed to continue
        bit-identically: model/optimizer/occupancy state plus the trainer's
        host-side compaction bookkeeping (live fraction + overflow window).
        The returned flat-keyed dict is exactly what `CheckpointManager.save`
        expects, and `resume` (or `suspend` of a fresh `init` state, as a
        restore template) round-trips it."""
        win = np.zeros((self.cfg.occ.update_interval,), np.int32)
        recent = [int(x) for x in self._overflow_window[-len(win):]]
        if recent:
            win[-len(recent):] = recent
        return {
            "params": jax.device_get(state.params),
            "opt": jax.device_get(state.opt_state),
            "occ_ema": np.asarray(state.occ_state.density_ema),
            "occ_step": np.asarray(state.occ_state.step),
            "step": np.asarray(state.step, np.int32),
            "live_frac": np.asarray(self._live_frac, np.float32),
            "overflow_window": win,
        }

    def resume(self, tree: dict) -> TrainState:
        """Inverse of `suspend`: restore host state onto the device and
        re-seed the trainer's compaction bookkeeping."""
        self._live_frac = float(tree["live_frac"])
        self._overflow_window = [
            jnp.asarray(v, jnp.int32) for v in np.asarray(tree["overflow_window"])
        ]
        return TrainState(
            params=jax.tree.map(jnp.asarray, tree["params"]),
            opt_state=jax.tree.map(jnp.asarray, tree["opt"]),
            occ_state=occupancy.OccupancyState(
                jnp.asarray(tree["occ_ema"]), jnp.asarray(tree["occ_step"], jnp.int32)
            ),
            step=int(tree["step"]),
        )

    # ---- evaluation ----

    def render_image(self, params, pose: np.ndarray, ds, occ=None,
                     samples_per_ray: int | None = None):
        """Render one full view.  Dense by default; pass `occ` (the
        (density EMA, fold count) pair `suspend`/serve3d snapshots carry) to
        render through the configured redistribute variant instead — the
        exact vmapped group-of-1 entry the serve3d RenderService coalesces
        through, so an offline eval render is bit-identical to a served
        render of the same snapshot."""
        cfg = self.cfg
        h, w = ds.h, ds.w
        o, d, n, chunk = image_rays(pose, h, w, ds.focal, cfg.eval_chunk)
        ts = rendering.sample_ts(None, chunk, cfg.render)
        if occ is not None and cfg.use_occupancy:
            spr = (int(samples_per_ray) if samples_per_ray is not None
                   else default_samples_per_ray(cfg.render.n_samples))
            fn_r = batched_redistributed_render_fn(
                self.field.cfg, cfg.render, cfg.occ, chunk, 1, spr,
                redistribute_v3=cfg.redistribute_v3)
            occ_ema = jnp.asarray(occ[0])[None]
            occ_step = jnp.asarray([int(occ[1])], jnp.int32)
            stacked = jax.tree.map(lambda a: jnp.asarray(a)[None], params)
            fn = lambda p, oo, dd, tt: fn_r(  # noqa: E731
                stacked, oo[None], dd[None], tt, occ_ema, occ_step)
        else:
            fn = eval_render_fn(self.field.cfg, cfg.render, chunk)
        rgb_out, dep_out = [], []
        for i in range(0, o.shape[0], chunk):
            rgb_c, dep_c = fn(params, o[i : i + chunk], d[i : i + chunk], ts)
            if rgb_c.ndim == 3:          # strip the group-of-1 axis
                rgb_c, dep_c = rgb_c[0], dep_c[0]
            rgb_out.append(rgb_c)
            dep_out.append(dep_c)
        rgb = jnp.concatenate(rgb_out)[:n].reshape(h, w, 3)
        dep = jnp.concatenate(dep_out)[:n].reshape(h, w)
        return np.asarray(rgb), np.asarray(dep)

    def evaluate(self, params, ds, views=None, occ=None,
                 samples_per_ray: int | None = None) -> dict:
        """PSNR of rendered RGB and depth vs ground truth (paper Fig. 5
        stats).  With `occ`, views render through the redistribute variant
        (see `render_image`) so eval marches the serving quadrature."""
        views = views if views is not None else range(min(4, ds.images.shape[0]))
        rgb_ps, dep_ps = [], []
        for v in views:
            rgb, dep = self.render_image(params, ds.poses[v], ds, occ=occ,
                                         samples_per_ray=samples_per_ray)
            rgb_ps.append(float(losses.psnr(jnp.asarray(rgb), jnp.asarray(ds.images[v]))))
            # depth normalized to [0,1] over the far range for a bounded PSNR
            far = self.cfg.render.far
            dep_ps.append(float(losses.psnr(jnp.asarray(dep / far), jnp.asarray(ds.depths[v] / far))))
        return {"psnr_rgb": float(np.mean(rgb_ps)), "psnr_depth": float(np.mean(dep_ps))}


# ---- cohort driver: lockstep training of M same-config sessions ----


class _CohortGroup:
    """One stacked sub-cohort: members that currently share a compiled step
    variant (same use_bits + point budget).  Holds member-axis-stacked
    params/opt/occupancy plus the stacked ray pools their batches gather
    from.  The partition over groups only shifts when per-member budgets
    drift apart at an occupancy update, so stacked state persists across
    iterations — no per-step stack/unstack traffic."""

    def __init__(self, members, params, opt_state, ema, occ_step, samplers):
        self.members = list(members)          # global member indices, in order
        self.params = params                  # leading axis = len(members)
        self.opt_state = opt_state
        self.ema = ema                        # (G, R^3)
        self.occ_step = occ_step              # (G,) int32
        self.use_bits = False
        self.budget = None
        self.last_aux = None
        ns = {samplers[k].n for k in self.members}
        if len(self.members) > 1 and len(ns) == 1:
            # equal ray pools: one shared index draw gathers every member's
            # batch (identical indices to each member's own sampler.sample —
            # same key, same bound).  Only worth the stacked pool copy for a
            # real cohort; singletons (every plain train() call) gather from
            # the sampler's own arrays with zero extra device residency.
            self.pool = tuple(
                jnp.stack([getattr(samplers[k], f) for k in self.members])
                for f in ("origins", "dirs", "rgb")
            )
        else:
            self.pool = None

    def member_tree(self, tree, k: int):
        r = self.members.index(k)
        return jax.tree.map(lambda x: x[r], tree)

    def sample(self, samplers, key_batch, n_rays: int) -> rendering.RayBatch:
        if self.pool is not None:
            idx = samplers[self.members[0]].sample_idx(key_batch, n_rays)
            o, d, rgb = self.pool
            return rendering.RayBatch(o[:, idx], d[:, idx], rgb[:, idx])
        per = [samplers[k].sample(key_batch, n_rays) for k in self.members]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per)


def _partition_members(trainers, use_occupancy, occ_updates):
    """(use_bits, budget) step-variant key per member -> ordered partition."""
    keys = []
    for k, tr in enumerate(trainers):
        use_bits = use_occupancy and occ_updates[k] > 0
        keys.append((use_bits, tr._current_budget(use_bits)))
    part: list[tuple[tuple, list[int]]] = []
    for k, key in enumerate(keys):
        grouped = next((g for g in part if g[0] == key), None)
        if grouped is None:
            part.append((key, [k]))
        else:
            grouped[1].append(k)
    return part


def train_cohort(
    trainers: list,
    states: list,
    samplers: list,
    iters: int | None = None,
    log_every: int = 50,
    callback=None,
) -> tuple[list, list]:
    """Advance M same-config training sessions in lockstep.

    All members must share (field config, trainer config) and sit at the
    same absolute step; their params, optimizer state, occupancy EMAs and
    ray batches are stacked along a leading member axis and one compiled
    member-axis step (`cohort_step_fn`) advances the whole cohort per
    iteration.  Per-member host bookkeeping (live-fraction estimate,
    overflow window — each `trainers[k]`'s instance state, exactly what
    suspend/resume round-trips) is maintained identically to M sequential
    `Instant3DTrainer.train` runs, and the compiled body is the same one
    `train` itself runs at M=1, so the cohort is bit-identical to
    sequential time-slicing — params, optimizer moments and occupancy EMA
    (asserted in tests and BENCH_serve3d).

    Members whose measured point budgets drift apart at an occupancy update
    split into separately-stacked sub-cohorts (`_CohortGroup`) and keep
    advancing in lockstep; the shared-key sample stream, occupancy cadence
    and freeze schedule depend only on the absolute step, so the split
    changes where the work happens, never the numbers.

    Returns (new_states, histories), parallel to the inputs.
    """
    m = len(trainers)
    assert m == len(states) == len(samplers), "trainers/states/samplers must align"
    lead = trainers[0]
    cfg, field_cfg = lead.cfg, lead.field.cfg
    for t in trainers[1:]:
        if t.cfg != cfg or t.field.cfg != field_cfg:
            raise ValueError("cohort members must share field and trainer configs")
    step0 = states[0].step
    if any(s.step != step0 for s in states):
        raise ValueError("cohort members must be at the same training step")
    iters = iters if iters is not None else cfg.iters
    key = jax.random.PRNGKey(cfg.seed)
    # one clock for history wall_s, spans and benchmarks (repro.obs.trace owns
    # it) — telemetry and bench timings can never disagree on step wall time
    t0 = obs_trace.clock()

    histories = [
        {"step": [], "loss": [], "live_fraction": [], "wall_s": [],
         "points_queried": [], "overflow": []}
        for _ in range(m)
    ]
    # per-step overflow kept on device as stacked (M,) scalars — ONE list
    # append per iteration, no per-member slicing in the hot loop; member
    # columns are materialized only at the occupancy cadence (budget check)
    # and at the end (history totals + each trainer's rolling window)
    overflow_accum: list = []

    def window_sums(recent: list) -> np.ndarray:
        """(M,) per-member sums over stacked window entries (one host sync)."""
        if not recent:
            return np.zeros((m,), np.int64)
        return np.asarray(jnp.sum(jnp.stack(recent), axis=0))

    # bitfield is meaningless until the first EMA fold (init is zeros);
    # render dense until then, and budget from the measured live fraction
    occ_updates = [
        int(s.occ_state.step) if cfg.use_occupancy else 0 for s in states
    ]
    for k, tr in enumerate(trainers):
        if occ_updates[k] == 0:
            tr._live_frac = 1.0  # fresh state: forget any previous run
            tr._overflow_window = []

    # seed the stacked (M,)-per-entry overflow window from the members'
    # per-trainer windows (they advance in lockstep, so equal lengths is the
    # invariant; a ragged mix — cohort formed from sessions with unrelated
    # histories — keeps exactness by degrading to per-member entries)
    prior = [t._overflow_window for t in trainers]
    if len({len(w) for w in prior}) == 1:
        window = [
            jnp.stack([jnp.asarray(w[j], jnp.int32) for w in prior])
            for j in range(len(prior[0]))
        ]
    else:
        window = None

    def build_groups(partition, member_state):
        groups = []
        for (use_bits, budget), members in partition:
            stackit = lambda f: jax.tree.map(
                lambda *xs: jnp.stack(xs), *[f(k) for k in members]
            )
            g = _CohortGroup(
                members,
                stackit(lambda k: member_state[k][0]),
                stackit(lambda k: member_state[k][1]),
                jnp.stack([member_state[k][2] for k in members]),
                jnp.stack([member_state[k][3] for k in members]),
                samplers,
            )
            g.use_bits, g.budget = use_bits, budget
            groups.append(g)
        return groups

    partition = _partition_members(trainers, cfg.use_occupancy, occ_updates)
    groups = build_groups(
        partition,
        [(s.params, s.opt_state, s.occ_state.density_ema, s.occ_state.step)
         for s in states],
    )

    for local_i in range(iters):
        i = step0 + local_i
        key_batch, key_ts, key_occ = jax.random.split(jax.random.fold_in(key, i), 3)
        ts = rendering.sample_ts(key_ts, cfg.n_rays, cfg.render)

        update_color = _branch_update(i, cfg.f_color)
        update_density = _branch_update(i, cfg.f_density)
        freeze_color = (not update_color) and field_cfg.decomposed
        freeze_density = not update_density

        want = _partition_members(trainers, cfg.use_occupancy, occ_updates)
        if [p[0] for p in want] != [(g.use_bits, g.budget) for g in groups] or \
           [p[1] for p in want] != [g.members for g in groups]:
            member_state = {}
            for g in groups:
                for k in g.members:
                    member_state[k] = (
                        g.member_tree(g.params, k), g.member_tree(g.opt_state, k),
                        g.member_tree(g.ema, k), g.member_tree(g.occ_step, k),
                    )
            groups = build_groups(want, member_state)

        where = [None] * m  # member -> (group, row) for this iteration
        obs_on = obs_trace.enabled()
        for g in groups:
            with obs_trace.span("trainer/sample", cat="trainer"):
                batch = g.sample(samplers, key_batch, cfg.n_rays)
            if obs_on:
                # compile/execute split: a step variant's first-ever call is
                # the one that traces + compiles it (its cache key appears on
                # that call — `step_variant_cached`/`step_cache_keys` is the
                # observable).  The whole probe sits behind the knob so the
                # disabled hot loop never hashes a config tuple.
                fresh = not step_variant_cached(
                    field_cfg, cfg, freeze_color, freeze_density,
                    g.budget, g.use_bits, len(g.members))
                span = obs_trace.span(
                    "trainer/step_compile" if fresh else "trainer/step",
                    cat="trainer",
                    args={"step": int(i), "cohort": len(g.members),
                          "budget": g.budget, "use_bits": g.use_bits})
            else:
                span = obs_trace.NULL
            fn = cohort_step_fn(field_cfg, cfg, freeze_color, freeze_density,
                                g.budget, g.use_bits, len(g.members))
            with span:
                g.params, g.opt_state, loss, aux = fn(
                    g.params, g.opt_state, batch, ts, g.ema
                )
            g.last_aux = aux
            g.last_loss = loss
            for r, k in enumerate(g.members):
                where[k] = (g, r)
        if obs_on:
            obs_metrics.counter("trainer.steps").inc(m)
            obs_metrics.gauge("trainer.cohort_size").set(m)
            obs_metrics.gauge("trainer.cohort_groups").set(len(groups))
        # one stacked (M,) overflow entry per iteration (the single-group
        # common case appends the step's own aux with no regather)
        if len(groups) == 1:
            ov = groups[0].last_aux["overflow"]
        else:
            ov = jnp.stack([where[k][0].last_aux["overflow"][where[k][1]]
                            for k in range(m)])
        overflow_accum.append(ov)
        if window is not None:
            window.append(ov)
            del window[: -cfg.occ.update_interval]
        else:
            for k in range(m):
                trainers[k]._overflow_window.append(ov[k])
                del trainers[k]._overflow_window[: -cfg.occ.update_interval]

        if cfg.use_occupancy and i >= cfg.occ.warmup_steps and \
                (i + 1) % cfg.occ.update_interval == 0:
            # overflow since the last update, summed per member (one host
            # sync): overflow means the live set outgrew the bucket between
            # measurements — widen beyond the measurement so the next bucket
            # has room.  The window spans train()/cohort calls (time-sliced
            # sessions see the same history as one long sequential run).
            if window is not None:
                recent_sums = window_sums(window[-cfg.occ.update_interval:])
            for g in groups:
                upd = occ_update_fn(field_cfg, cfg.occ, len(g.members))
                with obs_trace.span("trainer/occ_update", cat="trainer",
                                    args={"step": int(i),
                                          "cohort": len(g.members)}):
                    new_occ = upd(g.params, g.ema, g.occ_step, key_occ)
                g.ema, g.occ_step = new_occ.density_ema, new_occ.step
                # re-measure the batch live fraction at the occupancy cadence
                # (one host sync per update, not per step) to size the budget
                if g.use_bits:
                    live = np.asarray(g.last_aux["live_fraction"])
                for r, k in enumerate(g.members):
                    occ_updates[k] += 1
                    if g.use_bits:
                        measured = float(live[r])
                        # consider every step since the last update, not just
                        # this one — per-step live counts fluctuate with
                        # stratified ts
                        if window is not None:
                            overflowed = int(recent_sums[k]) > 0
                        else:
                            recent = trainers[k]._overflow_window[-cfg.occ.update_interval:]
                            overflowed = bool(recent) and int(jnp.sum(jnp.stack(recent))) > 0
                        if overflowed:
                            measured = min(1.0, measured * 2.0)
                        trainers[k]._live_frac = measured

        if (local_i + 1) % log_every == 0 or local_i == iters - 1:
            wall = obs_trace.clock() - t0
            for g in groups:
                with obs_trace.span("trainer/log_sync", cat="trainer"):
                    loss_h = np.asarray(g.last_loss)
                    live_h = np.asarray(g.last_aux["live_fraction"])
                    pts_h = np.asarray(g.last_aux["points_queried"])
                    ov_h = np.asarray(g.last_aux["overflow"])
                for r, k in enumerate(g.members):
                    h = histories[k]
                    h["step"].append(i + 1)
                    h["loss"].append(float(loss_h[r]))
                    h["live_fraction"].append(float(live_h[r]))
                    h["points_queried"].append(int(pts_h[r]))
                    h["overflow"].append(int(ov_h[r]))
                    h["wall_s"].append(wall)
                    if callback is not None:
                        callback(i + 1, g.member_tree(g.params, k), h)
                if obs_on:
                    # strays folded into the registry at the log cadence —
                    # these host syncs already happen for the history above,
                    # so the metrics plane adds no extra device round-trips.
                    # Gauges carry last-step values; per-interval totals stay
                    # in the returned history (overflow_total/overflow_steps).
                    obs_metrics.gauge("trainer.live_fraction").set(
                        float(live_h[-1]))
                    obs_metrics.gauge("trainer.loss").set(float(loss_h[-1]))
                    obs_metrics.gauge("trainer.points_per_step").set(
                        int(np.sum(pts_h)))
                    obs_metrics.gauge("trainer.overflow_last_step").set(
                        int(np.sum(ov_h)))

    new_states = [None] * m
    for g in groups:
        for k in g.members:
            new_states[k] = TrainState(
                g.member_tree(g.params, k),
                g.member_tree(g.opt_state, k),
                occupancy.OccupancyState(
                    g.member_tree(g.ema, k), g.member_tree(g.occ_step, k)
                ),
                step0 + iters,
            )
    if overflow_accum:
        all_overflow = jnp.stack(overflow_accum)          # (iters, M)
        totals = np.asarray(jnp.sum(all_overflow, axis=0))
        steps_ = np.asarray(jnp.sum(all_overflow > 0, axis=0))
    else:
        totals = steps_ = np.zeros((m,), np.int64)
    for k, h in enumerate(histories):
        h["overflow_total"] = int(totals[k])
        h["overflow_steps"] = int(steps_[k])
    if window is not None:
        # hand each trainer back its per-member rolling window (one sync);
        # plain ints sum identically, so suspend/resume and later singleton
        # train() calls see exactly the sequential-path history
        tail = np.asarray(jnp.stack(window)) if window else \
            np.zeros((0, m), np.int64)
        for k, tr in enumerate(trainers):
            tr._overflow_window = [int(v) for v in tail[:, k]]
    return new_states, histories
