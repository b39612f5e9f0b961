"""Training driver: `Instant3DTrainer.train`, one step per call.

Set-up builds the trainer, its state and the program's own `RaySampler`
over the seed's scene, and drives the first `check_steps` steps through
the same call and feed the window uses; those steps compile both step
variants, and the rows the sampler drew for them are kept, so that the
reference re-does the same steps after the window.  The window then calls `train(iters=1)` and waits for the
parameters, step after step, until the deadline has passed and the steps
make whole cycles of the update frequencies.

The occupancy grid is off, so every step shades all candidate points, as
the trainer does through its occupancy warm-up; with it on, a window that
reached the end of the warm-up would compile the culled step variants.
"""
from __future__ import annotations

import math
import time
from fractions import Fraction

import jax
import numpy as np

from bench import compare, counts
from bench import traffic as T
from bench import weights
from bench.reference.train import freeze_color_at


# The trainer's own sample stream is the same for every seed, so that runs of
# one process on many seeds share compiled steps (the stream's seed is part
# of the step's cache key).  The run's seed varies the scene, the weights
# and, through the sampler, which rays every step draws.
TRAINER_SEED = 0


def recording_sampler(ds):
    """The program's `RaySampler` over `ds`, keeping the rows it draws
    while `recording` is set."""
    from repro.data.rays_dataset import RaySampler

    class Recording(RaySampler):
        def sample_idx(self, rng, batch):
            idx = super().sample_idx(rng, batch)
            if self.recording:
                self.given.append(idx)
            return idx

    sampler = Recording(ds)
    sampler.given, sampler.recording = [], False
    return sampler


class Driver:
    unit = "step"
    faults = ("half_batch",)          # planted in the reference, read by calibrate.py
    numbers = staticmethod(compare.train_numbers)

    def __init__(self, cell):
        self.cfg, self.t, self.seed = cell.config, cell.traffic, cell.seed
        self.kseed = T.key_seed(self.seed)

    # ---- the program's objects ----

    def _program(self):
        from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig
        from repro.core.rendering import RenderConfig

        opt, scene = self.cfg["optimizer"], self.cfg["scene"]
        render = RenderConfig(n_samples=self.t["samples_per_ray"], near=scene["near"],
                              far=scene["far"], aabb_min=scene["aabb_min"],
                              aabb_max=scene["aabb_max"],
                              white_background=scene["white_background"])
        tcfg = TrainerConfig(n_rays=self.t["rays"], render=render, seed=TRAINER_SEED,
                             lr=opt["lr"], eps=opt["eps"], b2=opt["b2"],
                             f_density=opt["f_density"], f_color=opt["f_color"],
                             use_occupancy=False)
        return Instant3DTrainer(Field(FieldConfig(**self.cfg["field"])), tcfg)

    def setup(self):
        from repro.core import TrainState, occupancy
        from repro.data.synthetic_scene import SceneDataset

        hw = self.cfg["scene_hw"]
        self.poses, self.images = T.scene_views(self.t, self.cfg["scene_views"], hw, self.seed)
        self.sampler = recording_sampler(SceneDataset(
            self.images, np.zeros(self.images.shape[:3], np.float32), self.poses,
            T.focal(self.t, hw), hw, hw))
        self.trainer = self._program()
        params = weights.make(self.cfg["field"], self.kseed)
        self.state = TrainState(params, self.trainer.opt.init(params),
                                occupancy.init_state(self.trainer.cfg.occ), 0)
        del params
        self.losses, self.first_m = [], None
        self.sampler.recording = True
        for i in range(self.t["check_steps"]):
            self._step()
            self.losses.append(self.last_loss)
            if i == 0:
                self.first_m = jax.device_get(self.state.opt_state.m)
        self.sampler.recording = False
        self.rows = [np.asarray(r) for r in self.sampler.given]
        self.checked_params = jax.device_get(self.state.params)

    def _step(self):
        with jax.profiler.TraceAnnotation("bench/train_step"):
            self.state, hist = self.trainer.train(self.state, self.sampler, iters=1,
                                                  log_every=1)
            jax.block_until_ready(self.state.params)
        self.last_loss = hist["loss"][-1]

    def period(self) -> int:
        """Steps in one cycle of the update frequencies (2 for F_C = 0.5)."""
        opt = self.cfg["optimizer"]
        p = 1
        for f in (opt["f_density"], opt["f_color"]):
            p = math.lcm(p, Fraction(f).limit_denominator(64).denominator)
        return p

    def window(self, seconds: float) -> dict:
        """Whole steps until the deadline has passed and the window holds
        whole update cycles, so each step variant counts as often as the
        schedule runs it."""
        first = self.state.step
        failed, marks = 0, []
        t0 = time.perf_counter()
        while True:
            self._step()
            failed += not np.isfinite(self.last_loss)
            marks.append(time.perf_counter())
            if marks[-1] - t0 >= seconds and (self.state.step - first) % self.period() == 0:
                break
        steps = list(range(first, self.state.step))
        return {"units": len(steps), "seconds": marks[-1] - t0, "attempted": len(steps),
                "failed": int(failed), "steps": steps,
                "unit_seconds": np.diff([t0] + marks).tolist()}

    def free(self):
        """Drop every device array the program holds, before the reference runs."""
        from repro.core import trainer as trainer_lib
        self.state = self.trainer = self.sampler = None
        trainer_lib._COHORT_STEP_CACHE.clear()
        jax.clear_caches()

    def serve_for_check(self):
        """Set-up already ran the steps the comparison checks."""

    # ---- what the readers count ----

    def points_per_unit(self) -> int:
        return self.t["rays"] * self.t["samples_per_ray"]

    def kernel_rows(self) -> int:
        """Rows of one call of a Pallas MLP kernel: every point of the step."""
        return self.points_per_unit()

    def window_flops(self, window: dict) -> float:
        """Model FLOPs of the steps the window completed, each by its variant."""
        n = self.points_per_unit()
        return sum(counts.train_step_flops(self.cfg["field"], n, freeze_color_at(i, self.cfg))
                   for i in window["steps"])

    # ---- the comparison's inputs ----

    def program_readings(self) -> dict:
        b1 = self.cfg["optimizer"]["b1"]
        grads = jax.tree.map(lambda m: np.asarray(m, np.float64) / (1.0 - b1), self.first_m)
        return {"losses": self.losses, "first_grads": grads, "params": self.checked_params}

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        """The reference's three steps on the rows the program drew: rays
        made by the reference from the same poses, pixels from the scene."""
        import jax.numpy as jnp
        from bench.reference import render as ref_render
        from bench.reference import train as ref

        cfg, hw = self.cfg, self.cfg["scene_hw"]
        rays = [ref_render.view_rays(p, hw, hw, T.focal(self.t, hw), precision)
                for p in self.poses]
        o = jnp.concatenate([r[0] for r in rays])
        d = jnp.concatenate([r[1] for r in rays])
        rgb = jnp.asarray(self.images.reshape(-1, 3))
        key = jax.random.PRNGKey(TRAINER_SEED)
        batches = []
        for i, rows in enumerate(self.rows):
            _, key_ts, _ = jax.random.split(jax.random.fold_in(key, i), 3)
            ts = ref.sample_ts(key_ts, self.t["rays"], self.t["samples_per_ray"], cfg["scene"])
            batches.append((o[rows], d[rows], rgb[rows], ts))
        params = weights.make(self.cfg["field"], self.kseed)
        init = jax.device_get(params)
        losses, grads, norms, after = ref.run(cfg, params, batches, precision,
                                              half_batch=fault == "half_batch")
        return {"losses": losses, "first_grads": grads, "params": after, "init": init,
                "grad_norms": [{k: float(v) for k, v in compare.leaves(n).items()}
                               for n in norms]}
