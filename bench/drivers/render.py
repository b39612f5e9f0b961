"""Render driver: one closed-loop client of serve3d's `RenderService`.

Set-up publishes a snapshot from the seed, taken before its first
occupancy update, with table entries of the order a trained field has
(`snapshot_table_scale`), so that every pixel depends on the encode;
registers the session for redistributed serving, and serves one view to
compile the render program.  The window
submits a view, drains the service and takes the pixels, one request at a
time, until the deadline has passed.  A seed-drawn sample of the views the
window served is kept for the comparison.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import compare, counts
from bench import traffic as T
from bench import weights

SESSION = "bench-scene"


class Driver:
    unit = "view"
    faults = ()
    numbers = staticmethod(compare.render_numbers)

    def __init__(self, cell):
        self.cfg, self.t, self.seed = cell.config, cell.traffic, cell.seed
        self.kseed = T.key_seed(self.seed)
        self.hw = self.t["hw"]
        self.focal = T.focal(self.t, self.hw)

    def setup(self):
        from repro.core import FieldConfig, occupancy
        from repro.core.rendering import RenderConfig
        from repro.serve3d.render import RenderService
        from repro.serve3d.snapshot import SnapshotStore

        scene = self.cfg["scene"]
        render = RenderConfig(n_samples=self.t["probe_samples"], near=scene["near"],
                              far=scene["far"], aabb_min=scene["aabb_min"],
                              aabb_max=scene["aabb_max"],
                              white_background=scene["white_background"])
        occ = occupancy.OccupancyConfig()
        params = jax.device_get(self._snapshot())
        store = SnapshotStore()
        store.publish(SESSION, params, step=0,
                      occ=(np.zeros(occ.resolution ** 3, np.float32), 0))
        self.service = RenderService(store)
        self.service.register_session(SESSION, FieldConfig(**self.cfg["field"]), render,
                                      self.hw, self.hw, self.focal,
                                      eval_chunk=self.t["eval_chunk"], occ_cfg=occ,
                                      samples_per_ray=self.t["samples_per_ray"])
        self.poses = T.render_poses(self.t, self.seed)
        self.served = []
        self._view(self.poses[-1])          # compiles the render program

    def _snapshot(self):
        return weights.make(self.cfg["field"], self.kseed, self.t["snapshot_table_scale"])

    def _view(self, pose):
        from repro.serve3d.render import RenderResult
        with jax.profiler.TraceAnnotation("bench/view"):
            self.service.submit(SESSION, pose)
            results = self.service.drain()
        ok = [r for r in results if isinstance(r, RenderResult)]
        return ok[0] if len(ok) == 1 and len(results) == 1 else None

    def window(self, seconds: float) -> dict:
        failed, n, marks = 0, 0, []
        t0 = time.perf_counter()
        while True:
            i = len(self.served)
            res = self._view(self.poses[i % len(self.poses)])
            self.served.append(None if res is None else (res.rgb, res.depth))
            failed += res is None
            n += 1
            marks.append(time.perf_counter())
            if marks[-1] - t0 >= seconds:
                break
        elapsed = marks[-1] - t0
        return {"units": n, "seconds": elapsed, "attempted": n, "failed": int(failed),
                "unit_seconds": np.diff([t0] + marks).tolist()}

    def free(self):
        self.service = None
        jax.clear_caches()

    def serve_for_check(self):
        """Serve, outside any window, the views the comparison checks."""
        while len(self.served) < self.t["check_views"]:
            self.window(0.0)

    # ---- what the readers count ----

    def points_per_unit(self) -> int:
        return self.hw * self.hw * self.t["samples_per_ray"]

    def kernel_rows(self) -> int:
        """Rows of one call of a Pallas MLP kernel: one chunk's points."""
        return self.t["eval_chunk"] * self.t["samples_per_ray"]

    def window_flops(self, window: dict) -> float:
        return counts.render_flops(self.cfg["field"], self.points_per_unit()) * window["units"]

    # ---- the comparison's inputs ----

    def checked_views(self) -> list[int]:
        """A seed-drawn sample of the views the window served."""
        rng = T.rng_for(self.seed, "check")
        n = len(self.served)
        return sorted(rng.choice(n, min(n, self.t["check_views"]), replace=False).tolist())

    def program_readings(self) -> dict:
        return {"views": [self.served[i] for i in self.checked_views()]}

    def reference_readings(self, precision: str = "highest", fault: str | None = None) -> dict:
        from bench.reference import render as ref
        params = self._snapshot()
        views = [ref.render_view(self.cfg, params, self.poses[i % len(self.poses)], self.hw,
                                 self.hw, self.focal, self.t["probe_samples"],
                                 self.t["samples_per_ray"], precision)
                 for i in self.checked_views()]
        return {"views": views}
