"""Cells cut to sizes the CPU runs in seconds, for driving the harness in tests.

Widths that decide a kernel's shape (64-wide MLPs, F=2, SH degree 4) stay.
"tiny" also shrinks levels, tables, rays, samples and views; "control" keeps
all 16 levels and 48 samples a ray over 1024 rays (a render cell keeps its
full size), which is what it takes for the lower-precision control to show
on the CPU.  The harness runs them without asking for a chip.
"""
import time

from bench import harness

SEED = 2_147_483_659


def cell(name: str, seed: int = SEED, size: str = "tiny"):
    c = harness.load_cell(name, seed)
    train = c.traffic["driver"] == "train"
    if size == "control":
        if train:
            c.config["field"].update(log2_table_density=12, log2_table_color=10)
            c.config.update(scene_views=4, scene_hw=32)
            c.traffic.update(rays=1024)
        return c
    c.config["field"].update(n_levels=3, log2_table_density=8, log2_table_color=6,
                             base_resolution=4, max_resolution=32)
    c.config.update(scene_views=2, scene_hw=8)
    if train:
        c.traffic.update(rays=32, samples_per_ray=8)
    else:
        c.traffic.update(hw=16, eval_chunk=64, poses=4, check_views=2, probe_samples=8,
                         samples_per_ray=2)
    return c


def run(name: str, trace: bool = False, seconds: float = 0.2, seed: int = SEED):
    return harness.execute(cell(name, seed), seconds, trace, t_start=time.perf_counter(),
                           require_chips=False, log=lambda s: None)
