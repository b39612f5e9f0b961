"""End-to-end Instant-3D training driver: checkpointing, preemption safety,
auto-resume, straggler watchdog — the production loop around the paper's
algorithm.

    PYTHONPATH=src python examples/train_nerf_instant3d.py \
        --scene-seed 0 --iters 300 --ckpt-dir /tmp/i3d_ckpt --auto-resume

Kill it mid-run (Ctrl-C) and re-run with --auto-resume: it continues from the
last atomic checkpoint with the exact data stream.
"""
import argparse
import time

import jax
import numpy as np

from repro import kernels
from repro.checkpoint import CheckpointManager
from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig, occupancy
from repro.core.rendering import RenderConfig
from repro.data import build_dataset, RaySampler
from repro.obs import export as obs_export, trace as obs_trace
from repro.runtime import DriverConfig, StragglerStats
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scene-seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="/tmp/i3d_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--auto-resume", action="store_true")
    ap.add_argument("--sd-sc", default="1:0.25", help="grid size ratio S_D:S_C")
    ap.add_argument("--fd-fc", default="1:0.5", help="update freq ratio F_D:F_C")
    ap.add_argument("--backend", default=None,
                    help="kernel backend: auto | ref | pallas | pallas-interpret | "
                         "pallas-tpu (default: $REPRO_BACKEND, else auto)")
    ap.add_argument("--no-compact", action="store_true",
                    help="disable occupancy-compacted field queries (dense path)")
    ap.add_argument("--no-fused-path", action="store_true",
                    help="shade the compacted batch with the per-grid encode "
                         "path instead of the fused kernel (debug/timing; "
                         "compaction stays Morton-ordered either way)")
    ap.add_argument("--redistribute", action="store_true",
                    help="occupancy-guided sample redistribution (pipeline "
                         "stage 2b): re-spend each ray's freed sample budget "
                         "on its live segments via inverse-CDF placement — "
                         "finer live-region stratification at <= the same "
                         "compacted point budget")
    ap.add_argument("--redistribute-v3", action="store_true",
                    help="density-weighted, workload-balanced redistribution "
                         "(stage 2b v3): strata weighted by occupancy EMA "
                         "density, per-ray variable S' from one global "
                         "inverse-CDF, sum(S') <= budget by construction; "
                         "supersedes --redistribute when both are given")
    ap.add_argument("--max-budget", type=int, default=None,
                    help="hard per-step point ceiling (on-device regime; "
                         "see trainer.autotune_max_budget to derive one "
                         "from a memory/latency envelope)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of the run (enables obs)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the final metrics snapshot JSON (enables obs)")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")

    if args.trace_out or args.metrics_out:
        obs_trace.configure(enabled=True)

    # explicit flag wins; otherwise the registry default ($REPRO_BACKEND / auto)
    be = kernels.set_backend(args.backend) if args.backend else kernels.get_backend()
    print(f"kernel backend: {be} (available: {', '.join(kernels.available_backends())})")
    print(f"kernel routing: {kernels.routing()}")

    render = RenderConfig(n_samples=24)
    scene, ds = build_dataset(seed=args.scene_seed, n_views=12, h=48, w=48,
                              cfg=render, gt_samples=128)

    sc = float(args.sd_sc.split(":")[1])
    fc = float(args.fd_fc.split(":")[1])
    log2_c = 13 + round(np.log2(sc) / 3 * 3)  # 1:0.25 -> -2 levels
    field = Field(FieldConfig(n_levels=6, max_resolution=96,
                              log2_table_density=13,
                              log2_table_color=int(13 + np.log2(sc))))
    trainer = Instant3DTrainer(field, TrainerConfig(
        n_rays=768, iters=args.iters, f_color=fc, render=render,
        occ=occupancy.OccupancyConfig(update_interval=16, warmup_steps=32),
        compact=not args.no_compact,
        fused_path=not args.no_fused_path,
        redistribute=args.redistribute,
        redistribute_v3=args.redistribute_v3,
        max_budget=args.max_budget,
    ))

    ckpt = CheckpointManager(args.ckpt_dir, keep_last=2)
    state = trainer.init(jax.random.PRNGKey(0))
    start = 0
    if args.auto_resume and ckpt.latest_step() is not None:
        tmpl = {"params": state.params, "opt": state.opt_state,
                "occ": state.occ_state.density_ema,
                "occ_step": state.occ_state.step}
        try:
            restored, meta = ckpt.restore(tmpl)
            occ_step = jax.numpy.asarray(restored["occ_step"], jax.numpy.int32)
        except KeyError:  # checkpoint predates the occ_step leaf
            del tmpl["occ_step"]
            restored, meta = ckpt.restore(tmpl)
            occ_step = jax.numpy.zeros((), jax.numpy.int32)
        # occ_step matters on resume: the trainer keeps rendering dense until
        # the occupancy EMA has folded at least one real update
        state = state._replace(
            params=restored["params"], opt_state=restored["opt"],
            occ_state=occupancy.OccupancyState(
                jax.numpy.asarray(restored["occ"]), occ_step),
            step=int(meta["step"]),
        )
        start = int(meta["step"])
        print(f"resumed from step {start}")

    watchdog = StragglerStats()
    done = start
    while done < args.iters:
        chunk = min(args.ckpt_every, args.iters - done)
        t0 = time.perf_counter()
        state, hist = trainer.train(state, RaySampler(ds), iters=chunk, log_every=chunk)
        dt = (time.perf_counter() - t0) / chunk
        if watchdog.update(dt, sigma=4.0, alpha=0.1):
            print(f"[straggler] step time {dt:.3f}s vs ewma {watchdog.ewma:.3f}s")
        done += chunk
        ckpt.save(done, {"params": state.params, "opt": state.opt_state,
                         "occ": state.occ_state.density_ema,
                         "occ_step": state.occ_state.step})
        print(f"step {done:5d}  loss {hist['loss'][-1]:.5f}  ({dt:.3f}s/iter)  ckpt saved")

    ckpt.wait()
    ev = trainer.evaluate(state.params, ds, views=[0, 1, 2])
    print(f"final PSNR rgb={ev['psnr_rgb']:.2f} depth={ev['psnr_depth']:.2f}")
    if args.trace_out:
        print(f"trace -> {obs_export.dump_trace(args.trace_out, process_name='repro.train')}")
    if args.metrics_out:
        print(f"metrics -> {obs_export.dump_metrics(args.metrics_out, extra={'iters': done})}")


if __name__ == "__main__":
    main()
