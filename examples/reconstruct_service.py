"""Multi-scene reconstruction service demo (Instant-3D as a service primitive).

    PYTHONPATH=src python examples/reconstruct_service.py \
        --scenes 4 --iters 96 --slice 8

Four procedural scenes train *concurrently in one process*: a round-robin
scheduler time-slices the device across their sessions, each slice publishes
an atomic parameter snapshot, and novel-view render requests are answered
mid-training from the latest snapshot — coalesced across sessions into
batched jitted renders.  Served views are scored against the scene's
analytic ground truth, so you can watch per-scene PSNR climb while all
scenes are still training.

Fleet mode (docs/SERVING.md): --devices N shards the sessions across a
device mesh (on CPU, run with
XLA_FLAGS=--xla_force_host_platform_device_count=N), --snapshot-levels k
streams cheap h>>k previews before each scene's first full snapshot, and
--async-serving serves renders from a dedicated thread.
"""
import argparse

import numpy as np

from repro.core import FieldConfig, TrainerConfig, losses, occupancy
from repro.core.rendering import RenderConfig
from repro.data import build_dataset
from repro.obs import export as obs_export, metrics as obs_metrics, trace as obs_trace
from repro.runtime.compile_cache import enable_compile_cache
from repro.serve3d import ReconstructionService


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenes", type=int, default=4)
    ap.add_argument("--iters", type=int, default=96, help="per-scene iterations")
    ap.add_argument("--slice", type=int, default=8, help="iterations per time slice")
    ap.add_argument("--hw", type=int, default=24)
    ap.add_argument("--max-resident", type=int, default=None)
    ap.add_argument("--max-cohort", type=int, default=None,
                    help="train-cohort cap (default unlimited; 1 = pure time-slicing)")
    ap.add_argument("--dense-render", action="store_true",
                    help="serve views dense instead of redistributed")
    ap.add_argument("--devices", type=int, default=None,
                    help="shard sessions across the first N local devices "
                         "(on CPU: XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N)")
    ap.add_argument("--snapshot-levels", type=int, default=0,
                    help="publish h>>k preview snapshots until a scene's "
                         "first full snapshot (0 = off)")
    ap.add_argument("--async-serving", action="store_true",
                    help="serve renders from a dedicated thread")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome-trace JSON of the demo run")
    args = ap.parse_args()
    print(f"compile cache: {enable_compile_cache()}")

    # the demo always runs instrumented: the progress lines below and the
    # final summary both read from the one obs metrics plane
    obs_trace.configure(enabled=True)

    render = RenderConfig(n_samples=16)
    field_cfg = FieldConfig(n_levels=4, max_resolution=64,
                            log2_table_density=12, log2_table_color=10)
    trainer_cfg = TrainerConfig(
        n_rays=256, render=render,
        occ=occupancy.OccupancyConfig(update_interval=8, warmup_steps=16),
        eval_chunk=args.hw * args.hw,
    )

    print(f"building {args.scenes} procedural scenes ({args.hw}x{args.hw})...")
    service = ReconstructionService(slice_iters=args.slice,
                                    max_resident=args.max_resident,
                                    max_cohort=args.max_cohort,
                                    redistributed_render=not args.dense_render,
                                    devices=args.devices,
                                    snapshot_levels=args.snapshot_levels,
                                    async_serving=args.async_serving)
    datasets = {}
    for i in range(args.scenes):
        _scene, ds = build_dataset(seed=i, n_views=6, h=args.hw, w=args.hw,
                                   cfg=render, gt_samples=48)
        sid = service.submit_scene(ds, field_cfg, trainer_cfg,
                                   target_iters=args.iters, seed=i)
        datasets[sid] = ds

    t0 = obs_trace.clock()
    held_out = 0  # every served render targets view 0, scored against its GT

    def hook(svc, event):
        # ask for a fresh view of every scene that just trained a slice
        # (one quantum advances a whole cohort when configs match)
        for sid in event["cohort"]:
            if svc.sessions[sid].step % (2 * args.slice) == 0:
                svc.request_render(sid, datasets[sid].poses[held_out])
        for r in event["results"]:
            gt = datasets[r.session_id].images[held_out]
            psnr = float(losses.psnr(np.asarray(r.rgb), gt))
            # served-view quality lands in the same metrics plane the final
            # summary prints from — one source for interactive and exported
            obs_metrics.gauge(f"demo.psnr_db.{r.session_id}").set(psnr)
            print(f"[{obs_trace.clock() - t0:6.1f}s] render {r.session_id} "
                  f"@step {r.snapshot_step:3d} (v{r.snapshot_version})  "
                  f"psnr {psnr:5.2f} dB  latency {r.latency_s * 1e3:5.0f} ms")

    tel = service.run(hook=hook)

    print("\nfinal state:")
    for p in tel["sessions"]:
        sess = service.sessions[p["session_id"]]
        ev = sess.evaluate(views=[0, 1])
        obs_metrics.gauge(f"demo.final_psnr_rgb_db.{p['session_id']}").set(
            ev["psnr_rgb"])
        obs_metrics.gauge(f"demo.final_psnr_depth_db.{p['session_id']}").set(
            ev["psnr_depth"])
        print(f"  {p['session_id']}: {p['step']}/{p['target_iters']} iters, "
              f"psnr rgb {ev['psnr_rgb']:.2f} dB  depth {ev['psnr_depth']:.2f} dB  "
              f"(train {p['train_wall_s']:.1f}s)")
    r = tel["render"]
    print(f"\n{tel['scenes_done']} scenes on {tel['devices']} device(s) "
          f"in {tel['wall_s']:.1f}s "
          f"({tel['scenes_per_sec']:.3f} scenes/sec)  "
          f"renders {r.get('count', 0)}: p50 {r.get('p50_ms', 0):.0f} ms, "
          f"p95 {r.get('p95_ms', 0):.0f} ms")
    print("\nmetrics snapshot:")
    print(obs_export.format_metrics(service.metrics()))
    if args.trace_out:
        print(f"\ntrace -> {service.dump_trace(args.trace_out)}")


if __name__ == "__main__":
    main()
