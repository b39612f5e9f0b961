"""serve3d service benchmark -> BENCH_serve3d.json.

Measures the reconstruction service end to end: N procedural scenes train
concurrently — scene-parallel by default, the scheduler advancing every
config-matched session through one member-axis compiled train step per
quantum — while novel-view renders of a held-out pose are requested after
every slice and served through the redistributed render path.  Records

* scenes/sec (completed reconstructions per wall-clock second) for train
  cohort caps {1, 2, 4} over the same scene set, with `speedup_4v1`
  (cohort=4 over cohort=1, pure time-slicing) as the headline,
* cohort bit-identity: the cohort-trained params must equal sequential
  single-scene training bit-for-bit (not just to PSNR tolerance),
* p50/p95 render latency (request submit -> result, mid-training) plus a
  steady-state dense-vs-redistributed comparison: `p50_ratio`
  (redistributed over dense) and `psnr_cost_db` at the served views,
* time-to-first-usable-view per scene (first served render whose PSNR
  against ground truth crosses the threshold),
* PSNR parity: the interleaved scheduler must reach the same PSNR per scene
  as sequential single-scene training at equal per-scene iteration counts,
* scale-out (`scale_out`): in this process, the session-sharded service
  is swept over device counts {1, 2, 4} at saturating residency and a fixed
  cohort cap — scenes/sec must be monotone in device count, the N=1
  placement must be bit-identical to the placement-free path, and render
  p95 is measured under mixed train+render load on the full mesh with the
  async serving plane.  It needs four devices; on CPU,
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` provides them.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m benchmarks.bench_serve3d [--smoke] [--scale-out]

CI gates these fields against the committed baseline via tools/bench_gate.py.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig, losses, occupancy
from repro.core.rendering import RenderConfig
from repro.data import build_dataset, RaySampler
from repro.serve3d import ReconstructionService, RenderService

from . import common

COHORT_SIZES = (1, 2, 4)
DEVICE_COUNTS = (1, 2, 4)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def run_scale_out(smoke: bool = False) -> dict:
    """The scale-out sweep over DEVICE_COUNTS.  One process measures every
    device count so compile caches and machine drift hit each count alike.

    The workload is a deliberately dispatch-lean regime (8-sample ladder,
    small field, 64 rays): on a host where the forced devices share one
    core, XLA execution time cannot shrink with device count — the honest
    scale-out win is overlapping per-device Python dispatch and blocking
    host syncs (occ-cadence live-fraction measures, snapshot transfers,
    guard reductions) with XLA's GIL-released execution on the other
    devices, plus amortizing per-quantum scheduler fixed costs over one
    cohort per device.  Moderate steps are the sweet spot (probed): fat
    compute-bound steps drown the overlap, and tiny steps drown in
    thread-switch overhead.  The cohort cap is fixed across device counts
    — cohort efficiency is constant, device count is the only variable."""
    assert jax.device_count() >= 4, (
        f"scale-out needs 4 devices, got {jax.device_count()} "
        "(run under XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    scenes = 8
    iters = 16 if smoke else 64
    slice_iters = 8
    hw = 24
    render = RenderConfig(n_samples=8)
    occ_cfg = occupancy.OccupancyConfig(resolution=16, update_interval=8,
                                        warmup_steps=8)
    field_cfg = FieldConfig(n_levels=2, max_resolution=32,
                            log2_table_density=10, log2_table_color=8)
    cfg = TrainerConfig(n_rays=64, render=render, occ=occ_cfg,
                        eval_chunk=hw * hw)
    datasets = {
        f"scene-{i:03d}": build_dataset(seed=i, n_views=2, h=hw, w=hw,
                                        cfg=render, gt_samples=32)[1]
        for i in range(scenes)
    }

    def make(devices, async_serving=False) -> ReconstructionService:
        svc = ReconstructionService(
            slice_iters=slice_iters, max_cohort=2, devices=devices,
            async_serving=async_serving,
        )
        for i, (sid, ds) in enumerate(datasets.items()):
            svc.submit_scene(ds, field_cfg, cfg, target_iters=iters,
                             seed=i, session_id=sid)
        return svc

    # device-count sweep: warm each count's per-device executables, then
    # interleave timed reps.  The headline estimator is the MEAN over reps:
    # per-rep spread on a shared-core host (~±5-7%) exceeds the true 1->2
    # gap, and best-of-N amplifies exactly that upper-tail noise — probed
    # distributions showed monotone means under a non-monotone best-of.
    hist = {str(c): [] for c in DEVICE_COUNTS}
    for c in DEVICE_COUNTS:
        make(c).run()
    for _rep in range(1 if smoke else 5):
        for c in DEVICE_COUNTS:
            tel = make(c).run()
            hist[str(c)].append(tel["scenes_per_sec"])
    mean = {k: sum(v) / len(v) for k, v in hist.items()}
    monotone = int(mean["1"] < mean["2"] < mean["4"])

    # N=1 degeneration: a one-device placement must be bit-identical to the
    # placement-free (pre-mesh) service
    placed, free = make(1), make(None)
    placed.run(), free.run()
    n1_bit = all(
        _leaves_equal(placed.store.latest(sid).params,
                      free.store.latest(sid).params)
        for sid in datasets
    )

    # mixed train+render load on the full mesh, async serving plane: one
    # held-out render per advanced session per quantum.  The warmup pass
    # runs the same schedule first (placement is deterministic, so sessions
    # land on the same devices) so every device's render executable is
    # already traced — p95 measures steady-state serving latency, not the
    # per-device first-contact trace.
    def hook(svc, event):
        for sid in event["cohort"]:
            svc.request_render(sid, datasets[sid].poses[0])

    make(4, async_serving=True).run(hook=hook)
    mixed = make(4, async_serving=True)
    mixed_tel = mixed.run(hook=hook)
    lat = mixed_tel["render"]
    return {
        "config": {"smoke": smoke, "scenes": scenes, "iters": iters,
                   "slice_iters": slice_iters, "hw": hw,
                   "n_rays": cfg.n_rays, "n_samples": render.n_samples,
                   "max_cohort": 2, "device_counts": list(DEVICE_COUNTS)},
        "scenes_per_sec": mean,
        "scenes_per_sec_reps": hist,
        "scenes_per_s_monotone": monotone,
        "speedup_4v1": mean["4"] / mean["1"] if mean["1"] > 0 else 0.0,
        "n1_bit_identical": bool(n1_bit),
        "render_p95_ms_mixed": lat.get("p95_ms"),
        "render_count_mixed": lat.get("count", 0),
    }


def run(smoke: bool = False):
    scenes = 4
    iters = 16 if smoke else 96
    slice_iters = 8
    hw = 32
    views = 3 if smoke else 6
    psnr_threshold = 10.0 if smoke else 15.0

    # Two configs, one per subsystem's design regime (both recorded below):
    #
    # * serving/render (16-sample dense ladder, 32x32 views = one 1024-ray
    #   chunk): shading dominates a render, so the redistributed path's 4x
    #   point saving shows up as p50 latency.
    # * cohort sweep (8-sample ladder): the paper's on-device training
    #   regime — modest per-step compute, where per-quantum fixed costs
    #   (step dispatch, ray sampling, PRNG folds, occupancy re-query) are a
    #   real fraction of a slice and member-axis batching pays.  At fat
    #   compute-bound steps the cohort is a wash (the member axis is a scan,
    #   not SIMD) — that regime needs the ROADMAP vmap-on-TPU follow-up.
    #
    # Smoke scales down the *service* (iters, views), never the per-step or
    # per-render shapes, so smoke and full gate the same two regimes.
    field_cfg = FieldConfig(n_levels=4, max_resolution=64,
                            log2_table_density=12, log2_table_color=10)
    occ_cfg = occupancy.OccupancyConfig(update_interval=8, warmup_steps=8)
    render = RenderConfig(n_samples=16)
    trainer_cfg = TrainerConfig(n_rays=128, render=render, occ=occ_cfg,
                                eval_chunk=hw * hw)
    cohort_render = RenderConfig(n_samples=8)
    cohort_cfg = TrainerConfig(n_rays=128, render=cohort_render, occ=occ_cfg,
                               eval_chunk=hw * hw)

    datasets = {}
    for i in range(scenes):
        _scene, ds = build_dataset(seed=i, n_views=views, h=hw, w=hw,
                                   cfg=render, gt_samples=48)
        datasets[f"scene-{i:03d}"] = ds

    def make_service(max_cohort, cfg=trainer_cfg, redistributed=True
                     ) -> ReconstructionService:
        service = ReconstructionService(
            slice_iters=slice_iters, max_cohort=max_cohort,
            redistributed_render=redistributed,
        )
        for i, (sid, ds) in enumerate(datasets.items()):
            service.submit_scene(ds, field_cfg, cfg,
                                 target_iters=iters, seed=i, session_id=sid)
        return service

    # ---- headline serving run: cohort training + mid-training renders ----

    service = make_service(max_cohort=None)
    t_start = time.perf_counter()
    ttfuv: dict[str, float | None] = {sid: None for sid in datasets}
    psnr_trace: dict[str, list] = {sid: [] for sid in datasets}

    def hook(svc, event):
        for sid in event["cohort"]:  # one render request per slice, per session
            svc.request_render(sid, datasets[sid].poses[0])
        for r in event["results"]:
            psnr = float(losses.psnr(np.asarray(r.rgb),
                                     datasets[r.session_id].images[0]))
            psnr_trace[r.session_id].append((r.snapshot_step, psnr))
            if ttfuv[r.session_id] is None and psnr >= psnr_threshold:
                ttfuv[r.session_id] = time.perf_counter() - t_start

    tel = service.run(hook=hook)

    # ---- steady-state guard overhead (faults off) ----
    # the divergence guard is on by default, so the headline run already
    # paid for every inspect (loss checks, jitted finiteness reductions,
    # periodic last-good host snapshots); its share of training wall time
    # is the overhead a fault-free service pays for fault tolerance
    train_wall = sum(s.train_wall_s for s in service.sessions.values())
    g = tel["guard"]
    guard_overhead = (g["inspect_wall_s"] / train_wall) if train_wall else 0.0

    # ---- parity + bit-identity vs sequential single-scene training ----

    psnr_interleaved, psnr_sequential = {}, {}
    sequential_params = {}
    for i, (sid, ds) in enumerate(datasets.items()):
        psnr_interleaved[sid] = service.sessions[sid].evaluate(views=[0])["psnr_rgb"]
        tr = Instant3DTrainer(Field(field_cfg), trainer_cfg)
        st = tr.init(jax.random.PRNGKey(i))
        st, _ = tr.train(st, RaySampler(ds), iters=iters, log_every=iters)
        sequential_params[sid] = st.params
        # evaluate the reference under the SAME serving quadrature the
        # session's evaluate routes through (eval == served since PR 10) —
        # a dense reference here would measure the redistribute-vs-dense
        # quadrature delta, not scheduler drift
        psnr_sequential[sid] = tr.evaluate(
            st.params, ds, views=[0],
            occ=(np.asarray(st.occ_state.density_ema), int(st.occ_state.step)),
            samples_per_ray=service.sessions[sid].render_spr,
        )["psnr_rgb"]
    parity = max(abs(psnr_interleaved[s] - psnr_sequential[s]) for s in datasets)
    cohort_bit_identical = all(
        _leaves_equal(sequential_params[sid],
                      service.sessions[sid]._current_params())
        for sid in datasets
    )

    # ---- cohort sweep: scenes/sec at train-cohort caps {1, 2, 4} ----
    # (no render traffic — pure multi-scene training throughput; one warmup
    # pass per cap compiles its member-axis steps, then the caps are timed
    # INTERLEAVED over several reps and each cap keeps its best, so machine
    # drift hits every cap alike instead of whichever ran last)

    sweep = {str(cap): 0.0 for cap in COHORT_SIZES}
    sweep_params: dict[int, dict] = {}
    for cap in COHORT_SIZES:
        make_service(max_cohort=cap, cfg=cohort_cfg).run()  # warm compile
    for rep in range(3):
        for cap in COHORT_SIZES:
            svc = make_service(max_cohort=cap, cfg=cohort_cfg)
            t = svc.run()
            sweep[str(cap)] = max(sweep[str(cap)], t["scenes_per_sec"])
            sweep_params[cap] = {
                sid: svc.sessions[sid]._current_params() for sid in datasets
            }
    speedup_4v1 = sweep["4"] / sweep["1"] if sweep["1"] > 0 else 0.0
    sweep_bit_identical = all(
        _leaves_equal(sweep_params[1][sid], sweep_params[4][sid])
        for sid in datasets
    )

    # ---- render path: steady-state dense vs redistributed on one store ----

    spr = min(render.n_samples, max(4, render.n_samples // 4))  # service default
    dense_renderer = RenderService(service.store)
    for sid, ds in datasets.items():
        dense_renderer.register_session(
            sid, field_cfg, render, ds.h, ds.w, ds.focal, trainer_cfg.eval_chunk)

    def steady_latency(renderer):
        lats, psnrs = [], []
        for rep in range(6):
            for sid, ds in datasets.items():
                renderer.submit(sid, ds.poses[0])
            results = renderer.drain()
            if rep < 2:  # discard compile + cache-warm rounds
                continue
            lats += [r.latency_s for r in results]
            psnrs += [float(losses.psnr(np.asarray(r.rgb),
                                        datasets[r.session_id].images[0]))
                      for r in results]
        return float(np.median(lats) * 1e3), float(np.mean(psnrs))

    redist_p50, redist_psnr = steady_latency(service.renderer)
    dense_p50, dense_psnr = steady_latency(dense_renderer)
    p50_ratio = redist_p50 / dense_p50 if dense_p50 > 0 else float("inf")
    psnr_cost = dense_psnr - redist_psnr

    # ---- scale-out: the session-sharded service on a forced device mesh ----

    scale_out = run_scale_out(smoke)

    lat = tel["render"]
    out = {
        "config": {
            "smoke": smoke, "scenes": scenes, "iters_per_scene": iters,
            "slice_iters": slice_iters, "hw": hw, "views": views,
            "n_rays": trainer_cfg.n_rays, "n_samples": render.n_samples,
            "cohort_sweep_n_samples": cohort_render.n_samples,
            "psnr_threshold_db": psnr_threshold,
            "render_samples_per_ray": spr,
        },
        "wall_s": tel["wall_s"],
        "scenes_per_sec": tel["scenes_per_sec"],
        "render_latency_ms": {
            "count": lat.get("count", 0),
            "p50": lat.get("p50_ms"), "p95": lat.get("p95_ms"),
            "max": lat.get("max_ms"),
        },
        "time_to_first_usable_view_s": ttfuv,
        "psnr_trace": psnr_trace,
        "parity": {
            "interleaved_db": psnr_interleaved,
            "sequential_db": psnr_sequential,
            "max_abs_diff_db": parity,
        },
        "cohort": {
            "scenes_per_sec": sweep,
            "speedup_4v1": speedup_4v1,
            "bit_identical": bool(cohort_bit_identical and sweep_bit_identical),
        },
        "render_path": {
            "dense_p50_ms": dense_p50,
            "redistributed_p50_ms": redist_p50,
            "p50_ratio": p50_ratio,
            "psnr_dense_db": dense_psnr,
            "psnr_redistributed_db": redist_psnr,
            "psnr_cost_db": psnr_cost,
        },
        "guard": {
            "overhead_frac": guard_overhead,
            "inspect_wall_s": g["inspect_wall_s"],
            "train_wall_s": train_wall,
            "checkpoints": g["checkpoints"],
            "rollbacks": g["rollbacks"],
        },
        "scale_out": scale_out,
    }
    with open("BENCH_serve3d.json", "w") as f:
        json.dump(out, f, indent=2)

    common.emit(
        "serve3d_service",
        tel["wall_s"] * 1e6 / max(1, scenes * iters),
        f"scenes_per_sec={tel['scenes_per_sec']:.3f};"
        f"p50_ms={lat.get('p50_ms', 0):.0f};p95_ms={lat.get('p95_ms', 0):.0f};"
        f"parity_db={parity:.4f}",
    )
    common.emit(
        "serve3d_cohort",
        0.0,
        ";".join(f"sps[{c}]={sweep[str(c)]:.3f}" for c in COHORT_SIZES)
        + f";speedup_4v1={speedup_4v1:.3f};bit_identical={out['cohort']['bit_identical']}",
    )
    common.emit(
        "serve3d_render_path",
        redist_p50 * 1e3,
        f"p50_ratio={p50_ratio:.3f};psnr_cost_db={psnr_cost:.3f};spr={spr}",
    )
    common.emit(
        "serve3d_guard_overhead",
        guard_overhead * 1e6,  # fraction in micro-units for the CSV column
        f"overhead_frac={guard_overhead:.5f};checkpoints={g['checkpoints']};"
        f"rollbacks={g['rollbacks']}",
    )
    for sid, t in ttfuv.items():
        common.emit(f"serve3d_ttfuv[{sid}]", (t or 0.0) * 1e6,
                    f"ttfuv_s={'%.2f' % t if t is not None else 'n/a'};"
                    f"threshold_db={psnr_threshold}")
    common.emit(
        "serve3d_scale_out",
        0.0,
        ";".join(f"sps[{c}]={scale_out['scenes_per_sec'][str(c)]:.3f}"
                 for c in DEVICE_COUNTS)
        + f";monotone={scale_out['scenes_per_s_monotone']}"
        + f";n1_bit_identical={scale_out['n1_bit_identical']}"
        + f";p95_mixed_ms={scale_out['render_p95_ms_mixed']:.0f}",
    )
    assert scale_out["n1_bit_identical"], (
        "one-device placement diverged bitwise from the placement-free path")
    assert parity <= 0.1, (
        f"interleaved vs sequential PSNR drifted {parity:.3f} dB (> 0.1)")
    assert out["cohort"]["bit_identical"], (
        "cohort-batched training diverged from sequential time-slicing")
    assert psnr_cost <= 0.1, (
        f"redistributed render path costs {psnr_cost:.3f} dB (> 0.1)")
    assert g["rollbacks"] == 0, (
        f"guard rolled back {g['rollbacks']}x in a fault-free run "
        "(divergence heuristic misfiring)")
    assert guard_overhead <= 0.01, (
        f"steady-state guard overhead {guard_overhead:.4f} > 1%")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="4 sessions x few iters x 1 render/slice (CI gate)")
    ap.add_argument("--scale-out", action="store_true",
                    help="run only the scale-out sweep and print its JSON "
                         "payload (needs four devices)")
    args = ap.parse_args()
    if args.scale_out:
        payload = run_scale_out(smoke=args.smoke)
        print("SCALE_OUT_JSON:" + json.dumps(payload))
        return
    run(smoke=args.smoke)


if __name__ == "__main__":
    main()
