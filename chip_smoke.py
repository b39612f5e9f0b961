"""Smoke run of the Instant-3D trainer and serve3d on a TPU, at full field width.

    python3 chip_smoke.py               # one chip: kernels, training, serving
    python3 chip_smoke.py --four-chips  # four chips: the serve3d session mesh only

Phases (one chip):

* kernels: every op that `repro.kernels.routing()` sends to a Pallas kernel
  runs at real width and is compared with its float32 `ref` oracle at the
  tolerances of the interpret-mode tests.
* train: `Instant3DTrainer.train` / `evaluate` at `FieldConfig()` widths
  (L=16, F=2, T_D=2^18, T_C=2^16, 64-wide MLPs, F_D:F_C = 1:0.5), 4096 rays x
  48 samples = 196,608 candidate points per step, on a procedural scene made
  from a seed.  The occupancy bitfield engages after 8 dense steps and
  compaction 4 steps later, so the dense step, the bitfield-culled dense
  step and the compacted one-kernel step all run (15 steps in all).  Step
  compile time is read from the trainer's `trainer/step_compile` trace
  spans; step time comes from windows closed with `block_until_ready`.
  Fails on a non-finite or non-falling loss, a non-finite held-out PSNR, no
  compacted step, or a compile inside a steady window.
* serve: `repro.serve3d` with 2 sessions at the same field widths for a few
  slices and render requests.  Fails on a guard rollback or quarantine, a
  render error, or a session that is not DONE.

With --four-chips only the session-mesh phase runs: 4 sessions on
`devices=4` against the same 4 sessions on `devices=1`, checking where each
session's arrays and renders live and that the params match bit for bit.

This is a smoke run, not a benchmark: times are printed so a reader can see
where a run went, and compile time is reported apart from step time.  The
last line of stdout is the JSON result; a run without a TPU, or with any
phase failing, exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
N_RAYS, N_SAMPLES = 4096, 48          # 196,608 candidate points per step
UPDATE_EVERY = 4                      # occupancy update cadence and warmup
HW, N_VIEWS, HELD_OUT = 64, 12, (10, 11)
EVAL_CHUNK = 1024
SERVE_ITERS = 16                      # two 8-step slices per session


def fail(msg: str):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def clock() -> float:
    return time.perf_counter()


def _block(tree):
    import jax
    return jax.block_until_ready(tree)


def phase_kernels(routing: dict):
    """Pallas kernel vs float32 ref on the chip, for each op routed to Pallas."""
    import jax
    import numpy as np

    pallas_ops = [op for op, be in routing.items() if be == "pallas-tpu"]
    checks = {"mlp": _check_mlp, "composite": _check_composite}
    missing = [op for op in pallas_ops if op not in checks]
    if missing:
        raise RuntimeError(f"no on-chip parity check for Pallas ops {missing}")
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 64))
    for op in pallas_ops:
        for name, got, want, atol, rtol in checks[op](keys):
            got, want = np.asarray(got), np.asarray(want)
            err = float(np.max(np.abs(got - want)))
            ok = bool(np.all(np.isfinite(got))) and np.allclose(got, want, atol=atol, rtol=rtol)
            print(f"kernels: {op}/{name} {tuple(got.shape)} max|pallas-ref|={err:.3e} "
                  f"(atol {atol}, rtol {rtol}) {'ok' if ok else 'MISMATCH'}")
            if not ok:
                raise AssertionError(f"{op}/{name}: Pallas differs from ref by {err}")


def _ref(fn):
    """The float32 oracle at highest matmul precision (TPU's default rounds
    float32 matmul operands to bfloat16)."""
    import jax
    with jax.default_matmul_precision("highest"):
        return _block(fn())


def _check_mlp(keys):
    import jax
    from repro.kernels.fused_mlp import ops as mlp_ops

    n = N_RAYS * N_SAMPLES

    def lin(d_in, d_out):
        bound = (6.0 / d_in) ** 0.5
        w = jax.random.uniform(next(keys), (d_in, d_out), minval=-bound, maxval=bound)
        return w, jax.random.uniform(next(keys), (d_out,), minval=-0.1, maxval=0.1)

    x2 = jax.random.uniform(next(keys), (n, 32), minval=-1.0, maxval=1.0)
    p2 = (*lin(32, 64), *lin(64, 16))
    x3 = jax.random.uniform(next(keys), (n, 48), minval=-1.0, maxval=1.0)
    p3 = (*lin(48, 64), *lin(64, 64), *lin(64, 3))
    yield ("mlp2", _block(mlp_ops.mlp2(x2, *p2, backend="pallas-tpu")),
           _ref(lambda: mlp_ops.mlp2(x2, *p2, backend="ref")), 1e-4, 1e-4)
    yield ("mlp3", _block(mlp_ops.mlp3(x3, *p3, backend="pallas-tpu")),
           _ref(lambda: mlp_ops.mlp3(x3, *p3, backend="ref")), 1e-4, 1e-4)


def _check_composite(keys):
    import jax
    import jax.numpy as jnp
    from repro.kernels.volume_render import ops as vr_ops

    r, s = N_RAYS, N_SAMPLES
    sigma = jax.random.uniform(next(keys), (r, s), minval=0.0, maxval=5.0)
    rgb = jax.random.uniform(next(keys), (r, s, 3))
    ts = jnp.sort(jax.random.uniform(next(keys), (r, s), minval=2.0, maxval=6.0), axis=1)
    deltas = jnp.diff(ts, axis=1, append=ts[:, -1:] + 4.0 / s)
    got = _block(vr_ops.composite(sigma, rgb, deltas, ts, backend="pallas-tpu"))
    want = _ref(lambda: vr_ops.composite(sigma, rgb, deltas, ts, backend="ref"))
    yield "color", got.color, want.color, 1e-5, 0.0
    yield "depth", got.depth, want.depth, 1e-4, 0.0
    yield "opacity", got.opacity, want.opacity, 1e-5, 0.0


def _compiles_in(t0: float, t1: float) -> tuple[int, float]:
    """Step compiles the trainer traced between t0 and t1 (perf_counter s):
    its `trainer/step_compile` spans, which cover a step variant's first
    call.  Returns (count, seconds)."""
    from repro.obs import trace as obs_trace

    durs = [e.dur_us * 1e-6 for e in obs_trace.events()
            if e.name == "trainer/step_compile" and t0 * 1e6 <= e.ts_us <= t1 * 1e6]
    return len(durs), sum(durs)


def phase_train():
    import jax
    import numpy as np
    from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig, occupancy
    from repro.core.rendering import RenderConfig
    from repro.data import RaySampler, build_dataset
    from repro.obs import trace as obs_trace

    field_cfg = FieldConfig()
    render = RenderConfig(n_samples=N_SAMPLES)
    # budget_headroom 1.0: the scene's bounding box leaves ~0.55 of the
    # candidate points live early on, and at the default 1.3 the pow2 budget
    # bucket for 196,608 points only drops below "all of them" once the live
    # fraction is at most 0.51 — hundreds of steps away
    cfg = TrainerConfig(
        n_rays=N_RAYS, render=render, seed=SEED, eval_chunk=EVAL_CHUNK,
        budget_headroom=1.0,
        occ=occupancy.OccupancyConfig(update_interval=UPDATE_EVERY,
                                      warmup_steps=UPDATE_EVERY),
    )
    assert cfg.fused_path and cfg.fused_step and cfg.compact
    t0 = clock()
    _scene, ds = build_dataset(seed=SEED, n_views=N_VIEWS, h=HW, w=HW, cfg=render,
                               gt_samples=128)
    print(f"train: scene {N_VIEWS} views {HW}x{HW} built in {clock() - t0:.1f}s")
    trainer = Instant3DTrainer(Field(field_cfg), cfg)
    state = trainer.init(jax.random.PRNGKey(SEED))
    sampler = RaySampler(ds, views=[v for v in range(N_VIEWS) if v not in HELD_OUT])
    n_total = N_RAYS * N_SAMPLES
    windows = []

    def window(name, iters):
        """`iters` training steps, blocked at the end; compile time is read
        from the trainer's spans and kept apart from step time."""
        nonlocal state
        t = clock()
        state, hist = trainer.train(state, sampler, iters=iters, log_every=iters)
        _block(state.params)
        t_end = clock()
        n_comp, comp_s = _compiles_in(t, t_end)
        w = {"name": name, "first": state.step - iters, "iters": iters,
             "s": t_end - t, "compiles": n_comp, "compile_s": comp_s,
             "ms": (t_end - t - comp_s) / iters * 1e3,
             "loss": hist["loss"][-1], "live_fraction": hist["live_fraction"][-1],
             "points_queried": hist["points_queried"][-1],
             "overflow": hist["overflow_total"]}
        windows.append(w)
        print(f"train: {name:<15} steps {w['first']:>3}-{state.step - 1:<3} "
              f"{w['ms']:9.2f} ms/step  step compiles {n_comp} ({comp_s:.1f}s)  "
              f"loss {w['loss']:.5f}  live_fraction {w['live_fraction']:.4f}  "
              f"points_queried {w['points_queried']}/{n_total}  overflow {w['overflow']}")
        return w

    # The occupancy update runs after steps 7, 11, 15, ... (warmup and
    # cadence UPDATE_EVERY = 4).  The one after step 7 switches the bitfield
    # on; the next measures the live fraction behind it and sets the
    # compaction budget.
    # The steady windows hold neither an occupancy update nor a compile.
    obs_trace.clear()
    obs_trace.set_enabled(True)
    try:
        window("dense first", 2)             # both freeze_color variants compile
        dense = window("dense", 2 * UPDATE_EVERY - 3)
        window("bitfield first", 3)          # first update; bitfield variants
        bitfield = window("bitfield", UPDATE_EVERY - 3)
        window("update", 1)                  # measures the live fraction
        for _ in range(4):  # the live fraction may need more updates to fall
            first = window("compacted first", 2)
            if first["points_queried"] < n_total:
                break
            window("update", UPDATE_EVERY - 2)
        compacted = window("compacted", UPDATE_EVERY - 3)
    finally:
        obs_trace.set_enabled(False)

    if compacted["points_queried"] >= n_total or \
            not any(k[2] is not None and k[3] for k in trainer.step_cache_keys()):
        raise AssertionError("no compacted fused step ran")
    recompiled = [w["name"] for w in (dense, bitfield, compacted) if w["compiles"]]
    if recompiled:
        raise AssertionError(f"a step compiled inside a steady window: {recompiled}")
    losses = [w["loss"] for w in windows]
    if not np.all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"loss not finite and falling: {losses}")

    t = clock()
    ev = trainer.evaluate(state.params, ds, views=list(HELD_OUT))
    print(f"train: held-out PSNR rgb {ev['psnr_rgb']:.3f} dB depth {ev['psnr_depth']:.3f} dB "
          f"(views {list(HELD_OUT)}; eval incl. its compile {clock() - t:.1f}s)")
    if not np.isfinite(ev["psnr_rgb"]):
        raise AssertionError(f"held-out PSNR not finite: {ev}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"train: step compile {sum(w['compile_s'] for w in windows):.1f}s "
          f"({sum(w['compiles'] for w in windows)} variants)  steady dense "
          f"{dense['ms']:.2f} ms/step  bitfield {bitfield['ms']:.2f} ms/step  "
          f"compacted {compacted['ms']:.2f} ms/step  peak_bytes_in_use {peak}")


def _serve(datasets, field_cfg, trainer_cfg, iters, devices, max_cohort=None):
    """Run every dataset as a session; one render request per session per slice."""
    from repro.serve3d import ReconstructionService

    svc = ReconstructionService(slice_iters=8, devices=devices, max_cohort=max_cohort)
    for i, (sid, ds) in enumerate(datasets.items()):
        svc.submit_scene(ds, field_cfg, trainer_cfg, target_iters=iters, seed=i,
                         session_id=sid)
    results = []

    def hook(s, event):
        for sid in event["cohort"]:
            s.request_render(sid, datasets[sid].poses[0])
        results.extend(event["results"])

    t0 = clock()
    tel = svc.run(hook=hook)
    return svc, tel, results, clock() - t0


def _serve_failures(tel, results) -> list[str]:
    from repro.launch.serve3d import failures
    from repro.serve3d import RenderError

    bad = failures(tel)
    g = tel["guard"]
    if g["rollbacks"] or g["quarantined"]:
        bad.append(f"guard rollbacks {g['rollbacks']} quarantined {g['quarantined']}")
    errors = [r for r in results if isinstance(r, RenderError)]
    if errors:
        bad.append(f"render errors {errors}")
    if not results:
        bad.append("no render was served")
    return bad


def _serve_setup(n_scenes, n_rays):
    from repro.core import FieldConfig, TrainerConfig, occupancy
    from repro.core.rendering import RenderConfig
    from repro.data import build_dataset

    render = RenderConfig(n_samples=N_SAMPLES)
    cfg = TrainerConfig(n_rays=n_rays, render=render, eval_chunk=32 * 32,
                        occ=occupancy.OccupancyConfig(update_interval=8, warmup_steps=16))
    datasets = {f"scene-{i:03d}": build_dataset(seed=SEED + i, n_views=4, h=32, w=32,
                                                cfg=render, gt_samples=64)[1]
                for i in range(n_scenes)}
    return datasets, FieldConfig(), cfg


def phase_serve():
    datasets, field_cfg, cfg = _serve_setup(2, 256)
    svc, tel, results, wall = _serve(datasets, field_cfg, cfg, iters=SERVE_ITERS, devices=None)
    r = tel["render"]
    print(f"serve: 2 sessions x {SERVE_ITERS} steps in {wall:.1f}s (incl. compile)  "
          f"renders {r.get('count', 0)} "
          f"p50 {r.get('p50_ms', float('nan')):.1f} ms  sessions "
          f"{[(p['session_id'], p['status'], p['step']) for p in tel['sessions']]}")
    bad = _serve_failures(tel, results)
    if bad:
        raise AssertionError("; ".join(bad))


def phase_mesh():
    """4 sessions on devices=4 against the same 4 on devices=1 (time-sliced,
    so both runs execute the same one-member step program)."""
    import jax
    import numpy as np

    if len(jax.devices()) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, have {len(jax.devices())}")
    datasets, field_cfg, cfg = _serve_setup(4, 256)
    runs = {}
    for devices in (4, 1):
        svc, tel, results, wall = _serve(datasets, field_cfg, cfg, iters=SERVE_ITERS,
                                         devices=devices, max_cohort=1)
        print(f"mesh: devices={devices} 4 sessions x {SERVE_ITERS} steps in {wall:.1f}s "
              f"(incl. compile)  "
              f"renders {tel['render'].get('count', 0)}  placement {tel['placement']}")
        bad = _serve_failures(tel, results)
        if bad:
            raise AssertionError(f"devices={devices}: " + "; ".join(bad))
        runs[devices] = (svc, results)

    svc4, results4 = runs[4]
    used = set()
    for sid, s in svc4.sessions.items():
        dev = svc4.placement.device(sid)
        used.add(dev)
        arrays = (s.state.params, s.state.opt_state, s.state.occ_state)
        held = {d for x in jax.tree_util.tree_leaves(arrays) for d in x.devices()}
        print(f"mesh: {sid} assigned {dev}  training state on {sorted(map(str, held))}")
        if held != {dev}:
            raise AssertionError(f"{sid}: arrays not on its assigned device {dev}")
    if len(used) != 4:
        raise AssertionError(f"sessions used {len(used)} devices, not 4")
    # snapshots are host copies; a render must run where its session trains
    for r in results4:
        if r.device != svc4.placement.device(r.session_id):
            raise AssertionError(
                f"render {r.request_id} of {r.session_id} ran on {r.device}, "
                f"session is on {svc4.placement.device(r.session_id)}")
    print(f"mesh: {len(results4)} renders each ran on its session's device")

    svc1, _ = runs[1]
    worst = 0.0
    for sid in datasets:
        a = jax.tree_util.tree_leaves(svc4.sessions[sid]._current_params())
        b = jax.tree_util.tree_leaves(svc1.sessions[sid]._current_params())
        for x, y in zip(a, b):
            worst = max(worst, float(np.max(np.abs(np.asarray(x) - np.asarray(y)))))
    print(f"mesh: params devices=4 vs devices=1 max|diff| = {worst:.3e}")
    if worst != 0.0:
        raise AssertionError(f"params differ between devices=4 and devices=1 by {worst}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the serve3d session-mesh phase on 4 chips")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax reports platform {dev.platform!r}; nothing was run")

    from repro import kernels
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"jax {jax.__version__}  device_kind {dev.device_kind!r}  count {len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    routing = kernels.routing()
    print(f"routing: {routing}")

    phases = ([("mesh", phase_mesh)] if args.four_chips else
              [("kernels", lambda: phase_kernels(routing)), ("train", phase_train),
               ("serve", phase_serve)])
    failed = []
    for name, fn in phases:
        t0 = clock()
        try:
            fn()
            print(f"phase {name}: ok in {clock() - t0:.1f}s", flush=True)
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            print(f"phase {name}: FAILED after {clock() - t0:.1f}s", flush=True)
            failed.append(name)
    if failed:
        fail(f"phases failed: {failed}")
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
