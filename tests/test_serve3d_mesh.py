"""Device-mesh serve3d: placement, cohort device axis, snapshot levels,
async serving, and the bit-identity contracts of the sharded service.

Single-device hosts run everything except the tests marked
``needs 4 devices`` — those run in-process on the CI multi-device leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and are covered
here by one subprocess test that forces the device count itself, so the
tier-1 suite exercises the mesh path everywhere.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import FieldConfig, TrainerConfig, occupancy
from repro.core.rendering import RenderConfig
from repro.data import build_dataset
from repro.launch.mesh import session_devices
from repro.serve3d import (
    DevicePlacement, ReconstructionService, SceneSession, SnapshotStore,
)

RCFG = RenderConfig(n_samples=8)
FIELD_CFG = FieldConfig(n_levels=2, max_resolution=32, log2_table_density=10,
                        log2_table_color=8, hidden=16)
OCFG = occupancy.OccupancyConfig(resolution=16, update_interval=4,
                                 warmup_steps=2)
TRAIN_CFG = TrainerConfig(n_rays=64, render=RCFG, occ=OCFG, eval_chunk=144)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def make_ds(seed=0):
    _scene, ds = build_dataset(seed=seed, n_views=2, h=12, w=12, cfg=RCFG,
                               gt_samples=24)
    return ds


def _leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---- placement policy (pure bookkeeping: fake devices are fine) ----


def test_placement_least_loaded_sticky_deterministic():
    p = DevicePlacement(["d0", "d1", "d2", "d3"])
    slots = [p.assign(f"s{i}") for i in range(6)]
    # least-loaded with ties toward the lowest slot: round-robin spread
    assert slots == [0, 1, 2, 3, 0, 1]
    # sticky: re-assigning returns the existing slot, no load double-count
    assert p.assign("s0") == 0
    assert p.loads() == [2, 2, 1, 1]
    assert p.device("s2") == "d2"
    assert p.device_for_slot(3) == "d3"
    assert p.device("unplaced") is None and p.slot("unplaced") is None


def test_placement_release_keeps_routing():
    p = DevicePlacement(["d0", "d1"])
    p.assign("a"), p.assign("b")
    p.release("a")
    # capacity returns to the pool, the mapping survives for render routing
    assert p.loads() == [0, 1]
    assert p.slot("a") == 0 and p.device("a") == "d0"
    p.release("a")                       # idempotent
    assert p.loads() == [0, 1]
    # the freed slot is the least-loaded target again
    assert p.assign("c") == 0


def test_placement_move():
    p = DevicePlacement(["d0", "d1", "d2"])
    for sid in ("a", "b", "c"):
        p.assign(sid)
    # rebalance move: least-loaded *other* slot
    assert p.move("a") in (1, 2)
    # explicit move updates loads
    p.move("b", 0)
    assert p.slot("b") == 0
    with pytest.raises(KeyError):
        p.move("nope")
    with pytest.raises(ValueError):
        p.move("a", 7)


def test_placement_validation():
    with pytest.raises(ValueError):
        DevicePlacement([])
    with pytest.raises(ValueError):
        session_devices(jax.device_count() + 1)
    with pytest.raises(ValueError):
        session_devices(0)
    assert DevicePlacement(1).n == 1
    assert len(session_devices()) == jax.device_count()


# ---- cohort keys carry the device axis ----


def test_cohort_key_device_axis():
    a = SceneSession("a", make_ds(0), FIELD_CFG, TRAIN_CFG, 16, seed=0)
    b = SceneSession("b", make_ds(1), FIELD_CFG, TRAIN_CFG, 16, seed=1)
    dev = jax.devices()[0]
    assert a.cohort_key() == b.cohort_key()       # both unplaced
    a.place(dev, 0), b.place(dev, 1)
    assert a.cohort_key() != b.cohort_key()       # split across slots
    b.place(dev, 0)
    assert a.cohort_key() == b.cohort_key()       # co-located: batch again


# ---- snapshot levels ----


def test_snapshot_levels_versions_and_gc():
    store = SnapshotStore()
    params = {"w": np.ones(3, np.float32)}
    s1 = store.publish("s", params, step=4, level=2)
    assert s1.version == 1 and s1.level == 2
    # no full snapshot yet: latest() falls back to the best preview,
    # latest(level=0) insists on full
    assert store.latest("s").level == 2
    assert store.latest("s", level=0) is None
    s2 = store.publish("s", params, step=8, level=0)
    assert s2.version == 2                        # monotone across levels
    assert store.latest("s").level == 0
    assert store.latest("s", level=2).version == 1
    assert store.levels("s") == [0, 2]
    assert store.gc_previews("s") == 1
    assert store.levels("s") == [0]
    assert store.latest("s").version == 2         # full snapshot survives
    assert store.gc_previews("s") == 0
    assert store.gc_previews("ghost") == 0


def test_preview_serving_resolution_and_gc():
    svc = ReconstructionService(slice_iters=4, snapshot_every=4,
                                snapshot_levels=2)
    ds = make_ds(0)
    sid = svc.submit_scene(ds, FIELD_CFG, TRAIN_CFG, target_iters=16, seed=0)
    svc.request_render(sid, ds.poses[0], level=2)
    svc.request_render(sid, ds.poses[0], level=0)
    got = []
    preview_first = []

    def hook(s, ev):
        got.extend(ev["results"])
        if not preview_first and ev["results"]:
            preview_first.extend(r.level for r in ev["results"])

    svc.run(hook=hook)
    by_level = {r.level: r for r in got}
    assert set(by_level) == {0, 2}
    # previews render at h>>k, full requests at full resolution
    assert by_level[2].rgb.shape == (ds.h >> 2, ds.w >> 2, 3)
    assert by_level[0].rgb.shape == (ds.h, ds.w, 3)
    # the preview was answerable before the first snapshot_every-gated full
    # publish, which is the point of progressive streaming
    assert preview_first == [2]
    assert by_level[2].snapshot_step < by_level[0].snapshot_step
    # finished sessions keep exactly their full snapshot
    assert svc.store.levels(sid) == [0]


# ---- bit-identity contracts ----


def test_devices_1_bit_identical_to_placement_free():
    results = {}
    for devices in (None, 1):
        svc = ReconstructionService(slice_iters=8, max_cohort=4,
                                    devices=devices)
        sids = [svc.submit_scene(make_ds(s), FIELD_CFG, TRAIN_CFG,
                                 target_iters=16, seed=s) for s in range(2)]
        svc.run()
        rid = svc.request_render(sids[0], make_ds(0).poses[0])
        out = {r.request_id: r for r in svc.renderer.drain()}
        results[devices] = (svc, sids, out[rid])
    svc_a, sids_a, render_a = results[None]
    svc_b, sids_b, render_b = results[1]
    for a, b in zip(sids_a, sids_b):
        assert _leaves_equal(svc_a.store.latest(a).params,
                             svc_b.store.latest(b).params)
    assert np.array_equal(render_a.rgb, render_b.rgb)
    assert np.array_equal(render_a.depth, render_b.depth)


def test_eval_matches_served_bitwise():
    """The trainer-side offline `evaluate` and the service's render path
    march the same redistributed quadrature on the same snapshot — the
    eval == served regression contract."""
    svc = ReconstructionService(slice_iters=8)
    ds = make_ds(0)
    sid = svc.submit_scene(ds, FIELD_CFG, TRAIN_CFG, target_iters=16, seed=0)
    svc.run()
    rid = svc.request_render(sid, ds.poses[0])
    served = {r.request_id: r for r in svc.renderer.drain()}[rid]
    sess = svc.sessions[sid]
    assert sess.render_spr is not None
    snap = svc.store.latest(sid)
    rgb, dep = sess.trainer.render_image(snap.params, ds.poses[0], ds,
                                         occ=snap.occ,
                                         samples_per_ray=sess.render_spr)
    assert np.array_equal(np.asarray(rgb), served.rgb)
    assert np.array_equal(np.asarray(dep), served.depth)
    # and the aggregate evaluate() runs the same path without error
    ev = sess.evaluate(views=[0])
    assert np.isfinite(ev["psnr_rgb"])


def test_async_serving_completes_and_matches_sync():
    ds = make_ds(0)
    finals = {}
    for async_mode in (False, True):
        svc = ReconstructionService(slice_iters=8, async_serving=async_mode)
        sid = svc.submit_scene(ds, FIELD_CFG, TRAIN_CFG, target_iters=16,
                               seed=0)
        svc.request_render(sid, ds.poses[0])
        got = []
        svc.run(hook=lambda s, ev: got.extend(ev["results"]))
        assert not svc.renderer.async_active
        assert len(got) == 1 and svc.renderer.pending == 0
        # post-run renders use the (now synchronous) drain on both services
        rid = svc.request_render(sid, ds.poses[1])
        finals[async_mode] = {r.request_id: r for r in
                              svc.renderer.drain()}[rid]
    # same snapshot, same compiled entry -> same pixels regardless of which
    # plane served the in-flight requests
    assert np.array_equal(finals[False].rgb, finals[True].rgb)
    assert np.array_equal(finals[False].depth, finals[True].depth)


# ---- multi-device (in-process on the CI mesh leg) ----


@needs_mesh
def test_mesh_spreads_and_matches_single_device():
    n_scenes = 6
    svc = ReconstructionService(slice_iters=8, devices=4, max_cohort=4)
    sids = [svc.submit_scene(make_ds(s), FIELD_CFG, TRAIN_CFG,
                             target_iters=16, seed=s) for s in range(n_scenes)]
    tel = svc.run()
    assert tel["scenes_done"] == n_scenes
    placed = tel["placement"]["placed"]
    assert set(placed.values()) == {0, 1, 2, 3}
    # released on completion: capacity returned, routing retained
    assert tel["placement"]["loads"] == [0, 0, 0, 0]

    ref = ReconstructionService(slice_iters=8, max_cohort=4)
    ref_sids = [ref.submit_scene(make_ds(s), FIELD_CFG, TRAIN_CFG,
                                 target_iters=16, seed=s)
                for s in range(n_scenes)]
    ref.run()
    for a, b in zip(sids, ref_sids):
        assert _leaves_equal(svc.store.latest(a).params,
                             ref.store.latest(b).params)


@needs_mesh
def test_per_device_residency_cap():
    # max_resident=1 per device, 4 devices -> 4 resident sessions at once
    svc = ReconstructionService(slice_iters=4, devices=4, max_resident=1)
    for s in range(6):
        svc.submit_scene(make_ds(s), FIELD_CFG, TRAIN_CFG, target_iters=8,
                         seed=s)
    resident_high = [0]

    def hook(service, _ev):
        resident_high[0] = max(resident_high[0],
                               service.scheduler._resident_count())

    tel = svc.run(hook=hook)
    assert tel["scenes_done"] == 6
    assert resident_high[0] <= 4


@needs_mesh
def test_device_move_suspend_resume_bit_identity():
    devs = jax.devices()
    ds = make_ds(0)

    moved = SceneSession("m", ds, FIELD_CFG, TRAIN_CFG, 16, seed=0)
    moved.place(devs[0], 0)
    moved.start()
    moved.run_slice(8)
    moved.suspend()
    moved.place(devs[1], 1)      # the device move: host round-trip, new slot
    moved.resume()
    moved.run_slice(8)

    ref = SceneSession("r", make_ds(0), FIELD_CFG, TRAIN_CFG, 16, seed=0)
    ref.start()
    ref.run_slice(8)
    ref.run_slice(8)

    assert moved.status == ref.status == "done"
    assert _leaves_equal(moved.state.params, ref.state.params)
    assert _leaves_equal(moved.state.opt_state, ref.state.opt_state)
    assert np.array_equal(np.asarray(moved.state.occ_state.density_ema),
                          np.asarray(ref.state.occ_state.density_ema))


# ---- forced-device-count subprocess (tier-1 coverage on any host) ----


_CHILD = textwrap.dedent("""
    import jax, numpy as np
    assert jax.device_count() == 4, jax.devices()
    from repro.core import FieldConfig, TrainerConfig, occupancy
    from repro.core.rendering import RenderConfig
    from repro.data import build_dataset
    from repro.serve3d import ReconstructionService

    RCFG = RenderConfig(n_samples=8)
    FIELD = FieldConfig(n_levels=2, max_resolution=32, log2_table_density=10,
                        log2_table_color=8, hidden=16)
    OCFG = occupancy.OccupancyConfig(resolution=16, update_interval=4,
                                     warmup_steps=2)
    TCFG = TrainerConfig(n_rays=64, render=RCFG, occ=OCFG, eval_chunk=144)

    def mk(seed):
        return build_dataset(seed=seed, n_views=2, h=12, w=12, cfg=RCFG,
                             gt_samples=24)[1]

    svc = ReconstructionService(slice_iters=8, devices=4, max_cohort=4,
                                async_serving=True)
    sids = [svc.submit_scene(mk(s), FIELD, TCFG, target_iters=16, seed=s)
            for s in range(4)]
    for sid in sids:
        svc.request_render(sid, mk(0).poses[0])
    got = []
    tel = svc.run(hook=lambda s, ev: got.extend(ev["results"]))
    assert tel["scenes_done"] == 4, tel
    assert len(got) == 4, got
    assert set(tel["placement"]["placed"].values()) == {0, 1, 2, 3}

    ref = ReconstructionService(slice_iters=8, max_cohort=4)
    rids = [ref.submit_scene(mk(s), FIELD, TCFG, target_iters=16, seed=s)
            for s in range(4)]
    ref.run()
    for a, b in zip(sids, rids):
        la = jax.tree.leaves(svc.store.latest(a).params)
        lb = jax.tree.leaves(ref.store.latest(b).params)
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(la, lb))
    print("MESH_CHILD_OK")
""")


def test_forced_host_device_count_subprocess():
    """End-to-end mesh run under a forced 4-device host topology: placement
    spread, async serving, and N=4 == N=1 params bit-identity."""
    env = dict(os.environ)
    # forced host devices are CPU devices; pinning the child to the CPU also
    # keeps it off an accelerator this process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    env.pop("REPRO_OBS", None)
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MESH_CHILD_OK" in proc.stdout
