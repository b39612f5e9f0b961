"""Fault tolerance: injection harness, checkpoint integrity, guard
rollback/quarantine, and graceful render degradation.

Chaos scenarios run the real service with `repro.testing.faults` armed and
assert the recovery contract: every session finishes, at least one rollback
happened, and — because training streams are keyed by absolute step —
recovered runs are *bit-identical* to fault-free runs."""
import functools

import numpy as np
import jax
import pytest

from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.core import FieldConfig, TrainerConfig, occupancy
from repro.core.rendering import RenderConfig
from repro.core.trainer import tree_all_finite
from repro.data import build_dataset
from repro.launch.serve3d import failures
from repro.serve3d import (
    DONE, QUARANTINED, GuardConfig, ReconstructionService, RenderError,
    RenderService, SceneSession, SnapshotStore,
)
from repro.testing import faults

RCFG = RenderConfig(n_samples=8)
FIELD_CFG = FieldConfig(n_levels=2, max_resolution=32, log2_table_density=10,
                        log2_table_color=8, hidden=16)
OCFG = occupancy.OccupancyConfig(resolution=16, update_interval=4, warmup_steps=2)
TRAIN_CFG = TrainerConfig(n_rays=64, render=RCFG, occ=OCFG, eval_chunk=144)


@functools.lru_cache(maxsize=None)
def _ds(seed: int = 0):
    # cached builder instead of a pytest fixture so the shim-based property
    # tests (zero-arg wrappers) can use the same datasets
    _scene, ds = build_dataset(seed=seed, n_views=2, h=12, w=12,
                               cfg=RCFG, gt_samples=24)
    return ds


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    faults.configure(enabled=False)
    yield
    faults.reset()
    faults.configure(enabled=False)


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _run_service(n_scenes=2, target_iters=16, slice_iters=4, guard=True,
                 **svc_kwargs):
    svc = ReconstructionService(slice_iters=slice_iters, guard=guard,
                                **svc_kwargs)
    for seed in range(n_scenes):
        svc.submit_scene(_ds(seed), FIELD_CFG, TRAIN_CFG,
                         target_iters=target_iters, seed=seed)
    tel = svc.run()
    return svc, tel


def _final_params(svc):
    return {sid: jax.device_get(s._current_params())
            for sid, s in svc.sessions.items()}


# ---- the injection harness itself ----


def test_faults_disabled_is_noop():
    assert not faults.enabled()
    assert faults.check("serve3d.slice", session="x", step=0) is None
    assert faults.fired() == []


def test_fault_matching_semantics():
    faults.configure(enabled=True)
    inj = faults.inject("serve3d.slice", "nan_params", session="a",
                        at_step=10, skip=1, times=2)
    # wrong session / early step never match
    assert faults.check("serve3d.slice", session="b", step=50) is None
    assert faults.check("serve3d.slice", session="a", step=5) is None
    # first matching call is skipped, the next two fire, then exhausted
    assert faults.check("serve3d.slice", session="a", step=10) is None
    assert faults.check("serve3d.slice", session="a", step=12) is inj
    assert faults.check("serve3d.slice", session="a", step=14) is inj
    assert faults.check("serve3d.slice", session="a", step=16) is None
    assert faults.fired_count("nan_params") == 2
    # non-match keys ride along as call-site params
    inj2 = faults.inject("serve3d.slice", "slow", seconds=0.5)
    assert inj2.params == {"seconds": 0.5}
    assert inj2.match == {}


def test_arming_enables_and_reset_clears():
    assert not faults.enabled()
    faults.inject("checkpoint.write", "corrupt")
    assert faults.enabled()
    assert faults.check("checkpoint.write", step=1) is not None
    faults.reset()
    assert faults.check("checkpoint.write", step=2) is None
    assert faults.fired() == []


def test_poison_tree_and_finiteness():
    tree = {"w": np.ones((3, 2), np.float32), "n": np.arange(4)}
    bad = faults.poison_tree(tree, float("nan"))
    assert np.isnan(np.asarray(bad["w"])).all()
    np.testing.assert_array_equal(np.asarray(bad["n"]), tree["n"])  # int kept
    assert tree_all_finite(tree)
    assert not tree_all_finite(bad)
    assert tree_all_finite(bad["n"])  # integer-only tree is trivially finite


# ---- checkpoint integrity (per-file checksums + atomicity) ----


def test_checkpoint_meta_carries_per_file_checksums(tmp_path):
    ckpt = CheckpointManager(tmp_path, async_save=False)
    ckpt.save(1, {"w": np.ones(4, np.float32)})
    _tree, meta = ckpt.restore({"w": np.zeros(4, np.float32)})
    assert "files" in meta and set(meta["files"]) == {"arrays.npz"}
    assert meta["sha256"] == meta["files"]["arrays.npz"]


def test_checkpoint_rejects_corruption_falls_back(tmp_path):
    ckpt = CheckpointManager(tmp_path, async_save=False)
    ckpt.save(1, {"w": np.full(8, 1.0, np.float32)})
    ckpt.save(2, {"w": np.full(8, 2.0, np.float32)})
    faults.corrupt_file(tmp_path / "step_00000002" / "arrays.npz")
    assert not ckpt._verify(2)
    tree, meta = ckpt.restore({"w": np.zeros(8, np.float32)})
    assert meta["step"] == 1
    np.testing.assert_array_equal(tree["w"], np.full(8, 1.0, np.float32))


def test_checkpoint_corrupt_injection_detected(tmp_path):
    faults.configure(enabled=True)
    ckpt = CheckpointManager(tmp_path, async_save=False)
    ckpt.save(1, {"w": np.full(8, 1.0, np.float32)})
    faults.inject("checkpoint.write", "corrupt", at_step=2)
    ckpt.save(2, {"w": np.full(8, 2.0, np.float32)})
    assert faults.fired_count("corrupt") == 1
    # the corrupted step committed but verification rejects it
    assert 2 in ckpt.all_steps() and not ckpt._verify(2)
    _tree, meta = ckpt.restore({"w": np.zeros(8, np.float32)})
    assert meta["step"] == 1


def test_checkpoint_kill_mid_write_is_atomic(tmp_path):
    """A crash between data write and rename must leave the previous
    checkpoint as the latest valid one — the torn tmp dir never shadows it."""
    faults.configure(enabled=True)
    ckpt = CheckpointManager(tmp_path, async_save=False)
    ckpt.save(10, {"w": np.full(8, 10.0, np.float32)})
    faults.inject("checkpoint.write", "kill_mid_write", at_step=20)
    with pytest.raises(faults.InjectedFault):
        ckpt.save(20, {"w": np.full(8, 20.0, np.float32)})
    assert (tmp_path / "tmp_step_00000020").exists()   # torn write left behind
    assert ckpt.all_steps() == [10]                    # never committed
    tree, meta = ckpt.restore({"w": np.zeros(8, np.float32)})
    assert meta["step"] == 10
    # the same step saves cleanly after the "restart" (tmp dir is reused)
    ckpt.save(20, {"w": np.full(8, 20.0, np.float32)})
    assert ckpt.all_steps() == [10, 20]
    _tree, meta = ckpt.restore({"w": np.zeros(8, np.float32)})
    assert meta["step"] == 20


# ---- guard: detection, rollback, quarantine ----


def test_nan_params_rollback_bit_identical():
    """The acceptance scenario at 2 scenes: NaN params in one cohort member
    -> rollback; both sessions finish; final params bit-identical to the
    fault-free run (including the faulted session — rollback + step-keyed
    retraining reproduces the stream exactly)."""
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "nan_params", session="scene-001", at_step=8)
    svc_f, tel_f = _run_service(target_iters=16)
    assert faults.fired_count("nan_params") == 1
    assert tel_f["guard"]["rollbacks"] >= 1
    assert all(s.status == DONE for s in svc_f.sessions.values())
    params_f = _final_params(svc_f)

    faults.configure(enabled=False)
    svc_c, tel_c = _run_service(target_iters=16)
    assert tel_c["guard"]["rollbacks"] == 0
    params_c = _final_params(svc_c)
    for sid in params_c:
        assert _leaves_equal(params_f[sid], params_c[sid]), sid


def test_nan_loss_detected_by_cheap_check():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "nan_loss", session="scene-000", at_step=4)
    svc, tel = _run_service(n_scenes=1, target_iters=16)
    assert tel["guard"]["divergences"].get("nan_loss", 0) >= 1
    assert svc.sessions["scene-000"].status == DONE


def test_loss_spike_trips_collapse_heuristic():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "loss_spike", session="scene-000",
                  at_step=20, factor=1e8)
    svc, tel = _run_service(n_scenes=1, target_iters=32)
    assert tel["guard"]["divergences"].get("collapse", 0) >= 1
    assert svc.sessions["scene-000"].status == DONE


def test_slice_exception_rolls_back_with_guard():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "exception", session="scene-000", at_step=8)
    svc, tel = _run_service(n_scenes=1, target_iters=16)
    assert tel["guard"]["divergences"].get("exception", 0) == 1
    assert svc.sessions["scene-000"].status == DONE


def test_slice_exception_unwinds_without_guard():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "exception", session="scene-000", at_step=8)
    with pytest.raises(faults.InjectedFault):
        _run_service(n_scenes=1, target_iters=16, guard=None)


def test_quarantine_after_max_retries_keeps_service_alive():
    """A persistently-sick scene is ejected after max_retries consecutive
    failures; the other session finishes untouched, the service terminates,
    and the quarantined scene keeps serving its last-good snapshot, stale."""
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "nan_params", session="scene-000",
                  at_step=8, times=None)
    svc, tel = _run_service(
        target_iters=16, guard=GuardConfig(checkpoint_every=2, max_retries=2))
    sick, healthy = svc.sessions["scene-000"], svc.sessions["scene-001"]
    assert sick.status == QUARANTINED
    assert healthy.status == DONE and healthy.step == 16
    assert svc.scheduler.all_done          # quarantine is terminal
    assert tel["guard"]["quarantined"] == ["scene-000"]
    assert tel["guard"]["rollbacks"] == 2  # max_retries, then ejected
    assert failures(tel) == ["scene-000 ended quarantined"]  # CLI exits non-zero

    # the quarantined scene still serves: last-good snapshot, marked stale
    snap = svc.store.latest("scene-000")
    assert snap is not None and snap.step <= 8
    assert tree_all_finite(snap.params)
    svc.request_render("scene-000", _ds(0).poses[0])
    (res,) = svc.renderer.drain()
    assert res.stale and res.snapshot_step == snap.step

    # healthy session's result is bit-identical to a fault-free run
    faults.configure(enabled=False)
    svc_c, _ = _run_service(target_iters=16)
    assert _leaves_equal(svc_c.sessions["scene-001"]._current_params(),
                         healthy._current_params())


def test_straggler_slice_flagged_not_blocked():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "slow", session="scene-000",
                  at_step=8, seconds=1.0)
    svc, tel = _run_service(target_iters=16)
    assert faults.fired_count("slow") == 1
    assert tel["stragglers_flagged"] >= 1
    # flagged means deprioritized, never starved: everyone still finishes
    assert all(s.status == DONE and s.step == 16
               for s in svc.sessions.values())


def test_guard_event_log_and_step_verdicts():
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", "nan_params", session="scene-000", at_step=8)
    svc = ReconstructionService(slice_iters=4)
    svc.submit_scene(_ds(0), FIELD_CFG, TRAIN_CFG, target_iters=16)
    verdicts = []
    svc.run(hook=lambda _svc, ev: verdicts.extend(ev["guard"].values()))
    assert "rolled_back" in verdicts
    events = svc.guard.session_events("scene-000")
    assert events and events[0]["event"] == "rollback"
    assert events[0]["to_step"] < events[0]["from_step"]


# ---- snapshot publish retry ----


def test_publish_failure_retains_last_good_and_retries():
    faults.configure(enabled=True)
    faults.inject("serve3d.snapshot_publish", "snapshot_fail",
                  session="scene-000", at_step=8)
    svc, tel = _run_service(n_scenes=1, target_iters=16, snapshot_every=1)
    assert faults.fired_count("snapshot_fail") == 1
    assert svc.publish_failures == 1
    snap = svc.store.latest("scene-000")
    # the retry landed: the final publish reflects the finished session
    assert snap is not None and snap.step == 16
    assert svc.sessions["scene-000"].status == DONE


# ---- render degradation ladder ----


def test_render_deadline_expires_as_typed_error():
    store = SnapshotStore()   # never publishes -> requests can only expire
    rs = RenderService(store, default_deadline_s=0.0)
    rs.register_session("s0", FIELD_CFG, RCFG, 12, 12, 30.0)
    rid = rs.submit("s0", np.eye(4))
    (err,) = rs.drain()
    assert isinstance(err, RenderError)
    assert err.request_id == rid and err.error == "deadline_expired"
    assert rs.pending == 0 and rs.expired == 1


def test_render_group_failure_retries_then_succeeds():
    faults.configure(enabled=True)
    svc, _ = _run_service(n_scenes=1, target_iters=8)
    faults.inject("serve3d.render_group", "render_fail", times=1)
    svc.request_render("scene-000", _ds(0).poses[0])
    assert svc.renderer.drain() == []          # attempt 1 fails, re-queued
    (res,) = svc.renderer.drain()              # attempt 2 succeeds
    assert not isinstance(res, RenderError) and res.rgb.shape == (12, 12, 3)
    assert res.device == jax.devices()[0]      # unplaced: the default device
    assert failures(svc.telemetry()) == []     # a retried render is no failure


def test_render_group_failure_exhausts_to_typed_error():
    faults.configure(enabled=True)
    svc, _ = _run_service(n_scenes=1, target_iters=8)
    faults.inject("serve3d.render_group", "render_fail", times=None)
    rid = svc.request_render("scene-000", _ds(0).poses[0])
    svc.renderer.drain()
    (err,) = svc.renderer.drain()
    assert isinstance(err, RenderError)
    assert err.request_id == rid and err.error == "render_failed"
    assert svc.renderer.failed == 1 and svc.renderer.pending == 0
    (bad,) = failures(svc.telemetry())          # CLI exits non-zero, with the cause
    assert bad.startswith("1 render(s) failed (InjectedFault:")


def test_overload_shedding_degrades_before_dropping():
    svc, _ = _run_service(n_scenes=2, target_iters=8, shed_threshold=1)
    for sid in ("scene-000", "scene-001"):
        svc.request_render(sid, _ds(0).poses[0])
    results = svc.renderer.drain()
    assert len(results) == 2                    # nothing dropped
    assert all(r.rgb.shape == (12, 12, 3) for r in results)
    assert svc.renderer.shed_drains >= 1
    stats = svc.renderer.latency_stats()
    assert stats["degraded"]["shed_fraction"] > 0


def test_stale_annotation_round_trip():
    svc, _ = _run_service(n_scenes=1, target_iters=8)
    svc.renderer.mark_stale("scene-000")
    svc.request_render("scene-000", _ds(0).poses[0])
    (res,) = svc.renderer.drain()
    assert res.stale
    svc.renderer.mark_stale("scene-000", False)
    svc.request_render("scene-000", _ds(0).poses[0])
    (res,) = svc.renderer.drain()
    assert not res.stale


# ---- suspend -> crash -> resume ----


def test_crash_resume_from_periodic_checkpoint_bit_identical(tmp_path):
    """Kill a session mid-training (its object is simply abandoned), restore
    a fresh process from the latest valid on-disk periodic checkpoint, train
    to target: the result must be bit-identical to an uninterrupted run —
    even when the newest checkpoint on disk is corrupt (fall-back path)."""
    ds = _ds(0)
    sess = SceneSession("s0", ds, FIELD_CFG, TRAIN_CFG, target_iters=32,
                        ckpt_dir=str(tmp_path / "ckpt"))
    sess.start()
    for _ in range(3):
        sess.run_slice(4)
        sess.ckpt.save(sess.step, sess.trainer.suspend(sess.state), block=True)
    # "crash": poison the newest checkpoint too — restore must fall back
    faults.corrupt_file(tmp_path / "ckpt" / "step_00000012" / "arrays.npz")

    fresh = SceneSession("s0", ds, FIELD_CFG, TRAIN_CFG, target_iters=32,
                         ckpt_dir=str(tmp_path / "ckpt"))
    fresh.resume()
    assert fresh.step == 8      # step 12 rejected, step 8 restored
    while fresh.status != DONE:
        fresh.run_slice(4)

    ref = SceneSession("s0-ref", ds, FIELD_CFG, TRAIN_CFG, target_iters=32)
    ref.start()
    while ref.status != DONE:
        ref.run_slice(4)
    assert _leaves_equal(fresh._current_params(), ref._current_params())


@settings(max_examples=4, deadline=None)
@given(fault_step=st.integers(4, 12),
       kind=st.sampled_from(["nan_params", "inf_params", "exception",
                             "nan_loss"]))
def test_recovery_bit_identity_property(fault_step, kind):
    """For any fault kind at any step: the guarded service converges to the
    exact params of a fault-free run (rollback never changes results)."""
    faults.reset()
    faults.configure(enabled=True)
    faults.inject("serve3d.slice", kind, session="scene-000",
                  at_step=fault_step)
    svc_f, tel_f = _run_service(n_scenes=1, target_iters=16)
    assert tel_f["guard"]["rollbacks"] >= 1
    assert svc_f.sessions["scene-000"].status == DONE

    faults.reset()
    faults.configure(enabled=False)
    svc_c, _ = _run_service(n_scenes=1, target_iters=16)
    assert _leaves_equal(svc_f.sessions["scene-000"]._current_params(),
                         svc_c.sessions["scene-000"]._current_params())
