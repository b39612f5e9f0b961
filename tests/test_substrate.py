"""Substrate: optimizer, checkpoint (atomic/elastic), driver, data, collectives."""
import os
import signal
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import AdamW, schedule, clip_by_global_norm
from repro.checkpoint import CheckpointManager
from repro.runtime import TrainDriver, DriverConfig, StragglerStats, resume_or_init
from repro.runtime import compile_cache
from repro.data import SyntheticLMStream, LMStreamConfig
from repro.parallel.collectives import compressed_psum_mean, _quantize, _dequantize


# ---- optimizer ----

def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, state = opt.apply(params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_adamw_mask_freezes_moments_and_params():
    opt = AdamW(lr=0.1)
    params = {"a": jnp.ones(3), "b": jnp.ones(3)}
    state = opt.init(params)
    grads = {"a": jnp.ones(3), "b": jnp.ones(3)}
    mask = {"a": True, "b": False}
    p1, s1 = opt.apply(params, grads, state, mask=mask)
    np.testing.assert_array_equal(np.asarray(p1["b"]), np.asarray(params["b"]))
    np.testing.assert_array_equal(np.asarray(s1.m["b"]), 0.0)
    assert not np.array_equal(np.asarray(p1["a"]), np.asarray(params["a"]))


def test_lr_schedule_shapes():
    fn = schedule.warmup_cosine(1.0, 10, 100, floor=0.1)
    assert float(fn(0)) == 0.0
    assert abs(float(fn(10)) - 1.0) < 1e-6
    assert abs(float(fn(100)) - 0.1) < 1e-6
    assert float(fn(55)) > 0.1


def test_clip_by_global_norm():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert abs(float(jnp.linalg.norm(clipped["a"])) - 1.0) < 1e-5


# ---- checkpointing ----

def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(tmp_path, keep_last=2, async_save=False)
    tree = {"w": jnp.arange(10, dtype=jnp.float32), "nested": {"b": jnp.ones((2, 3))}}
    for step in (10, 20, 30):
        mgr.save(step, jax.tree.map(lambda t: t + step, tree))
    assert mgr.all_steps() == [20, 30]  # keep_last=2 GC'd step 10
    restored, meta = mgr.restore(tree)
    assert meta["step"] == 30
    np.testing.assert_allclose(np.asarray(restored["w"]), np.arange(10) + 30)


def test_checkpoint_detects_corruption(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(1, {"w": jnp.ones(4)})
    mgr.save(2, {"w": jnp.ones(4) * 2})
    # corrupt the latest
    npz = tmp_path / "step_00000002" / "arrays.npz"
    npz.write_bytes(npz.read_bytes()[:-8] + b"deadbeef")
    restored, meta = mgr.restore({"w": jnp.ones(4)})
    assert meta["step"] == 1  # fell back to the previous valid snapshot


def test_checkpoint_elastic_mesh_change(tmp_path):
    """Save on one layout, restore sharded onto another (elastic scaling)."""
    from jax.sharding import PartitionSpec as P, NamedSharding
    n = jax.device_count()
    mesh_a = jax.make_mesh((1, 1), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    mgr = CheckpointManager(tmp_path, async_save=False)
    mgr.save(5, tree)
    sh = {"w": NamedSharding(mesh_a, P("data", None))}
    restored, _ = mgr.restore(tree, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(tree["w"]))


# ---- driver ----

def _fake_step(state, batch):
    return state + 1, {"loss": float(batch["x"])}


def test_driver_runs_and_checkpoints(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", async_save=False)
    drv = TrainDriver(DriverConfig(total_steps=7, checkpoint_every=3, log_every=2,
                                   metrics_path=str(tmp_path / "m.jsonl")), mgr)
    batches = iter([{"x": i} for i in range(100)])
    state, summary = drv.run(jnp.zeros(()), _fake_step, batches)
    assert int(state) == 7 and not summary["preempted"]
    assert mgr.latest_step() == 7


def test_driver_preemption(tmp_path):
    mgr = CheckpointManager(tmp_path / "ck", async_save=False)
    drv = TrainDriver(DriverConfig(total_steps=1000, checkpoint_every=10**6), mgr)

    calls = {"n": 0}
    def step(state, batch):
        calls["n"] += 1
        if calls["n"] == 5:
            drv._preempted = True  # simulate SIGTERM mid-training
        return state + 1, {}

    batches = iter([{"x": i} for i in range(100)])
    state, summary = drv.run(jnp.zeros(()), step, batches)
    assert summary["preempted"] and int(state) == 5
    assert mgr.latest_step() == 5  # checkpoint written on the way out


def test_resume_or_init(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=False)
    tmpl = {"w": jnp.zeros(3)}
    state, cursor = resume_or_init(mgr, tmpl, lambda: {"w": jnp.ones(3)})
    assert cursor == 0 and float(state["w"][0]) == 1.0
    mgr.save(42, {"w": jnp.full(3, 7.0)}, extra={"data_cursor": 42})
    state, cursor = resume_or_init(mgr, tmpl, lambda: {"w": jnp.ones(3)})
    assert cursor == 42 and float(state["w"][0]) == 7.0


def test_straggler_detector():
    s = StragglerStats()
    flags = [s.update(1.0, sigma=4.0, alpha=0.1) for _ in range(20)]
    assert not any(flags)
    assert s.update(10.0, sigma=4.0, alpha=0.1)  # 10x outlier flagged
    assert s.n_flagged == 1


# ---- data ----

def test_lm_stream_determinism_and_sharding():
    cfg = LMStreamConfig(vocab=128, seq=16, global_batch=8, seed=3)
    ds = SyntheticLMStream(cfg)
    a = ds.batch(step=5, dp_rank=0, dp_size=2)
    b = ds.batch(step=5, dp_rank=0, dp_size=2)
    np.testing.assert_array_equal(a, b)  # deterministic restart
    c = ds.batch(step=5, dp_rank=1, dp_size=2)
    assert not np.array_equal(a, c)  # shards differ
    assert a.shape == (4, 16)
    # learnable structure: bigrams come from the fixed successor table
    succ = ds.successors
    for row in a:
        for t in range(len(row) - 1):
            assert row[t + 1] in succ[row[t]]


# ---- compressed collectives ----

def test_quantize_roundtrip_error_bounded(rng):
    g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
    q, s = _quantize(g)
    err = np.abs(np.asarray(_dequantize(q, s) - g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_compressed_psum_matches_exact_mean():
    """Single-device axis: compressed psum == quantized identity; multi-step
    error feedback drives the accumulated bias to zero."""
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import PartitionSpec as P
    g = jnp.asarray(np.linspace(-1, 1, 64, dtype=np.float32))
    err = jnp.zeros_like(g)
    fn = jax.shard_map(lambda gg, ee: compressed_psum_mean(gg, ee, "pod"),
                       mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    total = jnp.zeros_like(g)
    exact = jnp.zeros_like(g)
    for _ in range(50):  # error feedback: accumulated sums converge
        out, err = fn(g, err)
        total = total + out
        exact = exact + g
    rel = float(jnp.linalg.norm(total - exact) / jnp.linalg.norm(exact))
    assert rel < 0.01, rel


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    """The entry points' persistent compile cache: $JAX_COMPILATION_CACHE_DIR
    when set (and then nothing is changed), else .jax_cache/ at the
    checkout root."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        assert os.path.basename(path) == ".jax_cache"
        assert os.path.samefile(os.path.dirname(path),
                                os.path.join(os.path.dirname(__file__), ".."))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
