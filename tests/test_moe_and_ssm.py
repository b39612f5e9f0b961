"""MoE: EP shard_map path vs dense oracle; SSM: chunked scan vs step recurrence."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest


from repro.models.config import ModelConfig, MoEConfig, SSMConfig
from repro.models import moe, ssm


def _moe_cfg(n_routed=8, top_k=2, n_shared=1, ep=True):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4,
        d_ff=64, vocab=64, dtype="float32",
        moe=MoEConfig(n_routed=n_routed, n_shared=n_shared, top_k=top_k,
                      d_expert_ff=64, ep_axis="model" if ep else None),
    )


def test_moe_ep_matches_dense_single_shard(rng):
    """With model-axis size 1 the EP path must agree with the dense oracle
    exactly (no drops possible)."""
    cfg = _moe_cfg()
    params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8, 32)).astype(np.float32))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dense = moe.moe_dense(params, x, cfg)
    ep = moe.moe_ep(params, x, cfg, mesh, capacity_factor=100.0)  # no drops
    np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), atol=1e-4, rtol=1e-4)


def test_moe_decode_path(rng):
    cfg = _moe_cfg()
    params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 1, 32)).astype(np.float32))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dense = moe.moe_dense(params, x, cfg)
    ep = moe.moe_ep(params, x, cfg, mesh)
    np.testing.assert_allclose(np.asarray(ep), np.asarray(dense), atol=1e-4, rtol=1e-4)


def test_router_topk_gates_normalized(rng):
    cfg = _moe_cfg(top_k=3)
    params = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(10, 32)).astype(np.float32))
    gates, ids = moe.route(params, x, cfg.moe)
    assert gates.shape == (10, 3) and ids.shape == (10, 3)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-5)
    assert (np.asarray(ids) >= 0).all() and (np.asarray(ids) < 8).all()


def _ssm_cfg(kind):
    return ModelConfig(
        name="t", family="ssm", n_layers=1, d_model=16, n_heads=1, n_kv_heads=1,
        d_ff=0, vocab=64, dtype="float32",
        ssm=SSMConfig(kind=kind, d_state=8, d_conv=4, expand=2, headdim=8, chunk=4),
    )


@pytest.mark.parametrize("kind", ["mamba1", "mamba2"])
def test_ssm_chunked_equals_tokenwise(kind, rng):
    """Chunked parallel scan over a sequence == feeding tokens one by one
    through the recurrent decode path (state-space correctness)."""
    cfg = _ssm_cfg(kind)
    params = ssm.init_ssm(jax.random.PRNGKey(0), cfg, jnp.float32)
    b, s = 2, 12
    x = jnp.asarray(rng.normal(size=(b, s, 16)).astype(np.float32) * 0.5)

    y_par, state_par = ssm.ssm_block(params, cfg, x)

    state = ssm.init_ssm_state(cfg, b, jnp.float32)
    ys = []
    for t in range(s):
        y_t, state = ssm.ssm_block(params, cfg, x[:, t : t + 1], state)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(state_par.h), np.asarray(state.h),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("kind", ["mamba1", "mamba2"])
def test_ssm_state_continuation(kind, rng):
    """Splitting a sequence across two calls with carried state == one call."""
    cfg = _ssm_cfg(kind)
    params = ssm.init_ssm(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, 16, 16)).astype(np.float32) * 0.5)
    y_full, _ = ssm.ssm_block(params, cfg, x)
    y1, st = ssm.ssm_block(params, cfg, x[:, :8])
    y2, _ = ssm.ssm_block(params, cfg, x[:, 8:], st)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([y1, y2], axis=1)), np.asarray(y_full),
        atol=1e-3, rtol=1e-3)
