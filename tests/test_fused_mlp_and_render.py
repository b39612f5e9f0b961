"""Kernel validation: fused MLP + volume render vs oracles; render invariants."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.fused_mlp import ref as mlp_ref, ops as mlp_ops
from repro.kernels.volume_render import ref as vr_ref, ops as vr_ops


@pytest.mark.parametrize("n,din,h,dout", [(700, 32, 64, 16), (512, 48, 64, 3), (33, 16, 32, 1)])
def test_fused_mlp3_matches(n, din, h, dout, rng):
    x = jnp.asarray(rng.normal(size=(n, din)).astype(np.float32))
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1)
    w1, b1, w2, b2, w3, b3 = mk(din, h), mk(h), mk(h, h), mk(h), mk(h, dout), mk(dout)
    p3 = mlp_ops.mlp3(x, w1, b1, w2, b2, w3, b3, backend="pallas")
    r3 = mlp_ref.mlp3(x, w1, b1, w2, b2, w3, b3)
    np.testing.assert_allclose(np.asarray(p3), np.asarray(r3), atol=1e-4, rtol=1e-4)


def test_fused_mlp2_matches(rng):
    x = jnp.asarray(rng.normal(size=(300, 32)).astype(np.float32))
    mk = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32) * 0.1)
    w1, b1, w2, b2 = mk(32, 64), mk(64), mk(64, 16), mk(16)
    np.testing.assert_allclose(
        np.asarray(mlp_ops.mlp2(x, w1, b1, w2, b2, backend="pallas")),
        np.asarray(mlp_ref.mlp2(x, w1, b1, w2, b2)), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("r,s", [(300, 64), (128, 32), (77, 48)])
def test_volume_render_matches(r, s, rng):
    sigma = jnp.asarray(rng.uniform(0, 5, size=(r, s)).astype(np.float32))
    rgb = jnp.asarray(rng.uniform(0, 1, size=(r, s, 3)).astype(np.float32))
    ts = jnp.sort(jnp.asarray(rng.uniform(0.1, 4, size=(r, s)).astype(np.float32)), axis=1)
    deltas = jnp.diff(ts, axis=1, append=ts[:, -1:] + 0.01)
    o_ref = vr_ref.composite(sigma, rgb, deltas, ts)
    o_pal = vr_ops.composite(sigma, rgb, deltas, ts, backend="pallas")
    np.testing.assert_allclose(np.asarray(o_pal.color), np.asarray(o_ref.color), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_pal.depth), np.asarray(o_ref.depth), atol=1e-4)
    np.testing.assert_allclose(np.asarray(o_pal.opacity), np.asarray(o_ref.opacity), atol=1e-5)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), s=st.integers(2, 64), dense=st.booleans())
def test_render_invariants(seed, s, dense):
    """Physical invariants of Eq. 1 for arbitrary density fields:
    weights >= 0, sum(weights) == opacity <= 1, transmittance monotone."""
    r = np.random.default_rng(seed)
    scale = 50.0 if dense else 1.0
    sigma = jnp.asarray(r.uniform(0, scale, size=(4, s)).astype(np.float32))
    rgb = jnp.asarray(r.uniform(0, 1, size=(4, s, 3)).astype(np.float32))
    ts = jnp.sort(jnp.asarray(r.uniform(0.1, 6, size=(4, s)).astype(np.float32)), axis=1)
    deltas = jnp.diff(ts, axis=1, append=ts[:, -1:] + 0.01)
    out = vr_ref.composite(sigma, rgb, deltas, ts)
    w = np.asarray(out.weights)
    assert (w >= -1e-6).all()
    np.testing.assert_allclose(w.sum(1), np.asarray(out.opacity), atol=1e-5)
    assert (np.asarray(out.opacity) <= 1 + 1e-5).all()
    # colors bounded by max rgb
    assert (np.asarray(out.color) <= 1 + 1e-5).all()
