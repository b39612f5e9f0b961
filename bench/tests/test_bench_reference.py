"""The plain reference against the program's `ref` backend, at a small size on the CPU."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights
from bench.reference import field as F
from bench.reference import render as R

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def small(name: str) -> dict:
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg["field"].update(n_levels=4, log2_table_density=10, log2_table_color=8,
                        base_resolution=4, max_resolution=64)
    return cfg


def program_field(cfg):
    from repro.core import Field, FieldConfig
    return Field(FieldConfig(**cfg["field"]))


@pytest.mark.parametrize("name", ["instant3d", "ngp"])
def test_field_matches_program_values_and_gradients(name):
    cfg = small(name)
    params = weights.make(cfg["field"], 7)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    unit = jax.random.uniform(k1, (512, 3), maxval=1.0 - 1e-6)
    d = jax.random.normal(k2, (512, 3))
    dirs = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    prog = program_field(cfg)

    def loss(fn, p):
        sigma, rgb = fn(p)
        return jnp.sum(jnp.sin(sigma)) + jnp.sum(rgb * jnp.arange(3.0))

    def both(fn):
        return jax.jit(lambda p: (fn(p), jax.grad(lambda q: loss(fn, q))(p)))(params)

    with jax.default_matmul_precision("highest"):
        got, g_got = both(lambda q: prog.query(q, unit, dirs))
        want, g_want = both(lambda q: F.field(q, unit, dirs, cfg["field"]))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-6, atol=1e-7)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree_util.tree_leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-30
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * scale, path


def test_encode_dense_and_hashed_levels_match_program():
    from repro.kernels.hash_encode import ref as prog_ref
    res = F.level_resolutions(6, 2, 40)
    tables = jax.random.normal(jax.random.PRNGKey(0), (6, 256, 2))
    unit = jax.random.uniform(jax.random.PRNGKey(1), (300, 3), maxval=1.0 - 1e-6)
    dense = (res + 1) ** 3 <= 256
    assert dense.any() and not dense.all()       # both kinds of level are covered
    np.testing.assert_allclose(np.asarray(F.encode(unit, tables, res)),
                               np.asarray(prog_ref.hash_encode(unit, tables, res)),
                               rtol=1e-6, atol=1e-6)


def test_matmul_precisions_order():
    a = jax.random.normal(jax.random.PRNGKey(0), (64, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = {p: float(np.max(np.abs(np.asarray(F.mm(a, b, p)) - exact)))
           for p in ("highest", "high")}
    one_pass = jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    assert err["highest"] < err["high"] < float(np.max(np.abs(np.asarray(one_pass) - exact)))
    g = jax.grad(lambda x: jnp.sum(F.mm(x, b, "high") ** 2))(a)
    want = 2 * (exact @ np.asarray(b, np.float64).T)
    assert np.max(np.abs(np.asarray(g) - want)) <= 1e-4 * np.max(np.abs(want))


def test_trunc_exp_gradient_is_not_cut_at_the_clip():
    g = jax.grad(lambda x: F.trunc_exp(x))(20.0)
    assert float(g) == pytest.approx(float(np.exp(np.float32(11.0))), rel=1e-6)


def test_redistribute_matches_program_stage():
    from repro.core.pipeline import RenderPipeline
    from repro.core.rendering import RenderConfig
    cfg = small("instant3d")
    pipe = RenderPipeline(program_field(cfg), RenderConfig(n_samples=16))
    ts = jnp.broadcast_to(jnp.linspace(2.0, 6.0, 17)[:-1] + 0.125, (64, 16))
    live = jax.random.bernoulli(jax.random.PRNGKey(2), 0.4, (64, 16))
    got = pipe.redistribute(ts, live, n_out=4)
    want = R.redistribute(ts, live, 4, 2.0, 6.0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
