"""Staged RenderPipeline: compaction correctness, gradients, backend registry."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import kernels
from repro.core import Field, FieldConfig, occupancy
from repro.core.pipeline import RenderPipeline, suggest_budget, _cube_root
from repro.core.rendering import RenderConfig, sample_ts, render_rays

FIELD_CFG = FieldConfig(n_levels=4, max_resolution=64, log2_table_density=12,
                        log2_table_color=10)
RCFG = RenderConfig(n_samples=16)
OCFG = occupancy.OccupancyConfig(resolution=8)


def _rays(rng, b):
    origins = jnp.asarray(rng.uniform(-0.5, 0.5, (b, 3)).astype(np.float32))
    origins = origins.at[:, 2].set(4.0)  # look down at the box from above
    dirs = jnp.asarray(rng.normal(size=(b, 3)).astype(np.float32))
    dirs = dirs.at[:, 2].set(-jnp.abs(dirs[:, 2]) - 1.0)
    return origins, dirs / jnp.linalg.norm(dirs, axis=-1, keepdims=True)


def _setup(rng, b=32):
    field = Field(FIELD_CFG)
    params = field.init(jax.random.PRNGKey(0))
    origins, dirs = _rays(rng, b)
    ts = sample_ts(jax.random.PRNGKey(1), b, RCFG)
    return field, params, origins, dirs, ts


def _all_occupied():
    return jnp.ones((OCFG.resolution ** 3,), bool)


def _half_occupied():
    """Occupy only cells with z in the lower half of the unit cube."""
    r = OCFG.resolution
    centers = occupancy.cell_centers(OCFG)
    return (centers[:, 2] < 0.5).reshape(-1)


@pytest.mark.parametrize("bits_fn", [_all_occupied, _half_occupied])
def test_compacted_matches_dense(bits_fn, rng):
    """Compacted outputs == dense-masked outputs whenever budget >= n_live."""
    field, params, origins, dirs, ts = _setup(rng)
    pipe = RenderPipeline(field, RCFG)
    bits = bits_fn()
    n = ts.size

    dense = pipe(params, origins, dirs, ts, bitfield=bits)
    compacted = pipe(params, origins, dirs, ts, bitfield=bits, budget=n)
    assert int(compacted["overflow"]) == 0
    for k in ("rgb", "depth", "opacity"):
        np.testing.assert_allclose(
            np.asarray(compacted[k]), np.asarray(dense[k]), atol=1e-5,
            err_msg=f"{k} mismatch (bits={bits_fn.__name__})",
        )
    np.testing.assert_allclose(
        float(compacted["live_fraction"]), float(dense["live_fraction"]), atol=1e-6
    )


def test_compacted_matches_dense_tight_budget(rng):
    """With culled cells, a budget between n_live and n must still be exact."""
    field, params, origins, dirs, ts = _setup(rng)
    pipe = RenderPipeline(field, RCFG)
    bits = _half_occupied()
    n = ts.size

    dense = pipe(params, origins, dirs, ts, bitfield=bits)
    n_live = int(dense["n_live"])
    assert 0 < n_live < n, "test scene should cull some but not all samples"
    budget = 1 << (n_live - 1).bit_length()  # next pow2 >= n_live, < n
    assert budget < n

    compacted = pipe(params, origins, dirs, ts, bitfield=bits, budget=budget)
    assert int(compacted["overflow"]) == 0
    assert int(compacted["points_queried"]) == budget
    for k in ("rgb", "depth", "opacity"):
        np.testing.assert_allclose(
            np.asarray(compacted[k]), np.asarray(dense[k]), atol=1e-5,
            err_msg=f"{k} mismatch at budget {budget} (n_live {n_live})",
        )


def test_compaction_gradients_match_dense(rng):
    """Gather/scatter must be differentiable and gradient-equivalent."""
    field, params, origins, dirs, ts = _setup(rng)
    pipe = RenderPipeline(field, RCFG)
    bits = _half_occupied()
    n = ts.size
    target = jnp.asarray(rng.uniform(0, 1, (origins.shape[0], 3)).astype(np.float32))

    def loss(p, budget):
        out = pipe(p, origins, dirs, ts, bitfield=bits, budget=budget)
        return jnp.mean((out["rgb"] - target) ** 2)

    g_dense = jax.grad(loss)(params, None)
    g_comp = jax.grad(loss)(params, n)
    leaves_d = jax.tree_util.tree_leaves_with_path(g_dense)
    leaves_c = jax.tree_util.tree_leaves(g_comp)
    max_abs = max(float(jnp.abs(x).max()) for _, x in leaves_d)
    assert max_abs > 0, "degenerate test: zero gradient"
    for (path, d), c in zip(leaves_d, leaves_c):
        np.testing.assert_allclose(np.asarray(c), np.asarray(d), atol=1e-5,
                                   err_msg=f"grad mismatch at {path}")


def test_overflow_accounting(rng):
    """A budget below n_live must report the dropped live points."""
    field, params, origins, dirs, ts = _setup(rng)
    pipe = RenderPipeline(field, RCFG)
    out_dense = pipe(params, origins, dirs, ts, bitfield=_all_occupied())
    n_live = int(out_dense["n_live"])
    budget = max(1, n_live // 2)
    out = pipe(params, origins, dirs, ts, bitfield=_all_occupied(), budget=budget)
    assert int(out["overflow"]) == n_live - budget
    assert int(out["points_queried"]) == budget


def test_render_rays_wrapper_matches_pipeline(rng):
    """The legacy render_rays signature is a thin wrapper over the dense path."""
    field, params, origins, dirs, ts = _setup(rng)
    pipe = RenderPipeline(field, RCFG)
    bits = _half_occupied()
    mask_fn = lambda unit: occupancy.point_liveness(bits, unit, OCFG.resolution)
    legacy = render_rays(field, params, origins, dirs, ts, RCFG, mask_fn)
    staged = pipe(params, origins, dirs, ts, bitfield=bits)
    np.testing.assert_allclose(np.asarray(legacy["rgb"]), np.asarray(staged["rgb"]),
                               atol=1e-6)


def test_suggest_budget_buckets():
    n = 4096
    assert suggest_budget(1.0, n) == n
    assert suggest_budget(0.0, n) == 512
    b = suggest_budget(0.2, n)
    assert b >= int(0.2 * 1.3 * n) and b & (b - 1) == 0  # pow2, has headroom
    # bucketing: nearby fractions share a bucket (bounded recompiles)
    assert suggest_budget(0.15, n) == suggest_budget(0.18, n)


def test_cube_root():
    assert _cube_root(8 ** 3) == 8
    assert _cube_root(32 ** 3) == 32
    with pytest.raises(ValueError):
        _cube_root(100)


def test_backend_registry():
    assert "ref" in kernels.available_backends()
    ref = kernels.resolve_backend("ref", op="mlp")
    assert not ref.use_pallas
    pal = kernels.resolve_backend("pallas", op="mlp")  # alias: best flavor for platform
    assert pal.use_pallas
    with pytest.raises(ValueError):
        kernels.resolve_backend("cuda", op="mlp")
    with pytest.raises(ValueError):
        kernels.resolve_backend("ref", op="no_such_op")
    # the one user-facing knob: process default; explicit names still override
    prev = kernels.get_backend()
    try:
        assert kernels.set_backend("ref") == "ref"
        assert kernels.resolve_backend(None, op="mlp") == ref
        assert set(kernels.routing().values()) == {"ref"}
    finally:
        kernels.set_backend(prev)


def test_configs_have_no_backend_knob():
    """The registry is the single user-facing backend knob (ISSUE 1)."""
    from repro.core.encoding import HashGridConfig
    for cfg_cls in (FieldConfig, HashGridConfig, RenderConfig):
        assert "backend" not in cfg_cls.__dataclass_fields__, cfg_cls
