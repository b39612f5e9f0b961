"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, so the kernels `auto` sends to Pallas
on a TPU, and the full-width training step, are lowered and compiled here for
a `v5e:2x2` topology: what Mosaic or XLA:TPU would refuse on the chip fails
this file instead.  Nothing runs, so nothing here says anything about results
or times.  The topology is described inside a fixture (never at import), and
all of these compiles stay in this one file, so only the worker given this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.core import Field, FieldConfig, Instant3DTrainer, TrainerConfig, occupancy
from repro.core import trainer as trainer_lib
from repro.core.rendering import RayBatch, RenderConfig
from repro.kernels.fused_mlp import ops as mlp_ops
from repro.kernels.grid_update import ops as gu_ops
from repro.kernels.volume_render import ops as vr_ops

V5E_HBM_BYTES = 16 * 2**30
N_RAYS, N_SAMPLES = 4096, 48          # 196,608 points: the paper's ~200k per step
LEVELS, LOG2_T, F = 16, 18, 2         # FieldConfig()'s density grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile can be written to the persistent cache
    # but never read back without a chip
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Route ops as `auto` does on a TPU; the compiled step cache is swapped
    for an empty one so no TPU-routed step leaks into later tests."""
    monkeypatch.setattr(kernels, "_on_tpu", lambda: True)
    monkeypatch.setattr(trainer_lib, "_COHORT_STEP_CACHE", {})


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("op", sorted(kernels.TPU_LOWERING))
def test_auto_routing_follows_the_table(op, on_tpu):
    lowers = kernels.TPU_LOWERING[op] is None
    assert kernels.resolve_backend("auto", op=op).name == ("pallas-tpu" if lowers else "ref")
    if lowers:
        assert kernels.resolve_backend("pallas-tpu", op=op) is kernels.PALLAS_TPU
    else:
        with pytest.raises(ValueError, match="no Pallas TPU lowering"):
            kernels.resolve_backend("pallas-tpu", op=op)


def _mlp_programs(s):
    n = N_RAYS * N_SAMPLES

    def loss2(x, w1, b1, w2, b2):
        return mlp_ops.mlp2(x, w1, b1, w2, b2, backend="pallas-tpu").sum()

    def loss3(x, w1, b1, w2, b2, w3, b3):
        return mlp_ops.mlp3(x, w1, b1, w2, b2, w3, b3, backend="pallas-tpu").sum()

    # density head: L*F = 32 features -> 64 -> 1+15; color head: 32 + 16 SH -> 64 -> 64 -> 3
    yield loss2, (s((n, 32)), s((32, 64)), s((64,)), s((64, 16)), s((16,))), range(5)
    yield loss3, (s((n, 48)), s((48, 64)), s((64,)), s((64, 64)), s((64,)),
                  s((64, 3)), s((3,))), range(7)


def _composite_programs(s):
    def loss(sigma, rgb, deltas, ts):
        out = vr_ops.composite(sigma, rgb, deltas, ts, backend="pallas-tpu")
        return out.color.sum() + out.depth.sum() + out.opacity.sum()

    r, k = N_RAYS, N_SAMPLES
    yield loss, (s((r, k)), s((r, k, 3)), s((r, k)), s((r, k))), range(4)


def _bum_scatter_programs(s):
    """The hash grid backward's merge of its corner stream (N x 8 corners x
    L levels rows) into the flat (L*T, F) gradient table: itself a
    backward, so nothing of it is differentiated."""
    m = N_RAYS * N_SAMPLES * 8 * LEVELS

    def commit(table, idx, vals):
        return gu_ops.merged_scatter_add(table, idx, vals, backend="auto")

    yield commit, (s((LEVELS * 2**LOG2_T, F)), s((m,), jnp.int32), s((m, F))), ()


# op -> (program, argument shapes, the arguments its backward differentiates)
PROGRAMS = {"mlp": _mlp_programs, "composite": _composite_programs,
            "bum_scatter": _bum_scatter_programs}


@pytest.mark.parametrize("op", sorted(op for op, why in kernels.TPU_LOWERING.items()
                                      if why is None))
def test_pallas_op_compiles_fwd_and_bwd(op, one_chip, on_tpu):
    s = lambda shape, dtype=jnp.float32: _shape(one_chip, shape, dtype)  # noqa: E731
    for loss, args, argnums in PROGRAMS[op](s):
        fwd = jax.jit(loss).lower(*args).compile()
        assert "tpu_custom_call" in fwd.as_text()
        if argnums:
            jax.jit(jax.grad(loss, argnums=tuple(argnums))).lower(*args).compile()


def test_full_width_train_step_fits_v5e(one_chip, on_tpu):
    """The dense train step at FieldConfig() widths — the largest program the
    trainer compiles — fits one v5e's HBM."""
    field_cfg = FieldConfig()
    cfg = TrainerConfig(n_rays=N_RAYS, render=RenderConfig(n_samples=N_SAMPLES),
                        occ=occupancy.OccupancyConfig(update_interval=16, warmup_steps=16))
    state = jax.eval_shape(Instant3DTrainer(Field(field_cfg), cfg).init,
                           jax.random.PRNGKey(0))
    member = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _shape(one_chip, (1,) + a.shape, a.dtype), tree)
    args = (member(state.params), member(state.opt_state),
            RayBatch(*[_shape(one_chip, (1, N_RAYS, 3))] * 3),
            _shape(one_chip, (N_RAYS, N_SAMPLES)),
            member(state.occ_state.density_ema))
    step = trainer_lib.cohort_step_fn(field_cfg, cfg, False, False, None, False, 1)
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    assert "tpu_custom_call" in compiled.as_text()
