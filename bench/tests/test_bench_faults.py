"""A whole run of a cell at a small size on the CPU, with the timed path
broken underneath: `correct` has to come out false for every fault the
cell can have, and true for the program as it is."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import bench_tiny

TRAIN_CELLS = ["instant3d-train-dense", "ngp-train-dense"]


@pytest.fixture(autouse=True)
def fresh_program(monkeypatch):
    """No persistent compile cache, and no compiled step or render program
    shared between runs."""
    from repro.core import trainer as trainer_lib
    for name in dir(trainer_lib):
        if name.startswith("_") and name.endswith("_CACHE"):
            monkeypatch.setattr(trainer_lib, name, {})
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name", TRAIN_CELLS + ["instant3d-render"])
def test_sound_program_is_correct(name):
    line = bench_tiny.run(name)
    assert line["correct"], line["check"]
    assert line["failed"] == 0 and line["attempted"] >= 1


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_step_that_returns_its_state_unchanged_is_caught(name, monkeypatch):
    from repro.core import trainer as trainer_lib
    real = trainer_lib.cohort_step_fn

    def broken(*args, **kwargs):
        fn = real(*args, **kwargs)

        def step(params, opt_state, batch, ts, ema):
            copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
            _, _, loss, aux = fn(copy(params), copy(opt_state), batch, ts, ema)
            return params, opt_state, loss, aux
        return step

    monkeypatch.setattr(trainer_lib, "cohort_step_fn", broken)
    line = bench_tiny.run(name)
    assert not line["correct"]
    assert line["check"]["change_gap"]["value"] > line["check"]["change_gap"]["limit"]


@pytest.mark.parametrize("name", TRAIN_CELLS)
def test_loss_over_half_of_the_batch_is_caught(name, monkeypatch):
    from repro.core import losses

    def half_mse(pred, gt):
        n = pred.shape[0] // 2
        return jnp.mean(jnp.square(pred[:n] - gt[:n]))

    monkeypatch.setattr(losses, "mse", half_mse)
    line = bench_tiny.run(name)
    assert not line["correct"]


def test_altered_pixel_is_caught(monkeypatch):
    from repro.serve3d import render as render_lib
    real = render_lib.RenderService._render_group_inner

    def altered(self, *args, **kwargs):
        out = []
        for r in real(self, *args, **kwargs):
            rgb = np.array(r.rgb)
            rgb[3, 5, 1] += 1.0 / 255.0
            out.append(r._replace(rgb=rgb))
        return out

    monkeypatch.setattr(render_lib.RenderService, "_render_group_inner", altered)
    line = bench_tiny.run("instant3d-render")
    assert not line["correct"]
    assert line["check"]["rgb_gap"]["value"] == pytest.approx(1.0 / 255.0, rel=1e-3)


@pytest.mark.parametrize("fault", ["zeroed", "shifted_level"])
def test_broken_encode_in_the_render_path_is_caught(fault, monkeypatch):
    """The served snapshot's tables carry signal, so an encode that returns
    nothing, or hands each level the next level's features, moves pixels."""
    from repro.core import encoding
    real = encoding.HashEncoding.__call__

    def broken(self, points, tables):
        out = real(self, points, tables)
        if fault == "zeroed":
            return jnp.zeros_like(out)
        return jnp.roll(out, -self.cfg.n_features, axis=-1)

    monkeypatch.setattr(encoding.HashEncoding, "__call__", broken)
    line = bench_tiny.run("instant3d-render")
    assert not line["correct"]
    assert line["check"]["rgb_gap"]["value"] > line["check"]["rgb_gap"]["limit"]
