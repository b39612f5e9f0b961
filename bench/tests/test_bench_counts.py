"""FLOP and byte counts against hand arithmetic, and the peaks table."""
import json
from pathlib import Path

import pytest

from bench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def field(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["field"]


def test_mlp_layers_follow_the_published_heads():
    assert counts.mlp_layers(field("instant3d")) == {
        "density": [(32, 64), (64, 16)], "color": [(48, 64), (64, 64), (64, 3)]}
    assert counts.mlp_layers(field("ngp"))["color"][0] == (31, 64)   # 15 geometry + 16 SH


def test_instant3d_step_flops_by_hand():
    # forward products: 2(32*64 + 64*16) + 2(48*64 + 64*64 + 64*3) = 6144 + 14720
    fwd = 20864
    # backward: weight gradients (= forward) + input gradients of both density
    # layers, colour layers 2-3, and the 32 grid columns of colour layer 1
    bwd = fwd + 6144 + 2 * (64 * 64 + 64 * 3) + 2 * 32 * 64
    # interpolation: 16 levels x (16 weight + 32 blend) x 2 grids forward,
    # 16 levels x 32 into each trained table backward
    interp_both = 16 * 48 * 2 + 16 * 32 * 2
    interp_frozen = 16 * 48 * 2 + 16 * 32
    f = field("instant3d")
    assert counts.train_step_flops(f, 100, False) == 100 * (fwd + bwd + interp_both)
    assert counts.train_step_flops(f, 100, True) == 100 * (fwd + bwd - 2 * 32 * 64
                                                           + interp_frozen)


def test_ngp_and_render_flops_by_hand():
    f = field("ngp")
    fwd = 2 * (32 * 64 + 64 * 16) + 2 * (31 * 64 + 64 * 64 + 64 * 3)
    bwd = fwd + 2 * (32 * 64 + 64 * 16) + 2 * (64 * 64 + 64 * 3) + 2 * 15 * 64
    assert counts.train_step_flops(f, 10, False) == 10 * (fwd + bwd + 16 * 48 + 16 * 32)
    assert counts.render_flops(field("instant3d"), 10) == 10 * (20864 + 16 * 48 * 2)


def test_mlp_kernel_cost_and_roofline():
    flops, nbytes = counts.mlp_kernel_cost([(32, 64), (64, 16)], 512)
    assert flops == 2 * 512 * (32 * 64 + 64 * 16)
    assert nbytes == 4 * (512 * (32 + 16) + 32 * 64 + 64 + 64 * 16 + 16)
    peak = counts.peaks("TPU v5 lite")
    assert counts.roofline_seconds(flops, nbytes, peak) == nbytes / 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("cpu")
