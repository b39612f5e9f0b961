"""Operations and bytes of the field's work, computed from shapes.

Model FLOPs count the arithmetic the algorithm needs, not what a kernel
happens to issue: the MLP matrix products (forward, and backward for the
weights and for every input that carries a gradient) and the hash-grid
interpolation (trilinear weights and the blend of 8 corner rows forward;
one multiply-add per corner feature into the table gradient backward).
Sampling, SH, activations and compositing are left out: they are a few
dozen operations a point against tens of thousands.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def mlp_layers(field_cfg: dict) -> dict:
    """(d_in, d_out) of each layer of the density (2-layer) and colour
    (3-layer) heads."""
    c = field_cfg
    enc = c["n_levels"] * c["n_features"]
    h, geo, sh = c["hidden"], c["geo_features"], c["sh_degree"] ** 2
    cin = (enc if c["decomposed"] else geo) + sh
    return {"density": [(enc, h), (h, 1 + geo)], "color": [(cin, h), (h, h), (h, 3)]}


def _grids(field_cfg: dict) -> int:
    return 2 if field_cfg["decomposed"] else 1


def interp_flops(field_cfg: dict, backward_grids: int = 0) -> float:
    """Per point: trilinear weights (8 corners x 2 multiplies) and the blend
    (8 corners x F multiply-adds) per level and grid; backward, 8 x F
    multiply-adds into the table gradient per level of each trained grid."""
    l, f = field_cfg["n_levels"], field_cfg["n_features"]
    fwd = l * (8 * 2 + 8 * f * 2) * _grids(field_cfg)
    return float(fwd + l * 8 * f * 2 * backward_grids)


def mlp_flops(field_cfg: dict, backward: bool = False, color_input_grad: bool = True) -> float:
    """Per point: 2 d_in d_out per layer forward; backward adds the weight
    gradient of every layer and the input gradient of every layer whose
    input carries one (the first colour layer's only for its grid or
    geometry columns, and only while that input is trained)."""
    layers = mlp_layers(field_cfg)
    fwd = sum(2 * i * o for head in layers.values() for i, o in head)
    if not backward:
        return float(fwd)
    c = field_cfg
    enc = c["n_levels"] * c["n_features"]
    dw = fwd
    dx = sum(2 * i * o for i, o in layers["density"])
    dx += sum(2 * i * o for i, o in layers["color"][1:])
    first_in, first_out = layers["color"][0]
    grad_cols = (enc if c["decomposed"] else c["geo_features"]) if color_input_grad else 0
    dx += 2 * grad_cols * first_out
    return float(fwd + dw + dx)


def train_step_flops(field_cfg: dict, points: int, freeze_color: bool) -> float:
    """Model FLOPs of one training step over `points` candidate points."""
    decomposed = field_cfg["decomposed"]
    trained_grids = 1 + (decomposed and not freeze_color)
    color_in = not (decomposed and freeze_color)
    per_point = (mlp_flops(field_cfg, backward=True, color_input_grad=color_in)
                 + interp_flops(field_cfg, backward_grids=trained_grids))
    return per_point * points


def render_flops(field_cfg: dict, points: int) -> float:
    """Model FLOPs of shading `points` points forward."""
    return (mlp_flops(field_cfg) + interp_flops(field_cfg)) * points


def mlp_kernel_cost(head: list, rows: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one fused forward call of an MLP head over
    `rows` float32 rows: the products, and the rows read and written once
    plus the weights and biases read once."""
    flops = 2.0 * rows * sum(i * o for i, o in head)
    weights = sum(i * o + o for i, o in head)
    return flops, 4.0 * (rows * (head[0][0] + head[-1][1]) + weights)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
