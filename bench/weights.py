"""Seed weights of a configuration, made on the device in one jitted call.

Instant-NGP's initialisation: hash tables uniform in [-s, s] with s = 1e-4,
MLP weights He-uniform, biases zero.  A served snapshot stands for a field
that has trained, whose table entries are of order one: a traffic mix may
set s for it, so that the encode moves every pixel.  The pytree has the
layout the program's `Field` takes; the reference is handed the same arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shapes(field_cfg: dict) -> dict:
    """{top: shape} for grids, {top: {leaf: shape}} for the MLPs."""
    c = field_cfg
    enc = c["n_levels"] * c["n_features"]
    h, geo = c["hidden"], c["geo_features"]
    out = {"density_grid": (c["n_levels"], 1 << c["log2_table_density"], c["n_features"]),
           "density_mlp": {"w1": (enc, h), "b1": (h,), "w2": (h, 1 + geo), "b2": (1 + geo,)}}
    if c["decomposed"]:
        out["color_grid"] = (c["n_levels"], 1 << c["log2_table_color"], c["n_features"])
    cin = (enc if c["decomposed"] else geo) + c["sh_degree"] ** 2
    out["color_mlp"] = {"w1": (cin, h), "b1": (h,), "w2": (h, h), "b2": (h,), "w3": (h, 3),
                        "b3": (3,)}
    return out


INIT_TABLE_SCALE = 1e-4


def _leaf(key, name: str, shape, table_scale: float):
    if name.endswith("_grid"):
        return jax.random.uniform(key, shape, jnp.float32, -table_scale, table_scale)
    if name.startswith("w"):
        bound = (6.0 / shape[0]) ** 0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return jnp.zeros(shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg_items", "table_scale"))
def _make(key, cfg_items, table_scale):
    params = {}
    for i, (top, spec) in enumerate(sorted(shapes(dict(cfg_items)).items())):
        k = jax.random.fold_in(key, i)
        if isinstance(spec, dict):
            params[top] = {name: _leaf(jax.random.fold_in(k, j), name, shape, table_scale)
                           for j, (name, shape) in enumerate(sorted(spec.items()))}
        else:
            params[top] = _leaf(k, top, spec, table_scale)
    return params


def make(field_cfg: dict, key_seed: int, table_scale: float = INIT_TABLE_SCALE) -> dict:
    """The seed's parameter pytree, float32, on the default device."""
    return _make(jax.random.PRNGKey(key_seed), tuple(sorted(field_cfg.items())),
                 float(table_scale))
