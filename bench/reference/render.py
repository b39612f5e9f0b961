"""Plain float32 novel-view render through the occupancy-redistributed sampler.

A served view casts one ray through each pixel centre, probes S uniform
strata per ray (stratum midpoints), and re-spends a budget of S' samples
per ray on the strata the probe found live by inverting the per-ray live
CDF; each placed sample carries the live arc length it stands for as its
quadrature width.  Liveness is the scene box AND'ed with the occupancy
bitfield; before the first occupancy update the bitfield is all occupied,
which is the state served here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import field as F


def view_rays(pose, h: int, w: int, focal: float, precision: str = "highest"):
    """Pinhole rays through pixel centres, row-major: -> origins, dirs (H*W,3)."""
    py, px = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
    px, py = px.reshape(-1), py.reshape(-1)
    pose = jnp.asarray(pose, jnp.float32)
    x = (px.astype(jnp.float32) + 0.5 - w * 0.5) / focal
    y = -(py.astype(jnp.float32) + 0.5 - h * 0.5) / focal
    cam = jnp.stack([x, y, -jnp.ones_like(x)], axis=-1)
    d = F.mm(cam, pose[:3, :3].T, precision)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.broadcast_to(pose[:3, 3], d.shape), d


def redistribute(ts, live, n_out: int, near: float, far: float):
    """Inverse-CDF placement of n_out samples per ray over its live strata.
    ts (B,S) probe distances, live (B,S) bool -> (ts' (B,n_out), widths)."""
    s = ts.shape[1]
    h = (far - near) / s
    w = live.astype(jnp.float32)
    total = jnp.sum(w, axis=-1, keepdims=True)
    w = jnp.where(total > 0, w, 1.0)                  # a ray with nothing live: uniform
    pdf = w / jnp.sum(w, axis=-1, keepdims=True)
    cdf = jnp.cumsum(pdf, axis=-1)
    jitter = (ts[:, :n_out] - near) / (far - near) * s - jnp.arange(n_out)
    jitter = jnp.clip(jitter, 0.0, 1.0 - 1e-6)
    u = (jnp.arange(n_out) + jitter) / n_out
    u = u * cdf[:, -1:]
    j = jax.vmap(lambda c, q: jnp.searchsorted(c, q, side="right"))(cdf, u)
    j = jnp.clip(j, 0, s - 1)
    cdf_lo = jnp.where(j > 0, jnp.take_along_axis(cdf, jnp.maximum(j - 1, 0), axis=-1), 0.0)
    p = jnp.maximum(jnp.take_along_axis(pdf, j, axis=-1), 1e-12)
    frac = jnp.clip((u - cdf_lo) / p, 0.0, 1.0 - 1e-6)
    return near + (j.astype(jnp.float32) + frac) * h, h / (p * n_out)


@functools.partial(jax.jit, static_argnames=("cfg_key", "n_probe", "n_out", "precision"))
def _render(params, origins, dirs, *, cfg_key, n_probe, n_out, precision):
    cfg = _CFGS[cfg_key]
    scene = cfg["scene"]
    near, far = scene["near"], scene["far"]
    b = origins.shape[0]
    edges = jnp.linspace(near, far, n_probe + 1)
    mids = edges[:-1] + 0.5 * (edges[1:] - edges[:-1])
    ts = jnp.broadcast_to(mids[None, :], (b, n_probe))
    probe = origins[:, None, :] + ts[..., None] * dirs[:, None, :]
    ts, deltas = redistribute(ts, F.inside(probe, scene), n_out, near, far)
    points = (origins[:, None, :] + ts[..., None] * dirs[:, None, :]).reshape(-1, 3)
    flat_dirs = jnp.broadcast_to(dirs[:, None, :], (b, n_out, 3)).reshape(-1, 3)
    sigma, rgb = F.field(params, F.unit_coords(points, scene), flat_dirs, cfg["field"], precision)
    sigma = jnp.where(F.inside(points, scene), sigma, 0.0)
    color, depth, _ = F.composite(sigma.reshape(b, n_out), rgb.reshape(b, n_out, 3), deltas,
                                  ts, scene["white_background"])
    return color, depth


_CFGS: dict[str, dict] = {}


def render_view(cfg, params, pose, h: int, w: int, focal: float, n_probe: int, n_out: int,
                precision: str = "highest"):
    """-> (rgb (H,W,3), depth (H,W)) on the host."""
    _CFGS[cfg["name"]] = cfg
    with jax.default_matmul_precision("highest"):
        o, d = view_rays(pose, h, w, focal, precision)
        color, depth = _render(params, o, d, cfg_key=cfg["name"], n_probe=n_probe,
                               n_out=n_out, precision=precision)
    return (jax.device_get(color).reshape(h, w, 3), jax.device_get(depth).reshape(h, w))
