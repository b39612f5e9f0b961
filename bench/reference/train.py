"""Plain float32 training step: dense ray march, MSE loss, Adam.

One step renders every sample of a ray batch (B rays x S stratified
samples; samples outside the scene box carry no density), composites over
a white background, takes the mean squared error against the pixels, and
applies Adam with Instant-NGP's per-parameter learning rates (grids at lr,
MLPs at 0.1 lr).  Instant-3D's update frequencies: on a step where the
colour branch is frozen its grid gets no gradient and Adam leaves its
parameters and moments alone.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import field as F


def sample_ts(key, n_rays: int, n_samples: int, scene):
    """Stratified distances (B,S): one uniform draw per stratum of
    [near, far], as the trainer's step-keyed sample stream draws them."""
    edges = jnp.linspace(scene["near"], scene["far"], n_samples + 1)
    lo, hi = edges[:-1], edges[1:]
    u = jax.random.uniform(key, (n_rays, n_samples))
    return lo[None, :] + u * (hi - lo)[None, :]


def render_rays(params, origins, dirs, ts, cfg, precision, freeze_color=False):
    scene = cfg["scene"]
    b, s = ts.shape
    points = origins[:, None, :] + ts[..., None] * dirs[:, None, :]
    flat = points.reshape(-1, 3)
    flat_dirs = jnp.broadcast_to(dirs[:, None, :], points.shape).reshape(-1, 3)
    sigma, rgb = F.field(params, F.unit_coords(flat, scene), flat_dirs, cfg["field"],
                         precision, freeze_color)
    sigma = jnp.where(F.inside(flat, scene), sigma, 0.0)
    span = scene["far"] - scene["near"]
    deltas = jnp.diff(ts, axis=-1, append=ts[:, -1:] + span / s)
    color, _, _ = F.composite(sigma.reshape(b, s), rgb.reshape(b, s, 3), deltas, ts,
                              scene["white_background"])
    return color


def _lr_scale(path: str, opt) -> float:
    return opt["lr_scale_grid"] if path.endswith("_grid") else opt["lr_scale_mlp"]


@functools.partial(jax.jit, static_argnames=("cfg_key", "freeze_color", "precision",
                                             "half_batch"))
def _step(params, m, v, count, origins, dirs, rgb_gt, ts, *, cfg_key, freeze_color,
          precision, half_batch=False):
    cfg = _CFGS[cfg_key]
    opt = cfg["optimizer"]

    def loss_fn(p):
        color = render_rays(p, origins, dirs, ts, cfg, precision, freeze_color)
        err = jnp.square(color - rgb_gt)
        if half_batch:      # a planted fault: the mean over half of the rays
            err = err[: err.shape[0] // 2]
        return jnp.mean(err)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    t = (count + 1).astype(jnp.float32)
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]
    bias1, bias2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new_p, new_m, new_v = {}, {}, {}
    for top in params:
        frozen = freeze_color and top == "color_grid"
        scale = _lr_scale(top, opt)

        def leaf(p, g, m0, v0):
            if frozen:
                return p, m0, v0
            m1 = b1 * m0 + (1.0 - b1) * g
            v1 = b2 * v0 + (1.0 - b2) * jnp.square(g)
            upd = lr * scale * (m1 / bias1) / (jnp.sqrt(v1 / bias2) + eps)
            return p - upd, m1, v1

        out = jax.tree.map(leaf, params[top], grads[top], m[top], v[top])
        is_triple = lambda x: isinstance(x, tuple)  # noqa: E731
        new_p[top] = jax.tree.map(lambda o: o[0], out, is_leaf=is_triple)
        new_m[top] = jax.tree.map(lambda o: o[1], out, is_leaf=is_triple)
        new_v[top] = jax.tree.map(lambda o: o[2], out, is_leaf=is_triple)
    return new_p, new_m, new_v, loss, grads


# configs are plain dicts (unhashable): the jitted step finds its config
# by a key, so each config compiles once per variant
_CFGS: dict[str, dict] = {}


def freeze_color_at(step: int, cfg) -> bool:
    """Instant-3D's update frequency: the colour branch updates at step i
    iff floor((i+1) F_C) > floor(i F_C); the density branch always does."""
    import math
    f = cfg["optimizer"]["f_color"]
    if not cfg["field"]["decomposed"] or f >= 1.0:
        return False
    return not math.floor((step + 1) * f) > math.floor(step * f)


def run(cfg, params, batches, precision: str = "highest", half_batch: bool = False):
    """Train from `params` over `batches` [(origins, dirs, rgb_gt, ts), ...],
    one Adam step each.  Returns (losses, the first step's gradients, each
    step's gradient norms by leaf, the parameters after the last step), all
    on the host."""
    key = cfg["name"]
    _CFGS[key] = cfg
    zeros = jax.tree.map(jnp.zeros_like, params)
    m, v = zeros, zeros
    losses, first_grads, norms = [], None, []
    with jax.default_matmul_precision("highest"):
        for i, (o, d, gt, ts) in enumerate(batches):
            params, m, v, loss, grads = _step(
                params, m, v, jnp.int32(i), o, d, gt, ts, cfg_key=key,
                freeze_color=freeze_color_at(i, cfg), precision=precision,
                half_batch=half_batch)
            losses.append(float(loss))
            norms.append(jax.device_get(jax.tree.map(jnp.linalg.norm, grads)))
            if first_grads is None:
                first_grads = jax.device_get(grads)
    return losses, first_grads, norms, jax.device_get(params)
