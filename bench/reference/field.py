"""Plain float32 radiance field: hash-grid encode, SH, MLP heads, compositing.

Written from the published descriptions (Instant-NGP, arXiv:2201.05989 §3
and §5.4; Instant-3D, arXiv:2304.12467 §3) in straightforward `jax.numpy`,
with nothing imported from the program under test.  Every matrix product
goes through `mm`, whose precision is an argument: "highest" is the
reference, "high" (three bfloat16 passes) the control that the comparison
has to fail.  The control rounds the operands' parts to bfloat16 explicitly
and multiplies them exactly, so that it rounds the same way on a CPU and on
a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PI2 = np.uint32(2654435761)
PI3 = np.uint32(805459861)
# corner c = z<<2 | y<<1 | x, as Instant-NGP orders the 8 cube corners
CORNERS = np.array([[x, y, z] for z in (0, 1) for y in (0, 1) for x in (0, 1)], np.int32)


# ---- matrix products at a stated precision ----

def _bf16(a):
    """a rounded to bfloat16, kept in float32 (no compiler may skip this
    rounding, as it may skip a round trip through a bfloat16 array)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _exact(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _mm_raw(a, b, precision: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "highest":
        return _exact(a, b)
    if precision == "high":                 # three bfloat16 passes
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return _exact(ah, bh) + (_exact(ah, bl) + _exact(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def mm(a, b, precision: str):
    """a @ b with forward and backward products at `precision`."""
    return _mm_raw(a, b, precision)


def _mm_fwd(a, b, precision):
    return _mm_raw(a, b, precision), (a, b)


def _mm_bwd(precision, res, g):
    a, b = res
    return _mm_raw(g, b.T, precision), _mm_raw(a.T, g, precision)


mm.defvjp(_mm_fwd, _mm_bwd)


# ---- hash-grid encoding (Instant-NGP Eq. 3, trilinear interpolation) ----

def level_resolutions(n_levels: int, n_min: int, n_max: int) -> np.ndarray:
    """N_l = floor(N_min * b^l), b = exp((ln N_max - ln N_min) / (L - 1))."""
    if n_levels == 1:
        return np.array([n_min], np.int64)
    b = np.exp((np.log(n_max) - np.log(n_min)) / (n_levels - 1))
    return np.floor(n_min * b ** np.arange(n_levels) + 1e-6).astype(np.int64)


def encode(unit, tables, resolutions):
    """unit (N,3) in [0,1), tables (L,T,F) -> (N, L*F).

    A level whose (N_l+1)^3 vertices fit in the table is indexed densely,
    any other through the spatial hash; each point's feature is the
    trilinear blend of its 8 corner rows, gathered directly."""
    n_levels, t, f = tables.shape
    res = np.asarray(resolutions, np.int64)
    dense = (res + 1) ** 3 <= t
    stride = np.where(dense, res + 1, 0).astype(np.int32)
    scaled = unit[None, :, :] * jnp.asarray(res, jnp.float32)[:, None, None]   # (L,N,3)
    base = jnp.floor(scaled)
    frac = scaled - base
    corner = base.astype(jnp.int32)[:, :, None, :] + CORNERS[None, None]       # (L,N,8,3)
    ix, iy, iz = corner[..., 0], corner[..., 1], corner[..., 2]
    s = jnp.asarray(stride)[:, None, None]
    dense_idx = ix + iy * s + iz * s * s
    hashed = (ix.astype(jnp.uint32) ^ (iy.astype(jnp.uint32) * PI2)
              ^ (iz.astype(jnp.uint32) * PI3)) & np.uint32(t - 1)
    idx = jnp.where(jnp.asarray(dense)[:, None, None], dense_idx, hashed.astype(jnp.int32))
    off = CORNERS[None, None]                                                   # (1,1,8,3)
    fr = frac[:, :, None, :]
    w = jnp.where(off > 0, fr, 1.0 - fr)
    weight = w[..., 0] * w[..., 1] * w[..., 2]                                  # (L,N,8)
    n = unit.shape[0]
    rows = jnp.take_along_axis(tables, idx.reshape(n_levels, n * 8, 1), axis=1)
    feats = jnp.sum(weight[..., None] * rows.reshape(n_levels, n, 8, f), axis=2)  # (L,N,F)
    return jnp.transpose(feats, (1, 0, 2)).reshape(n, n_levels * f)


# ---- direction encoding: real spherical harmonics, degree 4 ----

def sh4(d):
    x, y, z = d[..., 0], d[..., 1], d[..., 2]
    xx, yy, zz = x * x, y * y, z * z
    xy, yz, xz = x * y, y * z, x * z
    return jnp.stack([
        jnp.full_like(x, 0.28209479177387814),
        -0.48860251190291987 * y, 0.48860251190291987 * z, -0.48860251190291987 * x,
        1.0925484305920792 * xy, -1.0925484305920792 * yz,
        0.94617469575755997 * zz - 0.31539156525251999,
        -1.0925484305920792 * xz, 0.54627421529603959 * (xx - yy),
        0.59004358992664352 * y * (-3.0 * xx + yy), 2.8906114426405538 * xy * z,
        0.45704579946446572 * y * (1.0 - 5.0 * zz), 0.3731763325901154 * z * (5.0 * zz - 3.0),
        0.45704579946446572 * x * (1.0 - 5.0 * zz), 1.4453057213202769 * z * (xx - yy),
        0.59004358992664352 * x * (-xx + 3.0 * yy),
    ], axis=-1)


# ---- activations ----

@jax.custom_vjp
def trunc_exp(x):
    """Instant-NGP's truncated exponential: exp of the clipped logit, whose
    gradient is exp of the clipped logit everywhere (never cut to zero)."""
    return jnp.exp(jnp.clip(x, -15.0, 11.0))


trunc_exp.defvjp(lambda x: (trunc_exp(x), x),
                 lambda x, g: (g * jnp.exp(jnp.clip(x, -15.0, 11.0)),))


def _linear(x, w, b, precision):
    return mm(x, w, precision) + b


def field(params, unit, dirs, field_cfg, precision="highest", freeze_color=False):
    """(sigma (N,), rgb (N,3)) at unit coords `unit` seen along `dirs`.

    Instant-3D (decomposed): density grid -> 2-layer MLP -> sigma; color
    grid ++ SH(dir) -> 3-layer MLP -> rgb.  Instant-NGP: one grid -> density
    MLP -> (sigma, 15 geometry features); geometry ++ SH(dir) -> color MLP."""
    res = level_resolutions(field_cfg["n_levels"], field_cfg["base_resolution"],
                            field_cfg["max_resolution"])
    hd = encode(unit, params["density_grid"], res)
    m = params["density_mlp"]
    out = _linear(jnp.maximum(_linear(hd, m["w1"], m["b1"], precision), 0.0),
                  m["w2"], m["b2"], precision)
    sigma = trunc_exp(out[:, 0])
    if field_cfg["decomposed"]:
        table = params["color_grid"]
        if freeze_color:
            table = jax.lax.stop_gradient(table)
        first = encode(unit, table, res)
    else:
        first = out[:, 1:]
    c = params["color_mlp"]
    x = jnp.concatenate([first, sh4(dirs)], axis=-1)
    h = jnp.maximum(_linear(x, c["w1"], c["b1"], precision), 0.0)
    h = jnp.maximum(_linear(h, c["w2"], c["b2"], precision), 0.0)
    rgb = jax.nn.sigmoid(_linear(h, c["w3"], c["b3"], precision))
    return sigma, rgb


# ---- rays, samples, volume rendering ----

def unit_coords(points, scene):
    lo, hi = scene["aabb_min"], scene["aabb_max"]
    unit = (points - lo) / (hi - lo)
    return jnp.clip(unit, 0.0, 1.0 - 1e-6)


def inside(points, scene):
    lo, hi = scene["aabb_min"], scene["aabb_max"]
    return jnp.all((points >= lo) & (points <= hi), axis=-1)


def composite(sigma, rgb, deltas, ts, white_background: bool):
    """Quadrature of NeRF's volume rendering integral (paper Eq. 1):
    -> (color (R,3), depth (R,), opacity (R,))."""
    tau = sigma * deltas
    trans = jnp.exp(-(jnp.cumsum(tau, axis=-1) - tau))
    weights = trans * (1.0 - jnp.exp(-tau))
    color = jnp.sum(weights[..., None] * rgb, axis=-2)
    opacity = jnp.sum(weights, axis=-1)
    if white_background:
        color = color + (1.0 - opacity[..., None])
    return color, jnp.sum(weights * ts, axis=-1), opacity
