"""The one traffic generator: everything a run feeds the program, from a seed.

A traffic mix is a JSON file under `bench/traffic/`; this module reads its
parameters and makes, from `--seed`:

* a procedural scene (a few shaded spheres) and posed views of it on an
  orbit, rendered here analytically as the training pixels, which the
  program's own ray sampler draws its batches from;
* the camera poses a render client asks for, in an order drawn from the seed.

Every seed gets the same sizes; only values and order change.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent numpy streams per purpose, from any non-negative seed."""
    return np.random.default_rng([int(seed) % 2**64, sum(stream.encode())])


def key_seed(seed: int) -> int:
    """A 31-bit seed for jax.random.PRNGKey, derived from any seed."""
    return int(np.random.SeedSequence(int(seed) % 2**64).generate_state(1, np.uint32)[0] >> 1)


def focal(t: dict, hw: int) -> float:
    return 0.5 * hw / np.tan(np.deg2rad(t["fov_deg"]) / 2)


def orbit_poses(t: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """(n,3,4) camera-to-world poses on a sphere around the origin, looking
    at it (OpenGL axes), azimuths spread evenly, elevations jittered."""
    poses = []
    for i in range(n):
        az = 2 * np.pi * i / n + rng.uniform(0.0, 0.1)
        el = np.deg2rad(t["elevation_deg"] + rng.uniform(-1.0, 1.0) * t["elevation_jitter_deg"])
        eye = t["orbit_radius"] * np.array([np.cos(az) * np.cos(el), np.sin(az) * np.cos(el),
                                            np.sin(el)])
        fwd = -eye / np.linalg.norm(eye)
        right = np.cross(fwd, [0.0, 0.0, 1.0])
        right /= np.linalg.norm(right)
        up = np.cross(right, fwd)
        poses.append(np.stack([right, up, -fwd, eye], axis=1))
    return np.asarray(poses, np.float32)


def pixel_rays(pose: np.ndarray, hw: int, f: float):
    """Rays through the pixel centres of an hw x hw view, row-major (float64)."""
    py, px = np.meshgrid(np.arange(hw), np.arange(hw), indexing="ij")
    cam = np.stack([(px.ravel() + 0.5 - hw * 0.5) / f, -(py.ravel() + 0.5 - hw * 0.5) / f,
                    -np.ones(hw * hw)], axis=-1)
    d = cam @ pose[:3, :3].astype(np.float64).T
    d /= np.linalg.norm(d, axis=-1, keepdims=True)
    return np.broadcast_to(pose[:3, 3].astype(np.float64), d.shape), d


def shade(t: dict, rng: np.random.Generator, origins, dirs) -> np.ndarray:
    """Pixels of a scene of `primitives` spheres: albedo x soft Lambert at
    the first hit, white where a ray hits nothing."""
    k = t["primitives"]
    centers = rng.uniform(-0.8, 0.8, (k, 3))
    radii = rng.uniform(0.18, 0.45, k)
    albedo = rng.uniform(0.15, 0.95, (k, 3))
    oc = origins[:, None, :] - centers[None]
    b = np.sum(oc * dirs[:, None, :], axis=-1)
    c = np.sum(oc * oc, axis=-1) - radii[None] ** 2
    disc = b * b - c
    hit_t = np.where(disc > 0, -b - np.sqrt(np.maximum(disc, 0.0)), np.inf)
    hit_t = np.where(hit_t > 0, hit_t, np.inf)
    first = np.argmin(hit_t, axis=1)
    t_hit = hit_t[np.arange(len(first)), first]
    hit = np.isfinite(t_hit)
    p = origins + np.where(hit, t_hit, 0.0)[:, None] * dirs
    n = p - centers[first]
    n /= np.linalg.norm(n, axis=-1, keepdims=True)
    lam = 0.65 + 0.35 * np.clip(np.sum(-dirs * n, axis=-1), 0.0, 1.0)
    return np.where(hit[:, None], albedo[first] * lam[:, None], 1.0)


def scene_views(t: dict, views: int, hw: int, seed: int):
    """The training scene's posed views: poses (views,3,4) and pixels
    (views,hw,hw,3), float32, each pixel shaded along its own ray."""
    rng = rng_for(seed, "scene")
    poses = orbit_poses(t, views, rng)
    rays = [pixel_rays(p, hw, focal(t, hw)) for p in poses]
    rgb = shade(t, rng, np.concatenate([r[0] for r in rays]),
                np.concatenate([r[1] for r in rays]))
    return poses, rgb.reshape(views, hw, hw, 3).astype(np.float32)


def render_poses(t: dict, seed: int) -> np.ndarray:
    """The views a render client asks for, in a seed-drawn order."""
    rng = rng_for(seed, "poses")
    poses = orbit_poses(t, t["poses"], rng)
    return poses[rng.permutation(len(poses))]
