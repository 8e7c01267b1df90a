"""Scenes and cameras of the benchmark, made from a seed.

A configuration's `scene` block names a distribution and its parameters;
`make_scene` draws every array of it on the device in one jitted call, in
float32, the type the program serves. The arrays are returned as a dict
with the field names of the program's Gaussian scene (means, log_scales,
quats, opacity_logits, colors).

Distributions:
  slab  - means uniform in [-extent, extent]^3 with z folded to
          |z| + near_z (a slab in front of the origin), as the program's
          synthetic Full-HD workload scenes are drawn;
  ball  - means uniform in the shell between `inner_radius` and `radius`
          around `center` (an object's surface layer, as the Gaussians of a
          trained NeRF-Synthetic object sit on its surfaces).
Both draw log-scales uniform in `scale_range`, stretch the first axis by
`stretch` for a `spiky_frac` share of the Gaussians, and draw quaternions
normal, opacity logits uniform in `opacity_range` and colours uniform in
[0, 1].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (64 bits and more)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


@functools.partial(jax.jit, static_argnames=("n", "kind", "params"))
def _draw(key, *, n: int, kind: str, params: tuple) -> dict:
    p = dict(params)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    if kind == "slab":
        e = p["extent"]
        means = jax.random.uniform(k1, (n, 3), minval=-e, maxval=e)
        means = means.at[:, 2].set(jnp.abs(means[:, 2]) + p["near_z"])
    elif kind == "ball":
        ka, kb = jax.random.split(k1)
        d = jax.random.normal(ka, (n, 3))
        d = d / jnp.linalg.norm(d, axis=1, keepdims=True)
        lo, hi = p["inner_radius"] ** 3, p["radius"] ** 3
        r = (lo + (hi - lo) * jax.random.uniform(kb, (n, 1))) ** (1.0 / 3.0)
        means = jnp.asarray(p["center"], jnp.float32) + d * r
    else:
        raise ValueError(f"unknown scene distribution {kind!r}")
    lo, hi = p["scale_range"]
    log_scales = jax.random.uniform(k2, (n, 3), minval=lo, maxval=hi)
    spiky = jax.random.uniform(k3, (n,)) < p["spiky_frac"]
    log_scales = log_scales.at[:, 0].add(
        jnp.where(spiky, math.log(p["stretch"]), 0.0))
    olo, ohi = p["opacity_range"]
    return dict(
        means=means.astype(jnp.float32),
        log_scales=log_scales.astype(jnp.float32),
        quats=jax.random.normal(k4, (n, 4), jnp.float32),
        opacity_logits=jax.random.uniform(k5, (n,), jnp.float32, olo, ohi),
        colors=jax.random.uniform(k6, (n, 3), jnp.float32))


def _frozen(x):
    return tuple(x) if isinstance(x, list) else x


def make_scene(spec: dict, n: int, seed: int) -> dict:
    """The scene of a configuration's `scene` block for one seed."""
    params = tuple(sorted((k, _frozen(v)) for k, v in spec.items()
                          if k != "distribution"))
    return _draw(seed_key(seed), n=n, kind=spec["distribution"],
                 params=params)


def look_at(position, target, width: int, height: int, fov_x_deg: float,
            near: float = 0.2) -> dict:
    """Pinhole camera at `position` looking at `target` with world +y up,
    as a dict of the program's camera fields (R_wc rows: right, up, view
    direction; t_wc = -R_wc position; horizontal field of view)."""
    pos = np.asarray(position, np.float64)
    fwd = np.asarray(target, np.float64) - pos
    fwd /= np.linalg.norm(fwd)
    right = np.cross([0.0, 1.0, 0.0], fwd)
    right /= np.linalg.norm(right)
    up = np.cross(fwd, right)
    rot = np.stack([right, up, fwd]).astype(np.float32)
    f = np.float32(width / (2.0 * math.tan(math.radians(fov_x_deg) / 2.0)))
    return dict(R_wc=rot, t_wc=(-rot @ pos.astype(np.float32)),
                fx=f, fy=f, cx=np.float32(width / 2.0),
                cy=np.float32(height / 2.0),
                width=width, height=height, near=near)


def orbit_pose(rig: dict, azimuth_deg: float, elevation_deg: float,
               width: int, height: int) -> dict:
    """Camera on the configuration's orbit `rig` (center, radius, field of
    view) at an azimuth and elevation, looking at the center."""
    az, el = math.radians(azimuth_deg), math.radians(elevation_deg)
    c = np.asarray(rig["center"], np.float64)
    r = rig["radius"]
    pos = c + r * np.array([math.cos(el) * math.sin(az), math.sin(el),
                            -math.cos(el) * math.cos(az)])
    return look_at(pos, c, width, height, rig["fov_x_deg"])
