"""Weather fields made on the device from the seed, in one jitted call.

Each maker gives a field of ``(levels, *horizontal)`` float32 values on
WeatherBench 2's pressure levels: a standard-atmosphere profile, a
pole-to-equator contrast along the first horizontal axis (north to south),
a zonal wave, and noise.  ``steps`` distinct steps differ in the wave's
phase and in their noise.  The same seed gives the same fields.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

#: WeatherBench 2's 13 pressure levels (hPa)
WB2_LEVELS_HPA = (50, 100, 150, 200, 250, 300, 400, 500, 600, 700, 850, 925,
                  1000)


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any non-negative seed, including seeds wider
    than 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words),
                                    impl="threefry2x32")


def _coords(hshape: Sequence[int], step: jax.Array):
    """(u, wave): u runs from -1 (north) to 1 (south) along the first
    horizontal axis; the wave runs along the last (longitude, or the
    point order of a reduced grid)."""
    n_lat = hshape[0]
    u = jnp.linspace(-1.0, 1.0, n_lat, dtype=jnp.float32)
    u = u.reshape((n_lat,) + (1,) * (len(hshape) - 1))
    if len(hshape) == 1:        # reduced grid: points ordered by latitude
        phase = jnp.float32(np.pi * 1280) * u
    else:
        lon = jnp.linspace(0.0, 2 * np.pi, hshape[-1], endpoint=False,
                           dtype=jnp.float32)
        phase = 4 * lon + 3 * jnp.pi * u
    wave = jnp.sin(phase + 0.37 * step)
    return jnp.broadcast_to(u, hshape), jnp.broadcast_to(wave, hshape)


def _profile(levels: int):
    p = jnp.asarray(WB2_LEVELS_HPA[:levels], jnp.float32)
    sigma = (p / 1013.25) ** 0.1903
    t_level = jnp.maximum(288.15 * sigma, 216.65)
    z_level = 9.80665 * 44330.8 * (1 - sigma)
    return sigma[:, None], t_level[:, None], z_level[:, None]


def _field(kind: str, levels: int, hshape, step, key):
    u, wave = _coords(hshape, step)
    sigma, t_level, z_level = (a.reshape((levels,) + (1,) * len(hshape))
                               for a in _profile(levels))
    noise = jax.random.normal(key, (levels,) + tuple(hshape), jnp.float32)
    if kind == "temperature":           # K
        return t_level + 25 * jnp.cos(jnp.pi * u) - 15 + 3 * wave + 0.5 * noise
    if kind == "geopotential":          # m^2 s^-2
        return z_level + (-3000 * u * u + 800 * wave) + 5 * noise
    if kind == "wind_u":                # m s^-1
        return (30 * (1 - sigma) * jnp.cos(jnp.pi * u) ** 2 + 5 * wave
                + 2 * noise)
    if kind == "wind_v":                # m s^-1
        return 6 * wave + 2 * noise
    if kind == "humidity":              # kg kg^-1
        return jnp.maximum(0.018 * sigma ** 4 * jnp.cos(0.5 * jnp.pi * u)
                           * (1 + 0.2 * wave) + 5e-4 * noise, 0.0)
    if kind == "vertical_velocity":     # Pa s^-1
        return 0.1 * wave + 0.2 * noise
    raise ValueError(f"unknown field maker {kind!r}")


def make_fields(config: dict, seed: int) -> Dict[str, jax.Array]:
    """``{field: (steps, *shape) float32}`` on the default device for every
    field of ``config``, made in one jitted call."""
    specs = [(f["name"], f["maker"], tuple(f["shape"]))
             for f in config["fields"]]
    steps = int(config["distinct_steps"])

    @jax.jit
    def make(key):
        out = {}
        keys = jax.random.split(key, len(specs) * steps)
        for i, (name, kind, shape) in enumerate(specs):
            out[name] = jnp.stack([
                _field(kind, shape[0], shape[1:], jnp.float32(s),
                       keys[i * steps + s]) for s in range(steps)])
        return out

    return make(key_from_seed(seed))
