"""Monte Carlo draws from a seed, made the way the service documents it
(``repro.analysis.uncertainty.sample_spec``), written out here so that the
check does not take the draws from the program.

The root key is ``PRNGKey(seed)``, folded with the spec group (0) and then
with each axis in sorted ``(process, input, data-after-resource)`` order;
two raw 32-bit ``jax.random.bits`` streams make 53-bit uniforms, and the
inverse transforms are numpy float64 (Box-Muller for the lognormal).
"""

from __future__ import annotations

import numpy as np

N_UNIFORMS = {"lognormal": 2, "uniform": 1, "triangular": 1}


def uniform01(key, n: int, cols: int, dtype=np.float64) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    hi = np.asarray(jax.random.bits(jax.random.fold_in(key, 0), (n, cols),
                                    dtype=jnp.uint32), dtype=np.uint64)
    lo = np.asarray(jax.random.bits(jax.random.fold_in(key, 1), (n, cols),
                                    dtype=jnp.uint32), dtype=np.uint64)
    mant = (hi << np.uint64(21)) | (lo >> np.uint64(11))
    return (mant.astype(np.float64) * (1.0 / float(1 << 53))).astype(dtype)


def transform(family: str, params: list, u: np.ndarray, dtype=np.float64):
    c = [dtype(p) for p in params]
    if family == "lognormal":
        m, sigma = c
        u1 = np.clip(u[:, 0], dtype(1e-300) if dtype == np.float64 else
                     np.finfo(dtype).tiny, None)
        z = np.sqrt(dtype(-2.0) * np.log(u1)) * np.cos(dtype(2.0 * np.pi)
                                                        * u[:, 1])
        return m * np.exp(sigma * z)
    if family == "uniform":
        lo, hi = c
        return lo + (hi - lo) * u[:, 0]
    if family == "triangular":
        lo, mode, hi = c
        fc = (mode - lo) / (hi - lo)
        left = lo + np.sqrt(u[:, 0] * (hi - lo) * (mode - lo))
        right = hi - np.sqrt((dtype(1.0) - u[:, 0]) * (hi - lo) * (hi - mode))
        return np.where(u[:, 0] < fc, left, right)
    raise ValueError(f"unknown distribution {family!r}")


def draws(dists: dict, data_keys: set, n: int, seed: int,
          dtype=np.float64) -> dict:
    """``{"proc.input": (n,) factors}`` for a traffic file's ``dists``."""
    import jax

    gkey = jax.random.fold_in(jax.random.PRNGKey(int(seed)), 0)
    order = sorted(dists, key=lambda k: (*k.split("."), k in data_keys))
    out = {}
    for axis, key in enumerate(order):
        family, *params = dists[key]
        u = uniform01(jax.random.fold_in(gkey, axis), n, N_UNIFORMS[family],
                      dtype)
        out[key] = transform(family, params, u, dtype)
    return out
