"""Random weights, made from ``--seed``: what every model family shares.

Every leaf is a pure function of (seed, leaf name, layer): normal draws in
float32 times the leaf's scale, rounded to the served dtype. Each family
(``bench/models/<family>.py``) names its leaves and builds the program's
weights from them in one jitted call on the device, and the plain reference
rebuilds the same values layer by layer, so the two share nothing but the
seed. The scales follow the program's own random init (0.02; output
projections 0.002; norms 1).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

NORMAL, SMALL = 0.02, 0.002


def root_key(seed: int):
    """A key from any non-negative seed, also one past 32 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def leaf(key, name: str, layer, shape, scale, dtype):
    """One leaf's value; ``layer`` may be traced (vmap over layers)."""
    if scale is None:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())),
                           layer)
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)
