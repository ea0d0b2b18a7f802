"""Random weights of a dense decoder, made from ``--seed``.

Every leaf is a pure function of (seed, leaf name, layer): normal draws in
float32 times the leaf's scale, rounded to the served dtype. The benchmark
builds the program's weights from it in one jitted call on the device, and
the plain reference rebuilds the same values layer by layer, so the two
share nothing but the seed. The scales follow the program's own random init
(0.02; output projections 0.002; norms 1).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from bench.costs import dims

NORMAL, SMALL = 0.02, 0.002

# per-layer leaves: name -> (shape from dims, init scale or None for ones)
LAYER_LEAVES = {
    "ln1": (lambda x: (x["d"],), None),
    "wq": (lambda x: (x["d"], x["h"] * x["hd"]), NORMAL),
    "wk": (lambda x: (x["d"], x["hkv"] * x["hd"]), NORMAL),
    "wv": (lambda x: (x["d"], x["hkv"] * x["hd"]), NORMAL),
    "wo": (lambda x: (x["h"] * x["hd"], x["d"]), SMALL),
    "ln2": (lambda x: (x["d"],), None),
    "w_gate": (lambda x: (x["d"], x["f"]), NORMAL),
    "w_up": (lambda x: (x["d"], x["f"]), NORMAL),
    "w_down": (lambda x: (x["f"], x["d"]), SMALL),
}


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


def global_leaves(m: dict) -> dict:
    x = dims(m)
    out = {"tok": ((x["vocab"], x["d"]), NORMAL),
           "out_norm": ((x["d"],), None)}
    if not m["tie_word_embeddings"]:
        out["head"] = ((x["d"], x["vocab"]), NORMAL)
    return out


def layer_weights(m: dict, key, layer, dtype):
    """Every leaf of one layer, keyed by name."""
    x = dims(m)
    return {n: leaf(key, n, layer, shp(x), sc, dtype)
            for n, (shp, sc) in LAYER_LEAVES.items()}


def global_weights(m: dict, key, dtype):
    return {n: leaf(key, n, 0, shp, sc, dtype)
            for n, (shp, sc) in global_leaves(m).items()}
