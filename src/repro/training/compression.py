"""Gradient compression: int8 quantization with error feedback.

Two layers:
  * ``quantize_with_feedback`` / integration in train_step — the math:
    per-leaf symmetric int8 quantization, the residual carried in an
    error-feedback buffer so compression error does not accumulate
    (convergence-safe; property-tested against fp32 training).
  * ``compressed_psum`` — the comms: an explicit ``shard_map`` all-reduce
    that moves int8 over the wire (4x fewer bytes than fp32).  Its
    lowered HLO is inspected in tests/benchmarks to confirm the
    all-reduce operand really is int8 — this is the §Perf lever for
    collective-bound training cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_leaf(g, err):
    """Symmetric int8 quantization with error feedback.  Returns
    (dequantized g_hat, new error buffer)."""
    gf = g.astype(jnp.float32) + err
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    g_hat = q.astype(jnp.float32) * scale
    return g_hat.astype(g.dtype), gf - g_hat


def quantize_with_feedback(grads, err_tree):
    out = jax.tree.map(quantize_leaf, grads, err_tree)
    leaves, treedef = jax.tree.flatten(out,
                                       is_leaf=lambda x: isinstance(x, tuple))
    g_hat = treedef.unflatten([l[0] for l in leaves])
    new_err = treedef.unflatten([l[1] for l in leaves])
    return g_hat, new_err


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def compressed_psum(x, mesh, axis: str = "data"):
    """All-reduce ``x`` over ``axis`` moving int8 on the wire.

    Each shard quantizes against a pre-agreed scale (max|x| is itself
    all-reduced in fp32 — one scalar), all-gathers the int8 payload (the
    wire format — an int8 psum would overflow), and accumulates locally
    in int32.  Wire bytes: ~1 byte/elem vs ~8 bytes/elem for a ring
    fp32 all-reduce.
    """
    def body(xs):
        local_max = jnp.max(jnp.abs(xs.astype(jnp.float32)))
        gmax = jax.lax.pmax(local_max, axis)
        scale = jnp.maximum(gmax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(xs.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        gathered = jax.lax.all_gather(q, axis)            # int8 on the wire
        total = gathered.astype(jnp.int32).sum(axis=0)
        return (total.astype(jnp.float32) * scale).astype(xs.dtype)

    return jax.shard_map(body, mesh=mesh, in_specs=P(*(None,) * x.ndim),
                         out_specs=P(*(None,) * x.ndim),
                         check_vma=False)(x)
