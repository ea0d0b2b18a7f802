"""Plain reference of the dense decoder block, in float32.

Pre-norm RMSNorm, rotary embedding on q and k (the half-split convention,
frequencies theta**(-i/half)), grouped-query causal attention with scale
head_dim**-0.5, SwiGLU MLP, final RMSNorm and the (tied or untied) head over
the padded vocabulary. It imports nothing of the program and makes its own
weights from the seed through the dense family's leaves
(``bench/models/dense.py``, which imports the program only inside the
functions that build it). Run layer by layer so that it fits beside
nothing: every matrix product at ``highest`` precision.

``quant`` replaces the identity at the inputs of every matrix product and at
the stored K and V: the reference with ``quant`` set is the lower-precision
control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dense import dims, global_weights, layer_weights
from bench.weights import root_key

Q_BLOCK = 256


def _ident(x, axis=-1):
    return x


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, hd); pos: (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _mm(x, w, quant):
    return quant(x, -1) @ quant(w, 0)


def layer(m, h, w, quant):
    """One decoder layer over a whole sequence h: (S, d) float32."""
    x = dims(m)
    S = h.shape[0]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    pos = jnp.arange(S)
    a = rmsnorm(h, w["ln1"], eps)
    q = _mm(a, w["wq"], quant).reshape(S, x["h"], x["hd"])
    k = _mm(a, w["wk"], quant).reshape(S, x["hkv"], x["hd"])
    v = _mm(a, w["wv"], quant).reshape(S, x["hkv"], x["hd"])
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    k, v = quant(k, -1), quant(v, -1)
    g = x["h"] // x["hkv"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scale = x["hd"] ** -0.5

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", quant(qb, -1) * scale, k)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", quant(p, -1), v)

    o = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, -1)
    h = h + _mm(o, w["wo"], quant)
    b = rmsnorm(h, w["ln2"], eps)
    f = jax.nn.silu(_mm(b, w["w_gate"], quant)) * _mm(b, w["w_up"], quant)
    return h + _mm(f, w["w_down"], quant)


def logits_at(m, seed: int, seqs: list, served_dtype, quant=_ident,
              pad_to: int = 0) -> jax.Array:
    """Reference logits, float32, at chosen positions of several sequences.

    seqs: list of (tokens (L,), positions (P,)) with positions < L. Returns
    (sum of P, padded vocab), in the order given. Every sequence is padded
    at its end to ``pad_to`` (a multiple of the query block), which the
    causal mask keeps from every position that counts.
    """
    x = dims(m)
    key = root_key(seed)
    S = max(pad_to, max(len(t) for t, _ in seqs))
    S = -(-S // Q_BLOCK) * Q_BLOCK
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        g = jax.jit(lambda k: global_weights(m, k, served_dtype))(key)
        tok = f32(g["tok"])
        hs = []
        for toks, _ in seqs:
            ids = np.zeros(S, np.int32)
            ids[:len(toks)] = toks
            hs.append(tok[jnp.asarray(ids)])
        mk = jax.jit(lambda k, l: jax.tree.map(
            f32, layer_weights(m, k, l, served_dtype)))
        run = jax.jit(lambda h, w: layer(m, h, w, quant))
        for l in range(x["layers"]):
            w = mk(key, l)
            hs = [run(h, w) for h in hs]
            del w
        pick = jnp.concatenate([h[jnp.asarray(np.asarray(p, np.int32))]
                                for h, (_, p) in zip(hs, seqs)])
        del hs
        pick = rmsnorm(pick, f32(g["out_norm"]), m["rms_norm_eps"])
        head = tok.T if m["tie_word_embeddings"] else f32(g["head"])
        return jax.jit(lambda a, b: _mm(a, b, quant))(pick, head)


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _fp8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


CONTROLS = {"int8": _int8, "fp8": _fp8}
