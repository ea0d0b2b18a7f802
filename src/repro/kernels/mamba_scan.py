"""Chunked SSD (Mamba-2) scan kernel.

TPU adaptation of the SSD chunked algorithm: the grid's last axis walks
chunks sequentially, carrying the (dh, N) recurrent state in VMEM
scratch; each chunk's intra-chunk work is three dense matmuls
((c,c)x(c,dh), (c,N)x(N,dh), (c,dh)^T x (c,N)) that land on the MXU with
c=chunk (128/256) and dh a multiple of 128.

The decay products ``ldec = dt * A`` are precomputed outside the kernel
(cheap elementwise) so the kernel takes no scalar operands; the D skip
connection is likewise applied outside.

Validated in interpret mode against ``ref.ssd_sequential`` /
``ref.ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, ldec_ref, b_ref, c_ref, y_ref, h_ref, *, c: int):
    z = pl.program_id(2)

    @pl.when(z == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[0, :, 0, :].astype(jnp.float32)        # (c, dh)
    dt = dt_ref[0, :, 0].astype(jnp.float32)          # (c,)
    ld = ldec_ref[0, :, 0].astype(jnp.float32)        # (c,)  = dt * A
    Bm = b_ref[0].astype(jnp.float32)                 # (c, N)
    Cm = c_ref[0].astype(jnp.float32)                 # (c, N)

    seg = jnp.cumsum(ld)                              # inclusive within-chunk
    tot = seg[-1]
    dec_to_end = jnp.exp(tot - seg)                   # (c,)
    dec_from_start = jnp.exp(seg)                     # includes own dt
    h_prev = h_ref[...]                               # (dh, N)

    # cross-chunk contribution: y_i += dec(start->i) * C_i . h_prev
    y_cross = jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * dec_from_start[:, None]  # (c, dh)

    # intra-chunk causal part
    rel = seg[:, None] - seg[None, :]                 # (c_i, c_j)
    causal = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decm = jnp.where(causal, jnp.exp(rel), 0.0)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (c, c)
    m = cb * decm * dt[None, :]
    y_intra = jax.lax.dot(m, xb, preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = (y_intra + y_cross).astype(y_ref.dtype)

    # state update: h = exp(tot) * h_prev + sum_i dt_i dec(i->end) x_i B_i^T
    w = (dt * dec_to_end)[:, None] * xb               # (c, dh)
    states = jax.lax.dot_general(w, Bm, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (dh, N)
    h_ref[...] = h_prev * jnp.exp(tot) + states


def ssd_pallas(x, dt, A, B, C, D, *, chunk: int = 256, h0=None,
               interpret: bool = False):
    """x:(b,s,nh,dh) dt:(b,s,nh) A:(nh,) B,C:(b,s,N) D:(nh,).

    Returns (y, h_final) like ``ref.ssd_chunked``.  h0 unsupported in the
    kernel path (forward/train only)."""
    assert h0 is None, "ssd_pallas is the full-sequence path; decode uses ssd_decode"
    b, s, nh, dh = x.shape
    N = B.shape[-1]
    c = min(chunk, s)
    assert s % c == 0
    nc = s // c

    ldec = dt.astype(jnp.float32) * A.astype(jnp.float32)[None, None, :]

    kern = functools.partial(_kernel, c=c)
    # grid: (batch, head, chunk) — chunks sequential (carried state)
    y = pl.pallas_call(
        kern,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, c, 1, dh), lambda i, h, z: (i, z, h, 0)),
            pl.BlockSpec((1, c, 1), lambda i, h, z: (i, z, h)),
            pl.BlockSpec((1, c, 1), lambda i, h, z: (i, z, h)),
            pl.BlockSpec((1, c, N), lambda i, h, z: (i, z, 0)),
            pl.BlockSpec((1, c, N), lambda i, h, z: (i, z, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, 1, dh), lambda i, h, z: (i, z, h, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, nh, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((dh, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunked_scan",
    )(x, dt.astype(jnp.float32), ldec, B, C)

    y = y + (D.astype(jnp.float32)[None, None, :, None]
             * x.astype(jnp.float32)).astype(y.dtype)

    # h_final is recomputed outside the kernel (cheap reduction); the
    # kernel scratch is not returned.  Serving keeps states via
    # ssd_decode; training does not need h_final.
    _, h_final = _final_state(x, dt, A, B, c)
    return y, h_final


def _final_state(x, dt, A, B, c):
    """Analytic final SSD state (matches ref.ssd_chunked's h_final)."""
    b, s, nh, dh = x.shape
    nc = s // c
    xf = x.astype(jnp.float32).reshape(b, nc, c, nh, dh)
    dtf = dt.astype(jnp.float32).reshape(b, nc, c, nh)
    Bf = B.astype(jnp.float32).reshape(b, nc, c, -1)
    Af = A.astype(jnp.float32)
    seg = jnp.cumsum(dtf, axis=2)
    tot = seg[:, :, -1:]
    dec_to_end = jnp.exp((tot - seg) * Af)
    w = dtf * dec_to_end
    states = jnp.einsum("bzch,bzchd,bzcn->bzhdn", w, xf, Bf)
    chunk_decay = jnp.exp(tot[:, :, 0] * Af)          # (b,nc,nh)

    def combine(e1, e2):
        a1, s1 = e1
        a2, s2 = e2
        return a1 * a2, s1 * a2[..., None, None] + s2

    a_all, h_all = jax.lax.associative_scan(
        combine, (chunk_decay.transpose(1, 0, 2),
                  states.transpose(1, 0, 2, 3, 4)), axis=0)
    return a_all, h_all[-1]
