"""FlashAttention TPU kernel (pl.pallas_call + BlockSpec VMEM tiling).

Design (TPU-native, not a CUDA port):
  * grid = (B, H, nq, nk); the last axis is the sequential reduction axis
    (``arbitrary`` dimension semantics) so the fp32 accumulator scratch
    persists across kv blocks — the online-softmax state never leaves
    VMEM.
  * q/k/v blocks are (bq, dk) / (bk, dk) VMEM tiles; matmul dims are
    multiples of 128 at the production block sizes (bq=512, bk=1024,
    dk 64..192) so both dots land on the MXU.
  * causal block-skip via ``pl.when`` — blocks strictly above the
    diagonal issue no MXU work.
  * GQA without KV expansion: the k/v index_map folds the q-head index
    onto its kv head (h // g), so KV tiles are fetched once per group.

Validated in interpret mode against ``ref.attention_naive`` (tests/).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        qb = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (bq, dk)
        kb = k_ref[0, :, 0, :].astype(jnp.float32)              # (bk, dk)
        vb = v_ref[0, :, 0, :].astype(jnp.float32)              # (bk, dv)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                                      # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip kv blocks strictly above this q block's diagonal
        pl.when(ik * bk <= iq * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, :, 0, :] = (acc_ref[...] /
                             jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 1024,
                           interpret: bool = False):
    """q: (B,S,H,dk)  k/v: (B,Sk,Hkv,d)  ->  (B,S,H,dv)."""
    B, S, H, dk = q.shape
    Sk, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    assert H % hkv == 0
    g = H // hkv
    scale = scale or dk ** -0.5
    bq, bk = min(block_q, S), min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, bq, Sk, bk)
    nq, nk = S // bq, Sk // bk
    grid = (B, H, nq, nk)

    kern = functools.partial(_kernel, scale=scale, causal=causal,
                             bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, 1, dk), lambda b, h, iq, ik: (b, iq, h, 0)),
            pl.BlockSpec((1, bk, 1, dk), lambda b, h, iq, ik: (b, ik, h // g, 0)),
            pl.BlockSpec((1, bk, 1, dv), lambda b, h, iq, ik: (b, ik, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dv), lambda b, h, iq, ik: (b, iq, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, S, H, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out
