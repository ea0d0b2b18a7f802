"""Single-token decode attention kernels (flash-decoding on TPU).

Two variants:
  * ``decode_attention_pallas``        — dense per-slot cache
    (B, S_max, Hkv, d), split-K over the sequence: grid's last axis
    walks S blocks sequentially, partial (max, sum, acc) live in VMEM
    scratch, blocks past the sequence length issue no work.
  * ``paged_decode_attention_pallas``  — vLLM-style paged cache.  The
    page table is a *scalar-prefetch* operand
    (``pltpu.PrefetchScalarGridSpec``): the k/v index_map dereferences
    ``page_table[b, j]`` so each grid step DMAs exactly one KV page
    from HBM into VMEM — the TPU analogue of paged attention's
    gather, with no host round trip.

Both are GQA-aware and take every kv head of a sequence block in one
grid step: the cache is viewed as (..., tokens, Hkv * d), so a K/V
block is (tokens, Hkv * d) — the two minor dims Mosaic tiles are a
multiple of 8 and the full head row — and kv head ``h`` is the static,
128-lane-aligned column slice ``[h * d, (h + 1) * d)``.  q is viewed as
(B, Hkv, G, dk); each head attends its G query heads (G x block dots).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _init_scratch(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _attend_block(start, length, q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                  *, scale, hkv, dk, dv):
    """Online-softmax update of every kv head against one block of
    tokens ``[start, start + block)``; positions >= length are masked."""
    for h in range(hkv):
        qb = q_ref[0, h].astype(jnp.float32) * scale                # (G, dk)
        kb = k_ref[0, :, h * dk:(h + 1) * dk].astype(jnp.float32)   # (bs, dk)
        vb = v_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32)   # (bs, dv)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, bs)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[h] = l_ref[h] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _finalize(o_ref, acc_ref, l_ref):
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _scratch(hkv, g, dv):
    return [pltpu.VMEM((hkv, g, dv), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32)]


# ------------------------------------------------------------ dense cache


def _dense_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, scale, bs, ns, hkv, dk, dv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    length = len_ref[b]

    @pl.when(j * bs < length)
    def _compute():
        _attend_block(j * bs, length, q_ref, k_ref, v_ref, acc_ref, m_ref,
                      l_ref, scale=scale, hkv=hkv, dk=dk, dv=dv)

    @pl.when(j == ns - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def decode_attention_pallas(q, k_cache, v_cache, lengths, *,
                            scale: Optional[float] = None,
                            block_s: int = 512, interpret: bool = False):
    """q: (B,H,dk)  caches: (B,S_max,Hkv,d)  lengths: (B,) -> (B,H,dv)."""
    B, H, dk = q.shape
    Smax, hkv, dv = k_cache.shape[1], k_cache.shape[2], v_cache.shape[-1]
    g = H // hkv
    scale = scale or dk ** -0.5
    bs = min(block_s, Smax)
    if Smax % bs:
        raise ValueError(f"S_max={Smax} is not a multiple of block_s={bs}")
    ns = Smax // bs
    qg = q.reshape(B, hkv, g, dk)
    kf = k_cache.reshape(B, Smax, hkv * dk)
    vf = v_cache.reshape(B, Smax, hkv * dv)

    kern = functools.partial(_dense_kernel, scale=scale, bs=bs, ns=ns,
                             hkv=hkv, dk=dk, dv=dv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, ns),
            in_specs=[
                pl.BlockSpec((1, hkv, g, dk), lambda b, j, lens: (b, 0, 0, 0)),
                pl.BlockSpec((1, bs, hkv * dk), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, bs, hkv * dv), lambda b, j, lens: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, hkv, g, dv),
                                   lambda b, j, lens: (b, 0, 0, 0)),
            scratch_shapes=_scratch(hkv, g, dv),
        ),
        out_shape=jax.ShapeDtypeStruct((B, hkv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(lengths.astype(jnp.int32), qg, kf, vf)
    return out.reshape(B, H, dv)


# ------------------------------------------------------------ paged cache


def _paged_kernel(len_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale, page, npp, hkv, dk, dv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_scratch(acc_ref, m_ref, l_ref)

    length = len_ref[b]

    @pl.when(j * page < length)
    def _compute():
        _attend_block(j * page, length, q_ref, k_ref, v_ref, acc_ref, m_ref,
                      l_ref, scale=scale, hkv=hkv, dk=dk, dv=dv)

    @pl.when(j == npp - 1)
    def _fin():
        _finalize(o_ref, acc_ref, l_ref)


def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, lengths, *,
                                  scale: Optional[float] = None,
                                  interpret: bool = False):
    """q: (B,H,dk)  pages: (n_pages, page, Hkv, d)  page_table: (B, npp)."""
    B, H, dk = q.shape
    n_pages, page = k_pages.shape[0], k_pages.shape[1]
    hkv, dv = k_pages.shape[2], v_pages.shape[-1]
    npp = page_table.shape[1]
    g = H // hkv
    scale = scale or dk ** -0.5
    qg = q.reshape(B, hkv, g, dk)
    kf = k_pages.reshape(n_pages, page, hkv * dk)
    vf = v_pages.reshape(n_pages, page, hkv * dv)

    kern = functools.partial(_paged_kernel, scale=scale, page=page, npp=npp,
                             hkv=hkv, dk=dk, dv=dv)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # lengths, page_table
            grid=(B, npp),
            in_specs=[
                pl.BlockSpec((1, hkv, g, dk),
                             lambda b, j, lens, tbl: (b, 0, 0, 0)),
                # the page table drives which KV page is DMA'd each step
                pl.BlockSpec((1, page, hkv * dk),
                             lambda b, j, lens, tbl: (tbl[b, j], 0, 0)),
                pl.BlockSpec((1, page, hkv * dv),
                             lambda b, j, lens, tbl: (tbl[b, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, hkv, g, dv),
                                   lambda b, j, lens, tbl: (b, 0, 0, 0)),
            scratch_shapes=_scratch(hkv, g, dv),
        ),
        out_shape=jax.ShapeDtypeStruct((B, hkv, g, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_flash_decode",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32),
      qg, kf, vf)
    return out.reshape(B, H, dv)
