"""Backend dispatch for the compute hot-spots.

Every op has three implementations:
  * ``naive``      — smallest oracle (tests only; O(S^2) memory etc.)
  * ``blockwise``  — pure-JAX production path (CPU smoke tests + dry-run
                     lowering; same math the Pallas kernel implements)
  * ``pallas``     — TPU kernel (``pl.pallas_call`` + BlockSpec).  Off
                     the TPU it runs in the Pallas interpreter only under
                     ``REPRO_FORCE_PALLAS_INTERPRET=1`` (kernel tests);
                     without it an explicit ``impl="pallas"`` raises.

``impl=None`` resolves to pallas on TPU (or under the override),
blockwise elsewhere.
"""
from __future__ import annotations

from typing import Optional

from repro import compat
from repro.kernels import ref


def _resolve(impl: Optional[str]) -> str:
    if impl in (None, "auto"):
        return ("pallas" if (compat.on_tpu() or compat.force_interpret())
                else "blockwise")
    return impl


# ------------------------------------------------------------- attention


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    impl: Optional[str] = None,
                    block_q: int = 512, block_k: int = 1024):
    impl = _resolve(impl)
    if impl == "naive":
        return ref.attention_naive(q, k, v, causal=causal, scale=scale)
    if impl == "blockwise":
        return ref.flash_attention_blockwise(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k)
    if impl == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(
            q, k, v, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k,
            interpret=compat.pallas_interpret())
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q, k_cache, v_cache, lengths, *, scale=None,
                     impl: Optional[str] = None):
    """Dense-cache single-token decode (flash-decoding split over S)."""
    impl = _resolve(impl)
    if impl in ("naive", "blockwise"):
        return ref.decode_attention_ref(q, k_cache, v_cache, lengths, scale=scale)
    if impl == "pallas":
        from repro.kernels.decode_attention import decode_attention_pallas
        return decode_attention_pallas(q, k_cache, v_cache, lengths,
                                       scale=scale,
                                       interpret=compat.pallas_interpret())
    raise ValueError(f"unknown decode impl {impl!r}")


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           scale=None, impl: Optional[str] = None):
    """Paged-KV single-token decode (the serving engine's fast path)."""
    impl = _resolve(impl)
    if impl in ("naive", "blockwise"):
        return ref.paged_decode_attention_ref(
            q, k_pages, v_pages, page_table, lengths, scale=scale)
    if impl == "pallas":
        from repro.kernels.decode_attention import paged_decode_attention_pallas
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, scale=scale,
            interpret=compat.pallas_interpret())
    raise ValueError(f"unknown paged decode impl {impl!r}")


# ------------------------------------------------------------------ SSD


def ssd(x, dt, A, B, C, D, *, chunk: int = 256, h0=None,
        impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "naive":
        return ref.ssd_sequential(x, dt, A, B, C, D, h0=h0)
    if impl == "blockwise":
        return ref.ssd_chunked(x, dt, A, B, C, D, chunk=chunk, h0=h0)
    if impl == "pallas":
        from repro.kernels.mamba_scan import ssd_pallas
        return ssd_pallas(x, dt, A, B, C, D, chunk=chunk, h0=h0,
                          interpret=compat.pallas_interpret())
    raise ValueError(f"unknown ssd impl {impl!r}")


def ssd_decode(h, x, dt, A, B, C, D):
    return ref.ssd_decode_step(h, x, dt, A, B, C, D)


# ---------------------------------------------------------------- mLSTM


def mlstm(q, k, v, i_gate, f_gate, *, chunk: int = 256, state=None,
          impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "naive":
        return ref.mlstm_sequential(q, k, v, i_gate, f_gate, state=state)
    if impl in ("blockwise", "pallas"):
        # the chunked form is already scan-over-chunks and MXU-shaped;
        # it serves as both the blockwise and the TPU production path
        return ref.mlstm_chunked(q, k, v, i_gate, f_gate, chunk=chunk,
                                 state=state)
    raise ValueError(f"unknown mlstm impl {impl!r}")


def mlstm_decode(state, q, k, v, i_gate, f_gate):
    return ref.mlstm_decode_step(state, q, k, v, i_gate, f_gate)
