"""Operations and bytes the algorithm needs, from shapes alone.

``m`` is a model dict as ``bench/configs/<name>.json`` holds it (Hugging Face
key names). Attention FLOPs count the positions each query attends: the live
length, not the cache's capacity.
"""
from __future__ import annotations

import numpy as np


def dims(m: dict) -> dict:
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    return {"d": d, "h": h, "hkv": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // h, "f": m["intermediate_size"],
            "layers": m["num_hidden_layers"], "vocab": m["vocab_padded"]}


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies in one dense layer: q, k, v, o and the
    three SwiGLU matrices."""
    x = dims(m)
    attn = x["d"] * x["h"] * x["hd"] * 2 + x["d"] * x["hkv"] * x["hd"] * 2
    return attn + 3 * x["d"] * x["f"]


def token_flops(m: dict, attended) -> float:
    """Model FLOPs of processing tokens, one per entry of ``attended`` (the
    number of positions that token's query attends, its own included):
    2 x every matmul weight, the logits, and QK plus PV over the attended
    positions in every layer."""
    x = dims(m)
    attended = np.asarray(attended, np.float64)
    dense = 2.0 * (x["layers"] * layer_matmul_params(m) + x["d"] * x["vocab"])
    attn = 4.0 * x["layers"] * x["h"] * x["hd"] * attended
    return float(dense * attended.size + attn.sum())


def flash_decode_cost(m: dict, lengths, dtype_bytes: int = 2) -> tuple:
    """(bytes, FLOPs) one ``flash_decode`` call needs for one layer, where
    ``lengths`` are the per-slot positions attended (the kernel's
    ``lengths`` argument): K and V of those positions for every kv head,
    plus q in and o out."""
    x = dims(m)
    lengths = np.asarray(lengths, np.float64)
    kv = 2.0 * lengths.sum() * x["hkv"] * x["hd"] * dtype_bytes
    qo = 2.0 * lengths.size * x["h"] * x["hd"] * dtype_bytes
    flops = 4.0 * x["h"] * x["hd"] * lengths.sum()
    return kv + qo, flops


def roofline_seconds(nbytes: float, flops: float, peak: dict) -> tuple:
    """(least seconds, bound) for work of ``nbytes`` and ``flops``."""
    t_mem = nbytes / peak["hbm_bw"]
    t_cmp = flops / peak["flops_bf16"]
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
